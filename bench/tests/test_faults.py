"""A run with the timed path broken underneath reads as not correct.

The harness's look for a chip is skipped; everything else of a run (set-up,
window, reference check) runs on the CPU at the tiny size. The
low-precision control means something on a TPU only: test_control.py.
"""
import pytest

from bench import faults
from bench.run import run


@pytest.mark.parametrize("workload", ["tiny.fit_tol", "tiny.fit_budget"])
def test_a_sound_run_is_correct(tiny_root, workload):
    result = run(workload, 2**31 + 17, 1.0, False, require_tpu=False,
                 root=tiny_root)
    assert result["correct"], result["check"]
    assert result["attempted"] >= 1 and result["failed"] == 0
    assert list(result)[-1] == "check"
    assert set(result["metrics"]) == {"fit_steps_per_s", "setup_s"}


@pytest.mark.parametrize("fault,number", [
    ("unchanged", "solve_gap"),
    ("half_probes", "grad_gap"),
    ("altered", "solve_gap"),
    ("frozen", "adam_gap"),
    ("sign_flip", "adam_gap"),
])
def test_a_planted_fault_is_not_correct(tiny_root, fault, number):
    with faults.FAULTS[fault]():
        result = run("tiny.fit_tol", 3, 1.0, False, require_tpu=False,
                     root=tiny_root)
    assert not result["correct"]
    failed = {k for k, c in result["check"].items()
              if not c["value"] <= c["limit"]}
    assert number in failed, result["check"]


def test_no_tpu_means_no_result(capsys):
    from bench.run import main

    assert main(["--workload", "pol.fit_tol", "--seed", "1", "--seconds",
                 "1"]) != 0
    assert capsys.readouterr().out == ""
