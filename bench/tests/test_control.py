"""The low-precision control reads as not correct.

The control is the program's own precision switch for the kernel MVM set
one step below the float32 the configurations state (bench.faults). On a
CPU that step is still float32, so this test runs on a TPU only:

    python -m pytest bench/tests/test_control.py   # on one TPU chip
"""
import jax
import pytest
from conftest import make_root

from bench import faults
from bench.run import run

# pol's width and probes, cut to a size a test run holds.
SMALL_POL = {"n_train": 2048, "d": 26, "num_probes": 16,
             "num_rff_pairs": 256, "outer_steps": 8,
             "tile": {"bm": 512, "bn": 512}}


@pytest.mark.parametrize("seed", [5, 6, 7])
def test_control_is_not_correct(tmp_path, seed):
    if jax.devices()[0].platform != "tpu":
        pytest.skip("the bf16 control differs from float32 on a TPU only")
    root = make_root(tmp_path, SMALL_POL)
    # The budgeted mix: under bf16 a solve to tolerance may never end.
    sound = run("tiny.fit_budget", seed, 2.0, False, root=root)
    assert sound["correct"], sound["check"]
    with faults.control_bf16():
        control = run("tiny.fit_budget", seed, 2.0, False, root=root)
    assert not control["correct"], control["check"]
