"""Shared set-up of the benchmark's own tests.

    python -m pytest bench/tests

They sit outside the repository's ``testpaths``. Everything but
``test_control.py`` runs on the CPU at tiny sizes; ``tiny_root`` builds a
benchmark root whose one configuration is the ``pol`` configuration cut to
n=256, d=3, 8 probes, 64 RFF pairs and 4 outer steps, under the real cells'
traffic mixes, limits and metric readers.
"""
import json
import shutil
import sys
from pathlib import Path

import pytest

ROOT = Path(__file__).resolve().parents[2]
BENCH = ROOT / "bench"
sys.path[:0] = [str(ROOT / "src"), str(ROOT)]

TINY = {"n_train": 256, "d": 3, "num_probes": 8, "num_rff_pairs": 64,
        "outer_steps": 4, "tile": {"bm": 128, "bn": 128}}


def make_root(path: Path, config_updates: dict | None = None) -> Path:
    """A benchmark root at ``path`` with cells ``tiny.fit_tol`` and
    ``tiny.fit_budget``, limited as ``pol.fit_tol`` and
    ``3droad.fit_budget``."""
    bench = path / "bench"
    for sub in ("end_to_end", "layer_metrics", "traffic"):
        shutil.copytree(BENCH / sub, bench / sub)
    (bench / "configs").mkdir()
    (bench / "limits").mkdir()
    config = json.loads((BENCH / "configs" / "pol.json").read_text())
    config.update(TINY, name="tiny")
    config["solver"]["precond_rank"] = 20
    config.update(config_updates or {})
    (bench / "configs" / "tiny.json").write_text(json.dumps(config))
    benchmark = json.loads((ROOT / "BENCHMARK.json").read_text())
    benchmark["configs"] = [dict(benchmark["configs"][0], name="tiny",
                                 file="bench/configs/tiny.json")]
    benchmark["workloads"] = [
        dict(benchmark["workloads"][0], name="tiny.fit_tol", config="tiny"),
        dict(benchmark["workloads"][1], name="tiny.fit_budget",
             config="tiny")]
    for m in benchmark["end_to_end"] + benchmark["per_layer"]:
        if "workloads" in m:
            m["workloads"] = ["tiny.fit_budget"]
    (path / "BENCHMARK.json").write_text(json.dumps(benchmark))
    for cell, limits in (("tiny.fit_tol", "pol.fit_tol"),
                         ("tiny.fit_budget", "3droad.fit_budget")):
        shutil.copy(BENCH / "limits" / f"{limits}.json",
                    bench / "limits" / f"{cell}.json")
    return path


@pytest.fixture(scope="session", autouse=True)
def compile_cache(tmp_path_factory):
    """One compile cache for the session, outside the checkout (JAX opens
    its persistent cache once per process)."""
    mp = pytest.MonkeyPatch()
    mp.setenv("JAX_COMPILATION_CACHE_DIR",
              str(tmp_path_factory.mktemp("jax_cache")))
    yield
    mp.undo()


@pytest.fixture
def tiny_root(tmp_path):
    return make_root(tmp_path / "root")
