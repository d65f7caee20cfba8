"""Process-level JAX rules: compile-cache location, no silent CPU
fallback, and one process per chip."""
import os
import subprocess
import sys

import pytest

from repro import runtime

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _python(code: str, env_update: dict, drop=()) -> subprocess.CompletedProcess:
    env = {k: v for k, v in os.environ.items() if k not in drop}
    env.update(env_update)
    env["PYTHONPATH"] = os.path.join(REPO, "src")
    return subprocess.run([sys.executable, "-c", code], capture_output=True,
                          text=True, env=env, timeout=120)


@pytest.mark.parametrize("env_dir", [None, "cache-from-env"])
def test_compilation_cache_dir(tmp_path, env_dir):
    """The env var wins and the code sets no other directory; otherwise the
    cache lands at one fixed, git-ignored path in the checkout."""
    want = str(tmp_path / env_dir) if env_dir else str(
        runtime.DEFAULT_CACHE_DIR)
    r = _python(
        "import jax\n"
        "from repro.runtime import enable_compilation_cache\n"
        "print(enable_compilation_cache())\n"
        "print(jax.config.jax_compilation_cache_dir)\n",
        {runtime.CACHE_ENV: want} if env_dir else {},
        drop=(runtime.CACHE_ENV,))
    assert r.returncode == 0, r.stderr
    assert r.stdout.split() == [want, want]
    assert runtime.DEFAULT_CACHE_DIR == runtime.DEFAULT_CACHE_DIR.parent / \
        ".jax_cache"
    with open(os.path.join(REPO, ".gitignore")) as f:
        assert ".jax_cache/" in f.read().split()


def test_cpu_fallback_with_a_tpu_attached_is_refused():
    """JAX_PLATFORMS unset, a TPU attached, yet JAX came up on the CPU."""
    r = _python(
        "from jax._src import hardware_utils\n"
        "hardware_utils.num_available_tpu_chips_and_device_id = "
        "lambda: (1, None)\n"
        "from repro.runtime import require_no_cpu_fallback\n"
        "require_no_cpu_fallback()\n",
        {}, drop=("JAX_PLATFORMS",))
    assert r.returncode != 0
    assert "refusing to run there" in r.stderr


def test_explicit_cpu_platform_is_obeyed():
    assert runtime.require_no_cpu_fallback() == "cpu"
    assert not runtime.holds_tpu()


def test_supervisor_refuses_to_spawn_from_a_tpu_holder(tmp_path, monkeypatch):
    from repro.serve.cluster.replica import ReplicaSupervisor

    monkeypatch.setattr(runtime, "holds_tpu", lambda: True)
    sup = ReplicaSupervisor(str(tmp_path), num_replicas=2)
    with pytest.raises(RuntimeError, match="in-process"):
        sup.start(timeout_s=1.0)
    assert all(p is None for p in sup._procs)


def test_isolate_parent_never_starts_a_backend(tmp_path):
    """`launch.batch --isolate` spawns its cells one at a time from a parent
    that has started no JAX backend, so each child can own the chip."""
    r = _python(
        "import subprocess, sys\n"
        "from jax._src import xla_bridge\n"
        "from repro.launch import batch\n"
        "seen = []\n"
        "def fake_run(cmd, **kw):\n"
        "    seen.append(xla_bridge.backends_are_initialized())\n"
        "    return subprocess.CompletedProcess(cmd, 0, '', '')\n"
        "batch.subprocess.run = fake_run\n"
        f"batch.main(['--isolate', '--out', {str(tmp_path)!r}, "
        "'--kernels', 'matern32', '--seeds', '2', '--smoke'])\n"
        "print(seen, xla_bridge.backends_are_initialized())\n",
        {})
    assert r.returncode == 0, r.stderr
    assert r.stdout.split("\n")[-2] == "[False, False] False"
