"""Process-level JAX set-up shared by the entry points.

Two rules every entry point follows on an accelerator:

* **Compile cache.** :func:`enable_compilation_cache` keeps JAX's
  persistent compilation cache where ``JAX_COMPILATION_CACHE_DIR`` says
  (JAX reads that variable itself) and otherwise at one fixed directory
  inside the checkout, ``<repo>/.jax_cache``. The path is part of the
  cache key, so a directory that moves never hits.
* **No silent CPU fallback, one process per chip.** With ``JAX_PLATFORMS``
  unset, JAX quietly starts on the CPU when the TPU backend fails to start
  (for example because another process holds the chip).
  :func:`require_no_cpu_fallback` turns that into an error, and
  :func:`holds_tpu` lets a parent refuse to spawn workers that would need
  the chip it already holds.

Importing this module initialises no backend.
"""
from __future__ import annotations

import os
import sys
from pathlib import Path

CACHE_ENV = "JAX_COMPILATION_CACHE_DIR"
DEFAULT_CACHE_DIR = Path(__file__).resolve().parents[2] / ".jax_cache"


def enable_compilation_cache() -> str:
    """Turn on the persistent compilation cache; returns its directory.

    Touches no backend, so a parent that must stay off the chip may call it.
    """
    import jax

    if os.environ.get(CACHE_ENV):
        return os.environ[CACHE_ENV]
    jax.config.update("jax_compilation_cache_dir", str(DEFAULT_CACHE_DIR))
    return str(DEFAULT_CACHE_DIR)


def holds_tpu() -> bool:
    """True when this process has already started JAX on a TPU.

    Never initialises a backend itself: a process that has not imported
    JAX, or has not started a backend yet, holds no chip.
    """
    if "jax" not in sys.modules:
        return False
    from jax._src import xla_bridge

    if not xla_bridge.backends_are_initialized():
        return False
    import jax

    return jax.default_backend() == "tpu"


def require_no_cpu_fallback() -> str:
    """Start JAX and return its platform; raise if the TPU failed to start.

    A worker that cannot get the chip must fail, not carry on on the CPU.
    """
    import jax
    from jax._src import hardware_utils

    platform = jax.default_backend()
    if platform != "cpu" or jax.config.jax_platforms:
        # An explicit JAX_PLATFORMS is obeyed, and fails loudly in JAX.
        return platform
    chips, _ = hardware_utils.num_available_tpu_chips_and_device_id()
    if chips > 0:
        raise RuntimeError(
            f"{chips} TPU chip(s) are attached but JAX started on the CPU "
            "(is another process holding the chip?); refusing to run there"
        )
    return platform
