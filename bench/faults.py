"""Faults planted under the timed path, and the low-precision control.

Each entry is a context manager that breaks one thing in the program while
it is open and clears JAX's in-memory caches on entry and exit, so that the
programs are traced again with (and then without) the fault:

* ``control_bf16``: the program's own switch for the precision of every
  kernel-MVM contraction (``MVM_PRECISION``) set from ``HIGHEST`` (float32)
  to ``DEFAULT``, one bf16 pass on a TPU: the step below the float32 the
  configurations state. On a CPU ``DEFAULT`` is float32, so this control
  means something on a TPU only.
* ``unchanged``: the outer step returns its state unchanged (its metrics
  are still reported).
* ``half_probes``: the gradient leaves out half of the probe systems and
  takes the mean over the rest.
* ``altered``: the solver's answer for the mean system is altered where it
  is produced (scaled by 1.1).
* ``frozen``: the hyperparameters stop moving after the first step, while
  Adam's moments go on being updated.
* ``sign_flip``: the update descends the marginal likelihood instead of
  ascending it (the gradient's sign flipped where Adam takes it).

One chip only: no cell has an exchange between chips to leave out.
"""
from __future__ import annotations

import contextlib
import sys

import jax


@contextlib.contextmanager
def _patched(module, name: str, value):
    old = getattr(module, name)
    setattr(module, name, value)
    jax.clear_caches()
    try:
        yield
    finally:
        setattr(module, name, old)
        jax.clear_caches()


@contextlib.contextmanager
def control_bf16():
    holders = [m for name, m in list(sys.modules.items())
               if name.startswith("repro") and hasattr(m, "MVM_PRECISION")]
    old = {m: m.MVM_PRECISION for m in holders}
    for m in holders:
        m.MVM_PRECISION = jax.lax.Precision.DEFAULT
    jax.clear_caches()
    try:
        yield
    finally:
        for m, value in old.items():
            m.MVM_PRECISION = value
        jax.clear_caches()


def unchanged():
    from repro.core import outer

    step = outer._outer_step

    def faulty(state, x, y, cfg, numerics=None):
        _, metrics = step(state, x, y, cfg, numerics)
        return state, metrics

    return _patched(outer, "_outer_step", faulty)


def half_probes():
    from repro.core import outer

    grad = outer.mll_grad_estimate

    def faulty(x, y, params, v, targets, estimator, **kwargs):
        keep = 1 + (v.shape[1] - 1) // 2
        return grad(x, y, params, v[:, :keep], targets[:, :keep], estimator,
                    **kwargs)

    return _patched(outer, "mll_grad_estimate", faulty)


def altered():
    from repro.core import outer

    solve = outer.solve

    def faulty(*args, **kwargs):
        res = solve(*args, **kwargs)
        return res._replace(v=res.v.at[:, 0].multiply(1.1))

    return _patched(outer, "solve", faulty)


def frozen():
    from repro.core import outer

    update = outer.adam_update

    def faulty(grads, state, params, cfg, **kwargs):
        new_params, new_state = update(grads, state, params, cfg, **kwargs)
        keep = state.step >= 1
        return jax.tree.map(lambda old, new: jax.numpy.where(keep, old, new),
                            params, new_params), new_state

    return _patched(outer, "adam_update", faulty)


def sign_flip():
    from repro.core import outer

    update = outer.adam_update

    def faulty(grads, state, params, cfg, maximize=False):
        return update(grads, state, params, cfg, maximize=not maximize)

    return _patched(outer, "adam_update", faulty)


FAULTS = {"control_bf16": control_bf16, "unchanged": unchanged,
          "half_probes": half_probes, "altered": altered, "frozen": frozen,
          "sign_flip": sign_flip}
