"""Names of a fit's phases, defined once (stdlib only).

Device side: the ``jax.named_scope`` names around the parts of one outer
step. They reach the compiled program's ``metadata={op_name="..."}``, so a
profile of a fit can put every device operation down to a phase. A scope
nests in the path of the operations under it: the kernel MVM of a solve
reads ``.../gp.solve/.../gp.mvm/...``.

Host side: the ``fit.*`` spans (:func:`repro.obs.trace.span`) that
``repro.core.fit`` and ``fit_batch`` open around their own phases. Under a
profiler session each span is also an event on the profile's host plane,
on the clock the device planes share.

``docs/observability.md`` lists both with what each covers.
"""

# One outer step (repro.core.outer._outer_step and the solvers).
TARGETS = "gp.targets"  # probe draws and right-hand sides
SOLVE = "gp.solve"  # the whole linear solve
PRECOND = "gp.precond"  # preconditioner build and every apply
MVM = "gp.mvm"  # one full H @ V (HOperator.mvm, every backend)
GRAD = "gp.grad"  # the gradient pass, its own kernel MVMs included
ADAM = "gp.adam"  # the hyperparameter update

DEVICE_SCOPES = (TARGETS, SOLVE, PRECOND, MVM, GRAD, ADAM)

# The driver's host-side phases (repro.core.driver.fit / fit_batch).
FIT_INIT = "fit.init"  # state, probes, restore
FIT_CHUNK = "fit.chunk"  # dispatch of one outer_scan and the wait for it
FIT_METRICS = "fit.metrics"  # device-to-host copies, history append
FIT_EVAL = "fit.eval"
FIT_CKPT = "fit.ckpt"
FIT_FINISH = "fit.finish"
