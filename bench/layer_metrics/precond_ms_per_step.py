"""Device milliseconds per outer step in the preconditioner
(``gp.precond``: the pivoted-Cholesky build every step and the apply every
CG iteration), over the window's chunks that the device trace holds
(bench/scopes.py)."""
from bench import scopes


def read(ctx):
    phases = scopes.window_phases(ctx)
    if phases is None or phases.steps <= 0:
        return None
    return 1e3 * phases.seconds["precond"] / phases.steps
