"""Device milliseconds per outer step in the linear solve (``gp.solve``:
its kernel MVMs and the rest of the solve, the preconditioner left out),
over the window's chunks that the device trace holds (bench/scopes.py)."""
from bench import scopes


def read(ctx):
    phases = scopes.window_phases(ctx)
    if phases is None or phases.steps <= 0:
        return None
    solve = phases.seconds["solve_mvm"] + phases.seconds["solve"]
    return 1e3 * solve / phases.steps
