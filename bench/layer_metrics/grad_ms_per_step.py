"""Device milliseconds per outer step in the gradient pass (``gp.grad``,
its own kernel MVMs included), over the window's chunks that the device
trace holds (bench/scopes.py)."""
from bench import scopes


def read(ctx):
    phases = scopes.window_phases(ctx)
    if phases is None or phases.steps <= 0:
        return None
    return 1e3 * phases.seconds["grad"] / phases.steps
