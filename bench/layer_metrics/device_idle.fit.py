"""Share of the window in which no operation ran on the device, in percent,
from the profiler trace (1 - busy / window, averaged over the chips)."""


def read(ctx):
    trace = ctx["trace"]
    if trace is None or trace.window_s <= 0:
        return None
    return 100.0 * (1.0 - trace.busy_s / trace.window_s)
