"""The whole outer step's share of the chip's bf16 peak, in percent: the
operations of every step in the window (bench/flops.py, from each step's
solver epochs) over the window's seconds and the peak of bench/peaks.json."""
from bench import flops


def read(ctx):
    win = ctx["window"]
    if not win.histories:
        return None
    config = ctx["cell"].config
    n, d, s = config["n_train"], config["d"], config["num_probes"]
    total = sum(flops.step_flops(n, d, s, float(e))
                for h in win.histories for e in h["epochs"])
    return 100.0 * total / win.seconds / flops.peak(ctx["device_kind"])
