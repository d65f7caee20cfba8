"""The solve's kernel MVMs' share of the cell's chips' bf16 peak, in
percent: the operations of the solve's sweeps over the kernel matrix
(bench/flops.py, one per CG iteration plus the initial residual, from each
step's epochs in the fits' histories) over the device seconds of the
``gp.mvm`` operations under ``gp.solve``, over the steps of the window's
chunks that the device trace holds (bench/scopes.py, averaged over the
chips), and the peak of bench/peaks.json times the cell's chips. The work
is the problem's (n, d, s), not the tiles': padding is not credited."""
import numpy as np

from bench import flops, scopes


def read(ctx):
    phases = scopes.window_phases(ctx)
    if phases is None or phases.steps <= 0 or phases.seconds["solve_mvm"] <= 0:
        return None
    epochs = np.concatenate([h["epochs"] for h in ctx["window"].histories])
    if len(epochs) < phases.steps:
        return None
    cell = ctx["cell"]
    config = cell.config
    sweep = flops.sweep_flops(config["n_train"], config["d"],
                              config["num_probes"])
    total = sweep * float(np.sum(epochs[:phases.steps] + 1.0))
    return (100.0 * total / phases.seconds["solve_mvm"]
            / (flops.peak(ctx["device_kind"]) * cell.chips))
