"""Mean relative residual norm of the probe solves at the epoch budget
(``res_z``), over every step of every fit the window completed; with
``res_steps`` in the traffic, over steps 1..res_steps of each fit. Nothing
to read where the traffic sets no budget."""
import numpy as np


def read(ctx):
    traffic = ctx["cell"].traffic
    hists = ctx["window"].histories
    if traffic.get("max_epochs") is None or not hists:
        return None
    k = traffic.get("res_steps")
    return float(np.mean(np.concatenate([h["res_z"][:k] for h in hists])))
