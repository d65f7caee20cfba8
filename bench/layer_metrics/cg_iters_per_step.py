"""Mean solver iterations per outer step over the window's steps, from the
iteration counts each fit returns in its history."""
import numpy as np


def read(ctx):
    hists = ctx["window"].histories
    if not hists:
        return None
    return float(np.mean(np.concatenate([h["iters"] for h in hists])))
