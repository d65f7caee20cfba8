"""Outer MLL steps completed per second over the whole window (host clock),
every step of every fit counted."""


def read(ctx):
    win = ctx["window"]
    if win.steps == 0:
        return None
    return win.steps / win.seconds
