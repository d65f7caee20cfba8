"""Seconds from process start to the first timed step (host clock):
start-up, data, and compiling or loading every program the window runs."""


def read(ctx):
    return ctx["setup_s"]
