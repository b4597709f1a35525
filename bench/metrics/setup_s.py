"""Seconds from process start to the window's opening: weights, engine,
loading or compiling every program the traffic reaches, warm-up, and the
traffic served before the window."""


def read(run):
    return run.setup_s
