"""Engine host loop: programs lowered inside the window (each is then
compiled or loaded from the persistent cache while requests wait)."""


def read(run):
    return float(sum(1 for t in run.lowered_at if run.t_open <= t <= run.t_end))
