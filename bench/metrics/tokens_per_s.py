"""Output tokens delivered inside the window over the window's seconds.
The window runs from its opening to the end of the last engine turn that
started before its close."""
import tails


def read(run):
    n = tails.tokens_in(run.deliveries, run.t_open, run.t_end)
    return n / (run.t_end - run.t_open) if n else None
