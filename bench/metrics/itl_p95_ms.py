"""95th percentile, in ms, over every gap between consecutive output tokens
delivered in the window (k tokens in one delivery count as k gaps)."""
import tails


def read(run):
    v = tails.percentile(tails.itl_gaps(run.deliveries, run.t_open, run.t_end), 95)
    return None if v is None else v * 1e3
