"""Scheduler: 90th percentile over the window's requests of (start of the
admission call that took the request - due time)."""
import tails


def read(run):
    waits = [run.admitted_at[r] - d for r, d in run.due.items()
             if r in run.admitted_at]
    return tails.percentile(waits, 90)
