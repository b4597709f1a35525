"""Median over every request due in the window of (first token delivered -
due time)."""
import tails


def read(run):
    return tails.percentile(tails.ttfts(run.deliveries, run.due), 50)
