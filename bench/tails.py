"""Latency arithmetic of the end-to-end metrics, on the harness's clock.

A request is timed from when it was due. Its first token is delivered at
the end of the admission call that prefilled it; later tokens at the end of
the decode block that produced them. A block that delivers k tokens to a
request counts as k gaps of (delivery - previous delivery) / k, so a stall
between blocks (an admission's prefill) shows in every gap it lengthened.
"""
from __future__ import annotations

from collections import defaultdict
from typing import Dict, Iterable, List, Tuple

import numpy as np


def percentile(values: Iterable[float], q: float):
    v = np.asarray(list(values), float)
    if v.size == 0:
        return None
    return float(np.percentile(v, q))


def by_request(deliveries: Iterable[Tuple[int, float, int]]
               ) -> Dict[int, List[Tuple[float, int]]]:
    out: Dict[int, List[Tuple[float, int]]] = defaultdict(list)
    for rid, t, k in deliveries:
        out[rid].append((t, k))
    for v in out.values():
        v.sort()
    return out


def itl_gaps(deliveries, t0: float, t1: float) -> List[float]:
    """Every gap between consecutive output tokens delivered in (t0, t1]."""
    gaps: List[float] = []
    for seq in by_request(deliveries).values():
        for (ta, _), (tb, k) in zip(seq, seq[1:]):
            if t0 < tb <= t1:
                gaps += [(tb - ta) / k] * k
    return gaps


def tokens_in(deliveries, t0: float, t1: float) -> int:
    return sum(k for _, t, k in deliveries if t0 < t <= t1)


def ttfts(deliveries, due: Dict[int, float]) -> List[float]:
    """First token time less due time, for each rid in `due` that got one."""
    first: Dict[int, float] = {}
    for rid, t, _ in deliveries:
        if rid in due and (rid not in first or t < first[rid]):
            first[rid] = t
    return [first[r] - due[r] for r in due if r in first]
