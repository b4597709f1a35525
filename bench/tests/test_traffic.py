"""The generator: deterministic by seed; a seed never changes the amount of
work. An open loop's schedule is one Poisson draw from the mix's schedule
seed, a closed loop's sizes one stratified set in an order from the seed."""
import numpy as np
import pytest

import traffic
from tiny import CLOSED, OPEN

BIG = 2**31 + 12345


def _key(plan):
    return [(r.rid, r.due, r.max_new, r.prompt.tobytes()) for r in plan.requests]


@pytest.mark.parametrize("mix", [OPEN, CLOSED], ids=["open", "closed"])
def test_same_seed_same_requests(mix):
    a = traffic.make(mix, BIG, vocab=256, slots=4, seconds=5)
    b = traffic.make(mix, BIG, vocab=256, slots=4, seconds=5)
    assert _key(a) == _key(b)


@pytest.mark.parametrize("mix", [OPEN, CLOSED], ids=["open", "closed"])
def test_other_seed_same_work_other_order(mix):
    a = traffic.make(mix, 7, vocab=256, slots=4, seconds=5)
    b = traffic.make(mix, BIG, vocab=256, slots=4, seconds=5)
    la = sorted(len(r.prompt) for r in a.requests)
    lb = sorted(len(r.prompt) for r in b.requests)
    assert la == lb
    if mix["loop"] == "open":       # the same schedule, other token ids
        assert [(r.due, r.max_new, len(r.prompt)) for r in a.requests] == \
            [(r.due, r.max_new, len(r.prompt)) for r in b.requests]
    assert _key(a) != _key(b)


def test_open_loop_schedule():
    plan = traffic.make(OPEN, 3, vocab=256, slots=4, seconds=10)
    n = round(OPEN["rate_per_s"] * (OPEN["preroll_s"] + 10))
    dues = [r.due for r in plan.requests]
    assert len(dues) == n and dues == sorted(dues)
    assert -OPEN["preroll_s"] <= dues[0] and dues[-1] < 10
    lo, hi = traffic.length_bounds(OPEN["prompt"])
    assert all(lo <= len(r.prompt) <= hi for r in plan.requests)
    assert all(0 <= int(r.prompt.max()) < 256 for r in plan.requests)


def test_open_loop_gaps_are_exponential_with_bursts():
    """Arrivals are Poisson: gaps about exponential at the mix's rate, so
    runs of short gaps occur; another schedule seed gives another draw."""
    mix = dict(OPEN, rate_per_s=2.0, preroll_s=0.0)
    plan = traffic.make(mix, 1, vocab=256, slots=4, seconds=2000)
    gaps = np.diff([r.due for r in plan.requests])
    assert abs(gaps.mean() - 0.5) < 0.03
    # exponential: P(gap < mean/4) = 1 - e^-1/4 = 0.221; P(gap > 2 mean) = e^-2
    assert abs((gaps < 0.125).mean() - 0.221) < 0.03
    assert abs((gaps > 1.0).mean() - 0.135) < 0.03
    short = gaps < 0.25
    assert (short[:-2] & short[1:-1] & short[2:]).any()    # three in a row
    other = traffic.make(dict(mix, schedule_seed=2), 1, vocab=256, slots=4,
                         seconds=2000)
    assert [r.due for r in other.requests] != [r.due for r in plan.requests]


def test_closed_loop_first_wave_is_residual():
    plan = traffic.make(CLOSED, 3, vocab=256, slots=4, seconds=10)
    assert len(plan.clients) == 4
    assert all(len(c) == CLOSED["requests_per_client"] for c in plan.clients)
    first = [c[0] for c in plan.clients]
    assert all(r.max_new >= 1 for r in first)
    assert sum(r.max_new for r in first) < sum(
        r.max_new for c in plan.clients for r in c[1:]) / (CLOSED["requests_per_client"] - 1)


def test_quantiles_are_stratified():
    q = traffic.quantiles({"dist": "loguniform", "min": 100, "max": 10000}, 4)
    assert list(q) == [178, 562, 1778, 5623]
