"""The tail arithmetic: a stall inserted in the window moves itl_p95_ms and
ttft_p90_s."""
import pytest

import tails


def _steady(stall_at=(), stall=0.0):
    """4 requests decoding in blocks of 4 tokens every 0.1 s."""
    out, t = [], 0.0
    for b in range(40):
        t += 0.1 + (stall if b in stall_at else 0.0)
        out += [(r, t, 4) for r in range(4)]
    return out


def test_block_of_k_tokens_counts_k_gaps():
    gaps = tails.itl_gaps([(1, 0.0, 1), (1, 0.4, 4)], -1, 1)
    assert gaps == [pytest.approx(0.1)] * 4
    assert tails.itl_gaps([(1, 0.0, 1), (1, 0.4, 4)], 0.5, 1) == []


def test_stall_moves_itl_p95():
    base = tails.percentile(tails.itl_gaps(_steady(), 0, 10), 95)
    stalled = tails.percentile(tails.itl_gaps(_steady((3, 17, 30), 0.5), 0, 10), 95)
    assert base == pytest.approx(0.025)
    assert stalled == pytest.approx(0.15)


def test_stall_moves_ttft_p90():
    due = {r: 0.1 * r for r in range(20)}
    first = [(r, 0.1 * r + 0.05, 1) for r in range(20)]
    assert tails.percentile(tails.ttfts(first, due), 90) == pytest.approx(0.05)
    stalled = [(r, t + (1.0 if r >= 17 else 0.0), k) for r, t, k in first]
    assert tails.percentile(tails.ttfts(stalled, due), 90) > 0.5


def test_tokens_in_window():
    d = [(1, 0.5, 1), (1, 1.0, 4), (2, 2.0, 4)]
    assert tails.tokens_in(d, 0.5, 2.0) == 8
