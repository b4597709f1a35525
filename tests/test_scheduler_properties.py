"""Property tests for admission scheduling and block sizing.

The properties pinned here are the resilience PR's admission invariants:

  * `_block_len` over any mix of live budgets never overshoots the
    earliest completion (slots retire exactly at block boundaries, the
    invariant the scan==stepwise RNG guarantee rides on),
  * sub-quantum tail requeue keeps FCFS order exactly — admitted ++
    requeued ++ untouched is the original queue,
  * under a queue full of malformed requests, every rejection carries the
    right reason, every valid request still admits, and nothing is ever
    dropped on the floor (admitted + rejected == submitted).
"""
import collections

import numpy as np
from hypothesis import given, settings, strategies as st

from repro.serving.engine import Request, ServingEngine
from repro.serving.scheduler import Scheduler


class _EngineStub:
    """Just the slot state `_block_len` reads — no model, no devices."""

    def __init__(self, budgets, free, scan_steps):
        self.slot_budget = np.asarray(budgets, np.int32)
        self.slot_free = list(free)
        self.slots = len(budgets)
        self.scan_steps = scan_steps


@settings(deadline=None, max_examples=40)
@given(scan_steps=st.integers(1, 16),
       seed=st.integers(0, 10_000))
def test_block_len_never_overshoots_any_live_slot(scan_steps, seed):
    rng = np.random.RandomState(seed)
    slots = int(rng.randint(1, 9))
    budgets = rng.randint(1, 64, size=slots)
    free = rng.rand(slots) < 0.4
    n = ServingEngine._block_len(
        _EngineStub(budgets, free, scan_steps))
    live = [int(b) for b, f in zip(budgets, free) if not f]
    if not live:
        assert n == 0
        return
    assert 1 <= n <= scan_steps
    # the invariant everything else rides on: no live slot's budget is
    # overshot, and the earliest completion lands exactly on the boundary
    assert all(n <= b for b in live)
    assert n == min(min(live), scan_steps)


@settings(deadline=None, max_examples=40)
@given(quantum=st.integers(1, 5),
       num_free=st.integers(1, 12),
       seed=st.integers(0, 10_000))
def test_subquantum_tail_requeue_preserves_fcfs(quantum, num_free, seed):
    rng = np.random.RandomState(seed)
    n = int(rng.randint(1, 14))
    lens = rng.randint(1, 24, size=n)
    pending = collections.deque(
        Request(rid=i, prompt=np.ones((int(l),), np.int32))
        for i, l in enumerate(lens))
    sched = Scheduler(max_prefill_tokens=8192, pad_to=16,
                      slot_quantum=quantum)
    plan = sched.plan(pending, num_free=num_free)
    assert plan is not None
    took = [r.rid for r in plan.requests]
    left = [r.rid for r in pending]
    # FCFS exactly: what was admitted is the queue's head, what remains
    # (requeued tail included) is the rest, in submission order
    assert took + left == list(range(n))
    assert len(took) <= num_free
    # divisibility-aware trim: any batch larger than one quantum is a
    # quantum multiple (a lone sub-quantum batch still admits — liveness)
    if len(took) > quantum:
        assert len(took) % quantum == 0
    assert sched.take_rejected() == []


@settings(deadline=None, max_examples=40)
@given(vocab=st.integers(8, 64),
       max_prompt_len=st.integers(4, 32),
       seed=st.integers(0, 10_000))
def test_rejections_under_full_queue_account_for_everything(
        vocab, max_prompt_len, seed):
    rng = np.random.RandomState(seed)
    n = int(rng.randint(1, 16))
    reqs, expect_bad = [], {}
    for i in range(n):
        flavor = rng.randint(0, 5)
        if flavor == 0:
            prompt = np.zeros((0,), np.int32)
            expect_bad[i] = "empty prompt"
        elif flavor == 1:
            prompt = np.full((3,), vocab + 5, np.int32)
            expect_bad[i] = "out of range"
        elif flavor == 2:
            prompt = np.array([-1, 1], np.int32)
            expect_bad[i] = "out of range"
        elif flavor == 3:
            prompt = np.ones((max_prompt_len + 1,), np.int32)
            expect_bad[i] = "longer than max_prompt_len"
        else:
            length = int(rng.randint(1, max_prompt_len + 1))
            prompt = rng.randint(0, vocab, size=length).astype(np.int32)
        reqs.append(Request(rid=i, prompt=prompt))
    pending = collections.deque(reqs)
    sched = Scheduler(max_prefill_tokens=8192, pad_to=16,
                      max_prompt_len=max_prompt_len, vocab_size=vocab)
    admitted, rejected = [], []
    while pending:
        plan = sched.plan(pending, num_free=4)
        rejected += sched.take_rejected()
        if plan is not None:
            admitted += [r.rid for r in plan.requests]
        else:
            assert not pending    # None only once everything drained
    # total accounting: nothing dropped, nothing served twice
    assert sorted(admitted + [r.rid for r, _ in rejected]) == list(range(n))
    assert sorted(r.rid for r, _ in rejected) == sorted(expect_bad)
    for req, reason in rejected:
        assert expect_bad[req.rid] in reason, (expect_bad[req.rid], reason)
