"""Paged ring KV: block tables, prefix sharing, per-layer windows.

The paged cache contract (serving/paged.py + layers.init_paged_kv_cache)
promises paged decode is BITWISE the contiguous ring — PAGE_SIZE divides
every sparse allocation by construction, so the gather-view the kernel
sees has exactly the contiguous physical width. Everything here pins that:

  * paged engine == contiguous engine token-for-token, on the ref and
    pallas decode impls, sequential and speculative (k=2), and under
    chaos cache-poison quarantine,
  * prefix sharing: a batch with a common system prompt prefills the
    prefix ONCE (prefill_tokens_computed < 0.5x the no-sharing engine),
    block-shares the untouched prefix pages, copy-on-writes at the
    divergence point — and still emits identical tokens,
  * per-layer `window_schedule` (gemma2-style local/global alternation)
    allocates DISTINCT cache capacities per layer and decodes unchanged,
  * bounded retry: a request whose slot died with the donated caches is
    readmitted through the normal queue up to `max_retries` times,
  * host-side invariants (refcounts never negative, the free list never
    double-frees, trie match == longest common prefix) swept generatively
    under hypothesis.
"""
import collections
import dataclasses

import jax
import numpy as np
import pytest

from hypothesis import given, settings, strategies as st

from repro.configs import get_smoke_config, with_swat
from repro.core import model as Mod
from repro.serving.engine import Request, ServingEngine
from repro.serving.faults import FaultPlan
from repro.serving.paged import (BlockAllocator, PagedManager, RadixTrie,
                                 batch_lcp)
from repro.serving.scheduler import Scheduler


@pytest.fixture(scope="module")
def swat_setup():
    cfg = with_swat(get_smoke_config("llama3p2_1b"), window=16, num_global=4)
    params = Mod.init_model(jax.random.PRNGKey(0), cfg)
    return cfg, params


@pytest.fixture(scope="module")
def dense_setup():
    cfg = get_smoke_config("llama3p2_1b")
    params = Mod.init_model(jax.random.PRNGKey(0), cfg)
    return cfg, params


def _run(cfg, params, reqs, **kw):
    eng = ServingEngine(cfg, params, **kw)
    res = eng.run(reqs)
    return eng, {r.rid: r for r in res}


def _reqs(cfg, seed=0, n=4, budget=8, temps=None):
    rng = np.random.RandomState(seed)
    lens = rng.randint(5, 30, n)
    return [Request(rid=i,
                    prompt=rng.randint(0, cfg.vocab_size, (l,)).astype(
                        np.int32),
                    max_new_tokens=budget,
                    temperature=0.0 if temps is None else temps[i])
            for i, l in enumerate(lens)]


# ------------------------------------------------------- token identity ----

@pytest.mark.parametrize("impl", ["ref", "pallas"])
@pytest.mark.parametrize("which", ["swat", "dense"])
def test_paged_token_identical(impl, which, swat_setup, dense_setup):
    """Paged decode == contiguous decode, bitwise, with slot eviction and
    reuse (more requests than slots) and a sampled row in the mix."""
    cfg, params = swat_setup if which == "swat" else dense_setup
    temps = [0.0, 0.0, 0.9, 0.0]
    kw = dict(batch_slots=2, max_len=256, decode_impl=impl)
    _, a = _run(cfg, params, _reqs(cfg, temps=temps), **kw)
    _, b = _run(cfg, params, _reqs(cfg, temps=temps), kv_layout="paged",
                **kw)
    for rid in a:
        assert a[rid].tokens == b[rid].tokens, (impl, which, rid)


def test_paged_speculative_identical(swat_setup):
    cfg, params = swat_setup
    kw = dict(batch_slots=2, max_len=256, speculative=2)
    _, a = _run(cfg, params, _reqs(cfg, seed=3, n=3, budget=10), **kw)
    eng, b = _run(cfg, params, _reqs(cfg, seed=3, n=3, budget=10),
                  kv_layout="paged", **kw)
    for rid in a:
        assert a[rid].tokens == b[rid].tokens
    assert eng.stats["spec_steps"] > 0


def test_paged_chaos_quarantine(swat_setup):
    """Cache poison on a paged slot quarantines only that slot; the
    poison forces the slot's blocks private first so refcount-shared
    pages can't leak NaN into other slots."""
    cfg, params = swat_setup
    plan = FaultPlan(poison_cache=((1, 3),))
    kw = dict(batch_slots=3, max_len=256, kv_layout="paged")
    _, clean = _run(cfg, params, _reqs(cfg, seed=5, n=3, budget=10), **kw)
    _, hurt = _run(cfg, params, _reqs(cfg, seed=5, n=3, budget=10),
                   faults=plan, **kw)
    assert hurt[1].status == "poisoned"
    assert hurt[1].tokens == clean[1].tokens[:len(hurt[1].tokens)]
    for rid in (0, 2):
        assert hurt[rid].status == "ok"
        assert hurt[rid].tokens == clean[rid].tokens


# ------------------------------------------------------- prefix sharing ----

@pytest.mark.parametrize("which", ["swat", "dense"])
def test_prefix_sharing_identical_and_cheaper(which, swat_setup,
                                              dense_setup):
    """>= 8 requests behind one system prompt: sharing prefills the prefix
    once (< 0.5x the tokens), dedups prefix blocks in the pool, and still
    produces identical tokens — COW covers the post-prefix divergence."""
    cfg, params = swat_setup if which == "swat" else dense_setup
    rng = np.random.RandomState(7)
    sys_p = rng.randint(0, cfg.vocab_size, (96,)).astype(np.int32)
    prompts = [np.concatenate(
        [sys_p, rng.randint(0, cfg.vocab_size, (16,)).astype(np.int32)])
        for _ in range(8)]

    def reqs():
        return [Request(rid=i, prompt=p, max_new_tokens=6)
                for i, p in enumerate(prompts)]
    kw = dict(batch_slots=8, max_len=256, kv_layout="paged",
              prefill_chunk=32)
    off, r_off = _run(cfg, params, reqs(), **kw)
    on = ServingEngine(cfg, params, share_prefix=True, **kw)
    # admit first so pool occupancy is observable before slots retire
    pend = collections.deque(reqs())
    on._run_t0 = 0.0
    on._admit(pend)
    shared_blocks = on.paged_stats()["blocks_in_use"]
    on._run_t0 = None
    r_on = {r.rid: r for r in on.run(list(pend)) + on.take_completed()}
    for rid in r_off:
        assert r_off[rid].tokens == r_on[rid].tokens, (which, rid)
    assert on.stats["prefill_prefix_shared"] >= 1
    ratio = (on.stats["prefill_tokens_computed"]
             / off.stats["prefill_tokens_computed"])
    assert ratio < 0.5, ratio
    if which == "dense":
        # dense layers map positions to pages 1:1, so the 96-token prefix
        # must dedup: leader pages + one divergence page per follower,
        # far below 8 private full allocations
        total = on.paged_stats()["blocks_total"]
        assert shared_blocks < total // 2, (shared_blocks, total)


def test_scheduler_plans_prefix_len():
    sched = Scheduler(max_prefill_tokens=8192, pad_to=16)
    shared = np.arange(40, dtype=np.int32)
    pend = collections.deque([
        Request(rid=0, prompt=np.concatenate([shared, [100, 101]])),
        Request(rid=1, prompt=np.concatenate([shared, [200, 201, 202]])),
        Request(rid=2, prompt=np.concatenate([shared, [300]])),
    ])
    plan = sched.plan(pend, 3)
    assert plan.prefix_len == 40
    pend = collections.deque([
        Request(rid=0, prompt=np.asarray([1, 2, 3], np.int32)),
        Request(rid=1, prompt=np.asarray([4, 5, 6], np.int32)),
    ])
    assert sched.plan(pend, 2).prefix_len == 0
    pend = collections.deque([Request(rid=0, prompt=shared)])
    assert sched.plan(pend, 1).prefix_len == 0  # single row: nothing shared


# --------------------------------------------------- per-layer windows -----

def test_gemma2_window_schedule_distinct_capacities():
    """gemma2-style local/global alternation with per-layer windows: the
    paged layout allocates DISTINCT per-layer cache capacities and decode
    is unchanged between layouts."""
    cfg = get_smoke_config("gemma2_2b")
    assert cfg.layer_pattern == ("local_attn", "attn")
    sched = tuple(8 if k == "local_attn" else 24 for k in cfg.layer_pattern)
    cfg2 = dataclasses.replace(cfg, window_schedule=sched)
    layout = Mod.paged_layout(cfg2, 256)
    caps = [layout[i]["cap"] for i in sorted(layout)]
    # local layer: w=8 -> 9 rows; global layer: dense overridden to w=24
    # -> 25 rows (not the dense 256) — genuinely per-layer capacities
    assert caps == [9, 25], caps
    base = Mod.paged_layout(cfg, 256)
    assert [base[i]["cap"] for i in sorted(base)] == [17, 256]
    params = Mod.init_model(jax.random.PRNGKey(0), cfg2)
    kw = dict(batch_slots=2, max_len=256)
    _, a = _run(cfg2, params, _reqs(cfg2, seed=9, n=2, budget=6), **kw)
    _, b = _run(cfg2, params, _reqs(cfg2, seed=9, n=2, budget=6),
                kv_layout="paged", **kw)
    for rid in a:
        assert a[rid].tokens == b[rid].tokens


def test_window_schedule_validation():
    cfg = get_smoke_config("gemma2_2b")
    with pytest.raises(AssertionError):
        dataclasses.replace(cfg, window_schedule=(8,))      # wrong length
    with pytest.raises(AssertionError):
        dataclasses.replace(cfg, window_schedule=(0, None))  # w must be > 0
    ok = dataclasses.replace(cfg, window_schedule=(None, 32))
    assert ok.window_schedule == (None, 32)


# ------------------------------------------------------- bounded retry -----

@pytest.mark.parametrize("layout", ["contiguous", "paged"])
def test_retry_readmission_after_cache_loss(layout, swat_setup):
    """A mid-execution kernel failure that consumed the donated caches
    finalizes slots as 'failed' — unless the request carries max_retries,
    in which case it is readmitted through the normal queue and re-served
    from the prompt, with the retry count surfaced on the Result."""
    cfg, params = swat_setup
    rng = np.random.RandomState(11)
    prompts = [rng.randint(0, cfg.vocab_size, (12,)).astype(np.int32)
               for _ in range(2)]
    eng = ServingEngine(cfg, params, batch_slots=2, max_len=256,
                        kv_layout=layout)
    reqs = [Request(rid=0, prompt=prompts[0], max_new_tokens=8,
                    max_retries=1),
            Request(rid=1, prompt=prompts[1], max_new_tokens=8)]
    pend = collections.deque(reqs)
    eng._run_t0 = 0.0
    eng._admit(pend)
    eng._decode_block(2)
    for leaf in jax.tree.leaves(eng.caches):
        leaf.delete()          # simulate consumed donation
    with pytest.warns(RuntimeWarning):
        done = eng._kernel_fallback(RuntimeError("boom"), 2)
    eng._run_t0 = None
    rest = eng.run([])
    res = {r.rid: r for r in done + rest + eng.take_completed()}
    assert res[1].status == "failed" and res[1].retries == 0
    assert res[0].status == "ok" and res[0].retries == 1
    assert eng.stats["readmitted"] == 1
    clean = ServingEngine(cfg, params, batch_slots=2, max_len=256).run(
        [Request(rid=0, prompt=prompts[0], max_new_tokens=8)])
    assert res[0].tokens == clean[0].tokens


def test_retry_zero_still_fails(swat_setup):
    cfg, params = swat_setup
    rng = np.random.RandomState(12)
    eng = ServingEngine(cfg, params, batch_slots=1, max_len=256,
                        kv_layout="paged")
    req = Request(rid=0, prompt=rng.randint(0, cfg.vocab_size, (10,)
                                            ).astype(np.int32),
                  max_new_tokens=8)
    pend = collections.deque([req])
    eng._run_t0 = 0.0
    eng._admit(pend)
    for leaf in jax.tree.leaves(eng.caches):
        leaf.delete()
    with pytest.warns(RuntimeWarning):
        done = eng._kernel_fallback(RuntimeError("boom"), 1)
    eng._run_t0 = None
    assert done[0].status == "failed" and done[0].retries == 0


# --------------------------------------------------- host-side invariants --

@settings(max_examples=30)
@given(seed=st.integers(min_value=0, max_value=10_000),
       num_blocks=st.integers(min_value=4, max_value=24))
def test_allocator_invariants(seed, num_blocks):
    """Refcounts never go negative, the free list never double-holds an
    id, reserved ids are never handed out — against a reference model."""
    rng = np.random.RandomState(seed)
    reserved = set(rng.choice(num_blocks, size=rng.randint(0, 3),
                              replace=False).tolist())
    alc = BlockAllocator(num_blocks, reserved=reserved)
    ref: dict = {}
    live: list = []
    for _ in range(200):
        op = rng.randint(0, 3)
        if op == 0 and alc.free_count:
            bid = alc.alloc()
            assert bid not in reserved
            assert ref.get(bid, 0) == 0, "alloc returned a referenced block"
            ref[bid] = 1
            live.append(bid)
        elif op == 1 and live:
            bid = live[rng.randint(len(live))]
            alc.retain(bid)
            ref[bid] += 1
            live.append(bid)
        elif op == 2 and live:
            bid = live.pop(rng.randint(len(live)))
            alc.release(bid)
            ref[bid] -= 1
            assert ref[bid] >= 0
        for bid in set(live):
            assert alc.refcount(bid) == ref[bid]
    assert alc.allocated == sum(1 for v in ref.values() if v > 0)
    # double-free of anything already at refcount 0 must raise
    dead = [b for b, v in ref.items() if v == 0]
    if dead:
        with pytest.raises(RuntimeError):
            alc.release(dead[0])
    # retain of a never-allocated block must raise
    with pytest.raises(RuntimeError):
        BlockAllocator(4).retain(0)


@settings(max_examples=30)
@given(seed=st.integers(min_value=0, max_value=10_000),
       nseq=st.integers(min_value=1, max_value=8),
       alpha=st.integers(min_value=2, max_value=5))
def test_radix_trie_matches_bruteforce_lcp(seed, nseq, alpha):
    """Trie longest_prefix == max pairwise LCP against every inserted
    sequence, on a small alphabet (forces edge splits)."""
    rng = np.random.RandomState(seed)
    seqs = [rng.randint(0, alpha, rng.randint(1, 20)).tolist()
            for _ in range(nseq)]
    trie = RadixTrie()
    for s in seqs:
        trie.insert(s)
    assert len(trie) == nseq

    def lcp(a, b):
        n = 0
        for x, y in zip(a, b):
            if x != y:
                break
            n += 1
        return n
    for _ in range(10):
        q = rng.randint(0, alpha, rng.randint(0, 25)).tolist()
        want = max((lcp(q, s) for s in seqs), default=0)
        assert trie.longest_prefix(q) == want, (q, seqs)
    # batch_lcp == brute force common prefix of ALL rows
    want_all = min((lcp(seqs[0], s) for s in seqs[1:]),
                   default=len(seqs[0])) if nseq > 1 else 0
    assert batch_lcp(seqs) == (want_all if nseq > 1 else 0)


@settings(max_examples=25)
@given(seed=st.integers(min_value=0, max_value=10_000))
def test_paged_manager_admit_free_cow_cycle(seed):
    """Random admit/free/cow cycles on a shared-mode manager: parked
    tables always point at scratch, occupied tables never reference a
    freed block, COW leaves every written block exclusively owned, and
    the shared-prefix retains keep the allocator balanced (reset drains
    to zero without a double-free)."""
    rng = np.random.RandomState(seed)
    layout = {0: {"page": 4, "nb": 6, "cap": 24, "g": 2, "ring": 22},
              2: {"page": 4, "nb": 3, "cap": 12, "g": 0, "ring": 12}}
    slots = 4
    pm = PagedManager(layout, slots, mode="shared")
    pos = np.zeros(slots, np.int64)
    for _ in range(60):
        op = rng.randint(0, 3)
        parked = [s for s in range(slots) if pm.parked[s]]
        busy = [s for s in range(slots) if not pm.parked[s]]
        if op == 0 and parked:
            take = sorted(rng.choice(parked,
                                     rng.randint(1, len(parked) + 1),
                                     replace=False).tolist())
            l_pad = int(rng.randint(8, 30))
            prefix = int(rng.randint(0, l_pad)) if len(take) >= 2 else 0
            pm.admit(take, [l_pad] * len(take), prefix_len=prefix)
            pos[take] = l_pad
        elif op == 1 and busy:
            s = busy[rng.randint(len(busy))]
            pm.free(s)
            pm.free(s)                      # idempotent, never double-free
        elif op == 2 and busy:
            span = int(rng.randint(1, 6))
            moves = pm.cow_moves({s: int(pos[s]) for s in busy}, span)
            for i, geo in layout.items():
                page, g, ring = geo["page"], geo["g"], geo["ring"]
                for s in busy:
                    p = np.arange(pos[s], pos[s] + span)
                    rows = np.where(p < g, p, g + (p - g) % ring)
                    for b in np.unique(rows // page):
                        bid = int(pm.tables[i][s][b])
                        assert pm.alloc[i].refcount(bid) == 1, \
                            "COW left a written block shared"
                for src, dst in moves[i]:
                    assert pm.alloc[i].refcount(dst) >= 1
            pos[busy] += span
        for i in pm.layout:
            for s in range(slots):
                if pm.parked[s]:
                    assert (pm.tables[i][s] == pm.scratch_id(i, s)).all()
                else:
                    for bid in pm.tables[i][s]:
                        assert pm.alloc[i].refcount(int(bid)) >= 1
    for s in range(slots):
        pm.free(s)
    assert pm.blocks_in_use() == 0


# ------------------------------------------------------- sharded paged -----

@pytest.mark.slow
def test_paged_slot_parallel_mesh():
    """4-device slot-parallel mesh: the paged engine (local-id pool, one-hot
    gather) is token-identical to the single-device contiguous engine, and
    the pool/table leaves actually shard over the slot axis."""
    import os
    import subprocess
    import sys
    import textwrap
    from pathlib import Path
    root = Path(__file__).resolve().parents[1]
    code = """
        import jax
        import numpy as np
        from repro.configs import get_smoke_config, with_swat
        from repro.core import model as Mod
        from repro.launch import mesh as mesh_lib
        from repro.serving.engine import Request, ServingEngine

        assert jax.device_count() == 4, jax.devices()
        cfg = with_swat(get_smoke_config("llama3p2_1b"), window=16,
                        num_global=4)
        params = Mod.init_model(jax.random.PRNGKey(0), cfg)
        rng = np.random.RandomState(3)
        prompts = [rng.randint(0, cfg.vocab_size, (l,)).astype(np.int32)
                   for l in (12, 30, 7, 18, 25, 10)]
        temps = [0.0, 1.5, 0.0, 2.5, 1.0, 0.0]
        budgets = [6, 9, 4, 7, 5, 8]

        def reqs():
            return [Request(rid=i, prompt=prompts[i],
                            max_new_tokens=budgets[i],
                            temperature=temps[i]) for i in range(6)]

        def run(mesh, **kw):
            eng = ServingEngine(cfg, params, batch_slots=4, max_len=128,
                                scan_steps=4, seed=11, mesh=mesh, **kw)
            return eng, {r.rid: r.tokens for r in eng.run(reqs())}

        _, base = run(None)
        eng, paged = run(mesh_lib.make_debug_mesh(4, 1), kv_layout="paged")
        assert paged == base, (paged, base)

        # the pool must actually shard: slot dim over 'data' on pk/pv and
        # on the block table (replication is the silent failure mode)
        seen = {"pk": 0, "table": 0}
        def visit(path, leaf):
            name = str(path[-1].key) if hasattr(path[-1], "key") else ""
            if name in seen:
                spec = tuple(leaf.sharding.spec)
                axes = [a for e in spec if e is not None
                        for a in ((e,) if isinstance(e, str) else e)]
                assert "data" in axes, (name, spec)
                assert spec[1] == "data", (name, spec)   # slot dim
                seen[name] += 1
            return leaf
        jax.tree_util.tree_map_with_path(visit, eng.caches)
        assert seen["pk"] > 0 and seen["table"] > 0, seen
        print("SHARDED-PAGED-OK")
    """
    env = dict(os.environ)
    env["XLA_FLAGS"] = ("--xla_force_host_platform_device_count=4 "
                        + env.get("XLA_FLAGS", ""))
    env["PYTHONPATH"] = f"{root}/src"
    env["TF_CPP_MIN_LOG_LEVEL"] = "2"
    out = subprocess.run([sys.executable, "-c", textwrap.dedent(code)],
                         capture_output=True, text=True, env=env,
                         timeout=600)
    assert out.returncode == 0, \
        f"STDOUT:\n{out.stdout}\nSTDERR:\n{out.stderr}"
    assert "SHARDED-PAGED-OK" in out.stdout


def test_paged_dense_needs_page_multiple():
    """Dense layers keep max_len rows unrounded; a max_len that PAGE_SIZE
    does not divide cannot page without changing the view width (which
    would break bitwise identity) — it must refuse loudly."""
    cfg = get_smoke_config("llama3p2_1b")
    with pytest.raises(ValueError):
        Mod.paged_layout(cfg, 250)
