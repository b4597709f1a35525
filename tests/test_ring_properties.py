"""Generative sweep of the ring-cache invariants.

Ring masking has now bitten twice at hand-picked shapes (PR 2's
tile-rounding tail, PR 3's silently-dropped `spec.window`), and the
speculative rollback leans on a third property (stale slots reconstruct to
window-masked positions). So pin the whole contract down generatively
across randomized (window, num_global, lookahead, wrap-point, raggedness):

  * `ring_slot_positions` == a literal numpy FIFO simulation (insert the
    tokens one by one, remember who lives where),
  * `ring_insert_ref` == the same simulation for ragged multi-row inserts,
  * the fused pallas kernel's IN-KERNEL insert produces bitwise the same
    cache as `ring_insert_ref`, and its attention output matches the
    unfused ref oracle, across wrap points and ragged `num_new`,
  * rollback safety: after insert-then-rollback, every garbage slot
    reconstructs to a position outside every live query's window (the
    no-resurrection guarantee speculative decode relies on).
"""
import dataclasses

import jax.numpy as jnp
import numpy as np
from hypothesis import given, settings, strategies as st

from repro.core.layers import _round_capacity
from repro.core.types import AttentionSpec
from repro.kernels.ops import decode_attention
from repro.kernels.ref import ring_insert_ref, ring_slot_positions


@dataclasses.dataclass(frozen=True)
class RingGeom:
    """One randomized ring geometry: logical capacity window+1+lookahead+g
    (the serving cache law), physical width tile-rounded above it."""
    window: int
    num_global: int
    lookahead: int

    @property
    def cap(self) -> int:           # logical rotation modulus
        return self.window + 1 + self.lookahead + self.num_global

    @property
    def wcap(self) -> int:          # physical rows incl. rounding tail
        return _round_capacity(self.cap)


GEOMS = st.builds(RingGeom,
                  window=st.integers(1, 24),
                  num_global=st.sampled_from([0, 1, 3, 4]),
                  lookahead=st.integers(0, 5))


def fifo_sim(geom: RingGeom, total: int):
    """Token-by-token numpy simulation: who lives in which slot after
    inserting tokens 0..total-1. Returns (pos (W,), valid (W,))."""
    g, ring = geom.num_global, geom.cap - geom.num_global
    pos = np.full((geom.wcap,), -1, np.int64)
    for p in range(total):
        slot = p if p < g else g + (p - g) % ring
        pos[slot] = p
    return pos, pos >= 0


# no deadline: the first example pays a jit compile
@settings(deadline=None, max_examples=40)
@given(geom=GEOMS, seed=st.integers(0, 10_000))
def test_slot_positions_match_fifo_simulation(geom, seed):
    rng = np.random.RandomState(seed)
    # wrap-points: empty, partial, exactly full, wrapped, multi-wrapped
    totals = np.array([0, 1,
                       rng.randint(0, geom.cap + 1),
                       geom.cap,
                       geom.cap + rng.randint(1, geom.cap + 1),
                       rng.randint(2, 5) * geom.cap + rng.randint(0, geom.cap)
                       ], np.int32)
    t_s, valid = ring_slot_positions(jnp.asarray(totals), geom.wcap,
                                     ring_cap=geom.cap,
                                     num_global=geom.num_global)
    t_s, valid = np.asarray(t_s), np.asarray(valid)
    for b, total in enumerate(totals):
        want_pos, want_valid = fifo_sim(geom, int(total))
        assert (valid[b] == want_valid).all(), (geom, total)
        assert (t_s[b][want_valid] == want_pos[want_valid]).all(), \
            (geom, total)
        # the tile-rounding tail is NEVER valid (the PR-2 bug)
        assert not valid[b][geom.cap:].any(), (geom, total)


# no deadline: the first example pays a jit compile
@settings(deadline=None, max_examples=40)
@given(geom=GEOMS, t=st.integers(1, 6), seed=st.integers(0, 10_000))
def test_ring_insert_matches_fifo_simulation(geom, t, seed):
    """Ragged multi-row insert == insert the rows one by one in numpy,
    skipping rows past each slot's num_new."""
    t = min(t, geom.lookahead + 1)      # the engine's own allocation law
    rng = np.random.RandomState(seed)
    b, h, d = 3, 2, 4
    cache = rng.randn(b, h, geom.wcap, d).astype(np.float32)
    new = rng.randn(b, h, t, d).astype(np.float32)
    pos = np.array([rng.randint(0, 3 * geom.cap) for _ in range(b)], np.int32)
    num_new = np.array([rng.randint(0, t + 1) for _ in range(b)], np.int32)

    got = np.asarray(ring_insert_ref(
        jnp.asarray(cache), jnp.asarray(new), jnp.asarray(pos),
        jnp.asarray(num_new), ring_cap=geom.cap,
        num_global=geom.num_global))

    want = cache.copy()
    g, ring = geom.num_global, geom.cap - geom.num_global
    for bi in range(b):
        for j in range(int(num_new[bi])):
            p = int(pos[bi]) + j
            slot = p if p < g else g + (p - g) % ring
            want[bi, :, slot] = new[bi, :, j]
    assert (got == want).all(), (geom, t, pos.tolist(), num_new.tolist())


# no deadline: the first example pays a jit compile
@settings(deadline=None, max_examples=25)
@given(geom=GEOMS, t=st.integers(1, 4), seed=st.integers(0, 10_000),
       causal=st.just(True))
def test_fused_kernel_insert_matches_ref(geom, t, seed, causal):
    """decode_attention(impl='pallas', new_kv=...) — the in-kernel
    input/output-aliased insert — returns bitwise the ring_insert_ref
    cache and a matching attention output, across randomized geometry,
    wrap point, and ragged num_new. This is the oracle pair the serving
    engine's two decode impls ride."""
    t = min(t, geom.lookahead + 1)
    spec = AttentionSpec(kind="swat", causal=causal, window=geom.window,
                         num_global=geom.num_global)
    rng = np.random.RandomState(seed)
    b, hq, hkv, d = 2, 4, 2, 8          # GQA group 2
    # per-slot wrap points; every query position must exist (pos >= t is
    # not required — pos counts BEFORE the insert, queries are the new
    # tokens — but positions must cover the pinned prefix)
    pos = np.array([rng.randint(geom.num_global, 3 * geom.cap),
                    rng.randint(geom.num_global, 3 * geom.cap)], np.int32)
    num_new = np.array([t, rng.randint(1, t + 1)], np.int32)
    q = rng.randn(b, hq, t, d).astype(np.float32)
    kc = rng.randn(b, hkv, geom.wcap, d).astype(np.float32)
    vc = rng.randn(b, hkv, geom.wcap, d).astype(np.float32)
    kn = rng.randn(b, hkv, t, d).astype(np.float32)
    vn = rng.randn(b, hkv, t, d).astype(np.float32)

    args = (jnp.asarray(q), jnp.asarray(kc), jnp.asarray(vc), None, spec)
    kw = dict(new_kv=(jnp.asarray(kn), jnp.asarray(vn)),
              num_new=jnp.asarray(num_new), pos=jnp.asarray(pos),
              ring_cap=geom.cap)
    out_r, kc_r, vc_r = decode_attention(*args, impl="ref", **kw)
    out_p, kc_p, vc_p = decode_attention(*args, impl="pallas",
                                         interpret=True, **kw)
    assert (np.asarray(kc_r) == np.asarray(kc_p)).all(), (geom, t)
    assert (np.asarray(vc_r) == np.asarray(vc_p)).all(), (geom, t)
    np.testing.assert_allclose(np.asarray(out_p), np.asarray(out_r),
                               rtol=2e-4, atol=2e-4, err_msg=str((geom, t)))


# no deadline: the first example pays a jit compile
@settings(deadline=None, max_examples=40)
@given(geom=GEOMS, t=st.integers(1, 6), seed=st.integers(0, 10_000))
def test_rollback_leaves_no_live_garbage(geom, t, seed):
    """The speculative rollback contract, stated on the ring alone: insert
    T rows at pos, roll the pointer back to pos+e. Every slot holding one
    of the T-e rejected rows must either (a) reconstruct as invalid, or
    (b) reconstruct to a position <= pos+e-1 - window — strictly outside
    the window of every future query (positions >= pos+e) — provided the
    ring obeys the engine's allocation law ring >= window + T. The very
    next T-row insert then overwrites all of them before anything attends
    wider. Globals are exempt: a pinned slot is only garbage-free because
    pos >= num_global implies rejected rows never land in the pinned
    prefix region's *final* state (they are overwritten by the next step's
    insert at the same positions)."""
    t = min(t, geom.lookahead + 1)
    rng = np.random.RandomState(seed)
    g, ring = geom.num_global, geom.cap - geom.num_global
    assert ring >= geom.window + t       # the allocation law under test
    for pos in (g, geom.cap - 1, geom.cap + rng.randint(0, geom.cap),
                3 * geom.cap + rng.randint(0, geom.cap)):
        for e in range(0, t + 1):
            total = pos + e              # rolled-back pointer
            t_s, valid = ring_slot_positions(
                jnp.asarray([total]), geom.wcap, ring_cap=geom.cap,
                num_global=g)
            t_s, valid = np.asarray(t_s)[0], np.asarray(valid)[0]
            for j in range(e, t):        # the rejected rows
                p = pos + j
                slot = p if p < g else g + (p - g) % ring
                if slot < g:
                    continue             # overwritten in place next step
                if not valid[slot]:
                    continue
                # the slot is live under the rolled-back pointer: whoever
                # it claims to hold must be out-of-window for all future
                # queries (>= total)
                assert t_s[slot] <= total - 1 - geom.window, (
                    geom, t, pos, e, j, slot, t_s[slot])
