"""Per-kernel allclose vs the pure-jnp oracle (interpret=True on CPU):
shape/dtype sweeps for the Pallas fwd, bwd, decode kernels; the paper's
Eq. 1 kernel-fusion equivalence; sliding-chunks baseline equivalence."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest
from hypothesis import given, settings, strategies as st

from repro.core import patterns
from repro.core.types import AttentionSpec
from repro.kernels import ref
from repro.kernels.ops import decode_attention, get_pattern, swat_attention
from repro.kernels.swat_decode import decode_block_kv, swat_decode


def rand_qkv(rng, b, hq, hkv, l, d, dtype=jnp.float32):
    mk = lambda h: jnp.asarray(rng.randn(b, h, l, d), dtype)
    return mk(hq), mk(hkv), mk(hkv)


SPEC_CASES = [
    AttentionSpec(kind="swat", window=64, causal=True),
    AttentionSpec(kind="swat", window=64, causal=False),
    AttentionSpec(kind="swat", window=32, num_global=16, causal=False),
    AttentionSpec(kind="swat", window=32, num_global=16, causal=True),
    AttentionSpec(kind="swat", window=32, num_random=2, causal=True,
                  random_seed=7),
    AttentionSpec(kind="swat", window=32, num_global=16, num_random=1,
                  causal=False, random_seed=3),
    AttentionSpec(kind="dense", causal=True),
    AttentionSpec(kind="dense", causal=False),
    AttentionSpec(kind="swat", window=64, causal=True, softcap=30.0),
]


@pytest.mark.parametrize("spec", SPEC_CASES, ids=str)
@pytest.mark.parametrize("impl", ["pallas", "xla"])
def test_forward_allclose(spec, impl, rng):
    b, hq, hkv, l, d = 2, 4, 2, 256, 64
    q, k, v = rand_qkv(rng, b, hq, hkv, l, d)
    pat = get_pattern(spec, l, l, 64, 64)
    want = ref.attention_ref(q, k, v, spec, pattern=pat)
    got = swat_attention(q, k, v, spec, block_q=64, block_kv=64, impl=impl)
    np.testing.assert_allclose(got, want, atol=2e-5, rtol=1e-4)


@pytest.mark.parametrize("shape", [
    (1, 1, 1, 128, 32), (2, 8, 2, 256, 64), (1, 4, 4, 320, 128),
    (3, 2, 1, 200, 64),
])
@pytest.mark.parametrize("impl", ["pallas", "xla"])
def test_shape_sweep(shape, impl, rng):
    b, hq, hkv, l, d = shape
    spec = AttentionSpec(kind="swat", window=48, causal=True)
    q, k, v = rand_qkv(rng, b, hq, hkv, l, d)
    pat = get_pattern(spec, l, l, 64, 64)
    want = ref.attention_ref(q, k, v, spec, pattern=pat)
    got = swat_attention(q, k, v, spec, block_q=64, block_kv=64, impl=impl)
    np.testing.assert_allclose(got, want, atol=2e-5, rtol=1e-4)


@pytest.mark.parametrize("dtype,atol", [(jnp.float32, 2e-5),
                                        (jnp.bfloat16, 3e-2)])
@pytest.mark.parametrize("impl", ["pallas", "xla"])
def test_dtype_sweep(dtype, atol, impl, rng):
    spec = AttentionSpec(kind="swat", window=64, num_global=8, causal=True)
    q, k, v = rand_qkv(rng, 2, 4, 2, 256, 64, dtype)
    pat = get_pattern(spec, 256, 256, 64, 64)
    want = ref.attention_ref(q.astype(jnp.float32), k.astype(jnp.float32),
                             v.astype(jnp.float32), spec, pattern=pat)
    got = swat_attention(q, k, v, spec, block_q=64, block_kv=64, impl=impl)
    np.testing.assert_allclose(got.astype(jnp.float32), want,
                               atol=atol, rtol=1e-2)


@pytest.mark.parametrize("spec", [
    AttentionSpec(kind="swat", window=48, causal=True),
    AttentionSpec(kind="swat", window=32, num_global=16, causal=False),
    AttentionSpec(kind="swat", window=48, causal=True, softcap=25.0),
    AttentionSpec(kind="dense", causal=True),
], ids=str)
@pytest.mark.parametrize("impl", ["pallas", "xla"])
def test_gradients_allclose(spec, impl, rng):
    b, hq, hkv, l, d = 1, 4, 2, 192, 64
    q, k, v = rand_qkv(rng, b, hq, hkv, l, d)
    pat = get_pattern(spec, l, l, 64, 64)

    def loss(fn):
        return lambda q, k, v: jnp.sum(jnp.sin(fn(q, k, v)))

    want = jax.grad(loss(lambda q, k, v: ref.attention_ref(
        q, k, v, spec, pattern=pat)), argnums=(0, 1, 2))(q, k, v)
    got = jax.grad(loss(lambda q, k, v: swat_attention(
        q, k, v, spec, block_q=64, block_kv=64, impl=impl)),
        argnums=(0, 1, 2))(q, k, v)
    for g, w, name in zip(got, want, "qkv"):
        np.testing.assert_allclose(g, w, atol=5e-5, rtol=1e-3,
                                   err_msg=f"d{name}")


# ---------------------------------------------------------------------------
# Paper Eq. 1: the deferred-denominator fusion is EXACTLY softmax attention
# ---------------------------------------------------------------------------

@settings(max_examples=15, deadline=None)
@given(seed=st.integers(0, 100), causal=st.booleans(),
       window=st.sampled_from([16, 48]))
def test_fusion_equivalence_eq1(seed, causal, window):
    rng = np.random.RandomState(seed)
    spec = AttentionSpec(kind="swat", window=window, causal=causal)
    q, k, v = rand_qkv(rng, 1, 2, 2, 128, 32)
    three_step = ref.attention_ref(q, k, v, spec)
    fused = ref.fused_attention_ref(q, k, v, spec, stabilize=True)
    np.testing.assert_allclose(fused, three_step, atol=1e-5, rtol=1e-5)
    # the paper's literal (unstabilized) form agrees at moderate scale too
    fused_raw = ref.fused_attention_ref(q, k, v, spec, stabilize=False)
    np.testing.assert_allclose(fused_raw, three_step, atol=1e-4, rtol=1e-4)


def test_unstabilized_fusion_overflows_where_flash_does_not(rng):
    """Documents our deviation from the paper: raw exp overflows for large
    logits; the running-max version does not."""
    spec = AttentionSpec(kind="swat", window=16, causal=True)
    q, k, v = rand_qkv(rng, 1, 1, 1, 64, 32)
    q = q * 40.0  # logits ~ sqrt(32)*40^2/sqrt(32) — far beyond exp range
    raw = ref.fused_attention_ref(q, k, v, spec, stabilize=False)
    stable = ref.fused_attention_ref(q, k, v, spec, stabilize=True)
    assert not bool(jnp.isfinite(raw).all())
    assert bool(jnp.isfinite(stable).all())


def test_sliding_chunks_equals_band(rng):
    """The baseline computes the same function (only wastes FLOPs)."""
    for causal in (True, False):
        spec = AttentionSpec(kind="swat", window=32, causal=causal)
        q, k, v = rand_qkv(rng, 2, 2, 2, 256, 32)
        want = ref.attention_ref(q, k, v, spec)
        got = swat_attention(q, k, v, spec, impl="sliding_chunks")
        np.testing.assert_allclose(got, want, atol=2e-5, rtol=1e-4)


# ---------------------------------------------------------------------------
# decode kernel
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("w,lens", [
    (128, [128, 128]), (300, [1, 299]), (64, [64, 17]), (511, [511, 200]),
])
def test_decode_kernel_allclose(w, lens, rng):
    b, hq, hkv, d = len(lens), 4, 2, 64
    q = jnp.asarray(rng.randn(b, hq, 1, d), jnp.float32)
    kc = jnp.asarray(rng.randn(b, hkv, w, d), jnp.float32)
    vc = jnp.asarray(rng.randn(b, hkv, w, d), jnp.float32)
    cl = jnp.asarray(lens, jnp.int32)
    got = swat_decode(q, kc, vc, cl, interpret=True)
    want = ref.decode_ref(q, kc, vc, cl[:, None, None, None],
                          AttentionSpec(kind="dense"))
    np.testing.assert_allclose(got, want, atol=2e-5, rtol=1e-4)


@settings(max_examples=10, deadline=None)
@given(seed=st.integers(0, 50))
def test_decode_ring_permutation_invariance(seed):
    """Softmax permutation invariance is what makes the ring buffer valid:
    shuffling cache rows never changes the decode output."""
    rng = np.random.RandomState(seed)
    b, h, w, d = 1, 2, 64, 32
    q = jnp.asarray(rng.randn(b, h, 1, d), jnp.float32)
    kc = jnp.asarray(rng.randn(b, h, w, d), jnp.float32)
    vc = jnp.asarray(rng.randn(b, h, w, d), jnp.float32)
    perm = rng.permutation(w)
    full = jnp.full((b,), w, jnp.int32)
    a = swat_decode(q, kc, vc, full, interpret=True)
    bb = swat_decode(q, kc[:, :, perm], vc[:, :, perm], full, interpret=True)
    np.testing.assert_allclose(a, bb, atol=1e-5, rtol=1e-5)


@pytest.mark.parametrize("form", ["scalar", "flat", "b111"])
def test_decode_attention_cache_len_forms(form, rng):
    """decode_attention accepts every documented cache_len spelling — scalar
    (shared length), (B,), (B,1,1,1) — on BOTH impls. Regression: the pallas
    path used to jnp.reshape a scalar to (B,), which crashes for B > 1 (the
    cross-attention call site passes a full()'d (B,1,1,1))."""
    b, hq, hkv, w, d = 3, 4, 2, 64, 32
    q = jnp.asarray(rng.randn(b, hq, 1, d), jnp.float32)
    kc = jnp.asarray(rng.randn(b, hkv, w, d), jnp.float32)
    vc = jnp.asarray(rng.randn(b, hkv, w, d), jnp.float32)
    spec = AttentionSpec(kind="dense")
    ln = 37
    cl = {"scalar": jnp.int32(ln),
          "flat": jnp.full((b,), ln, jnp.int32),
          "b111": jnp.full((b, 1, 1, 1), ln, jnp.int32)}[form]
    want = ref.decode_ref(q, kc, vc, jnp.full((b, 1, 1, 1), ln, jnp.int32),
                          spec)
    got_ref = decode_attention(q, kc, vc, cl, spec, impl="ref")
    got_pal = decode_attention(q, kc, vc, cl, spec, impl="pallas",
                               interpret=True)
    np.testing.assert_allclose(got_ref, want, atol=2e-5, rtol=1e-4)
    np.testing.assert_allclose(got_pal, want, atol=2e-5, rtol=1e-4)


def test_decode_block_kv_never_pads_rounded_capacities(rng):
    """Ring allocations from init_kv_cache are pre-rounded
    (layers.cache_allocation) so the decode hot path must tile them exactly —
    the old unconditional pad copied the WHOLE cache every token. Odd ad-hoc
    widths may still pad (the cold fallback), but must stay correct."""
    from repro.core.layers import _round_capacity
    for cap in (17, 21, 64, 100, 261, 2049):
        w = _round_capacity(cap)
        blk, pads = decode_block_kv(w)
        assert not pads and w % blk == 0, (cap, w, blk)
    # unrounded odd width: falls back to pad, output still exact
    b, hq, hkv, w, d = 2, 4, 2, 300, 32
    q = jnp.asarray(rng.randn(b, hq, 1, d), jnp.float32)
    kc = jnp.asarray(rng.randn(b, hkv, w, d), jnp.float32)
    vc = jnp.asarray(rng.randn(b, hkv, w, d), jnp.float32)
    cl = jnp.asarray([299, 123], jnp.int32)
    got = swat_decode(q, kc, vc, cl, interpret=True)
    want = ref.decode_ref(q, kc, vc, cl[:, None, None, None],
                          AttentionSpec(kind="dense"))
    np.testing.assert_allclose(got, want, atol=2e-5, rtol=1e-4)


def _fifo_ring_caches(rng, lens, hkv, cap, alloc, d, num_global=0,
                      dtype=np.float32):
    """Build per-slot ring caches by simulating sequential FIFO insertion
    (pinned globals below num_global, ring above), plus the linear "last
    window" layout for dense-reference checks. Returns (k_ring, v_ring)."""
    b = len(lens)
    kc = np.zeros((b, hkv, alloc, d), dtype)
    vc = np.zeros((b, hkv, alloc, d), dtype)
    ring = cap - num_global
    for i, ln in enumerate(lens):
        hk = rng.randn(hkv, max(ln, 1), d).astype(dtype)
        hv = rng.randn(hkv, max(ln, 1), d).astype(dtype)
        for t in range(ln):
            slot = t if t < num_global else (num_global
                                             + (t - num_global) % ring)
            kc[i, :, slot] = hk[:, t]
            vc[i, :, slot] = hv[:, t]
    return kc, vc


@pytest.mark.parametrize("group", [1, 4, 8])
@pytest.mark.parametrize("t", [1, 4])
@pytest.mark.parametrize("dtype,atol", [(jnp.float32, 2e-5),
                                        (jnp.bfloat16, 3e-2)])
def test_decode_fused_parity_sweep(group, t, dtype, atol, rng):
    """The ISSUE-3 acceptance sweep: the fused pallas kernel (GQA-packed
    query tile, in-kernel ring insert) matches the jnp oracle across
    group in {1,4,8}, T in {1,4}, bf16/fp32, and mixed per-slot depths —
    cold slots, partially filled, freshly wrapped, multiply wrapped. Cache
    updates must be BITWISE identical (broken aliasing shows up here)."""
    spec = AttentionSpec(kind="swat", window=12, num_global=4, causal=True)
    hkv, d = 2, 32
    hq = group * hkv
    cap = spec.window + 1 + (t - 1) + spec.num_global  # lookahead ring
    from repro.core.layers import _round_capacity
    alloc = _round_capacity(cap)                       # tile-rounded tail
    lens = [0, 3, cap - 1, cap, 4 * cap + 7]           # per-slot depths
    b = len(lens)
    np_dtype = np.float32
    kc, vc = _fifo_ring_caches(rng, lens, hkv, cap, alloc, d,
                               num_global=spec.num_global, dtype=np_dtype)
    kc, vc = jnp.asarray(kc, dtype), jnp.asarray(vc, dtype)
    q = jnp.asarray(rng.randn(b, hq, t, d), dtype)
    nk = jnp.asarray(rng.randn(b, hkv, t, d), dtype)
    nv = jnp.asarray(rng.randn(b, hkv, t, d), dtype)
    pos = jnp.asarray(lens, jnp.int32)
    nn = jnp.asarray([t, t, max(1, t - 1), t, t], jnp.int32)  # ragged
    o_ref, kr, vr = decode_attention(q, kc, vc, None, spec, impl="ref",
                                     new_kv=(nk, nv), num_new=nn, pos=pos,
                                     ring_cap=cap)
    o_pal, kp, vp = decode_attention(q, kc, vc, None, spec, impl="pallas",
                                     new_kv=(nk, nv), num_new=nn, pos=pos,
                                     ring_cap=cap, interpret=True)
    np.testing.assert_array_equal(np.asarray(kr), np.asarray(kp))
    np.testing.assert_array_equal(np.asarray(vr), np.asarray(vp))
    for i in range(b):
        real = int(nn[i])    # rows past num_new are garbage by contract
        np.testing.assert_allclose(
            np.asarray(o_pal[i, :, :real], np.float32),
            np.asarray(o_ref[i, :, :real], np.float32),
            atol=atol, rtol=1e-2, err_msg=f"slot {i}")


@pytest.mark.parametrize("impl", ["ref", "pallas"])
def test_decode_multi_token_equals_sequential(impl, rng):
    """A T=4 fused decode call == 4 sequential T=1 fused calls, outputs and
    final caches alike — the property speculative-decode verification
    stands on. Needs the lookahead ring (T-1 extra rows): without it the
    step's own inserts would evict tokens still inside early queries'
    windows."""
    spec = AttentionSpec(kind="swat", window=10, num_global=2, causal=True)
    hkv, group, t, d = 2, 3, 4, 16
    hq = hkv * group
    cap = spec.window + 1 + (t - 1) + spec.num_global
    lens = [0, 5, 3 * cap + 2]
    b = len(lens)
    kc, vc = _fifo_ring_caches(rng, lens, hkv, cap, cap, d,
                               num_global=spec.num_global)
    kc, vc = jnp.asarray(kc), jnp.asarray(vc)
    q = jnp.asarray(rng.randn(b, hq, t, d), jnp.float32)
    nk = jnp.asarray(rng.randn(b, hkv, t, d), jnp.float32)
    nv = jnp.asarray(rng.randn(b, hkv, t, d), jnp.float32)
    pos = jnp.asarray(lens, jnp.int32)
    out, kA, vA = decode_attention(q, kc, vc, None, spec, impl=impl,
                                   new_kv=(nk, nv), pos=pos, ring_cap=cap,
                                   interpret=True)
    outs = []
    for j in range(t):
        o1, kc, vc = decode_attention(
            q[:, :, j:j + 1], kc, vc, None, spec, impl=impl,
            new_kv=(nk[:, :, j:j + 1], nv[:, :, j:j + 1]),
            pos=pos + j, ring_cap=cap, interpret=True)
        outs.append(o1)
    np.testing.assert_allclose(np.asarray(out),
                               np.asarray(jnp.concatenate(outs, 2)),
                               atol=2e-5, rtol=1e-4)
    np.testing.assert_array_equal(np.asarray(kA), np.asarray(kc))
    np.testing.assert_array_equal(np.asarray(vA), np.asarray(vc))


@pytest.mark.parametrize("impl", ["ref", "pallas"])
def test_decode_window_masked_on_wide_cache(impl, rng):
    """Regression (ISSUE 3): a cache allocated wider than the ring capacity
    (dense-capped / lookahead allocations) used to attend the ENTIRE valid
    prefix — spec.window was silently dropped. Both impls must mask by
    per-slot ring distance: only the last window+1 tokens (plus pinned
    globals) are visible."""
    spec = AttentionSpec(kind="swat", window=8, causal=True)
    b, hq, hkv, W, L, d = 1, 4, 2, 64, 40, 16
    kc = jnp.asarray(rng.randn(b, hkv, W, d), jnp.float32)
    vc = jnp.asarray(rng.randn(b, hkv, W, d), jnp.float32)
    q = jnp.asarray(rng.randn(b, hq, 1, d), jnp.float32)
    nk = jnp.asarray(rng.randn(b, hkv, 1, d), jnp.float32)
    nv = jnp.asarray(rng.randn(b, hkv, 1, d), jnp.float32)
    pos = jnp.asarray([L], jnp.int32)
    got, _, _ = decode_attention(q, kc, vc, None, spec, impl=impl,
                                 new_kv=(nk, nv), pos=pos, ring_cap=W,
                                 interpret=True)
    # oracle: dense attention over ONLY the in-window tail (linear layout:
    # token i at slot i; query at L sees [L-8, L])
    kw = jnp.concatenate([kc[:, :, L - 8:L], nk], axis=2)
    vw = jnp.concatenate([vc[:, :, L - 8:L], nv], axis=2)
    want = ref.decode_ref(q, kw, vw, jnp.full((b, 1, 1, 1), 9, jnp.int32),
                          AttentionSpec(kind="dense"))
    np.testing.assert_allclose(got, want, atol=2e-5, rtol=1e-4)
    # and the buggy behavior is measurably different: attending the whole
    # prefix gives a different answer
    wide = ref.decode_ref(q, kc.at[:, :, L].set(nk[:, :, 0]),
                          vc.at[:, :, L].set(nv[:, :, 0]),
                          jnp.full((b, 1, 1, 1), L + 1, jnp.int32),
                          AttentionSpec(kind="dense"))
    assert not np.allclose(np.asarray(got), np.asarray(wide), atol=1e-3)


def test_decode_fused_equals_unfused_bitwise(rng):
    """The fused ref path (insert inside decode_attention) must be
    OP-FOR-OP the PR-2 unfused path (layers._dyn_update scatter, then
    prefix-masked attention): bitwise-equal caches AND outputs at T=1 on a
    standard ring. This is what keeps serving tokens byte-stable across
    the refactor (the slot-parallel mesh parity test rides on it)."""
    from repro.core.layers import _dyn_update
    spec = AttentionSpec(kind="swat", window=12, num_global=4, causal=True)
    hkv, group, d = 2, 2, 16
    hq = hkv * group
    cap = spec.window + 1 + spec.num_global
    lens = [0, 5, cap, 3 * cap + 2]
    b = len(lens)
    kc, vc = _fifo_ring_caches(rng, lens, hkv, cap, cap, d,
                               num_global=spec.num_global)
    kc, vc = jnp.asarray(kc), jnp.asarray(vc)
    q = jnp.asarray(rng.randn(b, hq, 1, d), jnp.float32)
    nk = jnp.asarray(rng.randn(b, hkv, 1, d), jnp.float32)
    nv = jnp.asarray(rng.randn(b, hkv, 1, d), jnp.float32)
    step = jnp.asarray(lens, jnp.int32)
    fused, kf, vf = decode_attention(q, kc, vc, None, spec, impl="ref",
                                     new_kv=(nk, nv), pos=step,
                                     ring_cap=cap)
    g, ring = spec.num_global, cap - spec.num_global
    slot = jnp.where(step < g, step, g + (step - g) % ring)
    ku = _dyn_update(kc, nk, slot)
    vu = _dyn_update(vc, nv, slot)
    unfused = decode_attention(q, ku, vu,
                               jnp.minimum(step + 1, cap), spec, impl="ref")
    np.testing.assert_array_equal(np.asarray(kf), np.asarray(ku))
    np.testing.assert_array_equal(np.asarray(vf), np.asarray(vu))
    np.testing.assert_array_equal(np.asarray(fused), np.asarray(unfused))


def test_decode_pad_fallback_warns_once(rng, caplog):
    """The pad-and-copy fallback (cache width with no sublane-aligned
    divisor) must log a one-time warning naming the offending W — the
    silent full-cache copy per token is exactly what went unnoticed before
    the pre-rounded allocations."""
    import logging
    from repro.kernels import swat_decode as sd
    w = 37  # no divisor >= 16 shared with 128
    assert decode_block_kv(w)[1]
    sd._PAD_WARNED.discard(w)
    b, hq, hkv, d = 1, 2, 1, 32
    q = jnp.asarray(rng.randn(b, hq, 1, d), jnp.float32)
    kc = jnp.asarray(rng.randn(b, hkv, w, d), jnp.float32)
    vc = jnp.asarray(rng.randn(b, hkv, w, d), jnp.float32)
    cl = jnp.full((b,), w, jnp.int32)
    with caplog.at_level(logging.WARNING, logger=sd.logger.name):
        swat_decode(q, kc, vc, cl, interpret=True)
        swat_decode(q, kc, vc, cl, interpret=True)
    hits = [r for r in caplog.records if "W=37" in r.getMessage()]
    assert len(hits) == 1, [r.getMessage() for r in caplog.records]


def test_decode_per_slot_ring_offsets(rng):
    """One batched swat_decode call serving slots at DIFFERENT ring depths
    (cold, exactly-full, wrapped, multiply-wrapped): each row's ring-laid-out
    cache must match the dense reference over that row's contiguous history
    tail — the property the continuous-batching engine relies on."""
    b, hq, hkv, w, d = 4, 4, 2, 32, 16
    lens = [5, 32, 47, 90]
    kc_ring = np.zeros((b, hkv, w, d), np.float32)
    vc_ring = np.zeros((b, hkv, w, d), np.float32)
    kc_lin = np.zeros((b, hkv, w, d), np.float32)
    vc_lin = np.zeros((b, hkv, w, d), np.float32)
    for i, ln in enumerate(lens):
        hk = rng.randn(hkv, ln, d).astype(np.float32)
        hv = rng.randn(hkv, ln, d).astype(np.float32)
        start = max(0, ln - w)
        for t in range(start, ln):          # FIFO: token t lives at t % w
            kc_ring[i, :, t % w] = hk[:, t]
            vc_ring[i, :, t % w] = hv[:, t]
        kc_lin[i, :, :ln - start] = hk[:, start:]
        vc_lin[i, :, :ln - start] = hv[:, start:]
    cl = jnp.asarray([min(ln, w) for ln in lens], jnp.int32)
    q = jnp.asarray(rng.randn(b, hq, 1, d), jnp.float32)
    got = swat_decode(q, jnp.asarray(kc_ring), jnp.asarray(vc_ring), cl,
                      interpret=True)
    want = ref.decode_ref(q, jnp.asarray(kc_lin), jnp.asarray(vc_lin),
                          cl[:, None, None, None],
                          AttentionSpec(kind="dense"))
    np.testing.assert_allclose(got, want, atol=2e-5, rtol=1e-4)
