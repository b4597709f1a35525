"""Offset (context-parallel hook) path of the Pallas kernels.

Strategy: compute the full (L) problem with the O(N^2) oracle, then ask the
kernel for a [q_off, q_off+lq) slice of rows given only the kv slice
[kv_off, kv_off+lkv) that covers those rows' bands — exactly what a CP shard
sees. Outputs must match the oracle's rows. Also: the kv_lo bound masks
"before sequence start" halo rows (leftmost-shard case).
"""
import numpy as np
import pytest
from hypothesis import given, settings, strategies as st

import jax
import jax.numpy as jnp

from repro.core import patterns
from repro.core.types import AttentionSpec
from repro.kernels import ops as kops
from repro.kernels import ref as R
from repro.kernels import swat_attention as F


def _mk(rng, b, h, l, d):
    return (jnp.asarray(rng.randn(b, h, l, d), jnp.float32) * 0.5
            for _ in range(1)).__next__()


@pytest.mark.parametrize("causal", [True, False])
@pytest.mark.parametrize("impl", ["pallas", "xla"])
def test_offset_slice_matches_oracle(causal, impl):
    b, h, L, d, w = 1, 2, 256, 16, 32
    bq = bk = 16
    spec = AttentionSpec(kind="swat", window=w, causal=causal)
    rng = np.random.RandomState(0)
    q = jnp.asarray(rng.randn(b, h, L, d), jnp.float32) * 0.5
    k = jnp.asarray(rng.randn(b, h, L, d), jnp.float32) * 0.5
    v = jnp.asarray(rng.randn(b, h, L, d), jnp.float32) * 0.5
    want = R.attention_ref(q, k, v, spec)

    q_off, lq = 64, 64
    kv_off, lkv = 32, 128          # covers [64-32, 128+32) for both masks
    qs = q[:, :, q_off:q_off + lq]
    ks = k[:, :, kv_off:kv_off + lkv]
    vs = v[:, :, kv_off:kv_off + lkv]
    pat = patterns.build_block_pattern(spec, lq, lkv, bq, bk,
                                       q_shift=q_off - kv_off)
    if impl == "pallas":
        got = F.swat_attention_fwd(qs, ks, vs, spec, pattern=pat,
                                   q_offset=q_off, kv_offset=kv_off,
                                   seq_kv_bound=L, interpret=True)
    else:
        got = kops._xla_banded(qs, ks, vs, spec, pat, d ** -0.5,
                               q_shift=q_off - kv_off)
    np.testing.assert_allclose(np.asarray(got),
                               np.asarray(want[:, :, q_off:q_off + lq]),
                               atol=2e-2, rtol=2e-2)


def test_offset_leftmost_shard_kv_lo():
    """Leftmost CP shard: the halo region is garbage (zeros from ppermute);
    kv_lo / negative global indices must mask it exactly."""
    b, h, L, d, w = 1, 2, 64, 8, 16
    bq = bk = 16
    spec = AttentionSpec(kind="swat", window=w, causal=True)
    rng = np.random.RandomState(1)
    q = jnp.asarray(rng.randn(b, h, L, d), jnp.float32) * 0.5
    k = jnp.asarray(rng.randn(b, h, L, d), jnp.float32) * 0.5
    v = jnp.asarray(rng.randn(b, h, L, d), jnp.float32) * 0.5
    want = R.attention_ref(q, k, v, spec)

    halo = 16
    garbage = jnp.full((b, h, halo, d), 7.7, jnp.float32)  # worse than zeros
    k_ext = jnp.concatenate([garbage, k[:, :, :32]], axis=2)
    v_ext = jnp.concatenate([garbage, v[:, :, :32]], axis=2)
    qs = q[:, :, :32]
    pat = patterns.build_block_pattern(spec, 32, 32 + halo, bq, bk,
                                       q_shift=halo)
    # pallas path: kv_offset=-halo puts halo rows at negative global indices
    got = F.swat_attention_fwd(qs, k_ext, v_ext, spec, pattern=pat,
                               q_offset=0, kv_offset=-halo,
                               seq_kv_bound=L, interpret=True)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want[:, :, :32]),
                               atol=2e-2, rtol=2e-2)
    # xla path: traced kv_lo bound
    got2 = kops._xla_banded(qs, k_ext, v_ext, spec, pat, d ** -0.5,
                            q_shift=halo, kv_lo=jnp.asarray(halo),
                            kv_hi=jnp.asarray(32 + halo))
    np.testing.assert_allclose(np.asarray(got2), np.asarray(want[:, :, :32]),
                               atol=2e-2, rtol=2e-2)


@settings(max_examples=10, deadline=None)
@given(w=st.sampled_from([8, 16, 24]),
       causal=st.booleans(),
       seed=st.integers(0, 3))
def test_offset_partials_merge_to_full_softmax(w, causal, seed):
    """Splitting one row's band across two kv buffers and lse-merging the
    partials must reproduce the unsplit softmax (the CP merge identity)."""
    from repro.distributed.context_parallel import _merge, _finalize
    b, h, L, d = 1, 1, 64, 8
    spec = AttentionSpec(kind="swat", window=w, causal=causal)
    rng = np.random.RandomState(seed)
    q = jnp.asarray(rng.randn(b, h, L, d), jnp.float32) * 0.5
    k = jnp.asarray(rng.randn(b, h, L, d), jnp.float32) * 0.5
    v = jnp.asarray(rng.randn(b, h, L, d), jnp.float32) * 0.5
    want = R.attention_ref(q, k, v, spec)

    bq = bk = 16
    half = 32
    # partial 1: kv buffer [0, 32); partial 2: kv buffer [32, 64)
    p1 = kops._xla_banded(q, k[:, :, :half], v[:, :, :half], spec,
                          patterns.build_block_pattern(spec, L, half, bq, bk),
                          d ** -0.5, return_partials=True)
    pat2 = patterns.build_block_pattern(spec, L, half, bq, bk,
                                        q_shift=-half)
    p2 = kops._xla_banded(q, k[:, :, half:], v[:, :, half:], spec, pat2,
                          d ** -0.5, q_shift=-half, return_partials=True)
    out = _finalize(_merge(p1, p2), q.dtype)
    np.testing.assert_allclose(np.asarray(out), np.asarray(want),
                               atol=2e-2, rtol=2e-2)
