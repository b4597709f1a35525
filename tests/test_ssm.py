"""Mamba2 SSD: chunked dual form vs the sequential recurrence oracle."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest
from hypothesis import given, settings, strategies as st

from repro.core import ssm as S
from repro.core.types import SSMSpec


def rand_inputs(rng, b=2, l=96, h=4, p=16, g=2, sd=8):
    x = jnp.asarray(rng.randn(b, l, h, p), jnp.float32)
    dt = jnp.asarray(np.abs(rng.randn(b, l, h)) * 0.1, jnp.float32)
    a = -jnp.asarray(np.abs(rng.randn(h)) + 0.3, jnp.float32)
    bm = jnp.asarray(rng.randn(b, l, g, sd), jnp.float32)
    cm = jnp.asarray(rng.randn(b, l, g, sd), jnp.float32)
    d = jnp.asarray(rng.randn(h), jnp.float32)
    return x, dt, a, bm, cm, d


@pytest.mark.parametrize("chunk", [8, 32, 128])
def test_chunked_matches_scan(chunk, rng):
    args = rand_inputs(rng)
    want = S.ssd_scan_ref(*args)
    got = S.ssd_chunked(*args, chunk=chunk)
    np.testing.assert_allclose(got, want, atol=2e-4, rtol=1e-3)


@settings(max_examples=10, deadline=None)
@given(seed=st.integers(0, 50), l=st.sampled_from([17, 64, 100]),
       chunk=st.sampled_from([16, 64]))
def test_chunked_matches_scan_property(seed, l, chunk):
    rng = np.random.RandomState(seed)
    args = rand_inputs(rng, l=l)
    want = S.ssd_scan_ref(*args)
    got = S.ssd_chunked(*args, chunk=chunk)
    np.testing.assert_allclose(got, want, atol=5e-4, rtol=2e-3)


def test_mamba_block_decode_parity(rng):
    spec = SSMSpec(state_dim=8, head_dim=16, expand=2, conv_width=4,
                   chunk_size=8, num_groups=1)
    dm, l = 32, 20
    params = S.init_mamba(jax.random.PRNGKey(1), dm, spec, dtype=jnp.float32)
    xs = jnp.asarray(rng.randn(2, l, dm) * 0.3, jnp.float32)
    full = S.mamba_block(params, xs, spec, chunk=8)
    cache = S.init_mamba_cache(dm, spec, 2, dtype=jnp.float32)
    outs = []
    for t in range(l):
        y, cache = S.mamba_decode(params, xs[:, t:t + 1], cache, spec)
        outs.append(y)
    np.testing.assert_allclose(jnp.concatenate(outs, 1), full,
                               atol=1e-3, rtol=1e-3)


def test_state_decay_contracts(rng):
    """|exp(dt*a)| < 1: the recurrence is stable (decay contract)."""
    _, dt, a, *_ = rand_inputs(rng)
    decay = jnp.exp(dt * a)
    assert float(decay.max()) < 1.0
    assert float(decay.min()) > 0.0


def test_grads_flow_through_chunked(rng):
    args = rand_inputs(rng, b=1, l=32)
    def loss(x):
        return jnp.sum(S.ssd_chunked(x, *args[1:], chunk=16) ** 2)
    g = jax.grad(loss)(args[0])
    assert bool(jnp.isfinite(g).all()) and float(jnp.abs(g).max()) > 0
