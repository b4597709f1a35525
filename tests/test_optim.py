"""AdamW + int8 error-feedback compression properties."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest
from hypothesis import given, settings, strategies as st

from repro.optim import adamw, compress


def test_adamw_converges_quadratic():
    cfg = adamw.AdamWConfig(lr=0.1, warmup_steps=0, total_steps=200,
                            weight_decay=0.0, grad_clip=1e9)
    target = jnp.asarray([1.0, -2.0, 3.0])
    params = {"w": jnp.zeros(3)}
    state = adamw.init_opt_state(params)
    for _ in range(200):
        grads = {"w": 2 * (params["w"] - target)}
        params, state, _ = adamw.apply_updates(params, grads, state, cfg)
    np.testing.assert_allclose(params["w"], target, atol=1e-2)


def test_grad_clip_bounds_update():
    cfg = adamw.AdamWConfig(lr=1.0, warmup_steps=0, grad_clip=1.0,
                            weight_decay=0.0)
    params = {"w": jnp.zeros(4)}
    state = adamw.init_opt_state(params)
    grads = {"w": jnp.full((4,), 1e6)}
    _, _, metrics = adamw.apply_updates(params, grads, state, cfg)
    assert float(metrics["grad_norm"]) > 1e5  # norm reported unclipped


def test_schedule_warmup_and_decay():
    cfg = adamw.AdamWConfig(lr=1.0, warmup_steps=10, total_steps=100,
                            min_lr_ratio=0.1)
    assert float(adamw.schedule(cfg, jnp.asarray(0))) == 0.0
    assert abs(float(adamw.schedule(cfg, jnp.asarray(10))) - 1.0) < 1e-6
    assert abs(float(adamw.schedule(cfg, jnp.asarray(100)))
               - 0.1) < 1e-6


@settings(max_examples=20, deadline=None)
@given(seed=st.integers(0, 1000))
def test_compression_error_feedback_bounded(seed):
    """EF property: the compounded error (residual) stays bounded by one
    quantization step — compressed-sum converges to true-sum."""
    rng = np.random.RandomState(seed)
    g = {"w": jnp.asarray(rng.randn(64), jnp.float32)}
    residual = compress.init_residual(g)
    acc_true = np.zeros(64)
    acc_comp = np.zeros(64)
    for t in range(20):
        gt = {"w": jnp.asarray(rng.randn(64), jnp.float32)}
        ct, residual = compress.compress_decompress(gt, residual)
        acc_true += np.asarray(gt["w"])
        acc_comp += np.asarray(ct["w"])
    # residual carries exactly the difference
    np.testing.assert_allclose(
        acc_true, acc_comp + np.asarray(residual["w"]), atol=1e-3)
    scale = max(1e-12, np.abs(acc_true).max())
    assert np.abs(acc_true - acc_comp).max() / scale < 0.5


def test_compression_roundtrip_small_error():
    rng = np.random.RandomState(0)
    g = {"w": jnp.asarray(rng.randn(1000), jnp.float32)}
    r = compress.init_residual(g)
    c, _ = compress.compress_decompress(g, r)
    rel = float(jnp.linalg.norm(c["w"] - g["w"]) / jnp.linalg.norm(g["w"]))
    assert rel < 0.02  # int8 per-tensor quantization error
