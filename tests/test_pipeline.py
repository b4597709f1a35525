"""Pipeline parallelism: GPipe loss == single-pass loss, grads flow to every
stage, and the PP train step runs. Subprocess multi-device pattern."""
import os
import subprocess
import sys
import textwrap
from pathlib import Path

import pytest

ROOT = Path(__file__).resolve().parents[1]


def run_sub(code: str, devices: int = 4) -> str:
    env = dict(os.environ)
    env["XLA_FLAGS"] = (f"--xla_force_host_platform_device_count={devices} "
                        + env.get("XLA_FLAGS", ""))
    env["PYTHONPATH"] = f"{ROOT}/src"
    env["TF_CPP_MIN_LOG_LEVEL"] = "2"
    out = subprocess.run([sys.executable, "-c", textwrap.dedent(code)],
                         capture_output=True, text=True, env=env,
                         timeout=900)
    assert out.returncode == 0, f"STDOUT:\n{out.stdout}\nSTDERR:\n{out.stderr}"
    return out.stdout


COMMON = """
    import dataclasses
    import jax, jax.numpy as jnp, numpy as np
    from repro.configs import get_smoke_config
    from repro.core import model as Mod
    from repro.distributed import pipeline as PP
    from repro.launch import mesh as mesh_lib

    cfg = get_smoke_config("llama3p2_1b")
    # 4 super-blocks so a 2-stage pipeline holds 2 each
    cfg = dataclasses.replace(cfg, num_layers=4)
    mesh = mesh_lib.make_debug_pp_mesh(2, 2)
    rng = np.random.RandomState(0)
    batch = {"tokens": jnp.asarray(rng.randint(0, cfg.vocab_size, (8, 32)),
                                   jnp.int32)}
    batch["labels"] = batch["tokens"]
    params = Mod.init_model(jax.random.PRNGKey(0), cfg)
    pcfg = PP.PipelineConfig(num_stages=2, num_microbatches=4)
"""


@pytest.mark.slow
def test_pp_loss_matches_single_pass():
    run_sub(COMMON + """
    with jax.set_mesh(mesh):
        loss_fn = PP.make_pipeline_loss(cfg, pcfg, mesh)
        l_pp, m_pp = jax.jit(loss_fn)(params, batch)
    l_ref, m_ref = Mod.loss_fn(params, cfg, batch, remat=False)
    np.testing.assert_allclose(float(l_pp), float(l_ref), rtol=2e-3)
    np.testing.assert_allclose(float(m_pp["loss"]), float(m_ref["loss"]),
                               rtol=2e-3)
    print("ok", float(l_pp), float(l_ref))
    """)


@pytest.mark.slow
def test_pp_grads_match_single_pass():
    """The autodiff-transposed reverse pipeline == plain backward, for every
    stage's blocks AND the pipe-replicated embed/head."""
    run_sub(COMMON + """
    with jax.set_mesh(mesh):
        loss_fn = PP.make_pipeline_loss(cfg, pcfg, mesh)
        g_pp = jax.jit(jax.grad(lambda p, b: loss_fn(p, b)[0]))(params, batch)
    g_ref = jax.grad(lambda p: Mod.loss_fn(p, cfg, batch, remat=False)[0])(
        params)
    flat_pp = jax.tree_util.tree_leaves_with_path(g_pp)
    flat_ref = {jax.tree_util.keystr(k): v
                for k, v in jax.tree_util.tree_leaves_with_path(g_ref)}
    checked = 0
    for k, v in flat_pp:
        ref = flat_ref[jax.tree_util.keystr(k)]
        np.testing.assert_allclose(
            np.asarray(v, np.float32), np.asarray(ref, np.float32),
            atol=2e-2, rtol=2e-2, err_msg=jax.tree_util.keystr(k))
        checked += 1
    assert checked >= 10
    print("ok", checked, "leaves")
    """)


@pytest.mark.slow
def test_pp_train_step_runs_and_updates():
    run_sub(COMMON + """
    from repro.optim import adamw
    opt_cfg = adamw.AdamWConfig(warmup_steps=1)
    opt = adamw.init_opt_state(params)
    with jax.set_mesh(mesh):
        step = jax.jit(PP.make_pp_train_step(cfg, opt_cfg, pcfg, mesh))
        p1, o1, metrics = step(params, opt, batch)
    assert np.isfinite(float(metrics["loss"]))
    # params actually moved
    d = jax.tree.map(lambda a, b: float(jnp.max(jnp.abs(
        a.astype(jnp.float32) - b.astype(jnp.float32)))), params, p1)
    assert max(jax.tree.leaves(d)) > 0
    print("ok", float(metrics["loss"]))
    """)


def test_bubble_fraction():
    from repro.distributed import pipeline as PP
    assert PP.bubble_fraction(PP.PipelineConfig(4, 4)) == pytest.approx(3 / 7)
    assert PP.bubble_fraction(PP.PipelineConfig(4, 32)) == pytest.approx(
        3 / 35)
    with pytest.raises(AssertionError):
        PP.PipelineConfig(num_stages=4, num_microbatches=2)
