"""Expert-parallel MoE (explicit all-to-all) vs the dense oracle.

The EP schedule must compute the same function as moe_ffn_dense_ref when
capacity is generous (no drops), shard-count included in the check (4
devices, experts 8/4 = 2 per shard). Gradients flow through both
all_to_alls (shard_map transposes them)."""
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
    import jax, jax.numpy as jnp, numpy as np
    from repro.core import moe as M
    from repro.core import moe_ep as EP
    from repro.core.types import MoESpec
    from repro.launch.mesh import make_mesh

    mesh = make_mesh((4,), ("model",))
    spec = MoESpec(num_experts=8, top_k=2)
    p = M.init_moe(jax.random.PRNGKey(0), 32, 64, spec, dtype=jnp.float32)
    x = jnp.asarray(np.random.RandomState(0).randn(2, 16, 32),
                    jnp.float32) * 0.5
"""


@pytest.mark.slow
def test_ep_matches_dense_ref_no_drops():
    run_sub(COMMON + """
    ref = M.moe_ffn_dense_ref(p, x, spec)
    with jax.set_mesh(mesh):
        out, aux = EP.moe_ffn_ep(p, x, spec, mesh=mesh, axis="model",
                                 capacity_factor=8.0)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               atol=1e-4, rtol=1e-4)
    assert np.isfinite(float(aux))
    print("ok", float(aux))
    """)


@pytest.mark.slow
def test_ep_matches_sort_dispatch_aux():
    """aux (load-balance statistic) must agree with the single-pass value."""
    run_sub(COMMON + """
    _, aux_ref = M.moe_ffn(p, x, spec, capacity_factor=8.0)
    with jax.set_mesh(mesh):
        _, aux_ep = EP.moe_ffn_ep(p, x, spec, mesh=mesh, axis="model",
                                  capacity_factor=8.0)
    np.testing.assert_allclose(float(aux_ep), float(aux_ref), rtol=1e-5)
    print("ok")
    """)


@pytest.mark.slow
def test_ep_gradients_flow():
    run_sub(COMMON + """
    def loss_ep(p, x):
        out, aux = EP.moe_ffn_ep(p, x, spec, mesh=mesh, axis="model",
                                 capacity_factor=8.0)
        return jnp.sum(out.astype(jnp.float32) ** 2) + 0.01 * aux

    def loss_ref(p, x):
        out = M.moe_ffn_dense_ref(p, x, spec)
        _, aux = M.moe_ffn(p, x, spec, capacity_factor=8.0)
        return jnp.sum(out.astype(jnp.float32) ** 2) + 0.01 * aux

    with jax.set_mesh(mesh):
        g_ep = jax.grad(loss_ep)(p, x)
    g_ref = jax.grad(loss_ref)(p, x)
    for k_ in ("w1", "w2", "w3", "router"):
        np.testing.assert_allclose(np.asarray(g_ep[k_]),
                                   np.asarray(g_ref[k_]),
                                   atol=5e-3, rtol=5e-3, err_msg=k_)
    print("ok")
    """)


def test_ep_wire_bytes_independent_of_global_batch():
    from repro.core.moe_ep import ep_wire_bytes_per_device
    # doubling global batch with fixed local tokens leaves wire bytes fixed
    a = ep_wire_bytes_per_device(4096, 8, 1024)
    assert a == ep_wire_bytes_per_device(4096, 8, 1024)
    assert a == 2 * 4096 * 8 * 1.25 * 1024 * 2
