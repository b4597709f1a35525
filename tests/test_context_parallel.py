"""Context-parallel (halo-exchange) window attention vs the O(N^2) oracle.

Subprocess pattern (device count must be set before jax init): a 4-device
1D mesh shards the sequence; the CP output must match attention_ref bit-for
tolerance, including sequence edges, global rows/cols, GQA, multi-hop halos
(w > Lp) and gradients through the ppermutes.
"""
import os
import subprocess
import sys
import textwrap
from pathlib import Path

import pytest

ROOT = Path(__file__).resolve().parents[1]

COMMON = """
    import dataclasses, functools
    import jax, jax.numpy as jnp, numpy as np
    from jax.sharding import PartitionSpec as P
    from repro.core.types import AttentionSpec
    from repro.distributed import context_parallel as CP
    from repro.kernels import ref as R
    from repro.launch.mesh import make_mesh

    assert len(jax.devices()) == 4
    mesh = make_mesh((4,), ("seq",))

    def run_case(spec, b=2, hq=4, hkv=2, l=64, d=16, tol=2e-2):
        rng = np.random.RandomState(0)
        q = jnp.asarray(rng.randn(b, hq, l, d), jnp.float32) * 0.5
        k = jnp.asarray(rng.randn(b, hkv, l, d), jnp.float32) * 0.5
        v = jnp.asarray(rng.randn(b, hkv, l, d), jnp.float32) * 0.5
        with jax.set_mesh(mesh):
            got = CP.swat_attention_context_parallel(
                q, k, v, spec, mesh=mesh, axis="seq",
                block_q=16, block_kv=16)
        want = R.attention_ref(q, k, v, spec)
        np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                                   atol=tol, rtol=tol)
        return q, k, v
"""


def run_sub(code: str, devices: int = 4) -> str:
    env = dict(os.environ)
    env["XLA_FLAGS"] = (f"--xla_force_host_platform_device_count={devices} "
                        + env.get("XLA_FLAGS", ""))
    env["PYTHONPATH"] = f"{ROOT}/src"
    env["TF_CPP_MIN_LOG_LEVEL"] = "2"
    out = subprocess.run([sys.executable, "-c",
                          textwrap.dedent(COMMON + code)],
                         capture_output=True, text=True, env=env,
                         timeout=900)
    assert out.returncode == 0, f"STDOUT:\n{out.stdout}\nSTDERR:\n{out.stderr}"
    return out.stdout


@pytest.mark.slow
def test_cp_causal_window():
    run_sub("""
        run_case(AttentionSpec(kind="swat", window=8, causal=True))
        print("ok")
    """)


@pytest.mark.slow
def test_cp_causal_window_global():
    run_sub("""
        run_case(AttentionSpec(kind="swat", window=8, num_global=4,
                               causal=True))
        print("ok")
    """)


@pytest.mark.slow
def test_cp_bidirectional_global():
    """Longformer-encoder style (the paper's own LRA configuration)."""
    run_sub("""
        run_case(AttentionSpec(kind="swat", window=8, num_global=4,
                               causal=False))
        print("ok")
    """)


@pytest.mark.slow
def test_cp_multihop_halo():
    """w > Lp: the halo spans two neighbour shards (2 ppermute hops)."""
    run_sub("""
        spec = AttentionSpec(kind="swat", window=24, causal=True)
        assert CP.halo_hops(24, 16) == 2
        run_case(spec)
        spec = AttentionSpec(kind="swat", window=24, causal=False)
        run_case(spec)
        print("ok")
    """)


@pytest.mark.slow
def test_cp_softcap_and_gqa():
    run_sub("""
        run_case(AttentionSpec(kind="swat", window=8, causal=True,
                               softcap=30.0), hq=8, hkv=2)
        print("ok")
    """)


@pytest.mark.slow
def test_cp_gradients():
    """shard_map transposes the halo ppermutes: grads match the oracle."""
    run_sub("""
        spec = AttentionSpec(kind="swat", window=8, num_global=4, causal=True)
        rng = np.random.RandomState(1)
        b, hq, hkv, l, d = 1, 2, 2, 64, 8
        q = jnp.asarray(rng.randn(b, hq, l, d), jnp.float32) * 0.5
        k = jnp.asarray(rng.randn(b, hkv, l, d), jnp.float32) * 0.5
        v = jnp.asarray(rng.randn(b, hkv, l, d), jnp.float32) * 0.5
        t = jnp.asarray(rng.randn(b, hq, l, d), jnp.float32)

        def loss_cp(q, k, v):
            with jax.set_mesh(mesh):
                o = CP.swat_attention_context_parallel(
                    q, k, v, spec, mesh=mesh, axis="seq",
                    block_q=16, block_kv=16)
            return jnp.sum((o.astype(jnp.float32) - t) ** 2)

        def loss_ref(q, k, v):
            o = R.attention_ref(q, k, v, spec)
            return jnp.sum((o.astype(jnp.float32) - t) ** 2)

        g_cp = jax.grad(loss_cp, argnums=(0, 1, 2))(q, k, v)
        g_ref = jax.grad(loss_ref, argnums=(0, 1, 2))(q, k, v)
        for a, b_ in zip(g_cp, g_ref):
            np.testing.assert_allclose(np.asarray(a), np.asarray(b_),
                                       atol=5e-2, rtol=5e-2)
        print("ok")
    """)


@pytest.mark.slow
def test_cp_wire_bytes_vs_allgather():
    """The headline claim: halo wire bytes are O(w), independent of L."""
    from repro.distributed import context_parallel as CP
    w, h, d = 512, 16, 64
    for L in (16384, 65536, 524288):
        halo = CP.cp_wire_bytes_per_device(L, 16, w, h, d)
        # all-gather alternative moves the full remote KV: (L - Lp) * 2 * row
        allgather = 2 * (L - L // 16) * h * d * 2
        assert halo < allgather / 10, (L, halo, allgather)
    # halo bytes CONSTANT in L once the window fits one shard: O(w) exactly
    assert (CP.cp_wire_bytes_per_device(2 ** 14, 16, w, h, d)
            == CP.cp_wire_bytes_per_device(2 ** 19, 16, w, h, d)
            == 2 * w * h * d * 2)
    # multi-hop (w > Lp) ships whole shards, bounded by 2w
    assert CP.cp_wire_bytes_per_device(2 ** 10, 16, w, h, d) \
        <= 2 * 2 * w * h * d * 2
