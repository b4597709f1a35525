"""Context-parallel window attention: the paper's FIFO locality across
devices. A sequence sharded over N devices exchanges only a w-token halo
(jax.lax.ppermute) per attention call — wire bytes independent of L —
instead of the O(L) kv all-gather dense attention would force.

Runs on CPU with 4 forced host devices (re-execs itself to set the flag
before jax initializes).

    PYTHONPATH=src python examples/context_parallel.py
"""
import os
import sys

if "XLA_FLAGS" not in os.environ or "device_count" not in os.environ.get(
        "XLA_FLAGS", ""):
    os.environ["XLA_FLAGS"] = ("--xla_force_host_platform_device_count=4 "
                               + os.environ.get("XLA_FLAGS", ""))
    os.environ["TF_CPP_MIN_LOG_LEVEL"] = "2"
    os.execv(sys.executable, [sys.executable] + sys.argv)

import jax                                                    # noqa: E402
import jax.numpy as jnp                                       # noqa: E402
import numpy as np                                            # noqa: E402

from repro.core.types import AttentionSpec                    # noqa: E402
from repro.distributed import context_parallel as CP          # noqa: E402
from repro.kernels import ref as R                            # noqa: E402
from repro.launch.mesh import make_mesh                       # noqa: E402


def main():
    assert len(jax.devices()) == 4
    mesh = make_mesh((4,), ("seq",))
    spec = AttentionSpec(kind="swat", window=256, num_global=16, causal=True)

    B, H, L, D = 1, 4, 4096, 64
    rng = np.random.RandomState(0)
    q = jnp.asarray(rng.randn(B, H, L, D), jnp.float32) * 0.3
    k = jnp.asarray(rng.randn(B, H, L, D), jnp.float32) * 0.3
    v = jnp.asarray(rng.randn(B, H, L, D), jnp.float32) * 0.3

    with jax.set_mesh(mesh):
        out = CP.swat_attention_context_parallel(
            q, k, v, spec, mesh=mesh, axis="seq")
    ref = R.attention_ref(q, k, v, spec)
    err = float(jnp.max(jnp.abs(out.astype(jnp.float32)
                                - ref.astype(jnp.float32))))
    print(f"CP(4 shards) vs O(N^2) oracle: max err {err:.2e}")
    assert err < 1e-3

    # the headline scaling: halo wire bytes don't grow with L
    print(f"{'L':>10} {'halo B/dev':>12} {'all-gather B/dev':>17} {'x':>7}")
    for L_ in (8192, 65536, 524288):
        halo = CP.cp_wire_bytes_per_device(L_, 16, 512, H, D, batch=B)
        ag = 2 * (L_ - L_ // 16) * H * D * 2 * B
        print(f"{L_:>10} {halo:>12,} {ag:>17,} {ag / halo:>6.0f}x")


if __name__ == "__main__":
    main()
