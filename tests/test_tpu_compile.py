"""Compile the Pallas kernels for a described TPU v5e at real widths.

Nothing here runs on a chip: the topology is described, not attached, and
the TPU compiler (Mosaic) lowers each kernel exactly as it would for the
device. That catches what interpret mode accepts and the chip refuses —
unaligned slices, over-budget VMEM, and ops Mosaic cannot lower (a select
whose values are i1 vectors refused every `num_global > 0` decode once).

Geometries:
  * gemma2-2b serving decode (B 4, Hkv 4, group 2, D 256, softcap 50): the
    local layers' 4160-row ring (window 4096, logical capacity 4097) and
    the global layers' 8192-row dense cache;
  * the `--swat` geometry of the head_dim-64 GQA-4 models (window 512,
    4 pinned global tokens);
  * `swat_attention` forward and backward at gemma2's local prefill shape.

The topology is described inside a fixture, never at import: only one
process may load the TPU library, and the tests run under several workers.
"""
import os

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.core.types import AttentionSpec
from repro.kernels import ops
from repro.kernels.swat_decode import swat_decode


@pytest.fixture(scope="module")
def topo():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    # a described-device compile written to a persistent cache could never
    # be read back without a chip: keep the cache off while these compile
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", was)


def _compiled_text(fn, *shapes):
    return jax.jit(fn).lower(*shapes).compile().as_text()


# (name, B, Hkv, group, D, W, ring_cap, window, num_global, softcap)
DECODE_GEOMETRIES = [
    ("gemma2_local", 4, 4, 2, 256, 4160, 4097, 4096, 0, 50.0),
    ("gemma2_global", 4, 4, 2, 256, 8192, 8192, 0, 0, 50.0),
    ("swat_g4_d64", 4, 8, 4, 64, 576, 517, 512, 4, 0.0),
]


@pytest.mark.parametrize("fused", [False, True], ids=["plain", "fused"])
@pytest.mark.parametrize("geom", DECODE_GEOMETRIES, ids=lambda g: g[0])
def test_swat_decode_compiles(one_chip, geom, fused):
    _, b, hkv, group, d, w, cap, window, g, softcap = geom
    dt = jnp.bfloat16

    def sds(shape, dtype=dt):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    shapes = [sds((b, hkv * group, 1, d)), sds((b, hkv, w, d)),
              sds((b, hkv, w, d)), sds((b,), jnp.int32)]
    if fused:
        shapes += [sds((b, hkv, 1, d)), sds((b, hkv, 1, d))]

    def fn(q, k, v, pos, *new):
        nk, nv = new if fused else (None, None)
        return swat_decode(q, k, v, pos, new_k=nk, new_v=nv, ring_cap=cap,
                           num_global=g, window=window, softcap=softcap,
                           interpret=False)

    assert "tpu_custom_call" in _compiled_text(fn, *shapes)


@pytest.mark.parametrize("direction", ["forward", "backward"])
def test_swat_attention_compiles_gemma2_local(one_chip, direction):
    b, hq, hkv, seq, d = 1, 8, 4, 8192, 256
    spec = AttentionSpec(kind="swat", window=4096, causal=True, softcap=50.0)
    q = jax.ShapeDtypeStruct((b, hq, seq, d), jnp.bfloat16, sharding=one_chip)
    kv = jax.ShapeDtypeStruct((b, hkv, seq, d), jnp.bfloat16,
                              sharding=one_chip)

    def fwd(q, k, v):
        return ops.swat_attention(q, k, v, spec, impl="pallas",
                                  interpret=False)

    if direction == "forward":
        fn = fwd
    else:
        def fn(q, k, v):
            return jax.grad(
                lambda *a: jnp.sum(fwd(*a).astype(jnp.float32)),
                argnums=(0, 1, 2))(q, k, v)

    assert "tpu_custom_call" in _compiled_text(fn, q, kv, kv)
