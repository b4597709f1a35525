"""Seeded random weights for a configuration, made on the device in one
jitted call, in the types they are served in (matmul weights bf16, norm
gains and the router float32). The layout is the benchmark's own plain one,
read by the reference as it is; `driver.py` maps it onto the program's
parameter tree without copying.

  embed       (vocab, d)              tied input embedding / output head
  final_norm  (d,)                    RMSNorm gain offset: gain = 1 + value
  layers/     stacked over layers (leading axis n):
    attn_norm, ffn_norm               (n, d)
    wq (n, d, hq*dh)  wk, wv (n, d, hkv*dh)  wo (n, hq*dh, d)
    dense FFN:  w1, w3 (n, d, f)  w2 (n, f, d)
    experts:    router (n, d, e)  w1, w3 (n, e, d, f)  w2 (n, e, f, d)

Matmul weights are normal with standard deviation 1/sqrt(fan_in); the
embedding has 1/sqrt(d), so the tied output logits have unit spread; norm
offsets are normal with standard deviation 0.1, so every gain matters.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

import cost


def jax_seed(seed: int) -> int:
    """A non-negative 31-bit key from any whole-number seed."""
    return int(np.random.SeedSequence(int(seed)).generate_state(1)[0]) & 0x7FFFFFFF


def shapes(cfg: dict) -> dict:
    s = cost.dims(cfg)
    d, hq, hkv, dh, f, e, n = (s["d"], s["hq"], s["hkv"], s["dh"], s["f"],
                               s["e"], s["n"])
    layer = {"attn_norm": ((d,), jnp.float32), "ffn_norm": ((d,), jnp.float32),
             "wq": ((d, hq * dh), jnp.bfloat16),
             "wk": ((d, hkv * dh), jnp.bfloat16),
             "wv": ((d, hkv * dh), jnp.bfloat16),
             "wo": ((hq * dh, d), jnp.bfloat16)}
    if e:
        layer.update(router=((d, e), jnp.float32),
                     w1=((e, d, f), jnp.bfloat16), w3=((e, d, f), jnp.bfloat16),
                     w2=((e, f, d), jnp.bfloat16))
    else:
        layer.update(w1=((d, f), jnp.bfloat16), w3=((d, f), jnp.bfloat16),
                     w2=((f, d), jnp.bfloat16))
    return {"n": n, "d": d, "v": s["v"], "layer": layer}


@functools.partial(jax.jit, static_argnums=(1,))
def _make(key, spec):
    n, d, v, layer = spec
    k_emb, k_norm, k_layers = jax.random.split(key, 3)

    def one_layer(k):
        out = {}
        for i, (name, shape, dtype) in enumerate(layer):
            kk = jax.random.fold_in(k, i)
            if name.endswith("norm"):
                out[name] = 0.1 * jax.random.normal(kk, shape, jnp.float32)
            else:
                scale = shape[-2] ** -0.5                 # 1/sqrt(fan_in)
                out[name] = (jax.random.normal(kk, shape, dtype)
                             * jnp.asarray(scale, dtype)).astype(dtype)
        return out

    layers = jax.lax.map(one_layer, jax.random.split(k_layers, n))
    embed = (jax.random.normal(k_emb, (v, d), jnp.bfloat16)
             * jnp.asarray(d ** -0.5, jnp.bfloat16))
    final = 0.1 * jax.random.normal(k_norm, (d,), jnp.float32)
    return {"embed": embed, "final_norm": final, "layers": layers}


def make(cfg: dict, seed: int):
    sh = shapes(cfg)
    spec = (sh["n"], sh["d"], sh["v"],
            tuple((k, s, dt) for k, (s, dt) in sorted(sh["layer"].items())))
    return _make(jax.random.PRNGKey(jax_seed(seed) ^ 0x5EED), spec)
