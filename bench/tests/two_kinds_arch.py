"""A test-only architecture: a decoder whose layers alternate a causal
window of `window` keys (no sinks) with full causal attention, a dense
silu-gated FFN and an output head of its own (untied). test_arch.py copies
this file into a copy of the benchmark as `arch/two_kinds.py`, beside a
configuration that names it, to show that a configuration brings its
architecture as new files alone.

Per layer, as the default architecture (arch/granite_swat.py) but with the
window of its kind: layer 2i a window, layer 2i+1 full. Program: layer
pattern ("attn", "attn") over dense causal attention, window_schedule
(window, None), tie_embeddings false.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

import arch
import reference as R

KINDS = ("window", "full")
ATTN = ("wq", "wk", "wv", "wo")
FFN = ("w1", "w2", "w3")


def _dims(cfg: dict):
    return (cfg["hidden_size"], cfg["num_attention_heads"],
            cfg["num_key_value_heads"], cfg["head_dim"],
            cfg["intermediate_size"], cfg["vocab_size"],
            cfg["num_hidden_layers"], cfg["window"])


def layers(cfg: dict):
    d, hq, hkv, dh, f, _, n, w = _dims(cfg)
    return [{"d": d, "norms": 2, "ffn": {"kind": "dense", "width": f},
             "mixer": {"kind": "attention", "hq": hq, "hkv": hkv, "dh": dh,
                       "window": w if i % 2 == 0 else None, "sinks": 0}}
            for i in range(n)]


def shapes(cfg: dict):
    d, hq, hkv, dh, f, v, n, _ = _dims(cfg)
    bf, f32 = jnp.bfloat16, jnp.float32

    def mat(shape):
        return (shape, bf, shape[-2] ** -0.5)

    layer = {"attn_norm": ((d,), f32, 0.1), "ffn_norm": ((d,), f32, 0.1),
             "wq": mat((d, hq * dh)), "wk": mat((d, hkv * dh)),
             "wv": mat((d, hkv * dh)), "wo": mat((hq * dh, d)),
             "w1": mat((d, f)), "w3": mat((d, f)), "w2": mat((f, d))}
    return {"top": [("embed", (v, d), bf, d ** -0.5),
                    ("final_norm", (d,), f32, 0.1), ("head",) + mat((d, v))],
            "stacks": [(kind, n // 2, layer) for kind in KINDS]}


def program(cfg: dict, weights: dict):
    from repro.core.types import AttentionSpec, ModelConfig
    d, hq, hkv, dh, f, v, n, w = _dims(cfg)
    mcfg = ModelConfig(
        name="two-kinds", num_layers=n, d_model=d, num_heads=hq,
        num_kv_heads=hkv, head_dim=dh, d_ff=f, vocab_size=v,
        layer_pattern=("attn", "attn"),
        attention=AttentionSpec(kind="dense", causal=True),
        window_schedule=(w, None), tie_embeddings=False,
        rope_theta=float(cfg["rope_theta"]), norm_eps=float(cfg["rms_norm_eps"]),
        dtype=cfg["torch_dtype"])

    def block(st):
        return {"norm1": {"scale": st["attn_norm"]},
                "mixer": {k: st[k] for k in ATTN},
                "norm2": {"scale": st["ffn_norm"]},
                "mlp": {k: st[k] for k in FFN}}

    params = {"embed": weights["embed"], "lm_head": weights["head"],
              "blocks": {f"l{i}": block(weights[k]) for i, k in enumerate(KINDS)},
              "final_norm": {"scale": weights["final_norm"]}}
    return mcfg, arch.checked(params, mcfg)


def departures(cfg: dict):
    return []


@functools.partial(jax.jit, static_argnames=("dims", "fp8"))
def _layer(x, stack, i, *, dims, fp8):
    hq, hkv, dh, window, theta, eps = dims
    lw = {k: jax.lax.dynamic_index_in_dim(a, i, 0, keepdims=False)
          for k, a in stack.items()}
    t = x.shape[0]
    pos = jnp.arange(t)
    h = R.a(R.rms(x, lw["attn_norm"], eps), fp8)
    q = R.rope(R.dot(h, R.w(lw["wq"], fp8)).reshape(t, hq, dh), pos, theta)
    k = R.rope(R.dot(h, R.w(lw["wk"], fp8)).reshape(t, hkv, dh), pos, theta)
    v = R.dot(h, R.w(lw["wv"], fp8)).reshape(t, hkv, dh)
    x = x + R.dot(R.a(R.attention(q, k, v, window, 0), fp8), R.w(lw["wo"], fp8))
    h = R.a(R.rms(x, lw["ffn_norm"], eps), fp8)
    gated = jax.nn.silu(R.dot(h, R.w(lw["w1"], fp8))) * R.dot(h, R.w(lw["w3"], fp8))
    return x + R.dot(R.a(gated, fp8), R.w(lw["w2"], fp8))


def score(weights, cfg, seq, positions, tokens, fp8=False, router_margin=None):
    _, hq, hkv, dh, _, _, n, w = _dims(cfg)
    theta, eps = float(cfg["rope_theta"]), float(cfg["rms_norm_eps"])
    x = R.embed(weights["embed"], jnp.asarray(R.pad(seq)))
    for i in range(n // 2):
        for kind, window in zip(KINDS, (w, None)):
            x = _layer(x, weights[kind], jnp.int32(i),
                       dims=(hq, hkv, dh, window, theta, eps), fp8=fp8)
    gap, arg, spread = R.head(x, weights["final_norm"], weights["head"],
                              jnp.asarray(positions, jnp.int32),
                              jnp.asarray(tokens, jnp.int32),
                              eps=eps, fp8=fp8, tied=False)
    return (np.asarray(gap), np.asarray(arg), np.asarray(spread),
            R.decisive(None, positions, router_margin))
