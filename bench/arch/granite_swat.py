"""Granite decoder (llama architecture, dense or with experts) whose every
attention layer is a causal window of `window` keys plus `num_global` sink
tokens (the configuration's `swat` group), the output head tied to the
embedding. The default architecture: a configuration without an `arch` key.

The reference, per layer, for hidden states x (T, d), written from the
published description:
  h = rmsnorm(x) * (1 + attn_norm)
  q, k, v = h @ wq, h @ wk, h @ wv; rotary (split halves, theta) on q and k
  query i sees key j iff j <= i and (i - j <= window or j < num_global);
  scores scaled by head_dim**-0.5; grouped kv heads (hq / hkv queries each)
  x = x + attn @ wo
  h = rmsnorm(x) * (1 + ffn_norm)
  dense:   x = x + (silu(h @ w1) * (h @ w3)) @ w2
  experts: p = softmax(h @ router); the top-k experts by p, their p
           renormalised to sum 1, weight each expert's silu-gated FFN
logits = (rmsnorm(x) * (1 + final_norm)) @ embed.T

As in the program: no Granite 3.0 multipliers (embedding, attention,
residual, logits; the Granite Code models have none), and norm gains stored
as offsets from 1. A configuration file lists such departures under
`assumed`. The program has no bias terms; a configuration that states them
(`attention_bias`, `mlp_bias`) is refused.

Weights (weights.make), matmul weights bf16, norm gains and the router
float32:
  embed       (vocab, d)              tied input embedding / output head
  final_norm  (d,)                    RMSNorm gain offset: gain = 1 + value
  layers/     stacked over layers (leading axis n):
    attn_norm, ffn_norm               (n, d)
    wq (n, d, hq*dh)  wk, wv (n, d, hkv*dh)  wo (n, hq*dh, d)
    dense FFN:  w1, w3 (n, d, f)  w2 (n, f, d)
    experts:    router (n, d, e)  w1, w3 (n, e, d, f)  w2 (n, e, f, d)
Matmul weights have standard deviation 1/sqrt(fan_in); the embedding
1/sqrt(d), so the tied output logits have unit spread; norm offsets 0.1,
so every gain matters.
"""
from __future__ import annotations

import dataclasses
import functools
from typing import List

import jax
import jax.numpy as jnp
import numpy as np

import arch
import reference as R


def dims(cfg: dict) -> dict:
    if cfg.get("attention_bias") or cfg.get("mlp_bias"):
        raise ValueError("bias terms are not modelled")
    d = cfg["hidden_size"]
    hq, hkv = cfg["num_attention_heads"], cfg["num_key_value_heads"]
    dh = cfg.get("head_dim") or d // hq
    e = cfg.get("num_local_experts", 0)
    return {"d": d, "hq": hq, "hkv": hkv, "dh": dh,
            "f": cfg["intermediate_size"], "e": e,
            "k": cfg.get("num_experts_per_tok", 0) if e else 0,
            "v": cfg["vocab_size"], "n": cfg["num_hidden_layers"],
            "w": cfg["swat"]["window"], "g": cfg["swat"]["num_global"]}


def layers(cfg: dict) -> List[dict]:
    s = dims(cfg)
    ffn = ({"kind": "experts", "router_width": s["e"], "held": s["e"],
            "k": s["k"], "width": s["f"], "shared": 0} if s["e"]
           else {"kind": "dense", "width": s["f"]})
    layer = {"d": s["d"], "norms": 2, "ffn": ffn,
             "mixer": {"kind": "attention", "hq": s["hq"], "hkv": s["hkv"],
                       "dh": s["dh"], "window": s["w"], "sinks": s["g"]}}
    return [layer] * s["n"]


def shapes(cfg: dict) -> dict:
    s = dims(cfg)
    d, hq, hkv, dh, f, e = s["d"], s["hq"], s["hkv"], s["dh"], s["f"], s["e"]
    bf, f32 = jnp.bfloat16, jnp.float32

    def mat(shape, dtype=bf):
        return (shape, dtype, shape[-2] ** -0.5)        # 1/sqrt(fan_in)

    layer = {"attn_norm": ((d,), f32, 0.1), "ffn_norm": ((d,), f32, 0.1),
             "wq": mat((d, hq * dh)), "wk": mat((d, hkv * dh)),
             "wv": mat((d, hkv * dh)), "wo": mat((hq * dh, d))}
    if e:
        layer.update(router=mat((d, e), f32), w1=mat((e, d, f)),
                     w3=mat((e, d, f)), w2=mat((e, f, d)))
    else:
        layer.update(w1=mat((d, f)), w3=mat((d, f)), w2=mat((f, d)))
    return {"top": [("embed", (s["v"], d), bf, d ** -0.5),
                    ("final_norm", (d,), f32, 0.1)],
            "stacks": [("layers", s["n"], layer)]}


# ------------------------------------------------------------- program ---

def model_config(cfg: dict):
    """The program's ModelConfig for a configuration file: the registered
    architecture with the file's sizes, converted to window-plus-sink
    attention as the file's `swat` group states."""
    from repro.configs import get_config, with_swat
    from repro.core.types import MoESpec
    if cfg.get("attention_bias") or cfg.get("mlp_bias"):
        raise ValueError("the program has no bias terms")
    base = get_config(cfg["repro_arch"])
    e = cfg.get("num_local_experts", 0)
    mc = dataclasses.replace(
        base,
        num_layers=cfg["num_hidden_layers"], d_model=cfg["hidden_size"],
        num_heads=cfg["num_attention_heads"],
        num_kv_heads=cfg["num_key_value_heads"],
        head_dim=cfg.get("head_dim") or cfg["hidden_size"] // cfg["num_attention_heads"],
        d_ff=cfg["intermediate_size"], vocab_size=cfg["vocab_size"],
        moe=(MoESpec(num_experts=e, top_k=cfg["num_experts_per_tok"],
                     dispatch=base.moe.dispatch) if e else MoESpec()),
        rope_theta=float(cfg["rope_theta"]), norm_eps=float(cfg["rms_norm_eps"]),
        tie_embeddings=bool(cfg["tie_word_embeddings"]),
        dtype=cfg.get("torch_dtype", "bfloat16"))
    return with_swat(mc, window=cfg["swat"]["window"],
                     num_global=cfg["swat"]["num_global"])


def to_params(weights: dict, mcfg):
    """The weights as the program's parameter tree (same arrays, no copy),
    checked leaf by leaf against `init_model`."""
    lw = weights["layers"]
    layer = {"norm1": {"scale": lw["attn_norm"]},
             "mixer": {k: lw[k] for k in ("wq", "wk", "wv", "wo")},
             "norm2": {"scale": lw["ffn_norm"]}}
    ffn = {k: lw[k] for k in ("w1", "w2", "w3")}
    if "router" in lw:
        layer["moe"] = dict(ffn, router=lw["router"])
    else:
        layer["mlp"] = ffn
    return arch.checked({"embed": weights["embed"], "blocks": {"l0": layer},
                         "final_norm": {"scale": weights["final_norm"]}}, mcfg)


def program(cfg: dict, weights: dict):
    mcfg = model_config(cfg)
    return mcfg, to_params(weights, mcfg)


def departures(cfg: dict) -> List[str]:
    from repro.configs import get_config
    base = get_config(cfg["repro_arch"])
    pairs = {"num_hidden_layers": base.num_layers, "hidden_size": base.d_model,
             "num_attention_heads": base.num_heads,
             "num_key_value_heads": base.num_kv_heads,
             "head_dim": base.resolved_head_dim,
             "intermediate_size": base.d_ff, "vocab_size": base.vocab_size,
             "num_local_experts": base.moe.num_experts,
             "num_experts_per_tok": base.moe.top_k,
             "rope_theta": base.rope_theta, "rms_norm_eps": base.norm_eps,
             "tie_word_embeddings": base.tie_embeddings}
    out = []
    for k, v in pairs.items():
        want = cfg.get(k, 0)
        if k not in cfg.get("reduced", []) and v != want:
            out.append(f"{k}: program {v} file {want}")
    return out


# ----------------------------------------------------------- reference ---

@functools.partial(jax.jit, static_argnames=("dims", "fp8"))
def _layer(x, stack, i, *, dims, fp8):
    """Layer i of the stack; returns x and, per row, the router's gap
    (reference.router_gap; infinite for a dense FFN)."""
    (hq, hkv, dh, e, k, w, g, theta, eps) = dims
    lw = {name: jax.lax.dynamic_index_in_dim(a, i, 0, keepdims=False)
          for name, a in stack.items()}
    t = x.shape[0]
    pos = jnp.arange(t)
    h = R.a(R.rms(x, lw["attn_norm"], eps), fp8)
    q = R.dot(h, R.w(lw["wq"], fp8)).reshape(t, hq, dh)
    kk = R.dot(h, R.w(lw["wk"], fp8)).reshape(t, hkv, dh)
    vv = R.dot(h, R.w(lw["wv"], fp8)).reshape(t, hkv, dh)
    q, kk = R.rope(q, pos, theta), R.rope(kk, pos, theta)
    x = x + R.dot(R.a(R.attention(q, kk, vv, w, g), fp8), R.w(lw["wo"], fp8))
    h = R.rms(x, lw["ffn_norm"], eps)
    if e:
        logits = R.dot(R.a(h, fp8), R.w(lw["router"], fp8))      # (T, e)
        probs = jax.nn.softmax(logits, axis=-1)
        top, idx = jax.lax.top_k(probs, k)
        top = top / jnp.sum(top, -1, keepdims=True)
        gates = jnp.zeros_like(probs).at[jnp.arange(t)[:, None], idx].set(top)
        hq8 = R.a(h, fp8)
        a = jnp.einsum("td,edf->etf", hq8, R.w(lw["w1"], fp8), precision=R.HI)
        b = jnp.einsum("td,edf->etf", hq8, R.w(lw["w3"], fp8), precision=R.HI)
        y = jnp.einsum("etf,efd->etd", R.a(jax.nn.silu(a) * b, fp8),
                       R.w(lw["w2"], fp8), precision=R.HI)
        x = x + jnp.einsum("te,etd->td", gates, y, precision=R.HI)
        return x, R.router_gap(logits, k)
    hq8 = R.a(h, fp8)
    a = R.dot(hq8, R.w(lw["w1"], fp8))
    b = R.dot(hq8, R.w(lw["w3"], fp8))
    x = x + R.dot(R.a(jax.nn.silu(a) * b, fp8), R.w(lw["w2"], fp8))
    return x, jnp.full((t,), jnp.inf, jnp.float32)


def score(weights, cfg: dict, seq: np.ndarray, positions: np.ndarray,
          tokens: np.ndarray, fp8: bool = False, router_margin=None):
    """Run the sequence `seq` once. For each row p of `positions` (a
    position whose next token was produced), return the gap by which
    `tokens[i]`'s logit lies below the best logit, the argmax token, the
    spread (standard deviation) of that row's logits, and whether p is
    decisive: at every expert layer the router's k-th logit lies more than
    `router_margin` above its (k+1)-th (reference.decisive)."""
    s = dims(cfg)
    dm = (s["hq"], s["hkv"], s["dh"], s["e"], s["k"], s["w"], s["g"],
          float(cfg["rope_theta"]), float(cfg["rms_norm_eps"]))
    x = R.embed(weights["embed"], jnp.asarray(R.pad(seq)))
    gaps = None
    for i in range(s["n"]):
        x, g = _layer(x, weights["layers"], jnp.int32(i), dims=dm, fp8=fp8)
        gaps = g if gaps is None else jnp.minimum(gaps, g)
    gap, arg, spread = R.head(x, weights["final_norm"], weights["embed"],
                              jnp.asarray(positions, jnp.int32),
                              jnp.asarray(tokens, jnp.int32),
                              eps=float(cfg["rms_norm_eps"]), fp8=fp8, tied=True)
    return (np.asarray(gap), np.asarray(arg), np.asarray(spread),
            R.decisive(gaps if s["e"] else None, positions, router_margin))
