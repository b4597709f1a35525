"""Trinity (arcee-ai, `model_type` afmoe): sliding-window and full attention
layers, both gated and QK-normed, over leading dense FFN layers and then
sigmoid-routed experts with a shared expert; sandwich norms, embeddings
scaled by sqrt(hidden_size), an untied head.

The reference, per layer, for hidden states x (T, d), written from the
afmoe modelling code (transformers `AfmoeForCausalLM`):
  x0 = embed[tokens] * sqrt(d)                          (mup_enabled)
  h = rms(x) * (1 + attn_norm)
  q = h @ wq (T, hq, dh); k = h @ wk, v = h @ wv (T, hkv, dh)
  q = rms_head(q) * (1 + q_norm); k = rms_head(k) * (1 + k_norm)
      (RMSNorm over head_dim)
  sliding layers: rotary (split halves, rope_theta) on q and k; full
      layers: none (NoPE)
  causal attention, scores scaled by dh**-0.5, grouped kv heads; a sliding
      query i sees key j iff i - j < sliding_window
  o = attn * sigmoid(h @ wg)                            (wg: d -> hq*dh)
  x = x + rms(o @ wo) * (1 + post_attn_norm)            (sandwich norm)
  h = rms(x) * (1 + ffn_norm)
  layers < num_dense_layers: y = (silu(h @ w1) * (h @ w3)) @ w2
  expert layers: s = sigmoid(h @ router) in float32 over the router's
      whole published width; chosen = top-k(s + expert_bias) (the bias
      steers selection only); w_e = route_scale * s_e / sum_chosen s;
      y = shared(h) + sum over chosen experts e held here of w_e FFN_e(h)
  x = x + rms(y) * (1 + post_ffn_norm)
logits = (rms(x) * (1 + final_norm)) @ head             (untied)

This chip's share (the configuration's `deployment`): of each expert
layer it holds experts 0..num_experts-1 of the router's published
num_experts; what the other experts add is left out here and in the program
alike. The file's `layer_types` are the published ones; the model is their
first num_hidden_layers. Read where the source is uncertain, as the file's
`assumed` says: NoPE on the full layers, the gate elementwise over all
hq*dh outputs, route_norm over all k chosen before the share is cut, norm
gains stored as offsets from 1 (the program's parametrisation).

Weights (weights.make), one stack per layer (`l0`, `l1`, ...), each of one
layer (leading axis 1, as the program's one super-block holds it), matmul
weights bf16, norm gains, router and expert bias float32:
  embed (vocab, d), final_norm (d,), head (d, vocab)
  l<i>/ attn_norm, post_attn_norm, ffn_norm, post_ffn_norm (1, d)
        q_norm, k_norm (1, dh)
        wq, wg (1, d, hq*dh)  wk, wv (1, d, hkv*dh)  wo (1, hq*dh, d)
    dense: w1, w3 (1, d, f)  w2 (1, f, d)
    experts: router (1, d, router width)  expert_bias (1, router width)
             w1, w3 (1, held, d, fe)  w2 (1, held, fe, d)
             shared_w1, shared_w3 (1, d, fs)  shared_w2 (1, fs, d)
Matmul weights have standard deviation 1/sqrt(fan_in), the embedding and
the head 1/sqrt(d) (so x0 and the logits have unit spread), norm offsets
0.1, the expert bias 0.01 (about the gap between neighbouring sigmoid
scores at the top-k boundary, so the bias moves some choices).
"""
from __future__ import annotations

import dataclasses
import functools
from typing import List

import jax
import jax.numpy as jnp
import numpy as np

import arch
import cost
import reference as R

BIAS_STD = 0.01
ATTN = ("wq", "wk", "wv", "wo", "wg")
FFN = ("w1", "w2", "w3")


def dims(cfg: dict) -> dict:
    """The sizes the module reads, after refusing what it does not model."""
    want = {"model_type": "afmoe", "hidden_act": "silu", "score_func": "sigmoid",
            "route_norm": True, "rope_scaling": None, "n_group": 1,
            "num_expert_groups": 1, "topk_group": 1, "num_limited_groups": 1}
    for k, v in want.items():
        if cfg.get(k) != v:
            raise ValueError(f"afmoe: {k} {cfg.get(k)!r} is not modelled (only {v!r})")
    n, d = cfg["num_hidden_layers"], cfg["hidden_size"]
    hq, hkv = cfg["num_attention_heads"], cfg["num_key_value_heads"]
    fe = cfg["moe_intermediate_size"]
    return {"n": n, "d": d, "hq": hq, "hkv": hkv, "dh": cfg["head_dim"],
            "f": cfg["intermediate_size"], "fe": fe,
            "fs": cfg["num_shared_experts"] * fe, "dense": cfg["num_dense_layers"],
            "held": cfg["num_experts"], "router": cfg["published"]["num_experts"],
            "k": cfg["num_experts_per_tok"], "v": cfg["vocab_size"],
            "window": cfg["sliding_window"] - 1,    # i - j < sliding_window
            "full": [t == "full_attention" for t in cfg["layer_types"][:n]],
            "scale": float(cfg["route_scale"]), "theta": float(cfg["rope_theta"]),
            "eps": float(cfg["rms_norm_eps"])}


# ---------------------------------------------------------------- cost ---

def _mixer(s: dict, window, experts: bool) -> dict:
    """A gated, QK-normed attention layer's cost: its projections (the gate
    one more of wq's size), attention over the keys its mask shows, its
    two head_dim-wide norm gains, and, in an expert layer, the router's
    selection bias (float32, read with the router)."""
    attn = {"hq": s["hq"], "hkv": s["hkv"], "dh": s["dh"], "window": window,
            "sinks": 0}
    params = (s["d"] * (2 * s["hq"] + 2 * s["hkv"]) * s["dh"]
              + s["hq"] * s["dh"] * s["d"])
    row = cost.kv_row_bytes(attn)

    def flops(start, stop):
        return (2 * params * (stop - start)
                + 4 * s["hq"] * s["dh"] * cost.sum_visible_keys(attn, start, stop))

    def state_bytes(positions):
        return row * sum(cost.visible_keys(attn, p) for p in positions)

    return {"kind": "gated_attention", "flops": flops, "state_bytes": state_bytes,
            "weight_bytes": (params * cost.BF16 + 2 * s["dh"] * cost.F32
                             + (s["router"] * cost.F32 if experts else 0))}


def layers(cfg: dict) -> List[dict]:
    """One descriptor per layer; like layers share one descriptor object,
    so cost.py groups them. Four d-wide norm gains a layer (sandwich)."""
    s = dims(cfg)
    dense = {"kind": "dense", "width": s["f"]}
    experts = {"kind": "experts", "router_width": s["router"], "held": s["held"],
               "k": s["k"], "width": s["fe"], "shared": s["fs"]}
    kinds = {}
    out = []
    for i, full in enumerate(s["full"]):
        key = (full, i >= s["dense"])
        if key not in kinds:
            kinds[key] = {"d": s["d"], "norms": 4,
                          "mixer": _mixer(s, None if full else s["window"], key[1]),
                          "ffn": experts if key[1] else dense}
        out.append(kinds[key])
    return out


def shapes(cfg: dict) -> dict:
    s = dims(cfg)
    d, hq, hkv, dh = s["d"], s["hq"], s["hkv"], s["dh"]
    bf, f32 = jnp.bfloat16, jnp.float32

    def mat(shape, dtype=bf):
        return (shape, dtype, shape[-2] ** -0.5)        # 1/sqrt(fan_in)

    def gain(width):
        return ((width,), f32, 0.1)

    attn = {"attn_norm": gain(d), "post_attn_norm": gain(d), "ffn_norm": gain(d),
            "post_ffn_norm": gain(d), "q_norm": gain(dh), "k_norm": gain(dh),
            "wq": mat((d, hq * dh)), "wg": mat((d, hq * dh)),
            "wk": mat((d, hkv * dh)), "wv": mat((d, hkv * dh)),
            "wo": mat((hq * dh, d))}
    dense = dict(attn, w1=mat((d, s["f"])), w3=mat((d, s["f"])),
                 w2=mat((s["f"], d)))
    e, fe, fs = s["held"], s["fe"], s["fs"]
    experts = dict(attn, router=mat((d, s["router"]), f32),
                   expert_bias=((s["router"],), f32, BIAS_STD),
                   w1=mat((e, d, fe)), w3=mat((e, d, fe)), w2=mat((e, fe, d)),
                   shared_w1=mat((d, fs)), shared_w3=mat((d, fs)),
                   shared_w2=mat((fs, d)))
    return {"top": [("embed", (s["v"], d), bf, d ** -0.5),
                    ("final_norm", (d,), f32, 0.1),
                    ("head", (d, s["v"]), bf, d ** -0.5)],
            "stacks": [(f"l{i}", 1, experts if i >= s["dense"] else dense)
                       for i in range(s["n"])]}


# ------------------------------------------------------------- program ---

def model_config(cfg: dict):
    """The program's ModelConfig: the registered architecture at the file's
    depth, with this chip's share of each expert layer."""
    from repro.configs import get_config
    from repro.configs.trinity_mini import layer_pattern
    s = dims(cfg)
    base = get_config(cfg["repro_arch"])
    return dataclasses.replace(
        base, num_layers=s["n"], d_model=s["d"], num_heads=s["hq"],
        num_kv_heads=s["hkv"], head_dim=s["dh"], d_ff=s["f"], vocab_size=s["v"],
        layer_pattern=layer_pattern(s["n"], s["dense"]),
        window_schedule=tuple(None if f else s["window"] for f in s["full"]),
        moe=dataclasses.replace(
            base.moe, num_experts=s["router"], top_k=s["k"], held=s["held"],
            expert_width=s["fe"], shared_width=s["fs"],
            score=cfg["score_func"], route_scale=s["scale"]),
        rope_theta=s["theta"], norm_eps=s["eps"],
        tie_embeddings=bool(cfg["tie_word_embeddings"]),
        embed_scale=bool(cfg["mup_enabled"]),
        dtype=cfg.get("torch_dtype", "bfloat16"))


def to_params(weights: dict, mcfg):
    """The weights as the program's parameter tree (the same arrays, no
    copy), checked leaf by leaf against `init_model`."""
    def block(lw):
        p = {"norm1": {"scale": lw["attn_norm"]},
             "post_norm1": {"scale": lw["post_attn_norm"]},
             "norm2": {"scale": lw["ffn_norm"]},
             "post_norm2": {"scale": lw["post_ffn_norm"]},
             "mixer": dict({k: lw[k] for k in ATTN},
                           q_norm={"scale": lw["q_norm"]},
                           k_norm={"scale": lw["k_norm"]})}
        if "router" in lw:
            p["moe"] = dict({k: lw[k] for k in FFN}, router=lw["router"],
                            bias=lw["expert_bias"],
                            shared={k: lw[f"shared_{k}"] for k in FFN})
        else:
            p["mlp"] = {k: lw[k] for k in FFN}
        return p

    blocks = {f"l{i}": block(weights[f"l{i}"]) for i in range(mcfg.num_layers)}
    return arch.checked({"embed": weights["embed"], "lm_head": weights["head"],
                         "blocks": blocks,
                         "final_norm": {"scale": weights["final_norm"]}}, mcfg)


def program(cfg: dict, weights: dict):
    mcfg = model_config(cfg)
    return mcfg, to_params(weights, mcfg)


def departures(cfg: dict) -> List[str]:
    """Where the registered program differs from the file's published
    sizes, leaving out the keys the file lists as reduced."""
    from repro.configs import get_config
    base = get_config(cfg["repro_arch"])
    windows = [w for w in base.window_schedule if w is not None]
    pairs = {"num_hidden_layers": base.num_layers, "hidden_size": base.d_model,
             "num_attention_heads": base.num_heads,
             "num_key_value_heads": base.num_kv_heads,
             "head_dim": base.resolved_head_dim, "intermediate_size": base.d_ff,
             "moe_intermediate_size": base.moe.expert_width,
             "num_shared_experts": base.moe.shared_width // base.moe.expert_width,
             "vocab_size": base.vocab_size, "num_experts": base.moe.num_experts,
             "num_experts_per_tok": base.moe.top_k,
             "num_dense_layers": base.layer_pattern.count("attn"),
             "route_scale": base.moe.route_scale, "score_func": base.moe.score,
             "sliding_window": windows[0] + 1, "rope_theta": base.rope_theta,
             "rms_norm_eps": base.norm_eps,
             "tie_word_embeddings": base.tie_embeddings,
             "mup_enabled": base.embed_scale}
    out = []
    for k, v in pairs.items():
        want = cfg.get(k, 0)
        if k not in cfg.get("reduced", []) and v != want:
            out.append(f"{k}: program {v} file {want}")
    if cfg["published"]["num_experts"] != base.moe.num_experts:
        out.append(f"router width: program {base.moe.num_experts} "
                   f"file {cfg['published']['num_experts']}")
    return out


# ----------------------------------------------------------- reference ---

def _ffn(h, w1, w3, w2, fp8):
    h = R.a(h, fp8)
    gated = jax.nn.silu(R.dot(h, R.w(w1, fp8))) * R.dot(h, R.w(w3, fp8))
    return R.dot(R.a(gated, fp8), R.w(w2, fp8))


@functools.partial(jax.jit, static_argnames=("dims", "fp8"))
def _layer(x, lw, *, dims, fp8):
    """One layer; returns x and, per row, the router's gap between its k-th
    and (k+1)-th selection score (reference.router_gap; infinite for a
    dense FFN)."""
    hq, hkv, dh, window, k, scale, theta, eps = dims
    lw = {name: a[0] for name, a in lw.items()}
    t = x.shape[0]
    pos = jnp.arange(t)
    h = R.a(R.rms(x, lw["attn_norm"], eps), fp8)
    q = R.dot(h, R.w(lw["wq"], fp8)).reshape(t, hq, dh)
    kk = R.dot(h, R.w(lw["wk"], fp8)).reshape(t, hkv, dh)
    vv = R.dot(h, R.w(lw["wv"], fp8)).reshape(t, hkv, dh)
    q, kk = R.rms(q, lw["q_norm"], eps), R.rms(kk, lw["k_norm"], eps)
    if window is not None:
        q, kk = R.rope(q, pos, theta), R.rope(kk, pos, theta)
    o = R.attention(q, kk, vv, window, 0)
    o = o * jax.nn.sigmoid(R.dot(h, R.w(lw["wg"], fp8)))
    x = x + R.rms(R.dot(R.a(o, fp8), R.w(lw["wo"], fp8)), lw["post_attn_norm"], eps)
    h = R.rms(x, lw["ffn_norm"], eps)
    if "router" not in lw:
        y = _ffn(h, lw["w1"], lw["w3"], lw["w2"], fp8)
        gap = jnp.full((t,), jnp.inf, jnp.float32)
    else:
        s = jax.nn.sigmoid(R.dot(R.a(h, fp8), R.w(lw["router"], fp8)))  # (T, E)
        select = s + lw["expert_bias"]
        _, idx = jax.lax.top_k(select, k)
        chosen = jnp.zeros_like(s).at[jnp.arange(t)[:, None], idx].set(1.0)
        weight = scale * s * chosen / jnp.sum(s * chosen, -1, keepdims=True)
        held = lw["w1"].shape[0]

        def expert(y, e):
            w1, w3, w2, g = e
            return y + g[:, None] * _ffn(h, w1, w3, w2, fp8), None

        y = _ffn(h, lw["shared_w1"], lw["shared_w3"], lw["shared_w2"], fp8)
        y, _ = jax.lax.scan(expert, y, (lw["w1"], lw["w3"], lw["w2"],
                                        weight[:, :held].T))
        gap = R.router_gap(select, k)
    return x + R.rms(y, lw["post_ffn_norm"], eps), gap


def score(weights, cfg: dict, seq: np.ndarray, positions: np.ndarray,
          tokens: np.ndarray, fp8: bool = False, router_margin=None):
    """As arch/__init__.py says: per position of `positions`, the gap of
    `tokens[i]` below the best logit, the argmax, the logits' spread, and
    whether the position is decisive (every expert layer's k-th selection
    score more than `router_margin` above its (k+1)-th)."""
    s = dims(cfg)
    x = R.embed(weights["embed"], jnp.asarray(R.pad(seq))) * np.float32(np.sqrt(s["d"]))
    gaps = None
    for i, full in enumerate(s["full"]):
        dm = (s["hq"], s["hkv"], s["dh"], None if full else s["window"], s["k"],
              s["scale"], s["theta"], s["eps"])
        x, g = _layer(x, weights[f"l{i}"], dims=dm, fp8=fp8)
        if i >= s["dense"]:
            gaps = g if gaps is None else jnp.minimum(gaps, g)
    gap, arg, spread = R.head(x, weights["final_norm"], weights["head"],
                              jnp.asarray(positions, jnp.int32),
                              jnp.asarray(tokens, jnp.int32),
                              eps=s["eps"], fp8=fp8, tied=False)
    return (np.asarray(gap), np.asarray(arg), np.asarray(spread),
            R.decisive(gaps, positions, router_margin))
