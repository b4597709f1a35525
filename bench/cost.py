"""Operations and bytes the served model needs, computed from the
configuration file's sizes alone (never from the program).

Conventions (the same as `banded_decode_cost` in the program's kernel
profiler, which counts the logical band for FLOPs, extended here to the
whole model):
  * a matmul of an (m, k) by (k, n) costs 2*m*k*n FLOPs;
  * attention at query position p visits the keys it may see under the
    window-plus-sink mask -- j <= p and (p - j <= window or j < sinks) --
    at 4 * head_dim FLOPs per (head, key): QK^T and PV;
  * "routed" counts the top-k experts a token is sent to (the work a
    mixture of experts needs); "dense" counts every expert, which is what
    dense dispatch computes;
  * weight bytes are read once per decode step; a key/value row is
    2 * kv_heads * head_dim * 2 bytes per layer in bf16.
"""
from __future__ import annotations

from typing import Iterable

BF16 = 2
F32 = 4


def dims(cfg: dict) -> dict:
    d = cfg["hidden_size"]
    hq, hkv = cfg["num_attention_heads"], cfg["num_key_value_heads"]
    dh = cfg.get("head_dim") or d // hq
    e = cfg.get("num_local_experts", 0)
    return {"d": d, "hq": hq, "hkv": hkv, "dh": dh,
            "f": cfg["intermediate_size"], "e": e,
            "k": cfg.get("num_experts_per_tok", 0) if e else 0,
            "v": cfg["vocab_size"], "n": cfg["num_hidden_layers"],
            "w": cfg["swat"]["window"], "g": cfg["swat"]["num_global"]}


def visible_keys(cfg: dict, pos: int) -> int:
    """Keys a query at absolute position `pos` attends under the
    window-plus-sink causal mask."""
    s = dims(cfg)
    band = min(pos + 1, s["w"] + 1)
    sinks = max(0, min(s["g"], pos - s["w"]))
    return band + sinks


def sum_visible_keys(cfg: dict, start: int, stop: int) -> int:
    """sum of visible_keys(pos) for pos in [start, stop), in closed form."""
    s = dims(cfg)
    w, g = s["w"], s["g"]

    def prefix(n):  # sum over pos in [0, n)
        if n <= 0:
            return 0
        m = min(n, w + 1)                 # pos < w+1: band = pos+1, no sinks
        total = m * (m + 1) // 2
        rest = n - m                      # pos >= w+1: band = w+1
        total += rest * (w + 1)
        # sinks: min(g, pos - w) for pos in [w+1, n)
        if rest > 0:
            full_from = w + g             # pos >= w+g: g sinks
            ramp_hi = min(n, full_from)   # pos in [w+1, ramp_hi): pos-w
            if ramp_hi > w + 1:
                a, b = 1, ramp_hi - w - 1
                total += (a + b) * (b - a + 1) // 2
            if n > full_from:
                total += (n - full_from) * g
        return total

    return prefix(stop) - prefix(start)


def layer_params(cfg: dict, routed: bool = True) -> dict:
    s = dims(cfg)
    attn = s["d"] * (s["hq"] + 2 * s["hkv"]) * s["dh"] + s["hq"] * s["dh"] * s["d"]
    if s["e"]:
        experts = s["k"] if routed else s["e"]
        ffn = experts * 3 * s["d"] * s["f"]
        router = s["d"] * s["e"]
    else:
        ffn = 3 * s["d"] * s["f"]
        router = 0
    return {"attn": attn, "ffn": ffn, "router": router}


def token_flops(cfg: dict, pos: int, *, routed: bool = True,
                unembed: bool = True) -> float:
    """FLOPs to run one token at absolute position `pos` through the model."""
    s = dims(cfg)
    p = layer_params(cfg, routed)
    per_layer = 2 * (p["attn"] + p["ffn"] + p["router"])
    attn = 4 * s["hq"] * s["dh"] * visible_keys(cfg, pos)
    out = s["n"] * (per_layer + attn)
    if unembed:
        out += 2 * s["d"] * s["v"]
    return float(out)


def prefill_flops(cfg: dict, length: int, *, routed: bool = True) -> float:
    """A prompt of `length` tokens: every token through every layer, and the
    vocabulary projection for the last one only (the only logits needed)."""
    s = dims(cfg)
    p = layer_params(cfg, routed)
    per_layer = 2 * (p["attn"] + p["ffn"] + p["router"])
    attn = 4 * s["hq"] * s["dh"] * sum_visible_keys(cfg, 0, length)
    return float(s["n"] * (per_layer * length + attn) + 2 * s["d"] * s["v"])


def decode_flops(cfg: dict, positions: Iterable[int], *,
                 routed: bool = True) -> float:
    """One output token for each slot at the given absolute positions."""
    return float(sum(token_flops(cfg, p, routed=routed) for p in positions))


def weight_bytes(cfg: dict, experts_read: float = None) -> float:
    """Bytes of weights one decode step must read. Matmul weights are bf16,
    norms and the router float32. `experts_read` is how many experts of each
    layer the step's tokens select (all of them when None)."""
    s = dims(cfg)
    attn = layer_params(cfg)["attn"] * BF16
    if s["e"]:
        e = s["e"] if experts_read is None else experts_read
        ffn = e * 3 * s["d"] * s["f"] * BF16 + s["d"] * s["e"] * F32
    else:
        ffn = 3 * s["d"] * s["f"] * BF16
    norms = 2 * s["d"] * F32
    return float(s["n"] * (attn + ffn + norms)
                 + s["v"] * s["d"] * BF16 + s["d"] * F32)


def expected_experts(cfg: dict, tokens: int) -> float:
    """Expected number of distinct experts of one layer that `tokens`
    independently routed tokens select (each picks k of e without
    replacement): e * (1 - (1 - k/e)**tokens)."""
    s = dims(cfg)
    if not s["e"]:
        return 0.0
    return s["e"] * (1.0 - (1.0 - s["k"] / s["e"]) ** tokens)


def kv_row_bytes(cfg: dict) -> float:
    """Key plus value bytes of one cached token in one layer."""
    s = dims(cfg)
    return float(2 * s["hkv"] * s["dh"] * BF16)


def decode_step_bytes(cfg: dict, positions: Iterable[int]) -> float:
    """Bytes one decode step needs: the weights once (experts as selected by
    the live tokens), each live slot's visible key/value rows, and the new
    row each slot writes. `positions` are the live slots' absolute positions
    (the position of the token being fed). The fed token is among its own
    visible keys: it is written once and never needs reading back, so each
    slot moves exactly visible_keys rows."""
    positions = list(positions)
    if not positions:
        return 0.0
    s = dims(cfg)
    experts = expected_experts(cfg, len(positions)) if s["e"] else None
    kv = sum(visible_keys(cfg, p) for p in positions)
    kv_bytes = s["n"] * kv_row_bytes(cfg) * kv
    return weight_bytes(cfg, experts) + kv_bytes
