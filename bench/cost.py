"""Operations and bytes the served model needs, computed from the
configuration file's sizes alone (never from the program), summed over the
layer descriptors its architecture module gives (`layers(cfg)`).

A layer descriptor: {"d": width, "norms": d-wide float32 gains, "mixer":
part, "ffn": part}, each part one of
  {"kind": "attention", "hq", "hkv", "dh", "window", "sinks"}
      window None is full causal attention;
  {"kind": "dense", "width"};
  {"kind": "experts", "router_width", "held", "k", "width", "shared"}
      the router scores `router_width` experts and picks k; this chip holds
      `held` of them; `shared` is a shared expert's width (0 if none);
  any other kind: {"kind", "flops": f(start, stop) -> FLOPs of the tokens
      at positions [start, stop), "weight_bytes": bytes,
      "state_bytes": f(positions) -> state bytes one decode step reads}.
The output head is the configuration's hidden_size x vocab_size.

Conventions (the same as `banded_decode_cost` in the program's kernel
profiler, which counts the logical band for FLOPs, extended here to the
whole model):
  * a matmul of an (m, k) by (k, n) costs 2*m*k*n FLOPs;
  * attention at query position p visits the keys it may see under its
    mask -- j <= p and, windowed, (p - j <= window or j < sinks) -- at
    4 * head_dim FLOPs per (head, key): QK^T and PV;
  * "routed" counts the experts a token is sent to that this chip holds,
    k * held / router_width on average (the work a mixture of experts
    needs), plus the shared expert; "dense" counts every held expert,
    which is what dense dispatch computes;
  * weight bytes are read once per decode step, experts as the step's
    tokens select them; a key/value row is 2 * kv_heads * head_dim * 2
    bytes per layer in bf16.
"""
from __future__ import annotations

from typing import Iterable, List, Optional

import arch

BF16 = 2
F32 = 4


def _groups(cfg: dict) -> List[list]:
    """[layer descriptor, count] for each distinct layer of the model:
    like layers are counted once and multiplied."""
    out: List[list] = []
    for layer in arch.load(cfg).layers(cfg):
        for g in out:
            if g[0] == layer:
                g[1] += 1
                break
        else:
            out.append([layer, 1])
    return out


def visible_keys(attn: dict, pos: int) -> int:
    """Keys a query at absolute position `pos` attends."""
    w, g = attn["window"], attn["sinks"]
    if w is None:
        return pos + 1
    band = min(pos + 1, w + 1)
    sinks = max(0, min(g, pos - w))
    return band + sinks


def sum_visible_keys(attn: dict, start: int, stop: int) -> int:
    """sum of visible_keys(pos) for pos in [start, stop), in closed form."""
    w, g = attn["window"], attn["sinks"]

    def prefix(n):  # sum over pos in [0, n)
        if n <= 0:
            return 0
        if w is None:
            return n * (n + 1) // 2
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


def expected_experts(ffn: dict, tokens: int) -> float:
    """Expected number of distinct held experts of one layer that `tokens`
    independently routed tokens select (each picks k of router_width
    without replacement): held * (1 - (1 - k/router_width)**tokens)."""
    return ffn["held"] * (1.0 - (1.0 - ffn["k"] / ffn["router_width"]) ** tokens)


def _attn_params(part: dict, d: int) -> int:
    return d * (part["hq"] + 2 * part["hkv"]) * part["dh"] + part["hq"] * part["dh"] * d


def _ffn_params(part: dict, d: int, routed: bool):
    """(FFN parameters one token uses, router parameters)."""
    if part["kind"] == "dense":
        return 3 * d * part["width"], 0
    experts = part["k"] * part["held"] / part["router_width"] if routed else part["held"]
    return (experts * 3 * d * part["width"] + 3 * d * part["shared"],
            d * part["router_width"])


def layer_params(layer: dict, routed: bool = True) -> dict:
    """Matmul parameters one token uses in an attention layer."""
    ffn, router = _ffn_params(layer["ffn"], layer["d"], routed)
    return {"attn": _attn_params(layer["mixer"], layer["d"]), "ffn": ffn,
            "router": router}


def _part_flops(part: dict, d: int, start: int, stop: int, routed: bool):
    """FLOPs of one part of a layer for the tokens at positions [start, stop)."""
    kind = part["kind"]
    if kind == "attention":
        return (2 * _attn_params(part, d) * (stop - start)
                + 4 * part["hq"] * part["dh"] * sum_visible_keys(part, start, stop))
    if kind in ("dense", "experts"):
        return 2 * sum(_ffn_params(part, d, routed)) * (stop - start)
    return part["flops"](start, stop)


def _flops(layer: dict, start: int, stop: int, routed: bool):
    return sum(_part_flops(layer[p], layer["d"], start, stop, routed)
               for p in ("mixer", "ffn"))


def _head_flops(cfg: dict) -> int:
    return 2 * cfg["hidden_size"] * cfg["vocab_size"]


def token_flops(cfg: dict, pos: int, *, routed: bool = True,
                unembed: bool = True) -> float:
    """FLOPs to run one token at absolute position `pos` through the model."""
    out = sum(n * _flops(layer, pos, pos + 1, routed) for layer, n in _groups(cfg))
    if unembed:
        out += _head_flops(cfg)
    return float(out)


def prefill_flops(cfg: dict, length: int, *, routed: bool = True) -> float:
    """A prompt of `length` tokens: every token through every layer, and the
    vocabulary projection for the last one only (the only logits needed)."""
    return float(sum(n * _flops(layer, 0, length, routed)
                     for layer, n in _groups(cfg)) + _head_flops(cfg))


def decode_flops(cfg: dict, positions: Iterable[int], *,
                 routed: bool = True) -> float:
    """One output token for each slot at the given absolute positions."""
    groups = _groups(cfg)
    return float(sum(sum(n * _flops(layer, p, p + 1, routed) for layer, n in groups)
                     + _head_flops(cfg) for p in positions))


def _part_weight_bytes(part: dict, d: int, tokens: Optional[int]):
    kind = part["kind"]
    if kind == "attention":
        return _attn_params(part, d) * BF16
    if kind == "dense":
        return 3 * d * part["width"] * BF16
    if kind == "experts":
        e = part["held"] if tokens is None else expected_experts(part, tokens)
        return (e * 3 * d * part["width"] * BF16 + d * part["router_width"] * F32
                + 3 * d * part["shared"] * BF16)
    return part["weight_bytes"]


def weight_bytes(cfg: dict, tokens: Optional[int] = None) -> float:
    """Bytes of weights one decode step of `tokens` tokens must read. Matmul
    weights are bf16, norms and routers float32; each expert layer reads
    the held experts the tokens are expected to select (all of them when
    tokens is None)."""
    total = sum(n * (_part_weight_bytes(layer["mixer"], layer["d"], tokens)
                     + _part_weight_bytes(layer["ffn"], layer["d"], tokens)
                     + layer["norms"] * layer["d"] * F32)
                for layer, n in _groups(cfg))
    d = cfg["hidden_size"]
    return float(total + cfg["vocab_size"] * d * BF16 + d * F32)


def kv_row_bytes(attn: dict) -> float:
    """Key plus value bytes of one cached token in one attention layer."""
    return float(2 * attn["hkv"] * attn["dh"] * BF16)


def state_bytes(cfg: dict, positions: Iterable[int]) -> float:
    """State one decode step reads and writes: for each attention layer,
    each live slot's visible key/value rows."""
    positions = list(positions)
    total = 0
    for layer, n in _groups(cfg):
        for part in (layer["mixer"], layer["ffn"]):
            if part["kind"] == "attention":
                kv = sum(visible_keys(part, p) for p in positions)
                total += n * kv_row_bytes(part) * kv
            elif part["kind"] not in ("dense", "experts"):
                total += n * part["state_bytes"](positions)
    return float(total)


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
    return weight_bytes(cfg, len(positions)) + state_bytes(cfg, positions)
