"""Pure-jnp oracles for every kernel. O(N^2) — tests and small shapes only.

Three reference implementations matter to the paper:
  attention_ref       - the standard 3-step QK / softmax / SV computation
  fused_attention_ref - the paper's Eq. 1 rewrite (exp, SV, divide-at-end);
                        proving attention_ref == fused_attention_ref is the
                        paper's kernel-fusion correctness claim
  sliding_chunks_ref  - the HuggingFace Longformer baseline the paper beats
                        (dense 2w-wide chunks, ~50% redundant FLOPs)
"""
from __future__ import annotations

from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import patterns
from repro.core.types import AttentionSpec


def _soft_cap(s, cap: float):
    return cap * jnp.tanh(s / cap) if cap else s


def attention_ref(q, k, v, spec: AttentionSpec, *,
                  pattern: Optional[patterns.BlockPattern] = None,
                  scale: Optional[float] = None):
    """Masked softmax attention, standard 3-step form, fp32 math.

    q: (B, Hq, Lq, D), k/v: (B, Hkv, Lk, D). GQA by head repetition.
    The mask comes from the *pattern* when given (includes random blocks),
    else from the dense spec mask.
    """
    b, hq, lq, d = q.shape
    hkv = k.shape[1]
    scale = scale if scale is not None else d ** -0.5
    if hq != hkv:
        rep = hq // hkv
        k = jnp.repeat(k, rep, axis=1)
        v = jnp.repeat(v, rep, axis=1)
    if pattern is not None:
        mask = patterns.random_blocks_mask(pattern)
    else:
        mask = patterns.dense_mask(spec, lq, k.shape[2])
    mask = jnp.asarray(mask)[None, None]
    s = jnp.einsum("bhqd,bhkd->bhqk", q.astype(jnp.float32),
                   k.astype(jnp.float32)) * scale
    s = _soft_cap(s, spec.softcap)
    s = jnp.where(mask, s, -jnp.inf)
    p = jax.nn.softmax(s, axis=-1)
    p = jnp.where(mask, p, 0.0)  # rows with no valid kv produce 0, not NaN
    return jnp.einsum("bhqk,bhkd->bhqd", p, v.astype(jnp.float32)
                      ).astype(q.dtype)


def fused_attention_ref(q, k, v, spec: AttentionSpec, *,
                        pattern: Optional[patterns.BlockPattern] = None,
                        scale: Optional[float] = None,
                        stabilize: bool = True):
    """Paper Eq. 1: Z_i = (1/sum_l exp(S_il)) * sum_n exp(S_in) V_n.

    With stabilize=False this is the paper's literal formulation (no max
    subtraction — overflows for large |S|, as on their FPGA it did not at
    fp16 scale). stabilize=True subtracts the row max first (our deviation,
    mathematically identical)."""
    b, hq, lq, d = q.shape
    hkv = k.shape[1]
    scale = scale if scale is not None else d ** -0.5
    if hq != hkv:
        rep = hq // hkv
        k = jnp.repeat(k, rep, axis=1)
        v = jnp.repeat(v, rep, axis=1)
    if pattern is not None:
        mask = patterns.random_blocks_mask(pattern)
    else:
        mask = patterns.dense_mask(spec, lq, k.shape[2])
    mask = jnp.asarray(mask)[None, None]
    s = jnp.einsum("bhqd,bhkd->bhqk", q.astype(jnp.float32),
                   k.astype(jnp.float32)) * scale
    s = _soft_cap(s, spec.softcap)
    s = jnp.where(mask, s, -jnp.inf)
    if stabilize:
        m = jnp.max(s, axis=-1, keepdims=True)
        m = jnp.where(jnp.isfinite(m), m, 0.0)
        e = jnp.where(mask, jnp.exp(s - m), 0.0)
    else:
        e = jnp.where(mask, jnp.exp(s), 0.0)
    num = jnp.einsum("bhqk,bhkd->bhqd", e, v.astype(jnp.float32))
    den = jnp.sum(e, axis=-1, keepdims=True)
    return (num / jnp.maximum(den, 1e-30)).astype(q.dtype)


def sliding_chunks_ref(q, k, v, spec: AttentionSpec, *,
                       scale: Optional[float] = None):
    """HF Longformer 'sliding chunks': split the sequence into chunks of 2w,
    compute *dense* attention of every chunk against [itself, next chunk]
    (bidirectional also previous), mask to the band afterwards. The overlap
    regions are the paper's ~50% wasted FLOPs. Exact same output as
    attention_ref with the band mask; only the compute schedule differs.

    Requires seq divisible by 2w. num_global/num_random unsupported (as in
    HF's kernel — globals are a separate dense pass there too).
    """
    assert spec.kind in ("swat", "sliding_chunks")
    w = spec.window
    c = 2 * w
    b, h, l, d = q.shape
    assert l % c == 0, f"sliding_chunks needs seq % {c} == 0, got {l}"
    hkv = k.shape[1]
    if h != hkv:
        k = jnp.repeat(k, h // hkv, axis=1)
        v = jnp.repeat(v, h // hkv, axis=1)
    scale = scale if scale is not None else d ** -0.5
    n = l // c
    qc = q.reshape(b, h, n, c, d).astype(jnp.float32)
    kc = k.reshape(b, h, n, c, d).astype(jnp.float32)
    vc = v.reshape(b, h, n, c, d).astype(jnp.float32)

    def neigh(x, shift):  # chunk at offset `shift`, zero-padded at the ends
        pad = jnp.zeros_like(x[:, :, :1])
        if shift == -1:
            return jnp.concatenate([pad, x[:, :, :-1]], axis=2)
        if shift == 1:
            return jnp.concatenate([x[:, :, 1:], pad], axis=2)
        return x

    shifts = (-1, 0) if spec.causal else (-1, 0, 1)
    ks = jnp.concatenate([neigh(kc, s) for s in shifts], axis=3)
    vs = jnp.concatenate([neigh(vc, s) for s in shifts], axis=3)
    s_ = jnp.einsum("bhncd,bhnkd->bhnck", qc, ks) * scale
    s_ = _soft_cap(s_, spec.softcap)

    # band mask in chunk coordinates
    q_idx = np.arange(c)[:, None]
    k_off = np.concatenate([np.arange(c) + s * c for s in shifts])[None, :]
    band = (k_off >= q_idx - w) & ((k_off <= q_idx) if spec.causal
                                   else (k_off <= q_idx + w))
    valid = np.ones((len(shifts) * c,), bool)[None, :]
    mask = jnp.asarray(band & valid)[None, None, None]
    # first/last chunk: padded neighbours are invalid
    chunk_ids = jnp.arange(n)[:, None, None]
    pad_lo = (jnp.asarray(k_off) < 0)[None] & (chunk_ids == 0)
    pad_hi = (jnp.asarray(k_off) >= c)[None] & (chunk_ids == n - 1)
    mask = mask & ~pad_lo[None, None] & ~pad_hi[None, None]

    s_ = jnp.where(mask, s_, -jnp.inf)
    p = jax.nn.softmax(s_, axis=-1)
    p = jnp.where(mask, p, 0.0)
    out = jnp.einsum("bhnck,bhnkd->bhncd", p, vs)
    return out.reshape(b, h, l, d).astype(q.dtype)


def ring_slot_positions(total, wcap: int, *, ring_cap: int, num_global: int):
    """Which absolute token index each cache slot holds, given per-slot
    `total` (B,) tokens inserted so far. Pinned slot s (< num_global) holds
    token s; ring slot r holds the newest token congruent to r below
    `total`. Returns (positions (B, W) int32, valid (B, W) bool); slots in
    the tile-rounding tail [ring_cap, W) are never valid."""
    g, ring = num_global, ring_cap - num_global
    s_idx = jnp.arange(wcap, dtype=jnp.int32)[None, :]
    last = jnp.asarray(total, jnp.int32).reshape(-1, 1) - 1
    t_ring = last - jnp.mod((last - g) - (s_idx - g), ring)
    t_s = jnp.where(s_idx < g, s_idx, t_ring)
    valid = jnp.where(s_idx < g, s_idx <= last, t_ring >= g)
    return t_s, valid & (s_idx < ring_cap)


def take_layer(stack, layer):
    """Layer `layer` (traced int ok) of a layer scan's stacked leaf."""
    return jax.lax.dynamic_index_in_dim(stack, layer, 0, keepdims=False)


def put_layer(stack, layer, x):
    """`stack` with layer `layer` replaced by `x`."""
    return jax.lax.dynamic_update_index_in_dim(
        stack, x.astype(stack.dtype), layer, 0)


def ring_insert_ref(cache, new, pos, num_new, *, ring_cap: int,
                    num_global: int, layer=None, row0=0):
    """Insert `new` (B, H, T, D) rows at their ring slots of `cache`
    (B, H, W, D), or of layer `layer` (traced int ok) of a layer scan's
    stack (N, B, H, W, D): token pos+j -> slot g + (pos+j-g) mod ring
    (pinned below g); rows j >= num_new[b] are dropped, and the
    tile-rounding tail [ring_cap, W) is never addressed. row0: the ring
    row that `cache`'s row 0 holds, where `cache` is one shard of a ring
    split along W; rows that fall outside the shard are dropped.

    One scatter of the step's rows, which reads and writes no other row:
    on a buffer XLA may update in place (a scan carry, a donated argument)
    a step costs its rows alone. The ring is bitwise the one iota==slot
    selects give (layers._dyn_update at T=1; ascending j, last writer
    wins), which read and write every row. A step's T slots are distinct
    (the ring holds at least T rows), so write order does not matter.
    Each update is one D-row, so the scatter's operand keeps its layout: a
    slot batching dimension, or (H, D) windows, would have XLA move the
    scatter dimensions to the front of the operand, a transpose of the
    whole ring."""
    lead = cache.ndim - 4
    b, h, t, _ = new.shape
    g, ring = num_global, ring_cap - num_global
    pos = jnp.asarray(pos, jnp.int32).reshape(b)
    num_new = jnp.asarray(num_new, jnp.int32).reshape(b)
    pj = pos[:, None] + jnp.arange(t, dtype=jnp.int32)[None, :]   # (B, T)
    slot = jnp.where(pj < g, pj, g + jnp.mod(pj - g, ring))
    slot = jnp.where(jnp.arange(t)[None, :] < num_new[:, None], slot, -1)
    shape = (b, h, t)                   # one index per row: [layer,] B, H, W
    cols = [jax.lax.broadcasted_iota(jnp.int32, shape, 0),
            jax.lax.broadcasted_iota(jnp.int32, shape, 1),
            jnp.broadcast_to(slot[:, None, :] - row0, shape)]
    if lead:
        cols.insert(0, jnp.broadcast_to(jnp.asarray(layer, jnp.int32), shape))
    dims = tuple(range(lead + 3))
    dnums = jax.lax.ScatterDimensionNumbers(
        update_window_dims=(3,), inserted_window_dims=dims,
        scatter_dims_to_operand_dims=dims)
    return jax.lax.scatter(cache, jnp.stack(cols, -1),
                           new.astype(cache.dtype), dnums,
                           mode=jax.lax.GatherScatterMode.FILL_OR_DROP)


def decode_ref(q, k_cache, v_cache, cache_len, spec: AttentionSpec, *,
               scale: Optional[float] = None, total=None, q0=None,
               ring_cap: Optional[int] = None):
    """Decode T query tokens against a (ring) cache. q: (B, Hq, T, D),
    caches: (B, Hkv, W, D). Ring order is irrelevant (softmax is permutation
    invariant). Two masking modes:

    * prefix (total=None, the legacy T=1 call): only the first
      min(cache_len, W) entries are valid; no window/causal terms.
    * positional (total/q0 given, (B,)): every slot's absolute token index
      is reconstructed from the ring layout (`ring_slot_positions` —
      rotation modulus ring_cap, pinned prefix spec.num_global) and query
      token q0+t sees a slot iff its token is causally past and within
      spec.window (globals always). This is the oracle for the fused
      multi-token pallas kernel, including caches wider than the window.

    Numerics note: scores come from a mixed-precision dot_general with fp32
    accumulation — never from an fp32 *copy* of the cache. Materializing
    `k_cache.astype(f32)` doubles decode HBM traffic and shows up as a
    convert-op FLOP avalanche in the roofline (EXPERIMENTS.md §Perf it.1)."""
    b, hq, t, d = q.shape
    hkv, wcap = k_cache.shape[1], k_cache.shape[2]
    group = hq // hkv
    scale = scale if scale is not None else d ** -0.5
    qg = q.reshape(b, hkv, group * t, d)
    # (B, Hkv, G*T, W) <- (B, Hkv, G*T, D) x (B, Hkv, W, D), fp32 accumulate
    from repro.kernels import dots
    s = dots.dot_general_f32(
        qg, k_cache, (((3,), (3,)), ((0, 1), (0, 1)))) * scale
    s = _soft_cap(s, spec.softcap)
    if total is None:
        assert t == 1, "multi-token decode_ref needs positional masks"
        valid = (jnp.arange(wcap)[None, None, None, :]
                 < jnp.minimum(cache_len.reshape(b, 1, 1, 1), wcap))
    else:
        cap = wcap if ring_cap is None else ring_cap
        g = spec.num_global if spec.is_sparse else 0
        t_s, ok = ring_slot_positions(total, wcap, ring_cap=cap, num_global=g)
        trow = jnp.arange(group * t, dtype=jnp.int32) % t
        qp = (jnp.asarray(q0, jnp.int32).reshape(b, 1)
              + trow[None, :])                         # (B, G*T)
        vis = ok[:, None, :]                           # (B, G*T, W)
        if spec.causal:
            vis = vis & (t_s[:, None, :] <= qp[:, :, None])
        if spec.is_sparse and spec.window:
            keep = t_s[:, None, :] >= qp[:, :, None] - spec.window
            if g > 0:
                keep = keep | (jnp.arange(wcap) < g)[None, None, :]
            vis = vis & keep
        valid = vis[:, None]                           # (B, 1, G*T, W)
    s = jnp.where(valid, s, -jnp.inf)
    p = jax.nn.softmax(s, axis=-1)
    p = jnp.where(valid, p, 0.0)
    out = dots.dot_general_f32(
        p.astype(v_cache.dtype), v_cache,
        (((3,), (2,)), ((0, 1), (0, 1))))          # (B, Hkv, G*T, D)
    return out.reshape(b, hq, t, d).astype(q.dtype)
