"""SWAT flash-decode kernel: T new tokens vs a ring-buffer KV cache, with
the ring insert fused into the attention pass.

The paper's FIFO K/V buffer with a moving replacement pointer (Fig. 4b) *is*
a ring KV cache: decode with window attention keeps exactly W = 2w (or w for
causal lookback) K/V rows per layer and evicts slot (step mod W). The paper's
input-stationary dataflow — the band stays resident while compute streams
over it — is exactly what the fused insert reproduces on TPU: the kernel
already holds each cache block in VMEM for the attention pass, so it writes
the step's new K/V rows into that same block (input/output aliasing) instead
of paying a separate full-cache scatter dispatch per layer per token. One
kernel = replacement-pointer write + exact-band attention, the same fusion
argument SWAT makes against unfused FPGA baselines.

Grid: (B, Hkv, W/BK). The query tile packs the `group = Hq/Hkv` heads that
share a KV head times the T new tokens into one (group*T, D) block, so GQA
configs drive the MXU with a real tile instead of a (1, D) row (~1/128 MXU
utilization at group=1). T > 1 is the multi-token primitive speculative
decode verifies drafts with.

Masks are computed from PER-SLOT ring positions (`pos`, scalar-prefetched):
each cache slot's absolute token index is reconstructed from the ring
arithmetic, so one call serves a continuous batch of slots at arbitrary
depths, cold/partially-filled/multiply-wrapped alike, AND the window is
enforced by token distance — a cache allocated wider than window+1 rows
(lookahead rings, dense-capped allocations) masks out in-ring-but-stale
tokens instead of attending the whole valid prefix. Ring rotation never
needs un-rotating (softmax is permutation invariant); only the masks depend
on depth.
"""
from __future__ import annotations

import functools
import logging
import math
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.swat_attention import LANES, NEG_INF

logger = logging.getLogger(__name__)
_PAD_WARNED: set = set()
_FORCE_FAIL = False


def set_force_fail(enabled: bool) -> None:
    """Arm/disarm the simulated dispatch failure: while armed, every
    `swat_decode` call raises `KernelDispatchError` at entry — the serving
    engine's graceful-degradation ladder catches it and falls back to the
    ref decode impl. Trace-time, so an armed scan compile fails before any
    donated buffer is consumed (retrying with the ref impl is safe).
    Module-global: the fault harness (`serving.faults`) manages it."""
    global _FORCE_FAIL
    _FORCE_FAIL = enabled
_PAD_EVENTS: list = []


def consume_pad_events() -> list:
    """Drain the structured pad-fallback record (one dict per offending W
    seen since the last drain). The static analyzer (repro.analysis) calls
    this after tracing an entry point so an odd window size surfaces as a
    warn-level finding in ANALYSIS.json instead of only a log line."""
    out, _PAD_EVENTS[:] = list(_PAD_EVENTS), []
    return out


def _warn_pad(w: int, requested: int, chosen: int) -> None:
    """One-time (per W) warning for the pad-and-copy fallback: padding the
    cache to a block multiple COPIES the whole cache every decode call —
    engine ring allocations are pre-rounded to avoid it, so hitting this
    means an ad-hoc capacity leaked into a hot path. Names both the
    requested block and the block the kernel actually RUNS with, so the log
    line alone says what the padded grid looks like. Every distinct W is
    also recorded as a structured event for the analyzer (the log dedups
    per process; the event buffer dedups per drain)."""
    if not any(e.get("kind") == "pad" and e["w"] == w for e in _PAD_EVENTS):
        _PAD_EVENTS.append({"kind": "pad", "w": w, "block_kv": requested,
                            "chosen_block": chosen,
                            "padded_w": -(-w // chosen) * chosen,
                            "min_block": _MIN_BLOCK_KV})
    if w in _PAD_WARNED:
        return
    _PAD_WARNED.add(w)
    logger.warning(
        "swat_decode: cache capacity W=%d is not tileable by block_kv=%d "
        "(no divisor >= %d): running with block_kv=%d over a jnp.pad-ed "
        "%d-row cache, which copies the ENTIRE cache on every call. Round "
        "the allocation (layers.cache_allocation) if this is a hot path.",
        w, requested, _MIN_BLOCK_KV, chosen, -(-w // chosen) * chosen)


def record_paged_fallback(nb: int, page: int, reason: str) -> None:
    """Structured event for paged-KV decode taking the materialized
    gather-view path instead of an in-kernel block gather (the table is
    resolved OUTSIDE the kernel, costing a pool-sized copy per step).
    Shares the `_PAD_EVENTS` channel so the analyzer surfaces it next to
    the pad-and-copy fallback — both are 'the hot path is copying the
    cache' findings. Deduped per (nb, page) per drain."""
    if any(e.get("kind") == "paged_gather" and e.get("nb") == nb
           and e.get("page") == page for e in _PAD_EVENTS):
        return
    _PAD_EVENTS.append({"kind": "paged_gather", "nb": nb, "page": page,
                        "reason": reason})


def _pmod(x, m: int):
    """Floored (always non-negative) remainder by a static positive int."""
    r = jax.lax.rem(x, m)
    return r + jnp.where(r < 0, m, 0)


def _decode_kernel(pos_ref, nn_ref, q_ref, k_ref, v_ref, *rest,
                   block_kv: int, num_blocks: int, rows: int, t_span: int,
                   g: int, ring: int, cap: int, window: int, causal: bool,
                   fuse: bool, scale: float, softcap: float):
    if fuse:
        nk_ref, nv_ref, o_ref, ko_ref, vo_ref, m_ref, l_ref, acc_ref = rest
    else:
        o_ref, m_ref, l_ref, acc_ref = rest
    b = pl.program_id(0)
    s = pl.program_id(2)

    @pl.when(s == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    p = pos_ref[b]
    base = s * block_kv
    k = k_ref[0, 0]                                      # (BK, D) cache dtype
    v = v_ref[0, 0]
    if fuse:
        nn = nn_ref[b]
        total = p + nn
        q0 = p
        # input-stationary ring insert: the new rows land in the block the
        # attention pass already holds in VMEM; the blended block is both
        # attended and written back through the aliased output.
        for j in range(t_span):
            pj = p + j
            slot = jnp.where(pj < g, pj, g + _pmod(pj - g, ring))
            ok = (j < nn) & (slot >= base) & (slot < base + block_kv)
            hit = (jax.lax.broadcasted_iota(jnp.int32, (block_kv, 1), 0)
                   == slot - base) & ok
            k = jnp.where(hit, nk_ref[0, 0, j][None, :], k)
            v = jnp.where(hit, nv_ref[0, 0, j][None, :], v)
        ko_ref[0, 0] = k
        vo_ref[0, 0] = v
    else:
        total = p
        q0 = p - t_span          # pre-inserted queries: last query == newest

    q = q_ref[0, 0].astype(jnp.float32) * scale          # (rows, D)
    st = jax.lax.dot_general(q, k.astype(jnp.float32),
                             (((1,), (1,)), ((), ())),
                             preferred_element_type=jnp.float32)  # (rows, BK)
    if softcap:
        st = softcap * jnp.tanh(st / softcap)

    # reconstruct each slot's absolute token index from the ring layout:
    # pinned slot s holds token s; ring slot r holds the newest token
    # congruent to r below `total`. Everything else is masking by position.
    s_idx = base + jax.lax.broadcasted_iota(jnp.int32, (rows, block_kv), 1)
    last = total - 1
    t_ring = last - _pmod((last - g) - (s_idx - g), ring)
    if g > 0:
        pinned = s_idx < g
        t_s = jnp.where(pinned, s_idx, t_ring)
        # masks combine with &/|, never a select over booleans: Mosaic
        # cannot lower a select whose values are i1 vectors
        valid = (pinned & (s_idx < total)) | (~pinned & (t_ring >= g))
    else:
        t_s = t_ring
        valid = t_ring >= 0
    valid &= s_idx < cap
    trow = jax.lax.broadcasted_iota(jnp.int32, (rows, block_kv), 0) % t_span
    qp = q0 + trow                                       # query token index
    vis = valid
    if causal:
        vis &= t_s <= qp
    if window:
        keep = t_s >= qp - window
        if g > 0:
            keep |= s_idx < g
        vis &= keep
    st = jnp.where(vis, st, NEG_INF)

    m_prev = m_ref[:, :1]
    m_new = jnp.maximum(m_prev, jnp.max(st, axis=-1, keepdims=True))
    alpha = jnp.exp(m_prev - m_new)
    pr = jnp.exp(st - m_new)
    pr = jnp.where(vis, pr, 0.0)
    l_ref[...] = jnp.broadcast_to(l_ref[:, :1] * alpha
                                  + jnp.sum(pr, -1, keepdims=True),
                                  l_ref.shape)
    pv = jax.lax.dot_general(pr, v.astype(jnp.float32),
                             (((1,), (0,)), ((), ())),
                             preferred_element_type=jnp.float32)  # (rows, D)
    acc_ref[...] = acc_ref[...] * alpha + pv
    m_ref[...] = jnp.broadcast_to(m_new, m_ref.shape)

    @pl.when(s == num_blocks - 1)
    def _finalize():
        o_ref[0, 0] = (acc_ref[...]
                       / jnp.maximum(l_ref[:, :1], 1e-30)).astype(o_ref.dtype)


_MIN_BLOCK_KV = 16  # bf16 sublane tile: smallest usable (BK, D) block


def decode_block_kv(w: int, block_kv: int = 128) -> Tuple[int, bool]:
    """Resolve the kv block for a cache of W rows: (block, needs_pad).

    Prefer the largest divisor of W that is <= block_kv and sublane-aligned —
    then the grid tiles the cache EXACTLY and the hot path never copies.
    `init_kv_cache` ring allocations are pre-rounded (layers.cache_allocation
    — logical window semantics untouched, only zero tail rows) so engine ring
    caches always hit the no-pad path; ad-hoc W (odd test shapes, dense caps
    at unaligned max_len) fall back to the old pad-and-copy (and log a
    one-time warning naming the offending W)."""
    if w % block_kv == 0:
        return block_kv, False
    if w <= block_kv and w % _MIN_BLOCK_KV == 0:
        return w, False
    g = math.gcd(w, block_kv)
    if g >= _MIN_BLOCK_KV:
        return g, False
    return block_kv, True


def swat_decode(q, k_cache, v_cache, pos, *,
                new_k=None, new_v=None, num_new=None,
                ring_cap: Optional[int] = None, num_global: int = 0,
                window: int = 0, causal: bool = True, pack_gqa: bool = True,
                block_kv: int = 128, scale: Optional[float] = None,
                softcap: float = 0.0, interpret: bool = False):
    """q: (B, Hq, T, D); caches: (B, Hkv, W, D); pos: int32 (B,).

    Two modes share one kernel:

    * plain (new_k=None): the cache already holds everything. `pos` is the
      number of tokens in the cache — the T query tokens are its newest.
      Legacy single-token calls passing the valid prefix length are
      backward compatible at window=0 (dense prefix semantics); with
      window > 0, `pos` must be the ABSOLUTE token count — a clamped
      prefix length loses the ring phase after a wrap and would mask the
      wrong slots (ops.decode_attention enforces this).
      Returns out (B, Hq, T, D).
    * fused (new_k/new_v given, (B, Hkv, T, D)): `pos` counts the tokens in
      the cache BEFORE this call; the kernel writes the new rows into their
      ring slots (token pos+j -> slot g + (pos+j-g) mod ring, pinned slots
      below num_global) in the VMEM-resident block and attends the blended
      result — no separate scatter pass, no second full-cache HBM round
      trip. The updated caches come back through input/output aliasing:
      returns (out, k_cache, v_cache). num_new: optional (B,) count of REAL
      new tokens per slot (ragged speculative accepts); rows j >= num_new
      are neither written nor attendable and their outputs are garbage the
      caller discards.

    Masking is positional (see module docstring): ring_cap is the LOGICAL
    rotation modulus (defaults to W), num_global the pinned prefix, window
    the causal lookback (0 = no band — dense prefix semantics). The kv
    block adapts to W (`decode_block_kv`) so ring capacities that aren't a
    multiple of the default block never jnp.pad — the pad is a full cache
    COPY per token per layer, dwarfing the attention itself."""
    if _FORCE_FAIL:
        from repro.serving.faults import KernelDispatchError
        raise KernelDispatchError(
            "injected pallas dispatch failure (set_force_fail armed)")
    b, hq, t, d = q.shape
    _, hkv, w, _ = k_cache.shape
    group = hq // hkv
    fuse = new_k is not None
    cap = w if ring_cap is None else int(ring_cap)
    g = int(num_global)
    ring = cap - g
    assert ring > 0, (cap, g)
    assert not fuse or new_v is not None
    assert not fuse or pack_gqa, "fused insert requires the packed layout"
    assert not fuse or t <= ring, (
        f"{t} new tokens would overwrite each other in a {ring}-row ring: "
        "allocate the cache with lookahead >= T-1")
    scale = float(d ** -0.5 if scale is None else scale)
    requested_block = block_kv
    block_kv, needs_pad = decode_block_kv(w, block_kv)
    from repro.telemetry import kernelprof as KP
    if KP.census_enabled():
        # trace-time only (see ops._census_dispatch): records the block
        # geometry the kernel actually RUNS with, pad fallback included
        KP.record_dispatch(op="swat_decode", b=b, h_q=hq, h_kv=hkv, t=t,
                           d=d, w=w, cap=cap, num_global=g,
                           window=int(window), fused=fuse,
                           block_kv=block_kv, needs_pad=needs_pad)
    if needs_pad:
        _warn_pad(w, requested_block, block_kv)
        w_pad = -(-w // block_kv) * block_kv
        padw = ((0, 0), (0, 0), (0, w_pad - w), (0, 0))
        k_cache, v_cache = jnp.pad(k_cache, padw), jnp.pad(v_cache, padw)
    else:
        w_pad = w
    nb = w_pad // block_kv
    pos = jnp.broadcast_to(jnp.asarray(pos, jnp.int32).reshape(-1), (b,))
    if num_new is None:
        nn = jnp.full((b,), t, jnp.int32)
    else:
        nn = jnp.broadcast_to(jnp.asarray(num_new, jnp.int32).reshape(-1),
                              (b,))

    if pack_gqa:
        rows, grid_h = group * t, hkv
        qk = q.reshape(b, hkv, rows, d)
        kv_head = lambda h: h
    else:
        rows, grid_h = t, hq
        qk = q
        kv_head = lambda h: h // group

    kern = functools.partial(
        _decode_kernel, block_kv=block_kv, num_blocks=nb, rows=rows,
        t_span=t, g=g, ring=ring, cap=cap, window=int(window),
        causal=bool(causal), fuse=fuse, scale=scale, softcap=softcap)
    in_specs = [
        pl.BlockSpec((1, 1, rows, d), lambda bb, h, s, *_: (bb, h, 0, 0)),
        pl.BlockSpec((1, 1, block_kv, d),
                     lambda bb, h, s, *_: (bb, kv_head(h), s, 0)),
        pl.BlockSpec((1, 1, block_kv, d),
                     lambda bb, h, s, *_: (bb, kv_head(h), s, 0)),
    ]
    o_spec = pl.BlockSpec((1, 1, rows, d), lambda bb, h, s, *_: (bb, h, 0, 0))
    o_shape = jax.ShapeDtypeStruct((b, grid_h, rows, d), q.dtype)
    scratch = [pltpu.VMEM((rows, LANES), jnp.float32),
               pltpu.VMEM((rows, LANES), jnp.float32),
               pltpu.VMEM((rows, d), jnp.float32)]
    if fuse:
        new_k = new_k.astype(k_cache.dtype)
        new_v = new_v.astype(v_cache.dtype)
        kv_spec = pl.BlockSpec((1, 1, block_kv, d),
                               lambda bb, h, s, *_: (bb, h, s, 0))
        in_specs += [
            pl.BlockSpec((1, 1, t, d), lambda bb, h, s, *_: (bb, h, 0, 0)),
            pl.BlockSpec((1, 1, t, d), lambda bb, h, s, *_: (bb, h, 0, 0)),
        ]
        out = pl.pallas_call(
            kern,
            grid_spec=pltpu.PrefetchScalarGridSpec(
                num_scalar_prefetch=2,
                grid=(b, grid_h, nb),
                in_specs=in_specs,
                out_specs=[o_spec, kv_spec, kv_spec],
                scratch_shapes=scratch,
            ),
            out_shape=[o_shape,
                       jax.ShapeDtypeStruct(k_cache.shape, k_cache.dtype),
                       jax.ShapeDtypeStruct(v_cache.shape, v_cache.dtype)],
            # operands: (pos, nn, q, k_cache, v_cache, new_k, new_v) —
            # the caches update in place (input-stationary, Fig. 4b)
            input_output_aliases={3: 1, 4: 2},
            interpret=interpret, name="swat_decode_fused",
        )(pos, nn, qk, k_cache, v_cache, new_k, new_v)
        o, k_out, v_out = out
        o = o.reshape(b, hq, t, d)
        if needs_pad:
            k_out, v_out = k_out[:, :, :w], v_out[:, :, :w]
        return o, k_out, v_out

    out = pl.pallas_call(
        kern,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2,
            grid=(b, grid_h, nb),
            in_specs=in_specs,
            out_specs=[o_spec],
            scratch_shapes=scratch,
        ),
        out_shape=[o_shape],
        interpret=interpret, name="swat_decode",
    )(pos, nn, qk, k_cache, v_cache)[0]
    return out.reshape(b, hq, t, d)
