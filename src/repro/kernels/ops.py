"""Public attention ops: jit-ready, differentiable, implementation-switched.

Implementations (impl=):
  pallas  - the SWAT Pallas kernels (custom_vjp; interpret mode on CPU).
            The TPU hot path.
  xla     - block-banded scan implementation. Same exact-band FLOPs as the
            Pallas kernel, pure jax.lax, natively differentiable and SPMD-
            partitionable: this is what the multi-pod dry-run lowers, so
            cost_analysis reflects banded compute without interpret-mode
            loop artifacts.
  sliding_chunks - the HuggingFace Longformer baseline (paper's comparison
            target, ~50% redundant FLOPs).
  ref     - O(N^2) masked reference (tests, tiny shapes).

Global tokens (Longformer) are composed here: the band+global-column kernel
covers every non-global row; a second dense pass over the first g rows
replaces their output — the TPU analogue of SWAT's dedicated global
attention cores. Autodiff flows through both passes.
"""
from __future__ import annotations

import dataclasses
import functools
from typing import Optional

import jax
import jax.numpy as jnp

from repro.core import patterns
from repro.core.types import AttentionSpec
from repro.distributed.sharding import decode_kernel_spec, ring_spec
from repro.kernels import dots
from repro.kernels import ref as ref_impl
from repro.kernels import swat_attention as fwd_mod
from repro.kernels import swat_backward as bwd_mod

NEG_INF = fwd_mod.NEG_INF


@functools.lru_cache(maxsize=512)
def get_pattern(spec: AttentionSpec, seq_q: int, seq_kv: int,
                block_q: int, block_kv: int) -> patterns.BlockPattern:
    return patterns.build_block_pattern(spec, seq_q, seq_kv, block_q, block_kv)


def default_interpret() -> bool:
    return jax.default_backend() != "tpu"


def _census_dispatch(q, k_cache, *, impl: str, fused: bool, cap: int,
                     g: int, window: int) -> None:
    """Trace-time dispatch census (telemetry.kernelprof, opt-in). jit
    traces each shape once, so recording here yields a complete
    shape -> dispatch map of what the engine compiled at ZERO runtime
    cost — the compiled program is byte-identical, census on or off."""
    from repro.telemetry import kernelprof as KP
    if not KP.census_enabled():
        return
    b, hq, t, d = q.shape
    KP.record_dispatch(op="decode_attention", impl=impl, fused=fused,
                       b=b, h_q=hq, h_kv=k_cache.shape[-3], t=t, d=d,
                       cap=cap, num_global=g, window=window)


# --------------------------------------------------------------------------
# Pallas primitive with custom VJP (one block pattern)
# --------------------------------------------------------------------------

@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5, 6))
def _pallas_attention(q, k, v, spec, pattern, scale, interpret):
    out, _ = _pallas_fwd(q, k, v, spec, pattern, scale, interpret)
    return out


def _pallas_fwd(q, k, v, spec, pattern, scale, interpret):
    out, lse = fwd_mod.swat_attention_fwd(
        q, k, v, spec, pattern=pattern, scale=scale, interpret=interpret,
        return_lse=True)
    return out, (q, k, v, out, lse)


def _pallas_bwd(spec, pattern, scale, interpret, res, do):
    q, k, v, out, lse = res
    dq, dk, dv = bwd_mod.swat_attention_bwd(
        q, k, v, out, lse, do, spec, pattern=pattern, scale=scale,
        interpret=interpret)
    return dq, dk, dv


_pallas_attention.defvjp(_pallas_fwd, _pallas_bwd)


# --------------------------------------------------------------------------
# XLA block-banded implementation (scan over q blocks)
# --------------------------------------------------------------------------

def _xla_dense(q, k, v, spec, scale):
    """Plain masked attention — the honest O(N^2) dense cost (the paper's
    GPU baseline). Used for dense specs so HLO FLOPs/bytes reflect true
    dense attention (flash-streaming is the Pallas kernel's job on TPU)."""
    b, hq, lq, d = q.shape
    _, hkv, lkv, _ = k.shape
    group = hq // hkv
    qb = q.reshape(b, hkv, group, lq, d) * jnp.asarray(scale, q.dtype)
    # mixed-precision dots with fp32 accumulation: no fp32 COPIES of K/V
    # (those double HBM traffic and dominate the convert-op flop count)
    s = dots.einsum_f32("bhgld,bhkd->bhglk", qb, k)
    if spec.softcap:
        s = spec.softcap * jnp.tanh(s / spec.softcap)
    if spec.causal:
        mask = (jnp.arange(lkv)[None, :] <= jnp.arange(lq)[:, None])
        s = jnp.where(mask[None, None, None], s, NEG_INF)
    m = jax.lax.stop_gradient(jnp.max(s, axis=-1, keepdims=True))
    p = jnp.exp(s - m)
    den = jnp.maximum(jnp.sum(p, -1, keepdims=True), 1e-30)
    o = dots.einsum_f32("bhglk,bhkd->bhgld", (p / den).astype(v.dtype), v)
    return o.reshape(b, hq, lq, d).astype(q.dtype)


def _xla_banded(q, k, v, spec, pattern, scale, *, q_shift: int = 0,
                kv_lo=None, kv_hi=None, return_partials: bool = False):
    """Vectorized exact-band attention: every q block gathers only its slot
    kv blocks — O(N * band) compute AND memory, no loop (so HLO cost
    analysis counts every FLOP; lax.scan bodies are counted once).

    Context-parallel hooks (all default to the plain single-buffer case):
      q_shift          - constant local-coordinate shift: q row i aligns with
                         kv row i + q_shift (the kv buffer carries a halo
                         prefix of q_shift rows). The pattern must be built
                         with the same q_shift. Static int.
      kv_lo / kv_hi    - valid kv half-open range in LOCAL coordinates. May
                         be traced scalars (per-shard edge masking inside
                         shard_map). Defaults: [0, pattern.seq_kv).
      return_partials  - return the flash state (acc, l, m) with
                         acc (B,H,L,D) fp32 unnormalized, l/m (B,H,L) fp32,
                         for cross-pass / cross-device logsumexp merging.
    """
    if not spec.is_sparse:
        assert q_shift == 0 and not return_partials
        return _xla_dense(q, k, v, spec, scale)
    if (q_shift == 0 and not return_partials and spec.num_random == 0
            and spec.window >= k.shape[2]
            and (spec.causal or spec.window >= q.shape[2])):
        # degenerate window (w >= seq): the band covers everything, but the
        # banded gather would still duplicate ~the whole KV once per q block
        # (nq x KV bytes — the gemma2 train_4k memory blow-up, §Perf cell 3
        # it.3). Fall through to the plain dense path instead.
        return _xla_dense(q, k, v, spec, scale)
    b, hq, lq, d = q.shape
    _, hkv, lkv, _ = k.shape
    group = hq // hkv
    bq, bk = pattern.block_q, pattern.block_kv
    nq, ns = pattern.num_q_blocks, pattern.num_slots
    lq_pad = nq * bq
    if lq_pad != lq:
        q = jnp.pad(q, ((0, 0), (0, 0), (0, lq_pad - lq), (0, 0)))
    lkv_pad = pattern.num_kv_blocks * bk
    if lkv_pad != lkv:
        pad = ((0, 0), (0, 0), (0, lkv_pad - lkv), (0, 0))
        k, v = jnp.pad(k, pad), jnp.pad(v, pad)
    if kv_lo is None:
        kv_lo = 0
    if kv_hi is None:
        kv_hi = pattern.seq_kv

    qb = q.reshape(b, hkv, group, nq, bq, d)
    kv_map = jnp.asarray(pattern.kv_block_map)        # (nq, ns)
    kinds = jnp.asarray(pattern.slot_kinds)           # (nq, ns)

    # gather all (nq, ns*bk) kv rows at once
    flat = (kv_map[:, :, None] * bk
            + jnp.arange(bk, dtype=jnp.int32)[None, None, :]
            ).reshape(nq, ns * bk)                    # (nq, S)
    kg = jnp.take(k, flat.reshape(-1), axis=2).reshape(
        b, hkv, nq, ns * bk, d)
    vg = jnp.take(v, flat.reshape(-1), axis=2).reshape(
        b, hkv, nq, ns * bk, d)

    s = dots.einsum_f32("bhgnqd,bhnkd->bhgnqk",
                        qb * jnp.asarray(scale, q.dtype), kg)
    if spec.softcap:
        s = spec.softcap * jnp.tanh(s / spec.softcap)

    q_idx = ((jnp.arange(nq)[:, None] * bq
              + jnp.arange(bq)[None, :])[:, :, None]
             + q_shift)                               # (nq, bq, 1)
    k_idx = flat[:, None, :]                          # (nq, 1, S)
    full = jnp.repeat(kinds, bk, axis=1)[:, None, :]  # (nq, 1, S)
    mask = (k_idx >= kv_lo) & (k_idx < kv_hi) & (full != patterns.PAD)
    band = k_idx >= q_idx - spec.window
    if not spec.causal:
        band &= k_idx <= q_idx + spec.window
    allowed = band
    if spec.num_global:
        allowed |= k_idx < spec.num_global
    if spec.num_random:
        allowed |= (full == patterns.RANDOM)
    mask &= allowed
    if spec.causal:
        mask &= k_idx <= q_idx

    s = jnp.where(mask[None, None, None], s, NEG_INF)
    m = jax.lax.stop_gradient(jnp.max(s, axis=-1, keepdims=True))
    p = jnp.exp(s - m)
    p = jnp.where(mask[None, None, None], p, 0.0)
    if return_partials:
        acc = dots.einsum_f32("bhgnqk,bhnkd->bhgnqd", p.astype(v.dtype), vg)
        acc = acc.astype(jnp.float32).reshape(b, hq, lq_pad, d)[:, :, :lq]
        l = jnp.sum(p, -1).reshape(b, hq, lq_pad)[:, :, :lq]
        mm = m[..., 0].reshape(b, hq, lq_pad)[:, :, :lq]
        return acc, l, mm
    den = jnp.maximum(jnp.sum(p, -1, keepdims=True), 1e-30)
    o = dots.einsum_f32("bhgnqk,bhnkd->bhgnqd", (p / den).astype(v.dtype),
                        vg)
    o = o.astype(q.dtype).reshape(b, hq, lq_pad, d)
    return o[:, :, :lq]


# --------------------------------------------------------------------------
# Context-parallel dispatch (set by the launcher / dry-run, not per-call:
# the model stack stays signature-stable while the distribution strategy
# changes underneath — the same pattern as native_mixed_dot)
# --------------------------------------------------------------------------

_CP_CTX: Optional[tuple] = None   # (mesh, axis) | None


def set_context_parallel(mesh, axis: str = "model") -> None:
    """Enable halo-exchange context parallelism for every eligible
    swat_attention call (sparse spec, no random blocks, seq divisible by the
    axis with shards wider than the window's halo usefulness)."""
    global _CP_CTX
    _CP_CTX = (mesh, axis) if mesh is not None else None


def _cp_eligible(spec: AttentionSpec, lq: int, lkv: int) -> bool:
    if _CP_CTX is None or not spec.is_sparse or spec.num_random:
        return False
    mesh, axis = _CP_CTX
    n = mesh.shape[axis]
    return (lq == lkv and lq % n == 0 and lq // n >= 128
            and spec.num_global <= lq // n)


# --------------------------------------------------------------------------
# Public entry point
# --------------------------------------------------------------------------

def attend_scope(spec: AttentionSpec) -> str:
    """Named scope of an attention pass: `attend` over a window,
    `attend_full` over every earlier key, so a trace tells them apart."""
    return "attend" if spec.is_sparse else "attend_full"


def swat_attention(q, k, v, spec: AttentionSpec, *,
                   block_q: int = 128, block_kv: int = 128,
                   scale: Optional[float] = None,
                   impl: str = "pallas",
                   interpret: Optional[bool] = None):
    """Fused window/global/random attention. q: (B, Hq, Lq, D);
    k, v: (B, Hkv, Lkv, D). Differentiable for all impls."""
    b, hq, lq, d = q.shape
    lkv = k.shape[2]
    scale = float(d ** -0.5 if scale is None else scale)
    interpret = default_interpret() if interpret is None else interpret

    if _cp_eligible(spec, lq, lkv):
        from repro.distributed import context_parallel as CP
        mesh, axis = _CP_CTX
        return CP.swat_attention_context_parallel(
            q, k, v, spec, mesh=mesh, axis=axis,
            block_q=block_q, block_kv=block_kv, scale=scale)

    if impl == "ref":
        pat = get_pattern(spec, lq, lkv, block_q, block_kv)
        return ref_impl.attention_ref(q, k, v, spec, pattern=pat, scale=scale)
    if impl == "sliding_chunks":
        return ref_impl.sliding_chunks_ref(q, k, v, spec, scale=scale)
    assert impl in ("pallas", "xla"), impl

    pat = get_pattern(spec, lq, lkv, block_q, block_kv)
    if impl == "pallas":
        out = _pallas_attention(q, k, v, spec, pat, scale, interpret)
    else:
        out = _xla_banded(q, k, v, spec, pat, scale)

    g = spec.num_global
    if spec.is_sparse and g > 0:
        # dense pass for global rows (paper §4.1's pinned global cores)
        gspec = dataclasses.replace(spec, kind="dense", window=0,
                                    num_global=0, num_random=0)
        gpat = get_pattern(gspec, g, lkv, block_q, block_kv)
        qg = q[:, :, :g]
        if impl == "pallas":
            og = _pallas_attention(qg, k, v, gspec, gpat, scale, interpret)
        else:
            og = _xla_banded(qg, k, v, gspec, gpat, scale)
        out = jnp.concatenate([og, out[:, :, g:]], axis=2)
    return out


def _per_slot(x, b: int):
    """Normalize scalar / (B,) / (B,1,1,1) spellings to (B,) int32:
    broadcast, never reshape — a scalar reshaped to (B,) crashes for B > 1
    even though a shared length is the common cross-attention case
    (model.py passes a full()'d (B,1,1,1))."""
    x = jnp.asarray(x, jnp.int32)
    return jnp.broadcast_to(x.reshape(()) if x.size == 1 else x.reshape(b),
                            (b,))


def _shard_decode_kernel(call, mesh, cache_shape, fuse: bool):
    """XLA does not partition a Mosaic kernel: under a mesh the decode
    kernel runs per shard inside shard_map. Slots and KV heads are
    independent in the kernel, so it shards over them as the serving cache
    sharding places them (`sharding.decode_kernel_spec`); q and the output
    follow the cache (a query-head block maps onto its KV-head block)."""
    from jax.sharding import PartitionSpec as P

    kv = decode_kernel_spec(cache_shape, mesh)     # all four dims named
    vec = P(kv[0])
    in_specs = (kv, kv, kv, vec) + ((kv, kv, vec) if fuse else ())
    return jax.shard_map(call, mesh=mesh, in_specs=in_specs,
                         out_specs=(kv, kv, kv) if fuse else kv,
                         check_vma=False)


def _shard_row_write(write, mesh, cache_shape):
    """The row write per shard (`jax.shard_map`), as the cache sharding
    places slots, kv heads and ring rows (`sharding.ring_spec`): each shard
    writes the rows that fall in its part of the ring and drops the rest.
    Left to SPMD, the write finds the slot axis parallel only from an iota
    it can see, which XLA folds away when a slot has a single row to
    write, and then gathers every slot's new rows to every device."""
    from jax.sharding import PartitionSpec as P

    lead = len(cache_shape) - 4
    ring = ring_spec(cache_shape[lead:], mesh)
    cache = P(*((None,) * lead + tuple(ring)))
    rows_axes = ring[2]

    def local(c, n, p, nn, layer):
        row0 = (0 if rows_axes is None
                else jax.lax.axis_index(rows_axes) * c.shape[-2])
        return write(c, n, p, nn, layer, row0)
    return jax.shard_map(local, mesh=mesh,
                         in_specs=(cache, P(ring[0], ring[1]), P(ring[0]),
                                   P(ring[0]), P()),
                         out_specs=cache, check_vma=False)


def ring_rows_in_place(impl: str, paged: bool) -> bool:
    """Whether a decode step writes its K/V rows straight into the layer
    scan's stacked rings (`decode_attention(layer=)`, the ref impl on
    contiguous rings), touching no other row. The pallas kernel inserts
    into the layer's ring inside its pass, and a paged pool is gathered
    into a contiguous ring and scattered back whole: both take the ring
    out of the stack and put it back."""
    return impl != "pallas" and not paged


def decode_rows_read(impl: str, cache_rows: int) -> int:
    """Rows of one slot's cache that one layer's `decode_attention` reads
    in a step, for a cache of `cache_rows` rows: every row, whatever the
    slot's position. The ref impl scores every row and masks those past
    the position; the pallas kernel's grid visits every `block_kv` block,
    over a cache padded to whole blocks (`decode_block_kv`). A paged pool
    is gathered into a whole contiguous cache first. A path that reads
    only the rows a position needs changes this rule with it."""
    if impl == "pallas":
        from repro.kernels.swat_decode import decode_block_kv
        block, _ = decode_block_kv(cache_rows)
        return -(-cache_rows // block) * block
    return cache_rows


def decode_attention(q, k_cache, v_cache, cache_len, spec: AttentionSpec, *,
                     scale: Optional[float] = None, impl: str = "ref",
                     interpret: Optional[bool] = None,
                     new_kv=None, num_new=None, pos=None,
                     ring_cap: Optional[int] = None, mesh=None,
                     layer=None):
    """Decode T >= 1 tokens vs a (ring) KV cache. q: (B, Hq, T, D).
    cache_len / pos / num_new are per-slot (scalar, (B,) or (B,1,1,1)): a
    continuously-batched engine serves slots at different ring depths from
    this one call.

    * plain (new_kv=None): the cache already holds everything;
      `cache_len` is the valid count and the query tokens are its newest.
    * fused (new_kv=(k_new, v_new), each (B, Hkv, T, D)): the step's K/V
      rows are inserted at their ring slots AND attended in the same pass —
      on the pallas impl inside the kernel's VMEM-resident block (no
      separate scatter dispatch, no second full-cache HBM round trip);
      the ref impl inserts then attends (the unfused parity oracle —
      identical masks). `pos` (required) counts tokens BEFORE the insert;
      `num_new` optionally ragged-limits how many of the T rows are real
      per slot (speculative accepts). Returns (out, k_cache, v_cache).

    The ref impl inserts with `ref.ring_insert_ref`, one scatter of the
    step's rows, under a mesh per shard (`_shard_row_write`).
    layer: None, or (ref impl, fused only) the index (traced int ok) of
    the layer to decode when k_cache / v_cache are a layer scan's stacked
    caches (N, B, Hkv, W, D); the caches returned are then the stacks,
    with the rows written in place (`ring_rows_in_place`).

    Masks are positional: `ring_cap` is the LOGICAL rotation modulus
    (defaults to the cache width), spec.num_global the pinned prefix, and
    spec.window is enforced by token distance — so a cache allocated wider
    than window+1 rows (lookahead rings, dense caps) no longer attends its
    entire valid prefix (the old behavior silently dropped the window).

    impl="ref" is the jnp path (CPU tests, dry-run lowering); "pallas" is
    the swat_decode flash kernel (the TPU hot path; interpret mode
    elsewhere). Ring order is irrelevant either way — softmax is
    permutation invariant. mesh: the mesh the caller's jit is partitioned
    over; the pallas kernel then runs per shard (`_shard_decode_kernel`).

    Named scopes: the ref impl's fused insert is `ring_write` and its
    attention `attend` (`attend_full` without a window, `attend_scope`);
    the pallas kernel, which inserts inside its attention pass, takes
    the same scope."""
    b, _, t, _ = q.shape
    w_phys = k_cache.shape[-2]
    cap = w_phys if ring_cap is None else int(ring_cap)
    g = spec.num_global if spec.is_sparse else 0
    window = spec.window if spec.is_sparse else 0
    fuse = new_kv is not None
    if fuse:
        assert pos is not None, "fused insert needs per-slot `pos`"
        assert t <= cap - g, (
            f"{t} new tokens would overwrite each other in a {cap - g}-row "
            "ring: allocate the cache with lookahead >= T-1")
        assert t == 1 or not (spec.is_sparse and spec.window) \
            or cap - g >= spec.window + t, (
                f"T={t} fused decode on a {cap - g}-row ring would evict "
                "tokens still inside early queries' windows (sequential "
                "equivalence needs ring >= window + T): allocate with "
                "lookahead >= T-1")
    if (spec.is_sparse and spec.window and cap > spec.window + 1 + g
            and pos is None):
        # cache_len is CLAMPED (min(step, cap)) and loses the ring phase:
        # reconstructing slot positions from it on a wrapped wider-than-band
        # ring would window-mask the wrong slots — silently. Demand the
        # absolute count instead of guessing.
        raise ValueError(
            "window masking on a cache wider than window+1+globals needs "
            "absolute per-slot `pos=` (cache_len is clamped and loses the "
            "ring phase after a wrap)")
    assert fuse or cache_len is not None or pos is not None, (
        "plain decode needs cache_len (valid prefix) or pos (absolute "
        "token count) — with neither, every slot would mask empty and the "
        "output would be silently all-zero")
    cl = _per_slot(cache_len if cache_len is not None else 0, b)
    pos = cl if pos is None else _per_slot(pos, b)
    nn = (jnp.full((b,), t, jnp.int32) if num_new is None
          else _per_slot(num_new, b))
    _census_dispatch(q, k_cache, impl=impl, fused=fuse, cap=cap, g=g,
                     window=window)
    if impl == "pallas":
        from repro.kernels.swat_decode import swat_decode
        interpret = default_interpret() if interpret is None else interpret
        kern = functools.partial(
            swat_decode, ring_cap=cap, num_global=g, window=window,
            causal=spec.causal, scale=scale, softcap=spec.softcap,
            interpret=interpret)
        if fuse:
            def call(q, kc, vc, pos, kn, vn, nn):
                return kern(q, kc, vc, pos, new_k=kn, new_v=vn, num_new=nn)
            args = (q, k_cache, v_cache, pos) + tuple(new_kv) + (nn,)
        else:
            call, args = kern, (q, k_cache, v_cache, pos)
        if mesh is not None and mesh.size > 1:
            call = _shard_decode_kernel(call, mesh, k_cache.shape, fuse)
        with jax.named_scope(attend_scope(spec)):
            return call(*args)
    if fuse:
        k_new, v_new = new_kv

        def write(c, n, p, nn, lyr, row0=0):
            return ref_impl.ring_insert_ref(c, n, p, nn, ring_cap=cap,
                                            num_global=g, layer=lyr,
                                            row0=row0)
        if mesh is not None and mesh.size > 1:
            write = _shard_row_write(write, mesh, k_cache.shape)
        lyr = 0 if layer is None else layer
        with jax.named_scope("ring_write"):
            k_cache = write(k_cache, k_new, pos, nn, lyr)
            v_cache = write(v_cache, v_new, pos, nn, lyr)
        kc, vc = k_cache, v_cache
        if layer is not None:
            kc, vc = ref_impl.take_layer(kc, lyr), ref_impl.take_layer(vc, lyr)
        with jax.named_scope(attend_scope(spec)):
            out = ref_impl.decode_ref(q, kc, vc, None, spec, scale=scale,
                                      total=pos + nn, q0=pos, ring_cap=cap)
        return out, k_cache, v_cache
    if t > 1 or (spec.is_sparse and spec.window
                 and cap > spec.window + 1 + g):
        # positional masks: multi-token queries need per-token causality,
        # and a cache wider than the band would otherwise attend stale
        # tokens through the prefix mask alone (the bug this path fixes).
        # Queries are the cache's newest tokens (pre-inserted convention).
        return ref_impl.decode_ref(q, k_cache, v_cache, None, spec,
                                   scale=scale, total=pos, q0=pos - t,
                                   ring_cap=cap)
    return ref_impl.decode_ref(q, k_cache, v_cache, cl, spec, scale=scale)
