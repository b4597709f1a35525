"""Neural net layers: norms, rotary embeddings, GQA attention, MLP.

Parameters are plain pytrees (dicts of jnp arrays); every layer has an
`init_*` returning params and an `apply`-style function. No framework
dependency — keeps scan-over-layers and sharding rules transparent.
"""
from __future__ import annotations

import dataclasses
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp

from repro.core.types import AttentionSpec, ModelConfig
from repro.kernels import ops as kops
from repro.kernels.ref import put_layer, take_layer

Params = Dict[str, Any]


def _dense_init(key, shape, scale=None, dtype=jnp.float32):
    fan_in = shape[0]
    scale = (1.0 / fan_in) ** 0.5 if scale is None else scale
    return (jax.random.truncated_normal(key, -2, 2, shape, jnp.float32)
            * scale).astype(dtype)


# ---------------------------------------------------------------- norms ----

def init_rmsnorm(d: int) -> Params:
    return {"scale": jnp.zeros((d,), jnp.float32)}  # (1+scale) parametrization


def rmsnorm(params: Params, x, eps: float = 1e-6):
    dtype = x.dtype
    x = x.astype(jnp.float32)
    var = jnp.mean(x * x, axis=-1, keepdims=True)
    x = x * jax.lax.rsqrt(var + eps)
    return (x * (1.0 + params["scale"])).astype(dtype)


# ----------------------------------------------------------------- rope ----

def rope_frequencies(head_dim: int, theta: float):
    return theta ** (-jnp.arange(0, head_dim, 2, dtype=jnp.float32)
                     / head_dim)


def apply_rope(x, positions, theta: float):
    """x: (..., L, D) with positions (..., L) or (L,). Rotates pairs
    (x[2i], x[2i+1]) — llama convention (split-half)."""
    d = x.shape[-1]
    freqs = rope_frequencies(d, theta)                    # (D/2,)
    angles = positions[..., None].astype(jnp.float32) * freqs  # (..., L, D/2)
    sin, cos = jnp.sin(angles), jnp.cos(angles)
    x1, x2 = jnp.split(x.astype(jnp.float32), 2, axis=-1)
    out = jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)
    return out.astype(x.dtype)


def sinusoidal_positions(length: int, d: int):
    pos = jnp.arange(length, dtype=jnp.float32)[:, None]
    dim = jnp.arange(0, d, 2, dtype=jnp.float32)[None, :]
    angle = pos / jnp.power(10000.0, dim / d)
    pe = jnp.zeros((length, d), jnp.float32)
    pe = pe.at[:, 0::2].set(jnp.sin(angle))
    pe = pe.at[:, 1::2].set(jnp.cos(angle))
    return pe


# ------------------------------------------------------- sharding helper ---

def with_activation_constraint(x, sharding):
    """Pin activations to a sharding at super-block boundaries (training SP
    layout, or the serving decode/prefill batch layout). `sharding` is a
    NamedSharding / PartitionSpec, or None for a no-op — call sites stay
    unconditional so the model code reads the same sharded and not."""
    if sharding is None:
        return x
    return jax.lax.with_sharding_constraint(x, sharding)


# ------------------------------------------------------------ attention ----

@dataclasses.dataclass(frozen=True)
class AttentionLayerCfg:
    d_model: int
    num_heads: int
    num_kv_heads: int
    head_dim: int
    spec: AttentionSpec
    qkv_bias: bool = False
    rope_theta: float = 10000.0
    use_rope: bool = True
    cross: bool = False          # cross-attention (whisper decoder)


def init_attention(key, cfg: AttentionLayerCfg, dtype=jnp.bfloat16) -> Params:
    ks = jax.random.split(key, 4)
    dm, hq, hkv, dh = cfg.d_model, cfg.num_heads, cfg.num_kv_heads, cfg.head_dim
    p = {
        "wq": _dense_init(ks[0], (dm, hq * dh), dtype=dtype),
        "wk": _dense_init(ks[1], (dm, hkv * dh), dtype=dtype),
        "wv": _dense_init(ks[2], (dm, hkv * dh), dtype=dtype),
        "wo": _dense_init(ks[3], (hq * dh, dm), dtype=dtype),
    }
    if cfg.qkv_bias:
        p["bq"] = jnp.zeros((hq * dh,), dtype)
        p["bk"] = jnp.zeros((hkv * dh,), dtype)
        p["bv"] = jnp.zeros((hkv * dh,), dtype)
    return p


def _project_qkv(params, cfg: AttentionLayerCfg, x, kv_x):
    b, l, _ = x.shape
    lkv = kv_x.shape[1]
    q = x @ params["wq"]
    k = kv_x @ params["wk"]
    v = kv_x @ params["wv"]
    if cfg.qkv_bias:
        q, k, v = q + params["bq"], k + params["bk"], v + params["bv"]
    q = q.reshape(b, l, cfg.num_heads, cfg.head_dim).transpose(0, 2, 1, 3)
    k = k.reshape(b, lkv, cfg.num_kv_heads, cfg.head_dim).transpose(0, 2, 1, 3)
    v = v.reshape(b, lkv, cfg.num_kv_heads, cfg.head_dim).transpose(0, 2, 1, 3)
    return q, k, v


def attention_layer(params: Params, cfg: AttentionLayerCfg, x, *,
                    kv_x=None, positions=None, impl: str = "xla") -> jax.Array:
    """Full-sequence attention (training / prefill). x: (B, L, Dm).
    Scopes `qkv`, `attend`, `out`."""
    b, l, _ = x.shape
    kv_x = x if kv_x is None else kv_x
    with jax.named_scope("qkv"):
        q, k, v = _project_qkv(params, cfg, x, kv_x)
        if cfg.use_rope and not cfg.cross:
            pos = (jnp.arange(l) if positions is None else positions)
            q = apply_rope(q, pos, cfg.rope_theta)
            k = apply_rope(k, pos, cfg.rope_theta)
    with jax.named_scope("attend"):
        out = kops.swat_attention(q, k, v, cfg.spec, impl=impl)
    with jax.named_scope("out"):
        out = out.transpose(0, 2, 1, 3).reshape(b, l, -1)
        return out @ params["wo"]


# KV cache ------------------------------------------------------------------

def _round_capacity(cap: int) -> int:
    """Round a ring ALLOCATION up to a TPU-friendly quantum so the
    swat_decode kernel can tile the cache exactly (block_kv | cap) instead
    of jnp.pad-ing — and copying — the WHOLE cache on every decode step.
    Small rings round to the bf16 sublane tile (16); larger ones to 64 so
    the kernel keeps a wide KV block. A few extra zero rows cost O(window)
    bytes once; the pad cost a full cache copy per token per layer."""
    q = 64 if cap > 64 else 16
    return -(-cap // q) * q


def cache_capacity(cfg: AttentionLayerCfg, max_len: int,
                   lookahead: int = 0) -> int:
    """LOGICAL ring capacity: window+1(+lookahead)(+globals) for causal
    sparse attention (the paper's FIFO — decode attends at most window+1
    rows, never more), full context for dense. `lookahead` adds extra ring
    rows so a T-token decode step (T <= lookahead+1) never overwrites a
    token still inside some query's window — the multi-token/speculative
    allocation knob; the attention window itself is unchanged (positional
    masking in `decode_attention` hides the extra depth). `max_len` may be
    a physical allocation width (`cache["k"].shape[2]`): the logical
    capacity is recoverable from it (with the same lookahead) because
    allocations are only ever >= logical (tile rounding).

    Like `max_len`, `lookahead` is part of the cache GEOMETRY: the same
    value must reach init_kv_cache/prefill/chunk/decode for one cache (a
    mismatch rotates at the wrong modulus — it cannot live in the cache
    dict because the kernel needs it static under jit). The engine threads
    it everywhere from one knob, `ServingEngine(tokens_per_step=)`."""
    if cfg.spec.is_sparse:
        cap = cfg.spec.window + 1 + lookahead + cfg.spec.num_global
        return min(cap, max_len)
    return max_len


def cache_allocation(cfg: AttentionLayerCfg, max_len: int,
                     lookahead: int = 0) -> int:
    """PHYSICAL rows allocated for the ring: the logical capacity rounded up
    to a tile quantum (clamped to max_len). Rows in [logical, physical) are
    never written and never attended (`cache_len` <= logical masks them) —
    they exist purely so the decode kernel's grid tiles the cache exactly
    and the hot path never re-pads. Window semantics are untouched: the
    rotation modulus stays the logical capacity."""
    cap = cache_capacity(cfg, max_len, lookahead)
    if cfg.spec.is_sparse:
        return min(_round_capacity(cap), max_len)
    return cap


def init_kv_cache(cfg: AttentionLayerCfg, batch: int, max_len: int,
                  dtype=jnp.bfloat16, lookahead: int = 0):
    """Ring KV cache with a PER-SLOT write pointer: `step` is (batch,) so a
    continuously-batched decode can serve slots at different depths from one
    kernel call (each row inserts at its own ring position). Allocated at
    `cache_allocation` width (tile-rounded; the tail rows past the logical
    capacity stay zero and masked forever). lookahead: extra ring rows for
    T-token decode steps (`cache_capacity`)."""
    cap = cache_allocation(cfg, max_len, lookahead)
    shape = (batch, cfg.num_kv_heads, cap, cfg.head_dim)
    return {"k": jnp.zeros(shape, dtype), "v": jnp.zeros(shape, dtype),
            "step": jnp.zeros((batch,), jnp.int32)}


# Paged KV ------------------------------------------------------------------
#
# The paged layout splits each layer's ring allocation into fixed PAGE_SIZE-
# row blocks living in a pool, addressed through a per-slot block table:
#   shared pool (single-device):  pk/pv (P, H, page, D), table (B, nb) holds
#       GLOBAL block ids — slots may reference the same block (prefix
#       sharing, refcounted copy-on-write on the host side).
#   local pool (under a mesh):    pk/pv (B, nb+1, H, page, D), table (B, nb)
#       holds LOCAL ids — no cross-slot references, so the pool shards over
#       the slot axis and decode stays collective-free (gather/scatter are
#       one-hot selects, the `_dyn_update` trick, never dynamic gathers).
# Both carry one spare block per slot (the scratch block): freed slots park
# their whole table on it so the decode scan's unconditional ring writes for
# dead rows land somewhere never read.
#
# Decode gathers the table into a contiguous (B, H, nb*page, D) view, runs
# the UNCHANGED ring attention (same kernel, same tiling, same masks — the
# view width equals the contiguous allocation exactly, which is why
# PAGE_SIZE divides every `cache_allocation`), and scatters the whole view
# back. Bitwise identity with the contiguous engine falls out by
# construction; the cost is a pool-sized copy per step, which the
# contiguous ring, written a row at a time in place, does not pay.

PAGE_SIZE = 16   # rows per block == the bf16 sublane tile `_round_capacity`
                 # rounds to, so every ring allocation is block-divisible


def paged_num_blocks(cfg: AttentionLayerCfg, max_len: int,
                     lookahead: int = 0, page: int = PAGE_SIZE) -> int:
    """Blocks per slot for one layer's ring. The allocation must tile
    exactly — true for every sparse ring (`_round_capacity` quantum 16/64)
    and for dense caches whenever max_len is a multiple of 16."""
    alloc = cache_allocation(cfg, max_len, lookahead)
    if alloc % page:
        raise ValueError(
            f"paged KV needs page-divisible allocations: {alloc} rows "
            f"% {page} != 0 (pick max_len a multiple of {page})")
    return alloc // page


def init_paged_kv_cache(cfg: AttentionLayerCfg, batch: int, max_len: int,
                        dtype=jnp.bfloat16, lookahead: int = 0,
                        page: int = PAGE_SIZE, shared_pool: bool = True):
    """Paged twin of `init_kv_cache`. shared_pool picks the global-id layout
    (block sharing possible) vs the slot-local layout (mesh-shardable).
    Tables start at the identity mapping: slot s owns its home blocks, so a
    freshly-initialized paged cache gathers to exactly `init_kv_cache`'s
    zeros."""
    nb = paged_num_blocks(cfg, max_len, lookahead, page)
    nbp = nb + 1                                   # + per-slot scratch block
    hkv, d = cfg.num_kv_heads, cfg.head_dim
    if shared_pool:
        shape = (batch * nbp, hkv, page, d)
        table = (jnp.arange(batch, dtype=jnp.int32)[:, None] * nbp
                 + jnp.arange(nb, dtype=jnp.int32)[None, :])
    else:
        shape = (batch, nbp, hkv, page, d)
        table = jnp.broadcast_to(jnp.arange(nb, dtype=jnp.int32),
                                 (batch, nb)).astype(jnp.int32)
    return {"pk": jnp.zeros(shape, dtype), "pv": jnp.zeros(shape, dtype),
            "table": table, "step": jnp.zeros((batch,), jnp.int32)}


def paged_gather(pool, table):
    """Contiguous (B, H, nb*page, D) view of each slot's blocks."""
    from repro.kernels import swat_decode as _sd
    _sd.record_paged_fallback(
        table.shape[-1], pool.shape[-2],
        "table resolved outside the kernel: materialized gather-view "
        "decode (pool-sized copy per step) instead of in-kernel gather")
    if pool.ndim == 4:    # shared pool (P, H, page, D), global ids
        blocks = pool[table]                            # (B, nb, H, page, D)
    else:                 # local pool (B, nbp, H, page, D), local ids
        nbp = pool.shape[1]
        hot = (table[..., None]
               == jnp.arange(nbp, dtype=jnp.int32))     # (B, nb, nbp)
        # one-hot select, not a gather: exact in any dtype (one nonzero
        # term per output) and partitionable under slot sharding
        blocks = jnp.einsum("bnp,bphkd->bnhkd", hot.astype(pool.dtype), pool)
    b, nb, h, page, d = blocks.shape
    return blocks.transpose(0, 2, 1, 3, 4).reshape(b, h, nb * page, d)


def paged_scatter(pool, table, view):
    """Write a (B, H, nb*page, D) contiguous view back through the table.
    Duplicate table entries (shared blocks, parked scratch rows) receive
    value-identical or never-read writes, so scatter order is immaterial."""
    b, h, rows, d = view.shape
    nb = table.shape[-1]
    page = rows // nb
    blocks = (view.reshape(b, h, nb, page, d)
              .transpose(0, 2, 1, 3, 4).astype(pool.dtype))  # (B,nb,H,pg,D)
    if pool.ndim == 4:
        return pool.at[table].set(blocks)
    nbp = pool.shape[1]
    hot = (table[..., None] == jnp.arange(nbp, dtype=jnp.int32))  # (B,nb,nbp)
    upd = jnp.einsum("bnp,bnhkd->bphkd", hot.astype(pool.dtype), blocks)
    written = jnp.any(hot, axis=1)                                # (B, nbp)
    return jnp.where(written[:, :, None, None, None], upd, pool)


def attention_decode(params: Params, cfg: AttentionLayerCfg, x, cache, *,
                     layer, impl: str = "ref", lookahead: int = 0,
                     mesh=None):
    """T-token decode of one layer of a layer scan. x: (B, T, Dm). cache:
    this layer kind's caches stacked over the scan (leading axis N, the
    scan's carry), of which layer `layer` (traced int) decodes; they come
    back stacked with the layer's rows written and its `step` advanced,
    every other leaf (xattn's `xk`/`xv`) untouched.

    Ring insertion at (step mod cap) for sparse specs — the paper's FIFO
    replacement policy (row index mod window) — happens INSIDE the
    attention call (`decode_attention(new_kv=)`): the pallas impl writes
    the new rows into the cache block it already holds in VMEM
    (input-stationary, Fig. 4b); the ref impl writes them, then attends
    with identical masks (the parity oracle). Where
    `kops.ring_rows_in_place` holds (the ref impl on contiguous rings) the
    write goes straight into the stack, rows only; the pallas kernel and a
    paged pool's contiguous view take the layer's ring out of the stack
    and put it back. Global
    tokens occupy pinned slots [0, g) (paper §4.1's fixed K/V buffers); the
    ring occupies [g, cap). `step` is per-slot (B,): every row rotates,
    ropes, and masks at its own depth, which is what lets one batched call
    serve slots mid-flight at different positions. T > 1 (the speculative-
    decode verify primitive) needs a cache allocated with lookahead >= T-1
    so the step's own inserts never evict an in-window token. mesh: the
    mesh the caller's jit is partitioned over (see `decode_attention`).

    Scopes: `qkv`, `ring_write` (the ring insert; a paged cache's
    write-back too), `attend` (a paged cache's gather too), `out`. The
    pallas kernel inserts inside its attention pass, under `attend`."""
    b, t, _ = x.shape
    step = take_layer(cache["step"], layer).astype(jnp.int32)
    step = jnp.broadcast_to(step, (b,))
    with jax.named_scope("qkv"):
        q, k_new, v_new = _project_qkv(params, cfg, x, x)
        if cfg.use_rope and not cfg.cross:
            pos = (step[:, None, None]
                   + jnp.arange(t, dtype=jnp.int32))          # (B,1,T)
            q = apply_rope(q, pos, cfg.rope_theta)
            k_new = apply_rope(k_new, pos, cfg.rope_theta)
    paged = "table" in cache
    in_place = kops.ring_rows_in_place(impl, paged)
    if paged:
        # contiguous view of the block table; width == the contiguous
        # allocation, so everything below is bit-for-bit the ring path
        pk, pv, table = (take_layer(cache[n], layer)
                         for n in ("pk", "pv", "table"))
        with jax.named_scope("attend"):
            k_arr = paged_gather(pk, table)
            v_arr = paged_gather(pv, table)
    elif in_place:
        k_arr, v_arr = cache["k"], cache["v"]
    else:
        k_arr = take_layer(cache["k"], layer)
        v_arr = take_layer(cache["v"], layer)
    # rotate and mask at the LOGICAL capacity: the allocation may carry a
    # tile-rounding tail of zero rows that must never be written or attended
    # (otherwise the rounding would silently widen the attention window)
    cap = cache_capacity(cfg, k_arr.shape[-2], lookahead)
    g = cfg.spec.num_global if cfg.spec.is_sparse else 0
    assert t == 1 or not cfg.spec.is_sparse \
        or cap - g >= cfg.spec.window + t, (
            f"T={t} decode on a {cap - g}-row ring would evict in-window "
            "tokens: allocate caches with lookahead >= T-1")
    out, k_cache, v_cache = kops.decode_attention(
        q, k_arr, v_arr, None, cfg.spec, impl=impl,
        new_kv=(k_new, v_new), pos=step, ring_cap=cap, mesh=mesh,
        layer=layer if in_place else None)
    new_cache = {**cache, "step": put_layer(cache["step"], layer, step + t)}
    if paged:
        with jax.named_scope("ring_write"):
            new_cache["pk"] = put_layer(cache["pk"], layer,
                                        paged_scatter(pk, table, k_cache))
            new_cache["pv"] = put_layer(cache["pv"], layer,
                                        paged_scatter(pv, table, v_cache))
    elif in_place:
        new_cache.update(k=k_cache, v=v_cache)
    else:
        new_cache.update(k=put_layer(cache["k"], layer, k_cache),
                         v=put_layer(cache["v"], layer, v_cache))
    with jax.named_scope("out"):
        out = out.transpose(0, 2, 1, 3).reshape(b, t, -1)
        return out @ params["wo"], new_cache


def _dyn_update(cache, new, slot):
    """Insert one row per batch element at its own dynamic `slot` along the
    cap axis.

    Implemented as iota==slot select, NOT dynamic_update_slice: a scatter at
    a dynamic index across a sequence-sharded cache forces XLA SPMD into
    "involuntary full rematerialization" (it replicates the whole cache
    every step). The select partitions trivially under any cap sharding at
    the cost of a full-cache read and write. Decode does not insert this
    way: it scatters the step's rows (`ref.ring_insert_ref`, bitwise this
    function's ring at T=1) per shard of the ring, rows split or not
    (`ops._shard_row_write`), which leaves SPMD no dynamic write to
    partition.
    cache: (B, H, cap, D); new: (B, H, 1, D); slot: (B,) or scalar int32."""
    b, _, cap, _ = cache.shape
    slot = jnp.broadcast_to(jnp.asarray(slot, jnp.int32), (b,))
    hit = (jnp.arange(cap, dtype=jnp.int32)[None, :]
           == slot[:, None])[:, None, :, None]
    return jnp.where(hit, new.astype(cache.dtype), cache)


def ring_scatter(cache_kv, new, positions, write, g: int, ring):
    """Write `new` (B, H, T, D) rows into their ring slots of a cache
    (B, H, cap, D). positions: (T,) absolute token indices (traced ok, shared
    across rows); write: (B, T) bool — which tokens are real for each row
    (right-padded rows just stop writing). Per (row, slot) the highest-index
    writer wins, so a span longer than the ring and per-row ragged lengths
    both resolve exactly as sequential FIFO insertion would."""
    b, _, cap, _ = cache_kv.shape
    t = new.shape[2]
    positions = jnp.asarray(positions, jnp.int32)
    slot = jnp.where(positions < g, positions, g + (positions - g) % ring)
    jidx = jnp.arange(t, dtype=jnp.int32)
    hit = slot[:, None] == jnp.arange(cap, dtype=jnp.int32)[None, :]  # (T,cap)
    cand = jnp.where(write[:, :, None] & hit[None], jidx[None, :, None], -1)
    winner = jnp.max(cand, axis=1)                                    # (B,cap)
    sel = jidx[None, :, None] == winner[:, None, :]                   # (B,T,cap)
    upd = jnp.einsum("bjs,bhjd->bhsd", sel.astype(cache_kv.dtype),
                     new.astype(cache_kv.dtype))
    return jnp.where((winner >= 0)[:, None, :, None], upd, cache_kv)


def prefill_kv_cache(params: Params, cfg: AttentionLayerCfg, x, max_len: int,
                     positions=None, lengths=None, lookahead: int = 0):
    """Fill a cache from a prompt (B, L, Dm). For ring caches only the last
    `cap` tokens are retained (earlier ones are outside every future window).

    lengths: optional (B,) int32 — per-row real prompt length for a padded
    batched prefill. Rows write only their first `lengths[i]` tokens and the
    cache step is set per row, so decode continues each row at its own
    position. Without it every row is taken at full length L."""
    b, l, _ = x.shape
    _, k, v = _project_qkv(params, cfg, x, x)
    if cfg.use_rope and not cfg.cross:
        pos = jnp.arange(l) if positions is None else positions
        k = apply_rope(k, pos, cfg.rope_theta)
    cap = cache_capacity(cfg, max_len, lookahead)
    cache = init_kv_cache(cfg, b, max_len, dtype=k.dtype,
                          lookahead=lookahead)
    g = cfg.spec.num_global if cfg.spec.is_sparse else 0
    if l <= cap:
        # no wrap possible: natural slots. Rows shorter than L carry pad K/V
        # above their step, permanently masked (valid = [0, min(step, cap)))
        # and overwritten one-for-one as decode advances.
        cache["k"] = jax.lax.dynamic_update_slice(
            cache["k"], k, (0, 0, 0, 0))
        cache["v"] = jax.lax.dynamic_update_slice(
            cache["v"], v, (0, 0, 0, 0))
    else:
        # pinned globals + ring tail, laid out to match attention_decode;
        # last-writer-wins scatter reproduces sequential FIFO insertion
        # per row even when rows wrap at different lengths.
        lens = (jnp.full((b,), l, jnp.int32) if lengths is None
                else jnp.asarray(lengths, jnp.int32))
        write = jnp.arange(l, dtype=jnp.int32)[None, :] < lens[:, None]
        cache["k"] = ring_scatter(cache["k"], k, jnp.arange(l), write,
                                  g, cap - g)
        cache["v"] = ring_scatter(cache["v"], v, jnp.arange(l), write,
                                  g, cap - g)
    cache["step"] = (jnp.full((b,), l, jnp.int32) if lengths is None
                     else jnp.asarray(lengths, jnp.int32))
    return cache


def attention_prefill_chunk(params: Params, cfg: AttentionLayerCfg, x, cache,
                            pos0, lengths, lookahead: int = 0):
    """One chunk of a batched chunked prefill: attend tokens [pos0, pos0+T)
    against the ring cache (all earlier chunks) plus the chunk itself, then
    append the chunk's K/V to the ring.

    This is exact — the ring holds every token a band query can still see
    (window + pinned globals), so chunked prefill computes the same function
    as full-sequence prefill while the score matrix stays (T, cap+T): VMEM
    is bounded by the chunk size, not the prompt length. Causal specs only.

    pos0 may be a traced scalar (shared by all rows — the scheduler chunks
    the padded batch in lockstep); per-row raggedness comes from `lengths`:
    rows stop writing past their own length and their surplus outputs are
    garbage the caller discards. Returns (output (B, T, Dm), new cache).
    Scopes `qkv`, `attend`, `ring_write`, `out`."""
    assert cfg.spec.causal and not cfg.cross
    b, t, _ = x.shape
    pos = pos0 + jnp.arange(t, dtype=jnp.int32)            # (T,) absolute
    with jax.named_scope("qkv"):
        q, k_new, v_new = _project_qkv(params, cfg, x, x)
        if cfg.use_rope:
            q = apply_rope(q, pos, cfg.rope_theta)
            k_new = apply_rope(k_new, pos, cfg.rope_theta)
    with jax.named_scope("attend"):
        out = _chunk_attend(cfg, q, k_new, v_new, cache, pos, pos0, lengths,
                            lookahead, x.dtype)
    lens = jnp.asarray(lengths, jnp.int32)
    cap = cache_capacity(cfg, cache["k"].shape[2], lookahead)
    g = cfg.spec.num_global if cfg.spec.is_sparse else 0
    with jax.named_scope("ring_write"):
        write = pos[None, :] < lens[:, None]                   # (B, T)
        new_cache = {
            **cache,
            "k": ring_scatter(cache["k"], k_new, pos, write, g, cap - g),
            "v": ring_scatter(cache["v"], v_new, pos, write, g, cap - g),
            "step": jnp.minimum(lens, pos0 + t).astype(jnp.int32),
        }
    with jax.named_scope("out"):
        return out @ params["wo"], new_cache


def _chunk_attend(cfg: AttentionLayerCfg, q, k_new, v_new, cache, pos, pos0,
                  lengths, lookahead: int, dtype):
    """A chunk's queries against the ring (earlier chunks) and the chunk
    itself: (B, T, Hq * D) in `dtype`."""
    b, t = q.shape[0], q.shape[2]
    cap_phys = cache["k"].shape[2]
    cap = cache_capacity(cfg, cap_phys, lookahead)  # logical: rot. modulus
    g = cfg.spec.num_global if cfg.spec.is_sparse else 0
    ring = cap - g
    w = cfg.spec.window if cfg.spec.is_sparse else cap + t  # dense: no band
    lens = jnp.asarray(lengths, jnp.int32)

    # which token each cache slot holds just before this chunk: pinned slot
    # s holds token s; ring slot r holds the latest token < pos0 congruent
    # to r (all traced arithmetic so pos0 never forces a retrace). Slots in
    # the tile-rounding tail [cap, cap_phys) are never occupied.
    s_idx = jnp.arange(cap_phys, dtype=jnp.int32)
    r = s_idx - g
    t_ring = (pos0 - 1) - jnp.mod((pos0 - 1 - g) - r, ring)
    slot_pos = jnp.where(s_idx < g, s_idx, t_ring)
    occupied = jnp.where(s_idx < g, pos0 > s_idx,
                         (pos0 > g + r) & (t_ring >= g)) & (s_idx < cap)
    live = occupied[None, :] & (slot_pos[None, :] < lens[:, None])  # (B,cap)

    # band/global masks (causality vs cache is automatic: slot_pos < pos0)
    allow_c = ((s_idx[None, :] < g)
               | (slot_pos[None, :] >= pos[:, None] - w)
               | (pos[:, None] < g))                       # (T, cap)
    mask_c = live[:, None, :] & allow_c[None]              # (B, T, cap)
    mask_s = ((pos[None, :] <= pos[:, None])
              & ((pos[None, :] >= pos[:, None] - w)
                 | (pos[None, :] < g) | (pos[:, None] < g)))  # (T, T)

    group = cfg.num_heads // cfg.num_kv_heads
    d = cfg.head_dim
    from repro.kernels import dots
    qg = (q.reshape(b, cfg.num_kv_heads, group, t, d)
          * jnp.asarray(d ** -0.5, q.dtype))
    s_c = dots.einsum_f32("bhgtd,bhcd->bhgtc", qg, cache["k"])
    s_s = dots.einsum_f32("bhgtd,bhkd->bhgtk", qg, k_new)
    if cfg.spec.softcap:
        s_c = cfg.spec.softcap * jnp.tanh(s_c / cfg.spec.softcap)
        s_s = cfg.spec.softcap * jnp.tanh(s_s / cfg.spec.softcap)
    s_c = jnp.where(mask_c[:, None, None], s_c, kops.NEG_INF)
    s_s = jnp.where(mask_s[None, None, None], s_s, kops.NEG_INF)
    s_all = jnp.concatenate([s_c, s_s], axis=-1)
    m = jax.lax.stop_gradient(jnp.max(s_all, axis=-1, keepdims=True))
    p = jnp.exp(s_all - m)
    p = jnp.where(jnp.concatenate(
        [jnp.broadcast_to(mask_c[:, None, None], s_c.shape),
         jnp.broadcast_to(mask_s[None, None, None], s_s.shape)], axis=-1),
        p, 0.0)
    den = jnp.maximum(jnp.sum(p, -1, keepdims=True), 1e-30)
    v_all = jnp.concatenate([cache["v"], v_new], axis=2)
    o = dots.einsum_f32("bhgtk,bhkd->bhgtd", (p / den).astype(v_all.dtype),
                        v_all)
    return (o.reshape(b, cfg.num_heads, t, d).astype(dtype)
            .transpose(0, 2, 1, 3).reshape(b, t, -1))


# ---------------------------------------------------------------- mlp ------

def init_mlp(key, d_model: int, d_ff: int, dtype=jnp.bfloat16,
             gated: bool = True) -> Params:
    ks = jax.random.split(key, 3)
    p = {"w1": _dense_init(ks[0], (d_model, d_ff), dtype=dtype),
         "w2": _dense_init(ks[1], (d_ff, d_model), dtype=dtype)}
    if gated:
        p["w3"] = _dense_init(ks[2], (d_model, d_ff), dtype=dtype)
    return p


def mlp(params: Params, x, activation: str = "silu"):
    act = {"silu": jax.nn.silu, "gelu": jax.nn.gelu}[activation]
    h = act(x @ params["w1"])
    if "w3" in params:
        h = h * (x @ params["w3"])
    return h @ params["w2"]


def softcap(x, cap: float):
    return cap * jnp.tanh(x / cap) if cap else x
