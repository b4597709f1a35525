"""Context parallelism for window attention: halo exchange, not all-gather.

This is the paper's central dataflow insight lifted from the FPGA fabric to
the pod fabric. SWAT's FIFO K/V buffer exists because the band makes each
row's working set *local*: row i needs only kv rows [i-w, i+w]. Across
devices the same locality means a sequence-sharded device needs only a
w-token *halo* from its neighbour(s) — O(w·D) wire bytes per device instead
of the O(L·D) all-gather that dense attention forces. Collective traffic
becomes independent of sequence length: the cross-device FIFO.

Mechanics (inside shard_map over `axis`, n shards, local length Lp):
  * left halo   : ceil(w/Lp) hops of jax.lax.ppermute shift the left
                  neighbour's shard(s) in; devices that receive nothing
                  (the left edge) get zeros, masked out by kv bounds.
  * right halo  : same, shifted the other way (bidirectional specs only).
  * band pass   : the exact-band kernel runs on [halo | local | halo] with a
                  constant local shift (band masks are shift-invariant); the
                  per-shard valid kv range [kv_lo, kv_hi) — traced scalars —
                  masks the sequence edges.
  * global cols : the first g kv rows (shard 0) are psum-broadcast (g is
                  static and small); every local row folds them in with a
                  local logsumexp merge. Columns already inside the row's
                  band are excluded (the single-device kernel dedupes these
                  via its slot pattern).
  * global rows : q rows < g attend everything, so each shard computes its
                  local partial and a pmax/psum logsumexp merge combines
                  them — one (g, D)-sized collective, not a kv gather.

Random (BigBird) blocks are NOT supported under context parallelism: a
random column set defeats the locality that makes the halo cheap (it would
need an all-to-all). Use data/tensor parallelism for BigBird-style specs, or
re-draw random blocks shard-locally (config opt-in) — see DESIGN.md
§Arch-applicability.
"""
from __future__ import annotations

import dataclasses
import functools
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, PartitionSpec as P

from repro.core import patterns
from repro.core.types import AttentionSpec
from repro.kernels import dots
from repro.kernels import ops as kops

NEG_INF = kops.NEG_INF


def halo_hops(window: int, local_len: int) -> int:
    """ppermute hops needed to cover a w-token halo with Lp-token shards."""
    return -(-window // local_len)


def halo_rows(window: int, local_len: int, block: int = 128) -> int:
    """Rows actually wired per side. When the window fits inside one shard
    only the (block-aligned) w-row tail travels — this is what makes the
    halo O(w), independent of L. Multi-hop (w > Lp) ships whole shards,
    bounded by < w + Lp < 2w."""
    if window <= local_len:
        return min(local_len, -(-window // block) * block)
    return halo_hops(window, local_len) * local_len


def _shift_in(x, axis: str, hops: int, direction: int, rows: int):
    """Collect the `rows`-deep halo along the sequence dim (dim 2).

    direction=+1: left halo (device i receives from i-1, ..., i-hops);
    direction=-1: right halo. Non-receiving edge devices get zeros (masked
    by kv bounds downstream). Returns the concatenation in sequence order.
    """
    n = jax.lax.axis_size(axis)
    lp = x.shape[2]
    if hops == 0 or n == 1:
        return x[:, :, :0]
    if direction > 0:
        perm = [(i, i + 1) for i in range(n - 1)]
    else:
        perm = [(i + 1, i) for i in range(n - 1)]
    if hops == 1 and rows < lp:
        # single hop: wire only the facing `rows`-deep edge of the shard
        edge = x[:, :, -rows:] if direction > 0 else x[:, :, :rows]
        return jax.lax.ppermute(edge, axis, perm)
    out = []
    cur = x
    for _ in range(hops):
        cur = jax.lax.ppermute(cur, axis, perm)
        if direction > 0:
            out.insert(0, cur)   # furthest shard first
        else:
            out.append(cur)
    return jnp.concatenate(out, axis=2)


def _merge(p1: Tuple, p2: Tuple) -> Tuple:
    """Logsumexp-merge two flash partials (acc, l, m). acc unnormalized
    fp32 (…, D); l, m (…,) fp32. NEG_INF m marks an empty partial."""
    acc1, l1, m1 = p1
    acc2, l2, m2 = p2
    m = jnp.maximum(m1, m2)
    a1 = jnp.exp(m1 - m)   # exp(-1e30 - m) underflows to exactly 0
    a2 = jnp.exp(m2 - m)
    return (acc1 * a1[..., None] + acc2 * a2[..., None],
            l1 * a1 + l2 * a2, m)


def _finalize(p: Tuple, dtype) -> jax.Array:
    acc, l, _ = p
    return (acc / jnp.maximum(l, 1e-30)[..., None]).astype(dtype)


def _dense_partial(q, k, v, scale, softcap, mask):
    """Flash partial of a small dense pass. q: (B,Hq,Lq,D); k/v (B,Hkv,S,D);
    mask broadcastable to (B,Hq,Lq,S)."""
    b, hq, lq, d = q.shape
    hkv = k.shape[1]
    group = hq // hkv
    qb = q.reshape(b, hkv, group, lq, d)
    s = dots.einsum_f32("bhgqd,bhkd->bhgqk",
                        qb * jnp.asarray(scale, q.dtype), k)
    s = s.reshape(b, hq, lq, k.shape[2])
    if softcap:
        s = softcap * jnp.tanh(s / softcap)
    s = jnp.where(mask, s, NEG_INF)
    m = jax.lax.stop_gradient(jnp.max(s, axis=-1))
    p = jnp.exp(s - m[..., None])
    p = jnp.where(mask, p, 0.0)
    pb = p.reshape(b, hkv, group, lq, -1)
    acc = dots.einsum_f32("bhgqk,bhkd->bhgqd", pb.astype(v.dtype), v)
    acc = acc.astype(jnp.float32).reshape(b, hq, lq, d)
    return acc, jnp.sum(p, -1), m


def swat_attention_cp_local(q, k, v, idx_arr=None, *, spec: AttentionSpec,
                            axis: str, seq_len: int,
                            block_q: int = 128, block_kv: int = 128,
                            scale: Optional[float] = None):
    """The per-shard body (call inside shard_map over `axis`).

    q, k, v: LOCAL shards (B, H, Lp, D) of a (B, H, L, D) problem with the
    sequence dim sharded over `axis`. Returns the local output shard.

    idx_arr: optional (1,) int32 carrying this shard's index (an arange
    sharded over `axis`). When given it replaces `lax.axis_index` — at depth
    (tens of identical manual regions) XLA CSE hoists the partition-id
    instruction out of the manual subgraphs and the auto partitioner rejects
    it ("PartitionId ... ambiguous"); a sharded input is just data and
    cannot be hoisted wrong (§Perf cell 2 follow-up).
    """
    assert spec.is_sparse and spec.window > 0, "CP needs a window spec"
    assert spec.num_random == 0, "random blocks break halo locality (DESIGN.md)"
    b, hq, lp, d = q.shape
    hkv = k.shape[1]
    scale = float(d ** -0.5 if scale is None else scale)
    n = jax.lax.axis_size(axis)
    idx = jax.lax.axis_index(axis) if idx_arr is None else idx_arr[0]
    assert lp * n == seq_len, (lp, n, seq_len)
    w, g = spec.window, spec.num_global

    hops_l = halo_hops(w, lp)
    hops_r = 0 if spec.causal else hops_l
    halo = halo_rows(w, lp, block_kv)
    halo_r = 0 if spec.causal else halo

    k_ext = jnp.concatenate(
        [_shift_in(k, axis, hops_l, +1, halo), k,
         _shift_in(k, axis, hops_r, -1, halo_r)], axis=2)
    v_ext = jnp.concatenate(
        [_shift_in(v, axis, hops_l, +1, halo), v,
         _shift_in(v, axis, hops_r, -1, halo_r)], axis=2)

    # valid local kv range: global index k_loc - halo + idx*Lp in [0, L)
    kv_lo = jnp.maximum(0, halo - idx * lp)
    kv_hi = jnp.minimum(k_ext.shape[2], seq_len - idx * lp + halo)

    band_spec = dataclasses.replace(spec, num_global=0, num_random=0)
    pattern = patterns.build_block_pattern(
        band_spec, lp, k_ext.shape[2], block_q, block_kv, q_shift=halo)
    part = kops._xla_banded(q, k_ext, v_ext, band_spec, pattern, scale,
                            q_shift=halo, kv_lo=kv_lo, kv_hi=kv_hi,
                            return_partials=True)

    q_global_idx = idx * lp + jnp.arange(lp)          # (Lp,)

    def _bcast0(x):
        """psum-broadcast shard 0's slice. fp32 on the wire: bf16 psum under
        partial-manual shard_map hits an XLA CPU partitioner bug ("Invalid
        binary instruction opcode copy"); fp32 lowers cleanly and the halo
        is tiny so the 2x wire cost is noise."""
        own32 = jnp.where(idx == 0, 1.0, 0.0)
        return jax.lax.psum(x.astype(jnp.float32) * own32, axis).astype(
            x.dtype)

    if g:
        gl = min(g, lp)
        assert gl == g, f"num_global={g} must fit one shard (Lp={lp})"
        # ---- global COLUMNS: broadcast shard 0's first g kv rows ----
        kg = _bcast0(k[:, :, :g])
        vg = _bcast0(v[:, :, :g])
        kcol = jnp.arange(g)[None, None, None, :]
        qrow = q_global_idx[None, None, :, None]
        colmask = kcol < qrow - w          # dedupe: band pass already covers
        if spec.causal:                    # [q-w, q]; globals add only k<q-w
            colmask &= kcol <= qrow
        part = _merge(part, _dense_partial(q, kg, vg, scale, spec.softcap,
                                           colmask))

    out = _finalize(part, q.dtype)

    if g:
        # ---- global ROWS: first g q rows (shard 0's, psum-broadcast) attend
        # ALL kv; every shard contributes its local partial, merged with
        # pmax/psum ----
        qg = _bcast0(q[:, :, :g])
        krow = (idx * lp + jnp.arange(lp))[None, None, None, :]
        growmask = jnp.broadcast_to(krow < seq_len, (1, 1, g, lp))
        if spec.causal:
            growmask = krow <= jnp.arange(g)[None, None, :, None]
        acc, l, m = _dense_partial(qg, k, v, scale, spec.softcap, growmask)
        m_star = jax.lax.pmax(m, axis)
        a = jnp.exp(m - m_star)
        acc = jax.lax.psum(acc * a[..., None], axis)
        l = jax.lax.psum(l * a, axis)
        g_out = (acc / jnp.maximum(l, 1e-30)[..., None]).astype(q.dtype)
        # replace rows with global q index < g (only shard 0 has any)
        is_global = (q_global_idx < g)[None, None, :, None]
        g_pad = jnp.pad(g_out, ((0, 0), (0, 0), (0, lp - g), (0, 0)))
        out = jnp.where(is_global, g_pad, out)
    return out


def swat_attention_context_parallel(
        q, k, v, spec: AttentionSpec, *, mesh: Mesh, axis: str = "model",
        block_q: int = 128, block_kv: int = 128,
        scale: Optional[float] = None):
    """Sequence-sharded window attention over `axis` of `mesh`.

    q, k, v: (B, H, L, D) global arrays; the op shards L over `axis`,
    exchanges w-token halos, and returns the (B, H, L, D) output with the
    same sharding. shard_map runs PARTIAL-MANUAL over `axis` only, so batch/
    head dims stay SPMD-auto (compose with DP on other mesh axes).
    Differentiable (shard_map transposes the ppermutes)."""
    n = mesh.shape[axis]
    lq = q.shape[2]
    assert lq % n == 0, f"seq {lq} must divide over {axis}={n}"
    io_spec = P(None, None, axis, None)
    body = functools.partial(
        swat_attention_cp_local, spec=spec, axis=axis, seq_len=lq,
        block_q=block_q, block_kv=block_kv, scale=scale)
    fn = jax.shard_map(body, mesh=mesh,
                       in_specs=(io_spec,) * 3 + (P(axis),),
                       out_specs=io_spec, axis_names={axis},
                       check_vma=False)
    # shard index travels as data (see swat_attention_cp_local docstring)
    return fn(q, k, v, jnp.arange(n, dtype=jnp.int32))


def cp_wire_bytes_per_device(seq_len: int, n_shards: int, window: int,
                             num_heads: int, head_dim: int,
                             bytes_per_el: int = 2, batch: int = 1,
                             causal: bool = True) -> int:
    """Analytic halo traffic (per device, per layer): the roofline model the
    dry-run numbers are checked against. K and V, halo_rows each way —
    O(w), independent of seq_len once the window fits one shard."""
    lp = seq_len // n_shards
    rows = halo_rows(window, lp)
    sides = 1 if causal else 2
    return 2 * sides * rows * num_heads * head_dim * bytes_per_el * batch
