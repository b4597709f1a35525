"""Expert parallelism with explicit token exchange (shard_map all-to-all).

The pure-SPMD sort/scatter dispatch (moe.py, dispatch="sort") leaves the
token exchange to XLA's partitioner, which lowers the global-index scatters
and gathers into replicate+all-reduce of (E, C_global, D) buffers — measured
at 10.5 TB/device/step on granite-moe x train_4k (EXPERIMENTS.md §Perf cell
1). This module is the schedule every production MoE system actually uses:

  1. each shard routes its LOCAL tokens (router + top-k, replicated weights),
  2. packs them into a (n_shards, cap, D) send buffer by destination shard,
  3. one jax.lax.all_to_all moves tokens to the shards owning their experts,
  4. local sort groups received tokens by local expert, batched FFN,
  5. the reverse all_to_all returns outputs, combined by gate locally.

Wire bytes per device per layer = 2 x t_local*k*cf*D (there and back) — it
scales with LOCAL tokens, independent of the global batch. shard_map runs
partial-manual over the expert axis only, so data-parallel batch dims stay
SPMD-auto.

Capacity: cap = ceil(t_local*k*cf / n_shards) per (src, dst) pair; overflow
drops (standard). cf is per-call so tests can use a no-drop setting.
"""
from __future__ import annotations

import functools
from typing import Any, Dict, Tuple

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, PartitionSpec as P

from repro.core.types import MoESpec

Params = Dict[str, Any]


def _group_by_dest(ids, cap: int, n_dest: int):
    """Pack routed slots by destination bucket.

    ids: (S,) destination bucket per routed slot. Returns (bucket, rank,
    keep, order): sorted slot order, per-slot rank within its bucket, and
    the keep mask (rank < cap)."""
    order = jnp.argsort(ids)                      # stable
    sorted_ids = ids[order]
    counts = jnp.bincount(sorted_ids, length=n_dest)
    starts = jnp.cumsum(counts) - counts
    rank = jnp.arange(ids.shape[0]) - starts[sorted_ids]
    keep = rank < cap
    return sorted_ids, jnp.where(keep, rank, 0), keep, order


def moe_ffn_ep_local(params: Params, x, spec: MoESpec, *, axis: str,
                     capacity_factor: float = 1.25,
                     return_aux: bool = True) -> Tuple[jax.Array, jax.Array]:
    """Per-shard body (inside shard_map, manual over `axis`).

    x: (B, L_local, D); params['w1'/'w3'/'w2'] carry the LOCAL experts on
    dim 0 (E/n each); params['router'] is replicated (D, E_global)."""
    n = jax.lax.axis_size(axis)
    b, l, d = x.shape
    e = spec.num_experts
    e_local = params["w1"].shape[0]
    assert e_local * n == e, (e_local, n, e)
    k = spec.top_k
    t = b * l
    xf = x.reshape(t, d)

    # ---- 1. local routing ----
    logits = xf.astype(jnp.float32) @ params["router"]          # (T, E)
    probs = jax.nn.softmax(logits, axis=-1)
    gate_vals, expert_ids = jax.lax.top_k(probs, k)             # (T, k)
    gate_vals = gate_vals / jnp.maximum(
        gate_vals.sum(-1, keepdims=True), 1e-9)

    # ---- 2. pack by destination shard ----
    cap = int(max(1, -(-t * k * capacity_factor // n)))
    flat_ids = expert_ids.reshape(-1)                           # (T*k,)
    dest = flat_ids // e_local
    sorted_dest, rank, keep, order = _group_by_dest(dest, cap, n)
    src_token = order // k
    send = jnp.zeros((n, cap, d), x.dtype).at[sorted_dest, rank].add(
        jnp.where(keep[:, None], xf[src_token], 0).astype(x.dtype))
    # local expert id rides along; -1 marks empty slots
    send_ids = jnp.full((n, cap), -1, jnp.int32).at[sorted_dest, rank].max(
        jnp.where(keep, flat_ids[order] % e_local, -1).astype(jnp.int32))

    # ---- 3. exchange: tokens travel to their experts' shard ----
    recv = jax.lax.all_to_all(send, axis, split_axis=0, concat_axis=0,
                              tiled=False)                      # (n, cap, D)
    recv_ids = jax.lax.all_to_all(send_ids, axis, split_axis=0,
                                  concat_axis=0, tiled=False)   # (n, cap)

    # ---- 4. local expert FFN (group received tokens by local expert) ----
    rt = n * cap
    rtok = recv.reshape(rt, d)
    rids = recv_ids.reshape(rt)
    cap2 = int(max(1, -(-rt // max(e_local, 1))) * 2)  # 2x slack, local only
    valid = rids >= 0
    # invalid slots go to a virtual bucket e_local: their scatter indices are
    # out of bounds for buf and get dropped (jax scatter OOB semantics), so
    # they can never exhaust a real expert's capacity
    sorted_e, rank2, keep2, order2 = _group_by_dest(
        jnp.where(valid, rids, e_local), cap2, e_local + 1)
    keep2 &= valid[order2]
    buf = jnp.zeros((e_local, cap2, d), x.dtype).at[sorted_e, rank2].add(
        jnp.where(keep2[:, None], rtok[order2], 0).astype(x.dtype))
    h = jax.nn.silu(jnp.einsum("ecd,edf->ecf", buf, params["w1"]))
    h = h * jnp.einsum("ecd,edf->ecf", buf, params["w3"])
    y_buf = jnp.einsum("ecf,efd->ecd", h, params["w2"])         # (El, C2, D)
    y = jnp.zeros((rt, d), jnp.float32).at[order2].add(
        jnp.where(keep2[:, None], y_buf[sorted_e, rank2], 0)
        .astype(jnp.float32))

    # ---- 5. return trip + gated combine ----
    y_back = jax.lax.all_to_all(y.reshape(n, cap, d).astype(x.dtype), axis,
                                split_axis=0, concat_axis=0, tiled=False)
    gathered = y_back[sorted_dest, rank]                        # (T*k, D)
    w = jnp.where(keep, gate_vals.reshape(-1)[order], 0.0)
    out = jnp.zeros((t, d), jnp.float32).at[src_token].add(
        gathered.astype(jnp.float32) * w[:, None])
    out = out.astype(x.dtype).reshape(b, l, d)

    if return_aux:
        frac = jnp.bincount(flat_ids, length=e).astype(jnp.float32) / (t * k)
        mean_p = jnp.mean(probs, axis=0)
        # frac and mean_p are per-token means: pmean each FACTOR (equal
        # shard sizes), then combine — pmean of the product would differ
        # from the single-pass statistic (product of means != mean of
        # products)
        frac = jax.lax.pmean(frac, axis)
        mean_p = jax.lax.pmean(mean_p, axis)
        return out, e * jnp.sum(frac * mean_p)
    return out, jnp.zeros((), jnp.float32)


def moe_ffn_ep(params: Params, x, spec: MoESpec, *, mesh: Mesh,
               axis: str = "model", capacity_factor: float = 1.25,
               return_aux: bool = True):
    """shard_map wrapper: x (B, L, D) with L sharded over `axis`; expert
    weights sharded on dim 0 over `axis`; router replicated. Partial-manual,
    so batch stays auto (DP on other axes composes)."""
    body = functools.partial(moe_ffn_ep_local, spec=spec, axis=axis,
                             capacity_factor=capacity_factor,
                             return_aux=return_aux)
    in_specs = ({"router": P(), "w1": P(axis), "w3": P(axis),
                 "w2": P(axis)},
                P(None, axis, None))
    fn = jax.shard_map(body, mesh=mesh, in_specs=in_specs,
                       out_specs=(P(None, axis, None), P()),
                       axis_names={axis}, check_vma=False)
    return fn({k: params[k] for k in ("router", "w1", "w3", "w2")}, x)


def ep_wire_bytes_per_device(t_local: int, top_k: int, d_model: int,
                             capacity_factor: float = 1.25,
                             bytes_per_el: int = 2) -> int:
    """Analytic all-to-all traffic per layer: there + back, local tokens
    only — independent of global batch (the napkin number §Perf checks)."""
    return int(2 * t_local * top_k * capacity_factor * d_model * bytes_per_el)
