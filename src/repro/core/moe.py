"""Mixture-of-Experts FFN with top-k routing and sort-based dispatch.

Dispatch is the sort/scatter formulation (not the Switch dispatch-einsum,
whose T x E*C x D cost is quadratic in tokens): tokens are argsorted by
expert id, ranked within their expert, dropped beyond capacity, scattered
into an (E, C, D) buffer, run through batched expert FFNs, and combined back
weighted by their gate. Active FLOPs = 3 * 2 * k * T * D * F * cf — matching
the 6*N_active*D roofline convention for MoE.

EP: the expert axis of `w1/w2/w3` and the (E, C, D) buffers shard over
'model' (see distributed/sharding.py); XLA SPMD turns the scatter/gather
into an all-to-all.
"""
from __future__ import annotations

from typing import Any, Dict, Tuple

import jax
import jax.numpy as jnp

from repro.core.layers import _dense_init, init_mlp, mlp
from repro.core.types import MoESpec

Params = Dict[str, Any]


_EP_MESH = None   # set by the launcher (same pattern as ops.set_context_parallel)


def set_expert_parallel(mesh) -> None:
    """Register the mesh the 'ep' dispatch shard_maps over. Explicit module
    state: `jax.sharding.get_abstract_mesh()` does NOT reflect the legacy
    `with mesh:` context, so ambient discovery silently no-ops (learned the
    hard way — §Perf cell 1 it.3a)."""
    global _EP_MESH
    _EP_MESH = mesh


def _ambient_mesh_with(axis: str):
    """The registered (or ambient) mesh when it carries `axis`, else None."""
    if _EP_MESH is not None and axis in _EP_MESH.axis_names:
        return _EP_MESH
    import jax.sharding as jsh
    try:
        mesh = jsh.get_abstract_mesh()
        if mesh is not None and axis in mesh.axis_names:
            return mesh
    except Exception:
        pass
    return None


def _constrain(x, *spec):
    """Best-effort sharding constraint against the ambient mesh (no-op when
    there is no mesh or the axes don't exist — single-device tests)."""
    import jax.sharding as jsh
    try:
        mesh = jsh.get_abstract_mesh()
        if mesh is None or not mesh.axis_names:
            return x
        if any(a is not None and a not in mesh.axis_names
               for part in spec
               for a in ((part,) if isinstance(part, (str, type(None)))
                         else part)):
            return x
        return jax.lax.with_sharding_constraint(x, jsh.PartitionSpec(*spec))
    except Exception:
        return x


def init_moe(key, d_model: int, d_ff: int, spec: MoESpec,
             dtype=jnp.bfloat16) -> Params:
    """The router scores all `num_experts`; w1/w3/w2 hold the held experts
    only, each `expert_width` wide (d_ff where 0). Sigmoid routing adds its
    selection bias, a shared expert its own gated MLP."""
    ks = jax.random.split(key, 4)
    e, f = spec.held_count, spec.expert_width or d_ff
    p = {
        "router": _dense_init(ks[0], (d_model, spec.num_experts),
                              dtype=jnp.float32),
        "w1": _dense_init(ks[1], (e, d_model, f), dtype=dtype),
        "w3": _dense_init(ks[2], (e, d_model, f), dtype=dtype),
        "w2": _dense_init(ks[3], (e, f, d_model), dtype=dtype),
    }
    if spec.score == "sigmoid":
        p["bias"] = jnp.zeros((spec.num_experts,), jnp.float32)
    if spec.shared_width:
        p["shared"] = init_mlp(jax.random.fold_in(key, 4), d_model,
                               spec.shared_width, dtype=dtype)
    return p


def route(params: Params, xf, spec: MoESpec):
    """Routing of tokens xf (T, D) over all `num_experts`: (probs (T, E)
    for the load-balance loss, gate_vals (T, k), expert_ids (T, k)).

    softmax: the top-k of the softmax of the router logits, renormalised
    to sum 1. sigmoid: scores s = sigmoid(logits); the experts chosen are
    the top-k of s + bias (the bias steers selection only), weighted by s
    renormalised over the k chosen. Either is then scaled by route_scale.
    The sigmoid router runs at full float32 precision: its top-k sits on
    score gaps of about 1e-2, which a one-pass bf16 matmul can flip."""
    k = spec.top_k
    if spec.score == "sigmoid":
        logits = jnp.dot(xf.astype(jnp.float32), params["router"],
                         precision=jax.lax.Precision.HIGHEST)
        probs = jax.nn.sigmoid(logits)
        _, expert_ids = jax.lax.top_k(probs + params["bias"], k)
        gate_vals = jnp.take_along_axis(probs, expert_ids, axis=-1)
        gate_vals = gate_vals / (jnp.sum(gate_vals, -1, keepdims=True)
                                 + 1e-20)
    else:
        logits = (xf.astype(jnp.float32) @ params["router"])    # (T, E)
        probs = jax.nn.softmax(logits, axis=-1)
        gate_vals, expert_ids = jax.lax.top_k(probs, k)         # (T, k)
        gate_vals = gate_vals / jnp.maximum(
            jnp.sum(gate_vals, -1, keepdims=True), 1e-9)        # renormalize
    if spec.route_scale != 1.0:
        gate_vals = gate_vals * spec.route_scale
    return probs, gate_vals, expert_ids


def moe_ffn(params: Params, x, spec: MoESpec, *,
            capacity_factor: float = 1.25,
            return_aux: bool = True,
            dispatch: str = None) -> Tuple[jax.Array, jax.Array]:
    """x: (B, L, D) -> (B, L, D), aux load-balancing loss (scalar fp32).

    dispatch overrides spec.dispatch per call site. Serving (prefill /
    decode) passes "dense": capacity-based dispatch couples tokens through
    the shared capacity sort, so a row's output would depend on its
    batch-mates and padding — the tokens-stationary combine is exact and
    padding-invariant, which continuous batching requires. Only the dense
    dispatch computes a held share of the experts (`spec.held`); the
    others refuse one.

    Scopes `router`, `experts` and `shared` (a shared expert's MLP, added
    to the routed experts' output)."""
    b, l, d = x.shape
    e, k = spec.num_experts, spec.top_k
    t = b * l
    xf = x.reshape(t, d)
    dispatch = spec.dispatch if dispatch is None else dispatch
    if dispatch != "dense" and not spec.whole:
        raise ValueError(
            f"a held share of the experts ({spec.held_count} of {e}) runs "
            f"only under dense dispatch, not {dispatch!r}")

    with jax.named_scope("router"):
        probs, gate_vals, expert_ids = route(params, xf, spec)

    if dispatch == "dense":
        with jax.named_scope("experts"):
            out = _dense_combine(params, xf, gate_vals, expert_ids, spec)
        out = _add_shared(params, xf, out).reshape(b, l, d)
        if return_aux:
            flat_e = expert_ids.reshape(-1)
            frac = jnp.bincount(flat_e, length=e).astype(jnp.float32) / (t * k)
            aux = e * jnp.sum(frac * jnp.mean(probs, axis=0))
            return out, aux
        return out, jnp.zeros((), jnp.float32)

    if dispatch == "ep":
        mesh = _ambient_mesh_with("model")
        if mesh is not None and e % mesh.shape["model"] == 0:
            if spec.score != "softmax" or spec.shared_width:
                raise ValueError("the 'ep' dispatch routes by softmax "
                                 "and has no shared expert")
            from repro.core import moe_ep
            return moe_ep.moe_ffn_ep(params, x, spec, mesh=mesh,
                                     axis="model",
                                     capacity_factor=capacity_factor,
                                     return_aux=return_aux)
        # single-device / indivisible: fall through to the sort schedule

    # ---- sort-based dispatch ----
    cap = int(max(k, min(t, round(t * k * capacity_factor / e))))
    flat_e = expert_ids.reshape(-1)                             # (T*k,)
    order = jnp.argsort(flat_e)                                 # stable
    sorted_e = flat_e[order]
    # rank of each routed token within its expert
    counts = jnp.bincount(sorted_e, length=e)                   # (E,)
    starts = jnp.cumsum(counts) - counts                        # (E,)
    rank = jnp.arange(t * k) - starts[sorted_e]
    keep = rank < cap
    src_token = order // k                                      # token index
    rank_c = jnp.where(keep, rank, 0)

    buf = jnp.zeros((e, cap, d), x.dtype)
    buf = buf.at[sorted_e, rank_c].add(
        jnp.where(keep[:, None], xf[src_token], 0).astype(x.dtype))
    # EP: keep the dispatch buffers expert-sharded — without the constraint
    # XLA SPMD replicates the (E, C_global, D) buffers on every device
    # (10.5 TB/device collective traffic at train_4k; §Perf cell 1 it.1)
    buf = _constrain(buf, "model", None, None)

    # ---- batched expert FFN ----
    h = jax.nn.silu(jnp.einsum("ecd,edf->ecf", buf, params["w1"]))
    h = h * jnp.einsum("ecd,edf->ecf", buf, params["w3"])
    y_buf = jnp.einsum("ecf,efd->ecd", h, params["w2"])         # (E, C, D)
    y_buf = _constrain(y_buf, "model", None, None)

    # ---- combine ----
    gathered = y_buf[sorted_e, rank_c]                          # (T*k, D)
    w = jnp.where(keep, gate_vals.reshape(-1)[order], 0.0)
    out = jnp.zeros((t, d), jnp.float32).at[src_token].add(
        gathered.astype(jnp.float32) * w[:, None])
    out = _add_shared(params, xf, out.astype(x.dtype)).reshape(b, l, d)

    # Switch-style load-balance aux: E * sum_e f_e * P_e
    frac = jnp.bincount(flat_e, length=e).astype(jnp.float32) / (t * k)
    mean_p = jnp.mean(probs, axis=0)
    aux = e * jnp.sum(frac * mean_p)
    return (out, aux) if return_aux else (out, jnp.zeros((), jnp.float32))


def _dense_combine(params: Params, xf, gate_vals, expert_ids,
                   spec: MoESpec):
    """Tokens-stationary dispatch: every held expert runs on every (local)
    token, outputs combined by the sparse gate matrix. No sort, no scatter,
    no capacity, no all-to-all — the only collective left is the FSDP
    gather of the (small) expert weights. The (E, T_local, F) intermediate
    stays token-sharded under SPMD because xf's token dim is sharded. A
    held share takes its experts' columns of the gate matrix: what the
    other experts add is left to the devices that hold them."""
    t = xf.shape[0]
    full_gates = jnp.zeros((t, spec.num_experts), jnp.float32).at[
        jnp.arange(t)[:, None], expert_ids].set(gate_vals)      # (T, E)
    if not spec.whole:
        full_gates = full_gates[:, :spec.held_count]
    h = jax.nn.silu(jnp.einsum("td,edf->etf", xf, params["w1"]))
    h = h * jnp.einsum("td,edf->etf", xf, params["w3"])
    y = jnp.einsum("etf,efd->etd", h, params["w2"])             # (E, T, D)
    out = jnp.einsum("te,etd->td", full_gates.astype(y.dtype), y)
    return out.astype(xf.dtype)


def _add_shared(params: Params, xf, out):
    """The routed experts' output plus the shared expert's, if any."""
    if "shared" not in params:
        return out
    with jax.named_scope("shared"):
        return out + mlp(params["shared"], xf).astype(out.dtype)


def moe_ffn_dense_ref(params: Params, x, spec: MoESpec):
    """O(E) reference: compute every expert for every token, combine by the
    (renormalized) top-k gates. Oracle for tests (no capacity drops) of
    softmax routing over every expert."""
    assert spec.score == "softmax" and spec.whole and not spec.shared_width
    b, l, d = x.shape
    xf = x.reshape(-1, d)
    logits = xf.astype(jnp.float32) @ params["router"]
    probs = jax.nn.softmax(logits, -1)
    gate_vals, expert_ids = jax.lax.top_k(probs, spec.top_k)
    gate_vals = gate_vals / jnp.maximum(gate_vals.sum(-1, keepdims=True), 1e-9)
    full_gates = jnp.zeros_like(probs).at[
        jnp.arange(probs.shape[0])[:, None], expert_ids].set(gate_vals)
    h = jax.nn.silu(jnp.einsum("td,edf->etf", xf, params["w1"]))
    h = h * jnp.einsum("td,edf->etf", xf, params["w3"])
    y = jnp.einsum("etf,efd->etd", h, params["w2"])             # (E, T, D)
    out = jnp.einsum("te,etd->td", full_gates, y.astype(jnp.float32))
    return out.astype(x.dtype).reshape(b, l, d)
