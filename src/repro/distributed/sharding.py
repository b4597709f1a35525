"""Sharding rules: FSDP + TP + EP + SP with divisibility fallback.

Every parameter / cache / batch leaf gets an ordered list of
(dim, axes-preference) rules by *role* (derived from its pytree path). Rules
are applied greedily: an axis is used only if the dim is divisible by it and
the axis is not already used by an earlier rule on the same leaf — otherwise
the next preference (or replication) applies. This is what lets one rule
table drive vocab sizes like 50280 (not 16-divisible -> falls back), kv_heads
8 < model=16 (falls back to sharding the cache's sequence dim => flash-decode
style sequence-split), and batch=1 long-context decode (shards the KV
sequence axis instead of batch).

Conventions:
  params:  TP on the contraction-adjacent dim over 'model'
           (column-parallel in-proj, row-parallel out-proj),
           FSDP over 'data' on another dim, experts over 'model' (EP).
  batch:   leading dim over ('pod','data').
  caches:  batch -> ('pod','data'), heads -> 'model', else seq -> 'model'.
  activations (training): batch -> ('pod','data'), sequence -> 'model' (SP)
           at super-block boundaries (layers.with_activation_constraint).
"""
from __future__ import annotations

import re
from typing import Any, List, Optional, Sequence, Tuple

import jax
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

# (regex on path, [(dim, (axes preference tuples...)), ...])
# dim indices are for the UNSTACKED leaf; stacked block leaves (leading
# super-block dim) are shifted automatically.
_PARAM_RULES: List[Tuple[str, List[Tuple[int, Sequence[Any]]]]] = [
    # order matters: specific (moe/...) before generic — first match wins
    (r"moe/(w1|w3)$",         [(0, ("model",)), (2, ("data",))]),
    (r"moe/w2$",              [(0, ("model",)), (1, ("data",))]),
    (r"embed$",               [(0, ("model", "data")), (1, ("data",))]),
    (r"lm_head$",             [(1, ("model", "data")), (0, ("data",))]),
    # the attention's output gate wg (d, hq*dh) is column-parallel like wq;
    # a shared expert (moe/shared/w1|w3|w2) is a dense MLP, so it takes the
    # generic rules, not the expert-parallel ones above
    (r"(wq|wk|wv|wg|w1|w3)$", [(1, ("model",)), (0, ("data",))]),
    (r"(wo|w2|out_proj)$",    [(0, ("model",)), (1, ("data",))]),
    (r"in_proj$",             [(1, ("model",)), (0, ("data",))]),
    (r"router$",              [(0, ("data",))]),
    (r"conv_w$",              [(1, ("model",))]),
    (r"(bq|bk|bv|conv_b)$",   [(0, ("model",))]),
    # norms (q_norm/k_norm over head_dim, post_norm1/post_norm2), the
    # router's selection bias moe/bias, a_log, d_skip, dt_bias, scalars:
    # replicated (no rule)
]

_CACHE_RULES: List[Tuple[str, List[Tuple[int, Sequence[Any]]]]] = [
    (r"(^|/)(k|v|xk|xv)$", [(0, (("pod", "data"), "data")),
                            (1, ("model",)),
                            (2, ("model", "data", ("model", "data")))]),
    # paged pool (local-id mode): (slots, nb+1, H, page, D) — slot dim rides
    # the batch axes, heads ride 'model'; page rows must never split
    (r"(^|/)(pk|pv)$",     [(0, (("pod", "data"), "data")),
                            (2, ("model",))]),
    (r"(^|/)table$",       [(0, (("pod", "data"), "data"))]),
    (r"conv$",             [(0, (("pod", "data"), "data")),
                            (2, ("model",))]),
    (r"ssm$",              [(0, (("pod", "data"), "data")),
                            (1, ("model",)),
                            (3, ("model",))]),
    # per-slot ring write pointer: MUST ride the same slot axis as the K/V
    # batch dim — a replicated `step` under a slot-sharded cache makes every
    # ring insert a cross-shard broadcast and desyncs the per-slot rotation
    (r"(^|/)step$",        [(0, (("pod", "data"), "data"))]),
]

# MoE sharded over 'model': expert dim of the dispatch buffers
_EXPERT_RULE = [(0, ("model",))]

# ---- "cp" profile: no TP — both mesh axes do FSDP (ZeRO-3 2D), compute is
# sequence-sharded everywhere and window attention runs halo-exchange context
# parallelism (kernels/ops.set_context_parallel). Weights are gathered on
# use (prefetch overlaps under async all-gather) instead of being
# matmul-partitioned; activations never all-gather. MoE keeps EP.
_PARAM_RULES_CP: List[Tuple[str, List[Tuple[int, Sequence[Any]]]]] = [
    (r"moe/(w1|w3|w2)$",      [(0, ("model",)), (1, ("data",))]),
    (r"embed$",               [(0, (("data", "model"), "data", "model")),
                               (1, ("data",))]),
    (r"lm_head$",             [(1, (("data", "model"), "data", "model")),
                               (0, ("data",))]),
    (r"(wq|wk|wv|wg|w1|w3|wo|w2|out_proj|in_proj)$",
                              [(0, (("data", "model"), "data")),
                               (1, (("data", "model"), "data"))]),
    (r"router$",              [(0, ("data",))]),
    (r"conv_w$",              [(1, (("data", "model"), "data"))]),
    (r"(bq|bk|bv|conv_b)$",   [(0, (("data", "model"), "data"))]),
]

# "fsdp": same 2D-FSDP parameter placement as "cp" but compute stays
# batch-parallel (no CP attention, activations batch-sharded over BOTH mesh
# axes). The right profile for small-model big-batch training cells where
# Megatron TP+SP is pure collective overhead (§Perf cell 3).
_PROFILES = {"tp": None, "cp": _PARAM_RULES_CP, "fsdp": _PARAM_RULES_CP}


def _path_str(path) -> str:
    parts = []
    for pk in path:
        if isinstance(pk, jax.tree_util.DictKey):
            parts.append(str(pk.key))
        elif isinstance(pk, jax.tree_util.GetAttrKey):
            parts.append(pk.name)
        else:
            parts.append(str(pk))
    return "/".join(parts)


def _axis_size(mesh: Mesh, axes) -> int:
    if isinstance(axes, str):
        axes = (axes,)
    size = 1
    for a in axes:
        size *= mesh.shape[a]
    return size


def _spec_for(shape: Tuple[int, ...], rules, mesh: Mesh,
              shift: int = 0) -> P:
    assignment: List[Optional[Any]] = [None] * len(shape)
    used: set = set()
    for dim, prefs in rules:
        d = dim + shift
        if d >= len(shape):
            continue
        for axes in prefs:
            names = (axes,) if isinstance(axes, str) else tuple(axes)
            if any(n not in mesh.axis_names for n in names):
                continue
            if any(n in used for n in names):
                continue
            if shape[d] % _axis_size(mesh, names) != 0:
                continue
            assignment[d] = axes if isinstance(axes, str) else tuple(axes)
            used.update(names)
            break
    return P(*assignment)


def _match_rules(path: str, tables) -> Optional[List]:
    for pattern, rules in tables:
        if re.search(pattern, path):
            return rules
    return None


def param_sharding(shapes, mesh: Mesh, profile: str = "tp"):
    """shapes: pytree of ShapeDtypeStruct (from jax.eval_shape(init_model)).
    Returns matching pytree of NamedSharding. profile: 'tp' (Megatron
    TP+FSDP, default) or 'cp' (2D-FSDP, for context-parallel compute)."""
    tables = _PROFILES.get(profile) or _PARAM_RULES

    def leaf(path, x):
        p = _path_str(path)
        shift = 1 if re.match(r"(blocks|enc_blocks)(/|$)", p) else 0
        rules = _match_rules(p, tables)
        spec = _spec_for(x.shape, rules, mesh, shift) if rules else P()
        if (shift and "pipe" in mesh.axis_names
                and x.shape[0] % mesh.shape["pipe"] == 0):
            # pipeline meshes shard the stacked super-block dim over stages
            # (rules are shifted, so dim 0 is always free here)
            assn = list(spec) + [None] * (len(x.shape) - len(spec))
            assn[0] = "pipe"
            spec = P(*assn)
        return NamedSharding(mesh, spec)
    return jax.tree_util.tree_map_with_path(leaf, shapes)


def _strip_batch_axes(spec: P) -> P:
    out = []
    for entry in spec:
        if entry is None:
            out.append(None)
            continue
        axes = entry if isinstance(entry, tuple) else (entry,)
        kept = tuple(a for a in axes if a not in ("data", "pod"))
        out.append(kept[0] if len(kept) == 1 else (kept or None))
    while out and out[-1] is None:
        out.pop()
    return P(*out)


def param_sharding_serving(shapes, mesh: Mesh, profile: str = "tp"):
    """Inference parameter placement: TP over 'model' only — the batch
    axes ('data'/'pod') REPLICATE the weights instead of FSDP-sharding
    them. Training's data-axis shard is a memory optimization paid for
    with an all-gather per use; inside the decode scan that puts a weight
    gather (or a row-parallel partial-sum all-reduce) in every step of the
    hot path, which breaks the slot-parallel collective-free contract the
    static analyzer enforces."""
    tree = param_sharding(shapes, mesh, profile)
    return jax.tree.map(
        lambda s: NamedSharding(mesh, _strip_batch_axes(s.spec)), tree)


def cache_sharding(shapes, mesh: Mesh):
    """Caches are stacked over super-blocks (leading dim) — shift always 1."""
    def leaf(path, x):
        p = _path_str(path)
        rules = _match_rules(p, _CACHE_RULES)
        spec = _spec_for(x.shape, rules, mesh, shift=1) if rules else P()
        return NamedSharding(mesh, spec)
    return jax.tree_util.tree_map_with_path(leaf, shapes)


def batch_sharding(shapes, mesh: Mesh, profile: str = "tp"):
    """Leading dim over ('pod','data') when divisible, else replicate.
    fsdp profile: over ('pod','data','model') — one sequence per chip."""
    baxes = tuple(a for a in ("pod", "data") if a in mesh.axis_names)

    def leaf(x):
        if profile == "fsdp":
            rules = [(0, (baxes + ("model",), baxes, "data"))]
        else:
            rules = [(0, (baxes, "data"))]
        return NamedSharding(mesh, _spec_for(x.shape, rules, mesh))
    return jax.tree_util.tree_map(leaf, shapes)


def activation_spec(mesh: Mesh, sequence_parallel: bool = True,
                    profile: str = "tp") -> P:
    """(B, L, D) activations at super-block boundaries.

    tp/cp : batch over ('pod','data'); sequence over 'model' (Megatron SP /
            the layout context-parallel attention consumes directly).
    fsdp  : batch over ('pod','data','model') — every chip holds whole
            sequences; no sequence collectives at all."""
    baxes = tuple(a for a in ("pod", "data") if a in mesh.axis_names)
    if profile == "fsdp":
        return P(baxes + ("model",), None, None)
    return P(baxes, "model" if sequence_parallel else None, None)


def decode_batch_sharding(shapes, mesh: Mesh, slots: int,
                          slot_dim: int = 0):
    """Serving decode-state shardings: any leaf whose `slot_dim` equals the
    engine's slot count rides the slot axis over ('pod','data') (divisibility
    permitting); everything else (RNG keys, scalars) replicates. This is the
    batch analogue of cache_sharding for the per-slot host vectors the
    engine threads through `_Compiled` — `step`/`slot_last`/`slot_budget`/
    `slot_temp`/`active` — and for (slots, ...) token/logit blocks.

    slot_dim is EXPLICIT (no shape sniffing): the scan's stacked per-step
    outputs are (T, slots) and pass slot_dim=1 — when T happens to equal
    the slot count, guessing the dim would shard the time axis and force a
    cross-device relayout of every decode block's output."""
    baxes = tuple(a for a in ("pod", "data") if a in mesh.axis_names)
    rules = [(0, (baxes, "data"))]

    def leaf(x):
        shape = tuple(x.shape)
        if len(shape) > slot_dim and shape[slot_dim] == slots:
            return NamedSharding(mesh,
                                 _spec_for(shape, rules, mesh,
                                           shift=slot_dim))
        return NamedSharding(mesh, P())
    return jax.tree_util.tree_map(leaf, shapes)


def decode_kernel_spec(shape, mesh: Mesh) -> P:
    """Spec of a (B, H, W, D) cache inside the decode kernel's shard_map:
    the cache rule's slot and head placements, never the ring axis — the
    kernel's softmax runs over the whole ring, so a W shard would need a
    cross-shard merge. Where the cache rule split W (kv heads fewer than
    the model axis), XLA gathers it at the shard_map boundary."""
    return _spec_for(tuple(shape), _CACHE_RULES[0][1][:2], mesh)


def ring_spec(shape, mesh: Mesh) -> P:
    """Spec of a (B, H, W, D) K/V ring as the cache rule places it: slots,
    then kv heads, then, where kv heads or slots do not divide their axes,
    the ring's rows W."""
    return _spec_for(tuple(shape), _CACHE_RULES[0][1], mesh)


def replicated(mesh: Mesh):
    return NamedSharding(mesh, P())
