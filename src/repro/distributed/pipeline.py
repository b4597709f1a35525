"""Pipeline parallelism: GPipe-style microbatching over the super-block seam.

The model is already `lax.scan`-over-super-blocks; pipeline parallelism
shards that leading super-block dim over a 'pipe' mesh axis and rotates
activations stage-to-stage with jax.lax.ppermute. The loop is written
forward-only — jax.grad transposes the ppermutes into the reverse pipeline,
so 1F1B-style backward scheduling falls out of autodiff rather than being
hand-scheduled.

shard_map runs in PARTIAL-MANUAL mode (axis_names={'pipe'}): the body is
explicit over the pipe axis but still SPMD-auto over data/model, so FSDP/TP/
SP sharding inside each stage keeps working unchanged — PP composes with the
rest of the mesh instead of replacing it.

Schedule (n stages, m microbatches, T = n + m - 1 ticks):
  tick t: stage 0 injects microbatch t (t < m); every stage applies its
  local super-blocks; outputs rotate +1; the last stage banks microbatch
  t-(n-1). Bubble fraction = (n-1)/T — reported by `bubble_fraction` and
  charged in the §Roofline pipeline analysis.

Numerical hygiene: stages compute on garbage during warmup/drain ticks (SPMD
runs the same program everywhere). Garbage is never *mixed into* results:
injection is a `where` on stage index, output banking is masked, the final
unembed sees zeros instead of drain garbage (zeros -> finite logits -> the
mask kills them; NaN would survive a `where`).
"""
from __future__ import annotations

import dataclasses
import functools
from typing import Any, Dict, Optional

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, PartitionSpec as P

from repro.core import model as Mod
from repro.core.types import ModelConfig


@dataclasses.dataclass(frozen=True)
class PipelineConfig:
    num_stages: int
    num_microbatches: int
    axis: str = "pipe"

    def __post_init__(self):
        assert self.num_microbatches >= self.num_stages, (
            "microbatches < stages leaves permanent bubbles")


def bubble_fraction(pcfg: PipelineConfig) -> float:
    n, m = pcfg.num_stages, pcfg.num_microbatches
    return (n - 1) / (n + m - 1)


def _stage_apply(blocks, cfg: ModelConfig, x, *, impl: str, remat: bool,
                 act_sharding=None):
    """One stage = this shard's super-blocks (leading dim already local)."""
    return Mod._stack_forward(blocks, cfg, x, cfg.layer_pattern,
                              impl=impl, remat=remat,
                              act_sharding=act_sharding)


def pipeline_loss_fn(params, cfg: ModelConfig, batch, pcfg: PipelineConfig,
                     *, stage_idx=None, impl: str = "xla",
                     remat: bool = True, aux_weight: float = 0.01,
                     act_sharding=None):
    """GPipe cross-entropy loss. Call inside shard_map (see make_* below).

    params['blocks'] leaves carry the LOCAL stage's super-blocks on dim 0;
    everything else (embed, head, norms) is pipe-replicated. batch tensors
    are pipe-replicated; only stage 0 reads them. stage_idx: (1,) int32 —
    the stage id travels AS DATA (pipe-sharded iota) because
    lax.axis_index under partial-manual shard_map lowers to a PartitionId
    op the SPMD partitioner rejects (same workaround as context_parallel)."""
    n = jax.lax.axis_size(pcfg.axis)
    stage = (jax.lax.axis_index(pcfg.axis) if stage_idx is None
             else stage_idx[0])
    m = pcfg.num_microbatches
    tokens, labels = batch["tokens"], batch["labels"]
    bsz, seq = tokens.shape
    assert bsz % m == 0, (bsz, m)
    mb = bsz // m
    tok_mb = tokens.reshape(m, mb, seq)

    def constrain(x):
        if act_sharding is not None:
            return jax.lax.with_sharding_constraint(x, act_sharding)
        return x

    dt = jnp.bfloat16 if cfg.dtype == "bfloat16" else jnp.float32
    buf = jnp.zeros((mb, seq, cfg.d_model), dt)
    outs = jnp.zeros((m, mb, seq, cfg.d_model), dt)
    aux_total = jnp.zeros((), jnp.float32)

    for t in range(m + n - 1):
        # stage 0 embeds & injects microbatch t; later stages use the buffer
        inj = Mod.embed_tokens(params, cfg, {"tokens": tok_mb[min(t, m - 1)]})
        x = jnp.where(stage == 0, inj.astype(dt), buf)
        x, aux = _stage_apply(params["blocks"], cfg, constrain(x), impl=impl,
                              remat=remat, act_sharding=act_sharding)
        # this stage works on microbatch t - stage; mask warmup/drain aux
        live = jnp.logical_and(t >= stage, t - stage < m)
        aux_total = aux_total + jnp.where(live, aux, 0.0)
        out_idx = t - (n - 1)
        if out_idx >= 0:
            keep = jnp.where(stage == n - 1, 1.0, 0.0).astype(x.dtype)
            outs = outs.at[out_idx].set(x * keep)
        if t < m + n - 2:
            buf = jax.lax.ppermute(
                x, pcfg.axis, [(i, i + 1) for i in range(n - 1)])

    # unembed + CE on the banked outputs (zeros on non-final stages -> finite
    # logits, masked below). Same TP-safe CE as Mod.loss_fn.
    x = outs.reshape(bsz, seq, cfg.d_model)
    logits = Mod._unembed(params, cfg, x)
    logits = logits[:, :-1].astype(jnp.float32)
    targets = labels.reshape(m, mb, seq).reshape(bsz, seq)[:, 1:]
    valid = targets >= 0
    tsafe = jnp.where(valid, targets, 0)
    mx = jax.lax.stop_gradient(jnp.max(logits, axis=-1, keepdims=True))
    z = logits - mx
    lse = jnp.log(jnp.sum(jnp.exp(z), axis=-1))
    hit = (jax.lax.broadcasted_iota(jnp.int32, logits.shape, 2)
           == tsafe[..., None])
    picked = jnp.sum(jnp.where(hit, z, 0.0), axis=-1)
    nll = jnp.where(valid, lse - picked, 0.0)
    denom = jnp.maximum(jnp.sum(valid), 1)
    ce = jnp.sum(nll) / denom
    is_last = jnp.where(stage == n - 1, 1.0, 0.0)
    loss = jax.lax.psum(ce * is_last, pcfg.axis)
    # aux is a per-token mean statistic: average over microbatches so PP
    # matches the single-pass loss (which sees the full batch once)
    aux_all = jax.lax.psum(aux_total, pcfg.axis) / m
    total = loss + aux_weight * aux_all
    return total, {"loss": loss, "aux_loss": aux_all,
                   "tokens": denom.astype(jnp.float32)}


def make_pipeline_loss(cfg: ModelConfig, pcfg: PipelineConfig, mesh: Mesh,
                       *, impl: str = "xla", remat: bool = True,
                       act_sharding=None):
    """shard_map-wrapped loss(params, batch) -> (loss, metrics).

    Partial-manual over the pipe axis only: params['blocks'] dim 0 is
    pipe-sharded, all else pipe-replicated; data/model axes stay auto so the
    in-stage FSDP/TP/SP sharding tables apply unchanged."""
    assert cfg.num_super_blocks % pcfg.num_stages == 0, (
        f"{cfg.num_super_blocks} super-blocks not divisible into "
        f"{pcfg.num_stages} stages")

    body = functools.partial(pipeline_loss_fn, cfg=cfg, pcfg=pcfg, impl=impl,
                             remat=remat, act_sharding=act_sharding)

    def loss(params, batch):
        in_specs = (
            {k: (jax.tree.map(lambda _: P(pcfg.axis), v)
                 if k == "blocks" else jax.tree.map(lambda _: P(), v))
             for k, v in params.items()},
            jax.tree.map(lambda _: P(), batch),
            P(pcfg.axis),
        )
        # partial-manual: pipe is manual, data/model stay auto
        fn = jax.shard_map(
            lambda p, b, s: body(p, batch=b, stage_idx=s),
            mesh=mesh, in_specs=in_specs,
            out_specs=(P(), {"loss": P(), "aux_loss": P(), "tokens": P()}),
            axis_names={pcfg.axis}, check_vma=False)
        # stage index as pipe-sharded data (see pipeline_loss_fn docstring)
        return fn(params, batch,
                  jnp.arange(pcfg.num_stages, dtype=jnp.int32))

    return loss


def make_pp_train_step(cfg: ModelConfig, opt_cfg, pcfg: PipelineConfig,
                       mesh: Mesh, *, impl: str = "xla",
                       act_sharding=None):
    """fwd + (autodiff-transposed) reverse pipeline + AdamW."""
    from repro.optim import adamw
    loss = make_pipeline_loss(cfg, pcfg, mesh, impl=impl,
                              act_sharding=act_sharding)

    def train_step(params, opt_state, batch):
        (l, metrics), grads = jax.value_and_grad(loss, has_aux=True)(
            params, batch)
        new_params, new_state, om = adamw.apply_updates(
            params, grads, opt_state, opt_cfg)
        return new_params, new_state, {**metrics, **om}

    return train_step
