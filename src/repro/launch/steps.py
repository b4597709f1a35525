"""Step functions: the units the dry-run lowers and the trainers run.

  train_step  : fwd + bwd + AdamW update (+ optional int8 EF compression)
  prefill_step: prompt -> (last logits, primed caches)
  serve_step  : one decode token against the caches
"""
from __future__ import annotations

import functools
from typing import Any, Callable, Dict, Optional

import jax
import jax.numpy as jnp

from repro.core import model as Mod
from repro.core.types import ModelConfig
from repro.optim import adamw, compress


def make_train_step(cfg: ModelConfig, opt_cfg: adamw.AdamWConfig, *,
                    impl: str = "xla", act_sharding=None,
                    grad_compression: bool = False,
                    donate: bool = True, unroll: bool = False,
                    remat_policy: str = "nothing",
                    remat: bool = True) -> Callable:
    def train_step(params, opt_state, batch, residual=None):
        (loss, metrics), grads = jax.value_and_grad(
            Mod.loss_fn, has_aux=True)(params, cfg, batch, impl=impl,
                                       act_sharding=act_sharding,
                                       unroll=unroll, remat=remat,
                                       remat_policy=remat_policy)
        if grad_compression:
            grads, residual = compress.compress_decompress(grads, residual)
        new_params, new_state, om = adamw.apply_updates(
            params, grads, opt_state, opt_cfg)
        metrics = {**metrics, **om}
        if grad_compression:
            return new_params, new_state, metrics, residual
        return new_params, new_state, metrics
    return train_step


def make_prefill_step(cfg: ModelConfig, max_len: int, *,
                      impl: str = "xla", unroll: bool = False) -> Callable:
    def prefill_step(params, batch):
        return Mod.prefill(params, cfg, batch, max_len=max_len, impl=impl,
                           unroll=unroll)
    return prefill_step


def make_serve_step(cfg: ModelConfig, *, impl: str = "xla",
                    unroll: bool = False, mesh=None) -> Callable:
    """mesh: the mesh the caches are sharded over; the pallas kernel and
    the ref impl's row write run per shard of it (`ops.decode_attention`)."""
    def serve_step(params, caches, batch):
        return Mod.decode_step(params, cfg, batch, caches, impl=impl,
                               unroll=unroll, mesh=mesh)
    return serve_step


def make_eval_step(cfg: ModelConfig, *, impl: str = "xla") -> Callable:
    def eval_step(params, batch):
        loss, metrics = Mod.loss_fn(params, cfg, batch, impl=impl,
                                    remat=False)
        return metrics
    return eval_step
