"""Composable model assembly: decoder LMs, hybrid (Jamba), MoE, enc-dec.

The stack is organized as `num_super_blocks` repetitions of
`cfg.layer_pattern` (a tuple of layer kinds), scanned with `jax.lax.scan` so
the HLO contains ONE super-block regardless of depth — essential for the
512-fake-device dry-run compile times and the natural seam for pipeline
parallelism / remat.

Layer kinds:
  attn / attn_moe     - GQA attention + (dense | MoE) FFN
  local_attn          - attention with cfg.local_attention spec (gemma2)
  mamba / mamba_moe   - Mamba2 mixer + optional (dense | MoE) FFN
  xattn               - self-attn + cross-attn + FFN (whisper decoder)

Three entry points per model: `loss_fn` (train), `prefill`, `decode_step`.

Serving programs (`prefill`, `prefill_chunk`, `decode_step`) carry
`jax.named_scope`s that a device trace reports with each operation:
`embed`, then per layer `attn` (norm through the residual add; children
`qkv` with `qk_norm`, `ring_write`, `attend` or, on a full layer,
`attend_full`, `out` with `gate`) and `mlp` or `moe` (norm through the
residual add; `moe` has `router`, `experts`, `shared`), then `unembed`.
Scopes are metadata: they change no value.

Trinity's (afmoe) block: a layer pattern that lists every layer of one
super-block (leading dense "attn" layers, then "attn_moe"), a window
schedule of windows and None (full layers, without rotary where
cfg.full_rope is off), QK norms and an output gate in the attention
(`qk_norm`, `attn_gate`), and sandwich norms (`sandwich_norm`: each
sub-block's output RMS-normed by post_norm1 / post_norm2 before its
residual add).
"""
from __future__ import annotations

import dataclasses
import functools
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp

from repro.core import layers as L
from repro.core import moe as M
from repro.core import ssm as S
from repro.core.types import AttentionSpec, ModelConfig
from repro.kernels import ops as kops
from repro.kernels.ref import put_layer, take_layer

Params = Dict[str, Any]


def _dtype(cfg: ModelConfig):
    return jnp.bfloat16 if cfg.dtype == "bfloat16" else jnp.float32


def attn_cfg(cfg: ModelConfig, kind: str, cross: bool = False,
             index: Optional[int] = None) -> L.AttentionLayerCfg:
    """index: position within cfg.layer_pattern; when cfg.window_schedule
    names a window there, it overrides this layer's attention spec (sparse
    specs keep num_global/softcap; dense specs become causal swat windows).
    Cache capacities follow the overridden spec, so scheduled layers
    allocate their own ring shapes. Full (unwindowed) layers carry rotary
    positions only where cfg.full_rope."""
    spec = cfg.local_attention if kind == "local_attn" else cfg.attention
    if cross:
        spec = AttentionSpec(kind="dense", causal=False)
    elif (index is not None and cfg.window_schedule is not None
          and cfg.window_schedule[index] is not None):
        w = cfg.window_schedule[index]
        if spec.is_sparse:
            spec = dataclasses.replace(spec, window=w)
        else:
            spec = AttentionSpec(kind="swat", window=w, causal=spec.causal,
                                 softcap=spec.softcap)
    return L.AttentionLayerCfg(
        d_model=cfg.d_model, num_heads=cfg.num_heads,
        num_kv_heads=cfg.num_kv_heads, head_dim=cfg.resolved_head_dim,
        spec=spec, qkv_bias=cfg.qkv_bias, rope_theta=cfg.rope_theta,
        use_rope=cfg.use_rope and (cfg.full_rope or spec.is_sparse),
        cross=cross, qk_norm=cfg.qk_norm and not cross,
        gate=cfg.attn_gate and not cross, norm_eps=cfg.norm_eps)


# ------------------------------------------------------------------ init ---

def _init_layer(key, cfg: ModelConfig, kind: str,
                index: Optional[int] = None) -> Params:
    dt = _dtype(cfg)
    ks = jax.random.split(key, 4)
    p: Params = {"norm1": L.init_rmsnorm(cfg.d_model)}
    if kind.startswith("mamba"):
        p["mixer"] = S.init_mamba(ks[0], cfg.d_model, cfg.ssm, dtype=dt)
    else:
        p["mixer"] = L.init_attention(ks[0], attn_cfg(cfg, kind, index=index),
                                      dtype=dt)
    if kind == "xattn":
        p["norm_x"] = L.init_rmsnorm(cfg.d_model)
        p["cross"] = L.init_attention(ks[1], attn_cfg(cfg, kind, cross=True),
                                      dtype=dt)
    if kind.endswith("_moe"):
        p["norm2"] = L.init_rmsnorm(cfg.d_model)
        p["moe"] = M.init_moe(ks[2], cfg.d_model, cfg.d_ff, cfg.moe, dtype=dt)
    elif cfg.d_ff > 0:
        p["norm2"] = L.init_rmsnorm(cfg.d_model)
        p["mlp"] = L.init_mlp(ks[3], cfg.d_model, cfg.d_ff, dtype=dt)
    if cfg.sandwich_norm:
        p["post_norm1"] = L.init_rmsnorm(cfg.d_model)
        if "norm2" in p:
            p["post_norm2"] = L.init_rmsnorm(cfg.d_model)
    return p


def _residual(p: Params, cfg: ModelConfig, norm: str, x, y):
    """x + y, with y RMS-normed by the layer's `norm` gain first where the
    layer has one (sandwich norm: post_norm1 after the mixer, post_norm2
    after the FFN)."""
    if norm in p:
        y = L.rmsnorm(p[norm], y, cfg.norm_eps)
    return x + y


def _init_super_block(key, cfg: ModelConfig, pattern) -> Params:
    keys = jax.random.split(key, len(pattern))
    return {f"l{i}": _init_layer(keys[i], cfg, kind, index=i)
            for i, kind in enumerate(pattern)}


def init_model(key, cfg: ModelConfig) -> Params:
    dt = _dtype(cfg)
    k_emb, k_blocks, k_head, k_enc = jax.random.split(key, 4)
    params: Params = {}
    if cfg.embed_inputs:
        params["embed"] = (jax.random.normal(k_emb,
                                             (cfg.vocab_size, cfg.d_model),
                                             jnp.float32) * 0.02).astype(dt)
    blk_keys = jax.random.split(k_blocks, cfg.num_super_blocks)
    params["blocks"] = jax.vmap(
        lambda k: _init_super_block(k, cfg, cfg.layer_pattern))(blk_keys)
    params["final_norm"] = L.init_rmsnorm(cfg.d_model)
    if not cfg.tie_embeddings:
        params["lm_head"] = (jax.random.normal(
            k_head, (cfg.d_model, cfg.vocab_size), jnp.float32) * 0.02
        ).astype(dt)
    if cfg.encoder_decoder:
        enc_keys = jax.random.split(k_enc, cfg.encoder_layers)
        params["enc_blocks"] = jax.vmap(
            lambda k: _init_super_block(k, cfg_encoder(cfg), ("attn",))
        )(enc_keys)
        params["enc_norm"] = L.init_rmsnorm(cfg.d_model)
    return params


@functools.lru_cache(maxsize=64)
def cfg_encoder(cfg: ModelConfig) -> ModelConfig:
    """Whisper encoder: bidirectional self-attention, no causality."""
    return dataclasses.replace(
        cfg, layer_pattern=("attn",), use_rope=False, window_schedule=None,
        attention=dataclasses.replace(cfg.attention, causal=False))


# --------------------------------------------------------------- forward ---

def _apply_layer(p: Params, cfg: ModelConfig, kind: str, x, *,
                 enc_out=None, impl: str, positions=None,
                 index: Optional[int] = None):
    h = L.rmsnorm(p["norm1"], x, cfg.norm_eps)
    if kind.startswith("mamba"):
        y = S.mamba_block(p["mixer"], h, cfg.ssm, chunk=cfg.ssm.chunk_size)
    else:
        y = L.attention_layer(p["mixer"], attn_cfg(cfg, kind, index=index),
                              h, positions=positions, impl=impl)
    x = _residual(p, cfg, "post_norm1", x, y)
    if kind == "xattn":
        h = L.rmsnorm(p["norm_x"], x, cfg.norm_eps)
        x = x + L.attention_layer(p["cross"], attn_cfg(cfg, kind, cross=True),
                                  h, kv_x=enc_out, impl=impl)
    aux = jnp.zeros((), jnp.float32)
    if "moe" in p:
        h = L.rmsnorm(p["norm2"], x, cfg.norm_eps)
        y, aux = M.moe_ffn(p["moe"], h, cfg.moe)
        x = _residual(p, cfg, "post_norm2", x, y)
    elif "mlp" in p:
        h = L.rmsnorm(p["norm2"], x, cfg.norm_eps)
        x = _residual(p, cfg, "post_norm2", x, L.mlp(p["mlp"], h))
    return x, aux


REMAT_POLICIES = {
    # recompute everything in the backward pass: minimum live memory,
    # maximum recompute bytes (the default at 1000-node scale where HBM is
    # the binding constraint)
    "nothing": jax.checkpoint_policies.nothing_saveable,
    # save matmul outputs (attention/FFN dots): ~2x checkpointed activation
    # footprint, removes the recompute of every heavy op from the bwd pass —
    # the memory-roofline lever for small models with HBM headroom
    # (EXPERIMENTS.md §Perf cell 3 it.2)
    "dots": jax.checkpoint_policies.checkpoint_dots_with_no_batch_dims,
}


def _stack_forward(blocks: Params, cfg: ModelConfig, x, pattern, *,
                   enc_out=None, impl: str, remat: bool,
                   act_sharding=None, unroll: bool = False,
                   remat_policy: str = "nothing"):
    def constrain(x):
        return L.with_activation_constraint(x, act_sharding)

    def block_fn(carry, blk_p):
        x, aux = carry
        for i, kind in enumerate(pattern):
            x, a = _apply_layer(blk_p[f"l{i}"], cfg, kind, x,
                                enc_out=enc_out, impl=impl, index=i)
            aux = aux + a
        return (constrain(x), aux), None

    if remat:
        block_fn = jax.checkpoint(block_fn,
                                  policy=REMAT_POLICIES[remat_policy])
    n_blocks = jax.tree.leaves(blocks)[0].shape[0]
    (x, aux), _ = jax.lax.scan(block_fn,
                               (constrain(x), jnp.zeros((), jnp.float32)),
                               blocks,
                               unroll=n_blocks if unroll else 1)
    return x, aux


def embed_tokens(params: Params, cfg: ModelConfig, batch: Dict[str, Any]):
    with jax.named_scope("embed"):
        if "embeddings" in batch:
            # modality-frontend stub (VLM patches / audio frames):
            # precomputed embeddings bypass the token table
            x = batch["embeddings"].astype(_dtype(cfg))
        elif cfg.embed_inputs:
            x = jnp.take(params["embed"], batch["tokens"], axis=0)
        else:
            raise ValueError("batch needs 'tokens' or 'embeddings'")
        if cfg.embed_scale:
            x = x * jnp.asarray(cfg.d_model ** 0.5, x.dtype)
        if not cfg.use_rope:  # sinusoidal absolute positions (whisper)
            x = x + L.sinusoidal_positions(x.shape[1], cfg.d_model
                                           ).astype(x.dtype)[None]
        return x


def _unembed(params: Params, cfg: ModelConfig, x):
    with jax.named_scope("unembed"):
        x = L.rmsnorm(params["final_norm"], x, cfg.norm_eps)
        head = (params["embed"].T if cfg.tie_embeddings
                else params["lm_head"])
        logits = jax.lax.dot_general(
            x, head, (((x.ndim - 1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
        return L.softcap(logits, cfg.final_softcap)


def _ffn_serving(p, cfg: ModelConfig, x):
    """The serving FFN sub-block, norm through the residual add, as scope
    `moe` (dense-dispatch experts) or `mlp`; no FFN passes x through."""
    if "moe" in p:
        with jax.named_scope("moe"):
            h = L.rmsnorm(p["norm2"], x, cfg.norm_eps)
            y, _ = M.moe_ffn(p["moe"], h, cfg.moe, dispatch="dense")
            return _residual(p, cfg, "post_norm2", x, y)
    if "mlp" in p:
        with jax.named_scope("mlp"):
            h = L.rmsnorm(p["norm2"], x, cfg.norm_eps)
            return _residual(p, cfg, "post_norm2", x, L.mlp(p["mlp"], h))
    return x


def encode(params: Params, cfg: ModelConfig, batch):
    """Whisper encoder over precomputed frame embeddings (stub frontend)."""
    x = batch["enc_embeddings"].astype(_dtype(cfg))
    x = x + L.sinusoidal_positions(x.shape[1], cfg.d_model
                                   ).astype(x.dtype)[None]
    ecfg = cfg_encoder(cfg)
    x, _ = _stack_forward(params["enc_blocks"], ecfg, x, ("attn",),
                          impl="xla", remat=False)
    return L.rmsnorm(params["enc_norm"], x, cfg.norm_eps)


def forward_logits(params: Params, cfg: ModelConfig, batch, *,
                   impl: str = "xla", remat: bool = True,
                   act_sharding=None, unroll: bool = False,
                   remat_policy: str = "nothing"):
    enc_out = encode(params, cfg, batch) if cfg.encoder_decoder else None
    x = embed_tokens(params, cfg, batch)
    x, aux = _stack_forward(params["blocks"], cfg, x, cfg.layer_pattern,
                            enc_out=enc_out, impl=impl, remat=remat,
                            act_sharding=act_sharding, unroll=unroll,
                            remat_policy=remat_policy)
    return _unembed(params, cfg, x), aux


def loss_fn(params: Params, cfg: ModelConfig, batch, *,
            impl: str = "xla", remat: bool = True,
            aux_weight: float = 0.01, act_sharding=None,
            unroll: bool = False, remat_policy: str = "nothing"):
    """Next-token cross entropy. batch["labels"]: (B, L) int32; positions
    with label < 0 are masked out."""
    logits, aux = forward_logits(params, cfg, batch, impl=impl, remat=remat,
                                 act_sharding=act_sharding, unroll=unroll,
                                 remat_policy=remat_policy)
    labels = batch["labels"]
    logits = logits[:, :-1].astype(jnp.float32)
    targets = labels[:, 1:]
    valid = targets >= 0
    tsafe = jnp.where(valid, targets, 0)
    # TP-safe cross entropy: no take_along_axis over the (model-sharded)
    # vocab axis — a dynamic gather there makes SPMD all-gather the full
    # logits (~67 GB/step for a 256k vocab; EXPERIMENTS.md §Perf it.2).
    # iota==label masking keeps every op vocab-partitioned; the only
    # collectives are the (B, L)-sized max/sum partial reductions.
    m = jax.lax.stop_gradient(jnp.max(logits, axis=-1, keepdims=True))
    z = logits - m
    lse = jnp.log(jnp.sum(jnp.exp(z), axis=-1))
    hit = (jax.lax.broadcasted_iota(jnp.int32, logits.shape, 2)
           == tsafe[..., None])
    picked = jnp.sum(jnp.where(hit, z, 0.0), axis=-1)
    nll = jnp.where(valid, lse - picked, 0.0)
    denom = jnp.maximum(jnp.sum(valid), 1)
    loss = jnp.sum(nll) / denom
    total = loss + aux_weight * aux
    return total, {"loss": loss, "aux_loss": aux,
                   "tokens": denom.astype(jnp.float32)}


# --------------------------------------------------------------- serving ---

def _layer_cache_init(cfg: ModelConfig, kind: str, batch: int, max_len: int,
                      enc_len: int = 0, lookahead: int = 0,
                      index: Optional[int] = None):
    dt = _dtype(cfg)
    if kind.startswith("mamba"):
        return S.init_mamba_cache(cfg.d_model, cfg.ssm, batch, dtype=dt)
    cache = L.init_kv_cache(attn_cfg(cfg, kind, index=index), batch, max_len,
                            dtype=dt, lookahead=lookahead)
    if kind == "xattn":
        shape = (batch, cfg.num_kv_heads, max(enc_len, 1),
                 cfg.resolved_head_dim)
        cache["xk"] = jnp.zeros(shape, dt)
        cache["xv"] = jnp.zeros(shape, dt)
    return cache


def init_caches(cfg: ModelConfig, batch: int, max_len: int,
                enc_len: int = 0, lookahead: int = 0) -> Params:
    """Stacked (num_super_blocks leading dim) decode caches. lookahead:
    extra ring rows per layer so a (lookahead+1)-token decode step never
    evicts an in-window token (`layers.cache_capacity`)."""
    def one(_):
        return {f"l{i}": _layer_cache_init(cfg, kind, batch, max_len,
                                           enc_len, lookahead, index=i)
                for i, kind in enumerate(cfg.layer_pattern)}
    caches = jax.vmap(one)(jnp.arange(cfg.num_super_blocks))
    return caches


def paged_layout(cfg: ModelConfig, max_len: int, lookahead: int = 0,
                 page: int = 0) -> Dict[int, Dict[str, int]]:
    """Block geometry per attention-bearing layer_pattern position: the
    host-side contract between `init_paged_caches` and the serving block
    allocator. Keys are pattern indices; values carry the page size, blocks
    per slot (nb), logical capacity, pinned-global count g and ring modulus
    — everything the allocator needs to map token positions to blocks."""
    page = page or L.PAGE_SIZE
    out: Dict[int, Dict[str, int]] = {}
    for i, kind in enumerate(cfg.layer_pattern):
        if kind.startswith("mamba"):
            continue
        acfg = attn_cfg(cfg, kind, index=i)
        cap = L.cache_capacity(acfg, max_len, lookahead)
        g = acfg.spec.num_global if acfg.spec.is_sparse else 0
        out[i] = {"page": page,
                  "nb": L.paged_num_blocks(acfg, max_len, lookahead, page),
                  "cap": cap, "g": g, "ring": cap - g}
    return out


def _layer_cache_init_paged(cfg: ModelConfig, kind: str, batch: int,
                            max_len: int, enc_len: int = 0,
                            lookahead: int = 0, index: Optional[int] = None,
                            shared_pool: bool = True):
    dt = _dtype(cfg)
    if kind.startswith("mamba"):
        return S.init_mamba_cache(cfg.d_model, cfg.ssm, batch, dtype=dt)
    cache = L.init_paged_kv_cache(attn_cfg(cfg, kind, index=index), batch,
                                  max_len, dtype=dt, lookahead=lookahead,
                                  shared_pool=shared_pool)
    if kind == "xattn":
        shape = (batch, cfg.num_kv_heads, max(enc_len, 1),
                 cfg.resolved_head_dim)
        cache["xk"] = jnp.zeros(shape, dt)
        cache["xv"] = jnp.zeros(shape, dt)
    return cache


def init_paged_caches(cfg: ModelConfig, batch: int, max_len: int,
                      enc_len: int = 0, lookahead: int = 0,
                      shared_pool: bool = True) -> Params:
    """Paged twin of `init_caches`: attention layers hold block pools +
    tables (`layers.init_paged_kv_cache`), mamba/xattn leaves are unchanged.
    Identity tables make a fresh paged cache gather-equal to a fresh
    contiguous one."""
    def one(_):
        return {f"l{i}": _layer_cache_init_paged(
                    cfg, kind, batch, max_len, enc_len, lookahead,
                    index=i, shared_pool=shared_pool)
                for i, kind in enumerate(cfg.layer_pattern)}
    return jax.vmap(one)(jnp.arange(cfg.num_super_blocks))


def _apply_layer_decode(p, cfg, kind, x, cache, *, layer, enc_out=None,
                        impl: str = "ref", lookahead: int = 0,
                        index: Optional[int] = None, mesh=None):
    """One layer of `decode_step`'s scan. cache: this pattern position's
    caches stacked over the scan (its carry); layer `layer` is read out of
    it and only what the step changes is written back."""
    mamba = kind.startswith("mamba")
    with jax.named_scope("mamba" if mamba else "attn"):
        h = L.rmsnorm(p["norm1"], x, cfg.norm_eps)
        if mamba:
            y, state = S.mamba_decode(
                p["mixer"], h,
                jax.tree.map(lambda c: take_layer(c, layer), cache), cfg.ssm)
            new_cache = jax.tree.map(lambda c, n: put_layer(c, layer, n),
                                     cache, state)
        else:
            y, new_cache = L.attention_decode(
                p["mixer"], attn_cfg(cfg, kind, index=index), h, cache,
                layer=layer, impl=impl, lookahead=lookahead, mesh=mesh)
        x = _residual(p, cfg, "post_norm1", x, y)
    if kind == "xattn":
        with jax.named_scope("xattn"):
            h = L.rmsnorm(p["norm_x"], x, cfg.norm_eps)
            ccfg = attn_cfg(cfg, kind, cross=True)
            q, _, _ = L._project_qkv(p["cross"], ccfg, h, h)
            xk = take_layer(cache["xk"], layer)
            out = kops.decode_attention(
                q, xk, take_layer(cache["xv"], layer),
                jnp.full((x.shape[0], 1, 1, 1), xk.shape[2], jnp.int32),
                ccfg.spec)
            out = out.transpose(0, 2, 1, 3).reshape(x.shape[0], x.shape[1],
                                                    -1)
            x = x + out @ p["cross"]["wo"]
    return _ffn_serving(p, cfg, x), new_cache


def decode_step(params: Params, cfg: ModelConfig, batch, caches, *,
                impl: str = "xla", unroll: bool = False,
                act_sharding=None, lookahead: int = 0, mesh=None):
    """T tokens for every sequence (usually T=1). batch:
    {"tokens": (B, T)} (or {"embeddings": (B, T, D)}). Per-slot cache
    steps: rows may sit at different positions (continuous batching).
    T > 1 is the speculative-decode verify primitive: the whole stack runs
    once for T draft tokens, each query masked to its own causal/window
    slice of the ring (attention-pattern configs only — mamba state updates
    are sequential), and needs caches allocated with lookahead >= T-1.
    impl="pallas" routes the cache attention through the fused swat_decode
    kernel (ring insert + attention in one pass); anything else uses the
    jnp reference. act_sharding: optional (B, T, D) sharding pinned at
    every super-block boundary — under a serving mesh this keeps the decode
    residual stream slot-sharded instead of letting SPMD replicate it
    between blocks. mesh: the serving mesh the caller's jit is partitioned
    over — the pallas kernel and the ref impl's row write then run per
    shard (`ops.decode_attention`).

    The layer scan carries the stacked caches: each layer reads its own
    out of the carry and writes back only what it changed. The ref impl
    writes a contiguous ring's new rows straight into the carry
    (`ops.ring_rows_in_place`), so a step moves its rows, not its rings;
    the pallas kernel and paged pools write the layer's whole ring.
    xattn's encoder memory is never written, mamba states and ring `step`s
    at their layer index.
    Returns (logits (B, T, V), new caches)."""
    x = embed_tokens(params, cfg, batch)
    assert x.shape[1] == 1 or all(
        not k.startswith("mamba") for k in cfg.layer_pattern), \
        "multi-token decode: mamba layers update state one token at a time"
    dec_impl = "pallas" if impl == "pallas" else "ref"

    def block_fn(carry, inp):
        x, caches = carry
        blk_p, layer = inp
        caches = dict(caches)
        for i, kind in enumerate(cfg.layer_pattern):
            x, caches[f"l{i}"] = _apply_layer_decode(
                blk_p[f"l{i}"], cfg, kind, x, caches[f"l{i}"], layer=layer,
                impl=dec_impl, lookahead=lookahead, index=i, mesh=mesh)
        return (L.with_activation_constraint(x, act_sharding), caches), None

    n = cfg.num_super_blocks
    (x, caches), _ = jax.lax.scan(
        block_fn, (x, caches), (params["blocks"], jnp.arange(n)),
        unroll=n if unroll else 1)
    return _unembed(params, cfg, x), caches


def prefill(params: Params, cfg: ModelConfig, batch, max_len: int, *,
            impl: str = "xla", unroll: bool = False, lengths=None,
            act_sharding=None, lookahead: int = 0):
    """Run the prompt, return (last-position logits, primed caches).

    Implemented as forward + cache extraction per layer: each attention layer
    re-projects K/V into its (ring) cache; mamba layers replay their final
    state. Prompt length L <= max_len.

    lengths: optional (B,) int32 real prompt lengths for a right-padded
    batched prefill — per-row cache steps, SSM states stopped at each row's
    length, and logits gathered at each row's last real token. Causality
    makes the pad tail inert for every valid position.

    act_sharding: optional (B, L, D) sharding pinned at super-block
    boundaries (serving-mesh prefill keeps rows batch-sharded)."""
    if lengths is not None:
        assert not cfg.encoder_decoder, "padded prefill: decoder-only"
    enc_out = encode(params, cfg, batch) if cfg.encoder_decoder else None
    x = embed_tokens(params, cfg, batch)
    l = x.shape[1]

    def block_fn(carry, blk_p):
        x, = carry
        new_caches = {}
        for i, kind in enumerate(cfg.layer_pattern):
            p = blk_p[f"l{i}"]
            mamba = kind.startswith("mamba")
            with jax.named_scope("mamba" if mamba else "attn"):
                h = L.rmsnorm(p["norm1"], x, cfg.norm_eps)
                if mamba:
                    y = S.mamba_block(p["mixer"], h, cfg.ssm,
                                      chunk=cfg.ssm.chunk_size)
                    cache = _mamba_prefill_cache(p["mixer"], h, cfg,
                                                 lengths=lengths)
                else:
                    acfg = attn_cfg(cfg, kind, index=i)
                    y = L.attention_layer(p["mixer"], acfg, h, impl=impl)
                    with jax.named_scope("ring_write"):
                        cache = L.prefill_kv_cache(
                            p["mixer"], acfg, h, max_len, lengths=lengths,
                            lookahead=lookahead)
                x = _residual(p, cfg, "post_norm1", x, y)
            if kind == "xattn":
                with jax.named_scope("xattn"):
                    h = L.rmsnorm(p["norm_x"], x, cfg.norm_eps)
                    ccfg = attn_cfg(cfg, kind, cross=True)
                    x = x + L.attention_layer(p["cross"], ccfg, h,
                                              kv_x=enc_out, impl=impl)
                    _, xk, xv = L._project_qkv(p["cross"], ccfg, enc_out,
                                               enc_out)
                cache = {**cache, "xk": xk, "xv": xv}
            x = _ffn_serving(p, cfg, x)
            new_caches[f"l{i}"] = cache
        return (L.with_activation_constraint(x, act_sharding),), new_caches

    (x,), caches = jax.lax.scan(
        block_fn, (x,), params["blocks"],
        unroll=cfg.num_super_blocks if unroll else 1)
    if lengths is None:
        last = x[:, -1:]
    else:
        idx = jnp.clip(jnp.asarray(lengths, jnp.int32) - 1, 0, l - 1)
        last = jnp.take_along_axis(
            x, jnp.broadcast_to(idx[:, None, None],
                                (x.shape[0], 1, x.shape[2])), axis=1)
    logits = _unembed(params, cfg, last)
    return logits, caches


def prefill_chunkable(cfg: ModelConfig) -> bool:
    """Whether `prefill_chunk` supports this config: rope attention-only
    patterns (mamba carries state between chunks we don't thread; xattn /
    sinusoidal-position configs take the single-shot path). The single
    source of truth for the engine's chunking decision."""
    return cfg.use_rope and all(
        not k.startswith("mamba") and k != "xattn"
        for k in cfg.layer_pattern)


def speculative_supported(cfg: ModelConfig) -> bool:
    """Whether the serving engine may run speculative decode on this
    config: every layer's decode state must be a ring KV cache whose
    `step` pointer can be rolled back after a rejected draft (mamba's
    recurrent state and xattn's encoder memory have no such rollback), and
    positions must be rotary so a (B, T) verify step is position-exact.
    The single source of truth for the engine's `speculative=` gate."""
    return prefill_chunkable(cfg)


def prefill_chunk(params: Params, cfg: ModelConfig, batch, caches, pos0,
                  lengths, act_sharding=None, lookahead: int = 0):
    """One lockstep chunk of a batched chunked prefill: run tokens
    [pos0, pos0+T) through the stack against the ring caches and append to
    them. Exact-band equal to single-shot `prefill`, but per-layer score
    memory is O(T * (cap + T)) — prefill VMEM is bounded by the chunk size,
    not the prompt length. Attention-only rope patterns (mamba/xattn configs
    take the single-shot path). pos0 may be traced: one compiled chunk
    function serves every chunk index. Returns (hidden states (B, T, D),
    new caches) — unembedding is the caller's job, which gathers the one
    last-real-token row per sequence first (a full-vocab projection of
    every prompt token would dwarf the chunking savings)."""
    assert prefill_chunkable(cfg), cfg.layer_pattern
    x = embed_tokens(params, cfg, batch)

    def block_fn(x, inp):
        blk_p, blk_cache = inp
        new_caches = {}
        for i, kind in enumerate(cfg.layer_pattern):
            p = blk_p[f"l{i}"]
            with jax.named_scope("attn"):
                h = L.rmsnorm(p["norm1"], x, cfg.norm_eps)
                y, nc = L.attention_prefill_chunk(
                    p["mixer"], attn_cfg(cfg, kind, index=i), h,
                    blk_cache[f"l{i}"], pos0, lengths, lookahead=lookahead)
                x = _residual(p, cfg, "post_norm1", x, y)
            x = _ffn_serving(p, cfg, x)
            new_caches[f"l{i}"] = nc
        return L.with_activation_constraint(x, act_sharding), new_caches

    x, new_caches = jax.lax.scan(block_fn, x, (params["blocks"], caches))
    return x, new_caches


def _mamba_prefill_cache(p, h, cfg: ModelConfig, lengths=None):
    """Final SSM + conv state after a full-sequence mamba pass. With
    `lengths`, each row's state is stopped at its own last real token: a
    zeroed dt beyond the length makes decay exp(0)=1 and update 0, so the
    cumulative scan freezes, and the conv window is gathered per row."""
    spec = cfg.ssm
    bsz, l, dm = h.shape
    di = spec.d_inner(dm)
    g, sdim = spec.num_groups, spec.state_dim
    zxbcdt = h @ p["in_proj"]
    _, xin, bc, dt = jnp.split(zxbcdt, [di, 2 * di, 2 * di + 2 * g * sdim],
                               -1)
    conv_in = jnp.concatenate([xin, bc], -1)
    kw = spec.conv_width
    if lengths is None:
        conv_state = conv_in[:, -(kw - 1):, :]
        if l < kw - 1:
            conv_state = jnp.pad(conv_in, ((0, 0), (kw - 1 - l, 0), (0, 0)))
    else:
        # per-row window [len-kw+1, len); zero-fill where it precedes t=0
        lens = jnp.asarray(lengths, jnp.int32)
        idx = lens[:, None] - (kw - 1) + jnp.arange(kw - 1)[None, :]
        gathered = jnp.take_along_axis(
            conv_in, jnp.broadcast_to(jnp.maximum(idx, 0)[:, :, None],
                                      (bsz, kw - 1, conv_in.shape[-1])),
            axis=1)
        conv_state = jnp.where((idx >= 0)[:, :, None], gathered, 0.0)
    conv_out = jax.nn.silu(S._causal_conv(conv_in, p["conv_w"], p["conv_b"]))
    xin2, b_mat, c_mat = jnp.split(conv_out, [di, di + g * sdim], -1)
    nh = spec.num_heads(dm)
    xh = xin2.reshape(bsz, l, nh, spec.head_dim)
    b_mat = b_mat.reshape(bsz, l, g, sdim)
    dtv = jax.nn.softplus(dt.astype(jnp.float32) + p["dt_bias"])
    if lengths is not None:
        # freeze the recurrence past each row's length: dt=0 -> decay 1,
        # update 0, so state(L) == state(len)
        tmask = (jnp.arange(l)[None, :]
                 < jnp.asarray(lengths, jnp.int32)[:, None])
        dtv = dtv * tmask[..., None]
    a = -jnp.exp(p["a_log"])
    # state = sum_j exp(sum_{k>j} dt_k a) dt_j B_j x_j  — one pass, fp32
    da = dtv * a
    cum = jnp.cumsum(da, axis=1)
    w = jnp.exp(cum[:, -1:, :] - cum)                      # (B,L,H)
    bm = jnp.repeat(b_mat, nh // g, axis=2)
    ssm = jnp.einsum("blh,blhs,blhp->bhps",
                     w * dtv, bm.astype(jnp.float32),
                     xh.astype(jnp.float32))
    return {"conv": conv_state.astype(_dtype(cfg)), "ssm": ssm}
