"""Plain float32 reference of a Granite decoder whose every attention layer
is a causal window of `window` keys plus `num_global` sink tokens (the
configuration's `swat` group), written from the published description in
straightforward `jax.numpy`: no kernels, no cache, no batching, no code or
arrays of the program. Every matmul runs at HIGHEST precision.

Per layer, for hidden states x (T, d):
  h = rmsnorm(x) * (1 + attn_norm)
  q, k, v = h @ wq, h @ wk, h @ wv; rotary (split halves, theta) on q and k
  query i sees key j iff j <= i and (i - j <= window or j < num_global);
  scores scaled by head_dim**-0.5; grouped kv heads (hq / hkv queries each)
  x = x + attn @ wo
  h = rmsnorm(x) * (1 + ffn_norm)
  dense:   x = x + (silu(h @ w1) * (h @ w3)) @ w2
  experts: p = softmax(h @ router); the top-k experts by p, their p
           renormalised to sum 1, weight each expert's silu-gated FFN
logits = (rmsnorm(x) * (1 + final_norm)) @ embed.T

As in the program: no Granite 3.0 multipliers (embedding, attention,
residual, logits; the Granite Code models have none), and norm gains stored
as offsets from 1. A configuration file lists such departures under
`assumed`.

The control (`fp8=True`) is this reference in the nearest precision below
the configuration's bfloat16: every matmul weight (the router's too) rounded
to float8_e4m3 with one scale per output channel, and every matmul's input
activations (the router's input too) with one scale per row. The rest as
above.

The program has no bias terms; a configuration that states them
(`attention_bias`, `mlp_bias`) is refused.
"""
from __future__ import annotations

import functools
from typing import Tuple

import jax
import jax.numpy as jnp
import numpy as np

import cost

HI = jax.lax.Precision.HIGHEST
QBLOCK = 512        # query rows per attention block: bounds score memory
BUCKET = 1024       # sequences are right-padded to a multiple of this


def _dot(a, b):
    return jnp.dot(a, b, precision=HI, preferred_element_type=jnp.float32)


def _rms(x, gain, eps):
    x = x.astype(jnp.float32)
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * (1.0 + gain)


def _rope(x, pos, theta):
    d = x.shape[-1]
    inv = theta ** (-jnp.arange(0, d, 2, dtype=jnp.float32) / d)
    ang = pos[:, None].astype(jnp.float32) * inv[None, :]        # (T, d/2)
    sin, cos = jnp.sin(ang)[:, None], jnp.cos(ang)[:, None]       # (T,1,d/2)
    x1, x2 = jnp.split(x, 2, axis=-1)
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


def _fp8(a, axis):
    amax = jnp.max(jnp.abs(a), axis=axis, keepdims=True)
    scale = jnp.maximum(amax, 1e-30) / 448.0
    return (a / scale).astype(jnp.float8_e4m3fn).astype(jnp.float32) * scale


def _w(a, fp8):
    """A matmul weight in float32, rounded through fp8 for the control
    (one scale per output channel)."""
    a = a.astype(jnp.float32)
    return _fp8(a, -2) if fp8 else a


def _a(x, fp8):
    """A matmul's input activations, rounded through fp8 for the control
    (one scale per row)."""
    return _fp8(x, -1) if fp8 else x


def _attention(q, k, v, window, sinks):
    """q (T, hq, dh), k/v (T, hkv, dh) -> (T, hq*dh), in query blocks."""
    t, hq, dh = q.shape
    hkv = k.shape[1]
    grp = hq // hkv
    kj = jnp.arange(t)[None, :]
    kh = k.transpose(1, 0, 2)                                     # (hkv,T,dh)
    vh = v.transpose(1, 0, 2)

    def block(i0):
        qb = jax.lax.dynamic_slice_in_dim(q, i0, QBLOCK, 0)       # (B,hq,dh)
        qb = qb.reshape(QBLOCK, hkv, grp, dh).transpose(1, 2, 0, 3)
        s = jnp.einsum("hgid,hjd->hgij", qb, kh, precision=HI) * dh ** -0.5
        qi = (i0 + jnp.arange(QBLOCK))[:, None]
        mask = (kj <= qi) & ((qi - kj <= window) | (kj < sinks))
        s = jnp.where(mask[None, None], s, -jnp.inf)
        p = jax.nn.softmax(s, axis=-1)
        o = jnp.einsum("hgij,hjd->ihgd", p, vh, precision=HI)
        return o.reshape(QBLOCK, hq * dh)

    return jax.lax.map(block, jnp.arange(0, t, QBLOCK)).reshape(t, hq * dh)


@functools.partial(jax.jit, static_argnames=("dims", "fp8"))
def _layer(x, layers, i, *, dims, fp8):
    (hq, hkv, dh, e, k, w, g, theta, eps) = dims
    lw = {name: jax.lax.dynamic_index_in_dim(a, i, 0, keepdims=False)
          for name, a in layers.items()}
    t = x.shape[0]
    pos = jnp.arange(t)
    h = _a(_rms(x, lw["attn_norm"], eps), fp8)
    q = _dot(h, _w(lw["wq"], fp8)).reshape(t, hq, dh)
    kk = _dot(h, _w(lw["wk"], fp8)).reshape(t, hkv, dh)
    vv = _dot(h, _w(lw["wv"], fp8)).reshape(t, hkv, dh)
    q, kk = _rope(q, pos, theta), _rope(kk, pos, theta)
    x = x + _dot(_a(_attention(q, kk, vv, w, g), fp8), _w(lw["wo"], fp8))
    h = _rms(x, lw["ffn_norm"], eps)
    if e:
        probs = jax.nn.softmax(_dot(_a(h, fp8), _w(lw["router"], fp8)),
                               axis=-1)                           # (T, e)
        top, idx = jax.lax.top_k(probs, k)
        top = top / jnp.sum(top, -1, keepdims=True)
        gates = jnp.zeros_like(probs).at[jnp.arange(t)[:, None], idx].set(top)
        hq8 = _a(h, fp8)
        a = jnp.einsum("td,edf->etf", hq8, _w(lw["w1"], fp8), precision=HI)
        b = jnp.einsum("td,edf->etf", hq8, _w(lw["w3"], fp8), precision=HI)
        y = jnp.einsum("etf,efd->etd", _a(jax.nn.silu(a) * b, fp8),
                       _w(lw["w2"], fp8), precision=HI)
        x = x + jnp.einsum("te,etd->td", gates, y, precision=HI)
    else:
        hq8 = _a(h, fp8)
        a = _dot(hq8, _w(lw["w1"], fp8))
        b = _dot(hq8, _w(lw["w3"], fp8))
        x = x + _dot(_a(jax.nn.silu(a) * b, fp8), _w(lw["w2"], fp8))
    return x


@functools.partial(jax.jit, static_argnames=("eps", "fp8"))
def _head(x, final_norm, embed, sel, tok, *, eps, fp8):
    """Logits at the selected rows; (gap of `tok`, argmax, logit spread)."""
    h = _a(_rms(x[sel], final_norm, eps), fp8)
    logits = _dot(h, _w(embed, fp8).T)                          # (n, V)
    best = jnp.max(logits, -1)
    mine = jnp.take_along_axis(logits, tok[:, None], -1)[:, 0]
    return best - mine, jnp.argmax(logits, -1), jnp.std(logits, -1)


@jax.jit
def _embed(embed, tokens):
    return jnp.take(embed, tokens, axis=0).astype(jnp.float32)


def _dims(cfg: dict):
    if cfg.get("attention_bias") or cfg.get("mlp_bias"):
        raise ValueError("bias terms are not modelled")
    s = cost.dims(cfg)
    return (s["hq"], s["hkv"], s["dh"], s["e"], s["k"], s["w"], s["g"],
            float(cfg["rope_theta"]), float(cfg["rms_norm_eps"]))


def score(weights, cfg: dict, seq: np.ndarray, positions: np.ndarray,
          tokens: np.ndarray, fp8: bool = False
          ) -> Tuple[np.ndarray, np.ndarray, np.ndarray]:
    """Run the sequence `seq` once. For each row p of `positions` (a
    position whose next token was produced), return the gap by which
    `tokens[i]`'s logit lies below the best logit, the argmax token, and the
    spread (standard deviation) of that row's logits."""
    t = len(seq)
    tp = -(-t // BUCKET) * BUCKET
    toks = np.zeros((tp,), np.int32)
    toks[:t] = seq
    dims = _dims(cfg)
    x = _embed(weights["embed"], jnp.asarray(toks))
    n = cfg["num_hidden_layers"]
    for i in range(n):
        x = _layer(x, weights["layers"], jnp.int32(i), dims=dims, fp8=fp8)
    gap, arg, spread = _head(x, weights["final_norm"], weights["embed"],
                             jnp.asarray(positions, jnp.int32),
                             jnp.asarray(tokens, jnp.int32),
                             eps=float(cfg["rms_norm_eps"]), fp8=fp8)
    return np.asarray(gap), np.asarray(arg), np.asarray(spread)
