"""Plain float32 building blocks of the benchmark's references, written in
straightforward `jax.numpy`: no kernels, no cache, no batching, no code or
arrays of the program. Every matmul runs at HIGHEST precision. Each
architecture module (`arch/<name>.py`) composes its `score` from these.

The control (`fp8=True` in a module's `score`) is a reference in the
nearest precision below the configurations' bfloat16: every matmul weight
rounded to float8_e4m3 with one scale per output channel (`w`), and every
matmul's input activations with one scale per row (`a`).
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np

HI = jax.lax.Precision.HIGHEST
QBLOCK = 512        # query rows per attention block: bounds score memory
BUCKET = 1024       # sequences are right-padded to a multiple of this


def dot(a, b):
    return jnp.dot(a, b, precision=HI, preferred_element_type=jnp.float32)


def rms(x, gain, eps):
    """RMSNorm with the gain stored as an offset from 1."""
    x = x.astype(jnp.float32)
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * (1.0 + gain)


def rope(x, pos, theta):
    """Rotary embedding on split halves; x (T, heads, d)."""
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


def w(a, fp8):
    """A matmul weight in float32, rounded through fp8 for the control
    (one scale per output channel)."""
    a = a.astype(jnp.float32)
    return _fp8(a, -2) if fp8 else a


def a(x, fp8):
    """A matmul's input activations, rounded through fp8 for the control
    (one scale per row)."""
    return _fp8(x, -1) if fp8 else x


def attention(q, k, v, window: Optional[int], sinks: int):
    """q (T, hq, dh), k/v (T, hkv, dh) -> (T, hq*dh), in query blocks.
    Query i sees key j iff j <= i and (i - j <= window or j < sinks);
    window None sees every earlier key. Scores scaled by dh**-0.5; grouped
    kv heads (hq / hkv queries each)."""
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
        mask = kj <= qi
        if window is not None:
            mask = mask & ((qi - kj <= window) | (kj < sinks))
        s = jnp.where(mask[None, None], s, -jnp.inf)
        p = jax.nn.softmax(s, axis=-1)
        o = jnp.einsum("hgij,hjd->ihgd", p, vh, precision=HI)
        return o.reshape(QBLOCK, hq * dh)

    return jax.lax.map(block, jnp.arange(0, t, QBLOCK)).reshape(t, hq * dh)


def router_gap(logits, k: int):
    """Per row, how far the k-th largest router logit lies above the
    (k+1)-th: within a margin of rounding, the choice of experts is
    undecided. Infinite where every expert is chosen."""
    if k >= logits.shape[-1]:
        return jnp.full(logits.shape[:-1], jnp.inf, jnp.float32)
    top, _ = jax.lax.top_k(logits, k + 1)
    return top[..., k - 1] - top[..., k]


def pad(seq: np.ndarray) -> np.ndarray:
    """The token ids right-padded with 0 to a multiple of BUCKET."""
    t = len(seq)
    toks = np.zeros((-(-t // BUCKET) * BUCKET,), np.int32)
    toks[:t] = seq
    return toks


@jax.jit
def embed(table, tokens):
    return jnp.take(table, tokens, axis=0).astype(jnp.float32)


@functools.partial(jax.jit, static_argnames=("eps", "fp8", "tied"))
def head(x, gain, table, sel, tok, *, eps, fp8, tied):
    """Logits at the selected rows of the final hidden states, through the
    final norm and the output head: `table` is (vocab, d) when `tied` to
    the embedding, else (d, vocab). Returns the gap by which `tok`'s logit
    lies below the best, the argmax, and the logits' spread (std)."""
    h = a(rms(x[sel], gain, eps), fp8)
    out = w(table, fp8)
    logits = dot(h, out.T if tied else out)                     # (n, V)
    best = jnp.max(logits, -1)
    mine = jnp.take_along_axis(logits, tok[:, None], -1)[:, 0]
    return best - mine, jnp.argmax(logits, -1), jnp.std(logits, -1)


def decisive(gaps, positions, margin: Optional[float]) -> np.ndarray:
    """Per position, whether every router's k-th logit lies more than
    `margin` above its (k+1)-th; `gaps` (T,) is the least such gap over the
    expert layers (None where there are none). No margin or no expert
    layer: every position is decisive."""
    if margin is None or gaps is None:
        return np.ones(len(positions), bool)
    return np.asarray(gaps)[np.asarray(positions)] > margin
