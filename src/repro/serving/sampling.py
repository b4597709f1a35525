"""Per-slot token sampling for the serving engine.

One jitted function covers the whole batch: each slot carries its own
temperature (a traced (B,) vector, so mixing greedy and sampling requests
never retraces), greedy rows take argmax, sampling rows draw from the
temperature-scaled (optionally top-k-truncated) distribution. This is where
the seed engine's bug lived — `step()` passed a hard-coded 0.0 instead of
each slot's `Request.temperature`; the engine now threads the per-slot
vector through every prefill and decode step.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp


def finite_rows(logits):
    """(B,) bool: row is entirely finite (no NaN/Inf anywhere in its
    trailing axes). The decode scan's numerical guard: a poisoned row's
    argmax/categorical output is garbage, so the engine quarantines the
    slot instead of emitting it. Strictly row-wise (like every sampling op
    here) — the reduction runs over the local vocab/position axes only, so
    under a slot-sharded mesh it adds ZERO collectives, and healthy rows'
    tokens are bitwise unchanged by the check existing."""
    return jnp.all(jnp.isfinite(logits), axis=tuple(range(1, logits.ndim)))


def sample(key, logits, temperatures, top_k: int = 0, any_sampling=None):
    """Draw one token per row. logits: (B, V); temperatures: (B,) — rows
    with temperature <= 0 are greedy. top_k: static int, 0 disables.
    any_sampling: optional scalar bool, the precomputed `any(temps > 0)`
    predicate. Under a slot-sharded mesh the in-place reduction lowers to
    a pred[] all-reduce; a caller that already knows the answer (the
    engine stages slot temperatures from host state) passes it here and
    keeps the decode scan collective-free. Either way the chosen branch —
    and therefore every token — is identical.

    The categorical draw consumes the same randomness whatever the active
    mask or temperatures are, so a scan-decode loop and a stepwise loop that
    split keys identically produce identical tokens.

    All-greedy batches take a runtime `lax.cond` fast path: one argmax and
    none of the top-k / gumbel ops. Greedy rows argmax the RAW logits (the
    same value the slow path's final `where` picks), so the branch is
    bitwise-invisible — it exists because on small models the sampling op
    chain costs as much as the forward pass it follows, and the speculative
    verify runs it at every one of T positions.

    Its operations carry the named scope `sample`."""
    with jax.named_scope("sample"):
        logits = logits.astype(jnp.float32)
        temperatures = jnp.asarray(temperatures, jnp.float32)

        def greedy_only(lg):
            return jnp.argmax(lg, axis=-1).astype(jnp.int32)

        def full(lg):
            greedy = jnp.argmax(lg, axis=-1).astype(jnp.int32)
            if top_k and top_k < lg.shape[-1]:
                kth = jax.lax.top_k(lg, top_k)[0][:, -1:]
                lg = jnp.where(lg < kth, -jnp.inf, lg)
            scaled = lg / jnp.maximum(temperatures[:, None], 1e-6)
            drawn = jax.random.categorical(key, scaled, axis=-1).astype(
                jnp.int32)
            return jnp.where(temperatures > 0, drawn, greedy)

        if any_sampling is None:
            any_sampling = jnp.any(temperatures > 0)
        return jax.lax.cond(any_sampling, full, greedy_only, logits)
