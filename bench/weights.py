"""Seeded random weights for a configuration, made on the device in one
jitted call, in the types they are served in. The layout is the
architecture module's (`shapes(cfg)`, arch/__init__.py): top-level leaves
in order, and stacks of layers, each leaf (name, shape, dtype, std).

Each leaf is normal with its standard deviation, drawn in its own dtype.
The key from the seed splits into one key per top-level leaf, in order, and
one for the stacks, which splits again into one key per layer over all
stacks in order; a layer's leaves, sorted by name, take that key folded
with their index.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

import arch


def jax_seed(seed: int) -> int:
    """A non-negative 31-bit key from any whole-number seed."""
    return int(np.random.SeedSequence(int(seed)).generate_state(1)[0]) & 0x7FFFFFFF


def _normal(key, shape, dtype, std):
    return (jax.random.normal(key, shape, dtype)
            * jnp.asarray(std, dtype)).astype(dtype)


@functools.partial(jax.jit, static_argnums=(1,))
def _make(key, spec):
    top, stacks = spec
    keys = jax.random.split(key, len(top) + 1)
    out = {name: _normal(k, shape, dtype, std)
           for (name, shape, dtype, std), k in zip(top, keys)}
    layer_keys = jax.random.split(keys[-1], sum(n for _, n, _ in stacks))
    first = 0
    for name, n, leaves in stacks:
        def one_layer(k, leaves=leaves):
            return {leaf: _normal(jax.random.fold_in(k, i), shape, dtype, std)
                    for i, (leaf, shape, dtype, std) in enumerate(leaves)}
        out[name] = jax.lax.map(one_layer, layer_keys[first:first + n])
        first += n
    return out


def make(cfg: dict, seed: int):
    sh = arch.load(cfg).shapes(cfg)
    spec = (tuple(tuple(t) for t in sh["top"]),
            tuple((name, n, tuple((k,) + tuple(v) for k, v in sorted(leaves.items())))
                  for name, n, leaves in sh["stacks"]))
    return _make(jax.random.PRNGKey(jax_seed(seed) ^ 0x5EED), spec)
