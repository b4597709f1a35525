"""Architectures: one module per architecture, `arch/<name>.py`, named by a
configuration file's `arch` key (`granite_swat` where the key is absent).
Everything the benchmark knows of a model's layers lives in its module:

  layers(cfg)    one descriptor per layer, for cost.py (its docstring
                 gives the form)
  shapes(cfg)    the weight layout that weights.make builds: ordered
                 top-level leaves and stacks of layers, each leaf
                 (name, shape, dtype, std)
  program(cfg, weights)
                 the program's ModelConfig and parameter tree (the same
                 arrays), the tree checked with `checked`
  departures(cfg)
                 where the program's registered config differs from the
                 file, other than the keys the file lists as reduced
  score(weights, cfg, seq, positions, tokens, fp8=False, router_margin=None)
                 the float32 reference (reference.py's blocks): per
                 position the gap of the token, the argmax, the logits'
                 spread and whether the position is decisive

A new architecture is a new module and a configuration that names it; no
file of the harness changes.
"""
from __future__ import annotations

import importlib
import sys
from pathlib import Path

DEFAULT = "granite_swat"
SRC = Path(__file__).resolve().parents[2] / "src"     # the program, for `program`
if str(SRC) not in sys.path:
    sys.path.insert(0, str(SRC))


def load(cfg: dict):
    """The architecture module of a configuration."""
    return importlib.import_module(f"{__name__}.{cfg.get('arch', DEFAULT)}")


def checked(params, mcfg):
    """`params` unchanged, once its tree matches `init_model`'s for the
    program's config `mcfg` leaf by leaf (shape and dtype)."""
    import jax
    from repro.core import model as Mod
    want = jax.eval_shape(lambda: Mod.init_model(jax.random.PRNGKey(0), mcfg))
    got = jax.tree.map(lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype), params)
    if jax.tree.structure(want) != jax.tree.structure(got) or any(
            (a.shape, a.dtype) != (b.shape, b.dtype)
            for a, b in zip(jax.tree.leaves(want), jax.tree.leaves(got))):
        raise ValueError(f"weights do not match the program's tree:\n"
                         f"want {want}\ngot {got}")
    return params
