"""Production mesh construction.

Single pod: (16, 16) = 256 chips, axes (data, model).
Multi-pod:  (2, 16, 16) = 512 chips, axes (pod, data, model) — the "pod"
axis crosses the DCN; gradient all-reduce over ("pod","data") is
hierarchical (ICI within a pod, DCN across) under XLA's collective
hierarchy. Defined as functions so importing never touches device state.

Every mesh here has Auto axes: the sharding rules (distributed/sharding.py)
place arrays with NamedShardings and let XLA propagate, which is the Auto
contract. `jax.make_mesh` defaults to Explicit axes, under which the
embedding gather and the slot-parallel decode would need explicit
`out_sharding=` annotations at every gather site.
"""
from __future__ import annotations

import jax
from jax.sharding import AxisType


def make_mesh(shape, axes):
    """`jax.make_mesh` with Auto axis types (see module docstring)."""
    return jax.make_mesh(tuple(shape), tuple(axes),
                         axis_types=(AxisType.Auto,) * len(axes))


def make_production_mesh(*, multi_pod: bool = False):
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return make_mesh(shape, axes)


def make_pp_mesh(*, num_stages: int = 4, multi_pod: bool = False):
    """Pipeline-parallel production mesh: the 'pipe' axis takes chips from
    'data' (gradient sync shrinks; activations rotate stage-to-stage over
    ICI). Single pod (4, 4, 16) = 256 chips; multi-pod keeps stages inside
    a pod (cross-DCN activation hops would serialize the pipeline)."""
    assert 16 % num_stages == 0, num_stages
    if multi_pod:
        return make_mesh((2, num_stages, 16 // num_stages, 16),
                         ("pod", "pipe", "data", "model"))
    return make_mesh((num_stages, 16 // num_stages, 16),
                     ("pipe", "data", "model"))


def parse_mesh(spec: str):
    """Build a mesh from a 'DxM' launcher flag: '4x1' = 4-way data (slot)
    parallel, '2x2' = data×model, '2x2x2' = pod×data×model. The serving
    launcher threads this straight into ServingEngine(mesh=)."""
    dims = tuple(int(x) for x in spec.lower().replace("×", "x").split("x"))
    assert all(d >= 1 for d in dims), spec
    axes = {1: ("data",), 2: ("data", "model"),
            3: ("pod", "data", "model")}[len(dims)]
    return make_mesh(dims, axes)


def make_debug_mesh(n_data: int = 2, n_model: int = 2):
    """Tiny mesh for multi-device CPU tests (subprocess sets device count)."""
    return make_mesh((n_data, n_model), ("data", "model"))


def make_debug_pp_mesh(n_pipe: int = 2, n_data: int = 2):
    return make_mesh((n_pipe, n_data), ("pipe", "data"))


def batch_axes(mesh) -> tuple:
    return tuple(a for a in ("pod", "data") if a in mesh.axis_names)
