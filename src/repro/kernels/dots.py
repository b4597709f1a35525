"""Mixed-precision matmul helper.

TPU MXUs compute bf16 x bf16 -> f32 natively (no fp32 copies of the
operands), which is what the kernels and the roofline assume. The CPU
backend can COMPILE that combination (the dry-run only lowers+compiles) but
cannot EXECUTE it — so execution paths on CPU upcast instead.

  native_mixed_dot(True)   dry-run lowering: keep operands bf16,
                           preferred_element_type=f32 (TPU semantics)
  native_mixed_dot(False)  CPU execution (tests/examples): upcast to f32

Default (no override): native when the backend is a TPU. The backend is
asked when a function is traced, never when this module is imported, so
importing it initializes no backend.
"""
from __future__ import annotations

from typing import Optional

import jax
import jax.numpy as jnp

_NATIVE: Optional[bool] = None     # None: decide from the backend at trace


def native_mixed_dot(value: Optional[bool]) -> None:
    global _NATIVE
    _NATIVE = value


def _native(a) -> bool:
    if a.dtype == jnp.float32:
        return True
    if _NATIVE is None:
        return jax.default_backend() == "tpu"
    return _NATIVE


def einsum_f32(subscripts: str, a, b):
    """einsum with fp32 accumulation, without fp32 operand copies when the
    backend supports mixed dots."""
    if _native(a):
        return jnp.einsum(subscripts, a, b,
                          preferred_element_type=jnp.float32)
    return jnp.einsum(subscripts, a.astype(jnp.float32),
                      b.astype(jnp.float32),
                      preferred_element_type=jnp.float32)


def dot_general_f32(a, b, dimension_numbers):
    if _native(a):
        return jax.lax.dot_general(a, b, dimension_numbers,
                                   preferred_element_type=jnp.float32)
    return jax.lax.dot_general(a.astype(jnp.float32),
                               b.astype(jnp.float32), dimension_numbers,
                               preferred_element_type=jnp.float32)
