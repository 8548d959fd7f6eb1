"""Matmul precision inside the Pallas kernels.

On a TPU, Mosaic contracts f32 operands at its default precision: one
MXU pass over inputs rounded to bf16. A kernel that promises f32
arithmetic (the ``fp`` precision policy, the f32 megakernel) asks for
the full-f32 contraction explicitly. Other operand dtypes (int8, bf16)
keep the default, which is exact for them. On the CPU (interpret mode)
the precision changes nothing: XLA's CPU dot is f32 either way.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp


def precision(*operands):
    """``Precision.HIGHEST`` when every operand is f32, else the
    default."""
    if all(o.dtype == jnp.float32 for o in operands):
        return jax.lax.Precision.HIGHEST
    return None


def dot(a, b):
    """``a @ b`` accumulated in f32, at full f32 precision for f32
    operands."""
    return jnp.dot(a, b, preferred_element_type=jnp.float32,
                   precision=precision(a, b))
