"""A matmul whose operands are rounded to a lower type first: what the
references' controls are made of (`training(config, operands)`), never the
reference itself, which passes `operands=None` and multiplies in float32."""

from __future__ import annotations

import jax
import jax.numpy as jnp


def rounded(t, operands):
    """float32 `t` at the precision of `operands`. To bfloat16 by
    `reduce_precision`: the TPU compiler takes a float32 -> bfloat16 ->
    float32 round trip for excess precision it may keep, and removes it."""
    if operands == jnp.bfloat16:
        return jax.lax.reduce_precision(t, exponent_bits=8, mantissa_bits=7)
    return t.astype(operands).astype(jnp.float32)


def mm(a, b, operands=None):
    """a @ b, both rounded to `operands` first where that is given (the
    rounding passes a gradient through as it is: a forward and a backward
    product of rounded operands, their cotangents float32)."""
    if operands is not None:
        a, b = (t + jax.lax.stop_gradient(rounded(t, operands) - t)
                for t in (a, b))
    return a @ b
