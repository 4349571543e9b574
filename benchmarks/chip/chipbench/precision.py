"""Precision of the plain reference and of its control.

The reference computes in float32 with every matrix product and convolution at
``Precision.HIGHEST``. Its control is the same code with the operands of those
products rounded to a lower type first (``quantize``): float8 e4m3 with one
scale per tensor, as float8 training does; the gradient passes the rounding
unchanged (straight through), so the backward products see the rounded
forward operands. ``bfloat16`` is no control: it rounds the operands as a
bfloat16 program does, forward and backward, and witnesses how far rounding
alone moves the numbers compared.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

HIGHEST = jax.lax.Precision.HIGHEST

# control name -> (type the operands are rounded to, its largest finite value)
CONTROLS = {"float8_e4m3fn": (jnp.float8_e4m3fn, 448.0)}
# witness name -> (exponent bits, mantissa bits); the rounding is linear for
# autodiff, so the gradient flowing back through it is rounded as well
WITNESSES = {"bfloat16": (8, 7)}


def quantize(x, qdt):
    """``x`` rounded to ``qdt`` (a key of CONTROLS, with a per-tensor scale,
    or of WITNESSES); ``None`` leaves it as it is."""
    if qdt is None:
        return x
    if qdt in WITNESSES:
        e, m = WITNESSES[qdt]
        return jax.lax.reduce_precision(x, exponent_bits=e, mantissa_bits=m)
    dt, top = CONTROLS[qdt]
    scale = jax.lax.stop_gradient(jnp.max(jnp.abs(x)) / top + 1e-30)
    rounded = (x / scale).astype(dt).astype(x.dtype) * scale
    return x + jax.lax.stop_gradient(rounded - x)
