"""The arithmetic a reference computes in: exact, or rounded to fp8 for the
control.

``quant`` selects it.  ``None`` is the reference: float32, and
``precision=HIGHEST`` on every contraction.  ``"fp8"`` is the control: the
same computation with every contraction's operand rounded to float8_e4m3
and every cotangent to float8_e5m2, each with a per-tensor scale to its own
largest magnitude (the usual fp8 training recipe).  A family's forward pass
wraps each contraction's operands in ``operand(quant)``; the shared round
rounds the stored rows with ``round_fp8``.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
from jax import lax

HIGHEST = lax.Precision.HIGHEST


def round_fp8(x, dtype, axis=None):
    amax = jnp.max(jnp.abs(x), axis=axis, keepdims=axis is not None)
    scale = jnp.where(amax > 0, float(jnp.finfo(dtype).max) / amax, 1.0)
    return (x * scale).astype(dtype).astype(jnp.float32) / scale


@jax.custom_vjp
def _fp8_operand(x):
    return round_fp8(x, jnp.float8_e4m3fn)


_fp8_operand.defvjp(lambda x: (round_fp8(x, jnp.float8_e4m3fn), None),
                    lambda _, g: (round_fp8(g, jnp.float8_e5m2),))


def operand(quant):
    if quant is None:
        return lambda x: x
    if quant == "fp8":
        return _fp8_operand
    raise ValueError(f"unknown arithmetic {quant!r}")
