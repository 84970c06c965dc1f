"""Matrix products at a named precision, for the plain references.

``highest``: float32 products (XLA's HIGHEST; a TPU's default float32 dot
is one bf16 pass).  ``bf16x3``: the three-pass bf16 product that XLA calls
HIGH, written out so that it rounds the same on every backend: each operand
splits into a high part and a remainder, each rounded to bf16's 8 bits of
mantissa, and the product keeps hi*hi + hi*lo + lo*hi with float32
accumulation, dropping lo*lo.  It is the control: the nearest precision
below the float32 the configurations state.

The split rounds with ``lax.reduce_precision``: a float32 -> bf16 -> float32
round trip would be removed as a no-op by XLA on a TPU (it allows excess
precision), leaving the remainder zero and the product a single bf16 pass.
"""

from __future__ import annotations

import jax.numpy as jnp
from jax import lax

PRECISIONS = ("highest", "bf16x3")


def _bf16(x):
    """``x`` rounded to bf16's precision, kept as float32; casting it to
    bf16 afterwards is exact."""
    return lax.reduce_precision(x, exponent_bits=8, mantissa_bits=7)


def _split(x):
    hi = _bf16(x)
    return hi.astype(jnp.bfloat16), _bf16(x - hi).astype(jnp.bfloat16)


def dot(x, y, precision: str = "highest"):
    """``x @ y`` for 2-D float32 operands, with float32 results."""
    x = x.astype(jnp.float32)
    y = y.astype(jnp.float32)
    if precision == "highest":
        return jnp.dot(x, y, precision=lax.Precision.HIGHEST,
                       preferred_element_type=jnp.float32)
    if precision == "bf16x3":
        (xh, xl), (yh, yl) = _split(x), _split(y)

        def one_pass(a, b):
            return jnp.dot(a, b, preferred_element_type=jnp.float32)

        return one_pass(xh, yh) + (one_pass(xh, yl) + one_pass(xl, yh))
    raise ValueError(f"unknown precision {precision!r}; known: {PRECISIONS}")
