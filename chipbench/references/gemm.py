"""Plain reference for the GEMM configurations: C = A @ B.

Imports nothing of the program under test.  ``shape`` is the
configuration's ``shape``: M, N, K and the dtype of A, B and C.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from chipbench import precision as _precision


def make_inputs(shape, key):
    """(A, B) from ``key``: standard normal entries."""
    ka, kb = jax.random.split(key)
    dtype = jnp.dtype(shape["dtype"])
    a = jax.random.normal(ka, (shape["M"], shape["K"]), dtype)
    b = jax.random.normal(kb, (shape["K"], shape["N"]), dtype)
    return a, b


def reference(shape, inputs, precision="highest"):
    a, b = inputs
    return _precision.dot(a, b, precision)


def flops(shape):
    return 2.0 * shape["M"] * shape["N"] * shape["K"]


def bytes_moved(shape):
    """A and B read once, C written once."""
    item = jnp.dtype(shape["dtype"]).itemsize
    return float(shape["M"] * shape["K"] + shape["K"] * shape["N"]
                 + shape["M"] * shape["N"]) * item
