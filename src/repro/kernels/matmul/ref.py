"""Pure-jnp oracle for the GEMM case study.

Paper form (section VI): C = alpha * A^T B + beta * C, single precision,
power-of-two dims.  ``trans_a`` selects whether A arrives K-major (the
paper's A^T layout) or M-major.
"""

from __future__ import annotations

import jax.numpy as jnp
from jax import lax


def gemm_reference(a, b, c=None, *, alpha: float = 1.0, beta: float = 0.0,
                   trans_a: bool = False, acc_dtype=jnp.float32):
    """C = alpha * op(A) @ B + beta * C with op(A) = A^T if trans_a.

    a: (M, K) or (K, M) when trans_a; b: (K, N); returns (M, N) in a.dtype.
    """
    lhs = a.T if trans_a else a
    # HIGHEST: on a TPU the default f32 dot is a single bf16 pass, far
    # below the float32 accuracy this oracle stands for
    out = jnp.dot(lhs.astype(acc_dtype), b.astype(acc_dtype),
                  preferred_element_type=acc_dtype,
                  precision=lax.Precision.HIGHEST)
    out = alpha * out
    if c is not None and beta != 0.0:
        out = out + beta * c.astype(acc_dtype)
    return out.astype(a.dtype)
