"""Pure-jnp oracle for the 2D-convolution case study (paper section V).

B[x,y] = w * sum_{i,j} F[i,j] * A[x+i-hx, y+j-hy]   (zero padding at borders)

Single-channel, single-precision, same-size output — exactly the paper's
deep-learning-style 2D convolution.
"""

from __future__ import annotations

import jax.numpy as jnp
from jax import lax


def conv2d_reference(image: jnp.ndarray, filt: jnp.ndarray,
                     weight: float = 1.0) -> jnp.ndarray:
    """image: (H, W) f32; filt: (Fh, Fw) f32; returns (H, W)."""
    h, w = image.shape
    fh, fw = filt.shape
    img = image[jnp.newaxis, jnp.newaxis]          # NCHW
    ker = filt[jnp.newaxis, jnp.newaxis]           # OIHW
    out = lax.conv_general_dilated(
        img, ker,
        window_strides=(1, 1),
        padding=((fh // 2, (fh - 1) // 2), (fw // 2, (fw - 1) // 2)),
        dimension_numbers=("NCHW", "OIHW", "NCHW"),
        # float32 products even on a TPU (default there: one bf16 pass)
        precision=lax.Precision.HIGHEST)
    return (weight * out[0, 0]).astype(image.dtype)


def conv_flops(H: int, W: int, Fh: int, Fw: int) -> float:
    """Paper footnote 2: GFLOPS computed as (1 + 2*Xf*Yf) * X * Y / t."""
    return (1.0 + 2.0 * Fh * Fw) * H * W


def conv_bytes(H: int, W: int, elt_bytes: int = 4) -> float:
    """Paper footnote 2: bandwidth as 2 * X * Y (read + write) / t."""
    return 2.0 * H * W * elt_bytes
