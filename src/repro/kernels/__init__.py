"""Pallas TPU kernels — the paper's two case studies + one extension.

  matmul/     GEMM          (paper section VI)
  conv2d/     2D convolution (paper section V)
  attention/  flash attention (beyond paper; same tuning methodology)
  moe/        grouped SwiGLU experts over ragged, routed groups (beyond paper)

Each package ships <name>.py (pl.pallas_call + BlockSpec), ops.py (a
``@tunable`` declaration + public op resolving configs via
``repro.core.registry.lookup``) and ref.py (pure-jnp oracle).  Importing
this package registers all four kernels in the tunable registry.
"""

from . import attention, conv2d, matmul, moe

__all__ = ["attention", "conv2d", "matmul", "moe"]
