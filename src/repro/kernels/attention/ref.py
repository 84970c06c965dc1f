"""Pure-jnp oracle for chunked (flash-style) attention."""

from __future__ import annotations

import jax.numpy as jnp
from jax import lax

#: float32 products even on a TPU, whose default f32 dot is one bf16 pass
_HIGHEST = lax.Precision.HIGHEST


def attention_reference(q, k, v, *, causal: bool = True,
                        scale: float | None = None):
    """q: (Sq, D), k/v: (Sk, D) -> (Sq, D).  Single head; vmap outside."""
    sq, d = q.shape
    sk = k.shape[0]
    scale = (d ** -0.5) if scale is None else scale
    s = jnp.dot(q.astype(jnp.float32), k.astype(jnp.float32).T,
                precision=_HIGHEST) * scale
    if causal:
        qi = jnp.arange(sq)[:, None] + (sk - sq)    # align ends (KV prefix)
        kj = jnp.arange(sk)[None, :]
        s = jnp.where(qi >= kj, s, -1e30)
    p = jnp.exp(s - s.max(axis=-1, keepdims=True))
    p = p / p.sum(axis=-1, keepdims=True)
    return jnp.dot(p, v.astype(jnp.float32),
                   precision=_HIGHEST).astype(q.dtype)


def attention_flops(Sq: int, Sk: int, D: int, causal: bool = True) -> float:
    f = 4.0 * Sq * Sk * D          # QK^T and PV matmuls
    return f / 2 if causal else f
