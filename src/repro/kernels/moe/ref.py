"""Pure-jnp oracle for the grouped SwiGLU experts.

out[n] = sum over the held experts e of w(n, e) * W_o,e (silu(W_g,e x_n) *
W_i,e x_n), where w(n, e) is the weight token n gave expert e among its
choices (zero where it did not choose it).  Every held expert runs densely
over every token; HIGHEST products, float32 accumulation.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
from jax import lax


def _dot(a, b):
    return jnp.dot(a, b, precision=lax.Precision.HIGHEST,
                   preferred_element_type=jnp.float32)


def moe_experts_reference(x, ids, weights, wg, wi, wo, *, expert_offset=0):
    """x (N, d), ids/weights (N, k), wg/wi (E_held, d, m), wo (E_held, m, d)
    -> (N, d) in x's dtype."""
    out = jnp.zeros(x.shape, jnp.float32)
    for e in range(wg.shape[0]):
        w = jnp.sum(jnp.where(ids == expert_offset + e, weights, 0), axis=-1)
        g = _dot(x, wg[e])
        h = jax.nn.silu(g) * _dot(x, wi[e])
        out = out + w[:, None].astype(jnp.float32) * _dot(h, wo[e])
    return out.astype(x.dtype)
