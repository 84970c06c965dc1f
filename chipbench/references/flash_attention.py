"""Plain reference for the attention configurations: softmax attention,
O = softmax(Q K^T / sqrt(D) + mask) V, head by head.

Imports nothing of the program under test.  ``shape`` is a configuration's
``shape`` or ``op_shape``: Sq, Sk, D, whether the mask is causal (query i
sees keys j <= i + Sk - Sq, so the ends of the two sequences align), the
dtype of Q, K, V and O, and, for a call over heads, ``H`` query heads that
share ``KV`` key/value heads (``H / KV`` query heads to each).  Without
``H`` the arrays are one head's, (S, D).  The whole score matrix of a head
is formed at once, one head at a time.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from chipbench import precision as _precision


def make_inputs(shape, key):
    """(Q, K, V) from ``key``: normal entries of standard deviation 0.5.
    With ``H`` heads, K and V are drawn for ``KV`` heads and repeated to
    ``H``, as a grouped-query model hands them to a per-head kernel."""
    dtype = jnp.dtype(shape["dtype"])
    kq, kk, kv = jax.random.split(key, 3)
    heads = shape.get("H")
    lead_q = (heads,) if heads else ()
    lead_kv = (shape["KV"],) if heads else ()

    def normal(k, lead, n):
        return 0.5 * jax.random.normal(k, lead + (n, shape["D"]), dtype)

    q = normal(kq, lead_q, shape["Sq"])
    k, v = (normal(kk, lead_kv, shape["Sk"]), normal(kv, lead_kv, shape["Sk"]))
    if heads:
        k, v = (jnp.repeat(x, heads // shape["KV"], axis=0) for x in (k, v))
    return q, k, v


def _one_head(shape, q, k, v, precision):
    sq, sk = shape["Sq"], shape["Sk"]
    s = _precision.dot(q, k.T, precision) * (shape["D"] ** -0.5)
    if shape["causal"]:
        visible = (jnp.arange(sq)[:, None] + (sk - sq)) >= jnp.arange(sk)[None, :]
        s = jnp.where(visible, s, -jnp.inf)
    p = jnp.exp(s - s.max(axis=-1, keepdims=True))
    return _precision.dot(p, v, precision) / p.sum(axis=-1, keepdims=True)


def reference(shape, inputs, precision="highest"):
    q, k, v = inputs
    if q.ndim == 2:
        return _one_head(shape, q, k, v, precision)
    return jax.lax.map(lambda qkv: _one_head(shape, *qkv, precision), (q, k, v))


def flops(shape):
    """Q K^T and P V, 2 operations per multiply-add each, for every query
    head; a causal mask halves the pairs that count, however many a kernel
    computes."""
    pairs = shape["Sq"] * shape["Sk"] * (0.5 if shape["causal"] else 1.0)
    return 4.0 * pairs * shape["D"] * shape.get("H", 1)


def bytes_moved(shape):
    """Q read and O written once for every query head, K and V read once
    for every key/value head."""
    item = jnp.dtype(shape["dtype"]).itemsize
    heads = shape.get("H", 1)
    kv_heads = shape.get("KV", heads)
    return float(2 * shape["Sq"] * heads
                 + 2 * shape["Sk"] * kv_heads) * shape["D"] * item
