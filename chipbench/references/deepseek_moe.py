"""Plain reference for the DeepSeek-V3 routed-expert configurations: one
chip's share of a MoE layer's routed experts.

Imports nothing of the program under test.  ``shape`` is the
configuration's ``shape``: N tokens of width d, experts of hidden width m,
E routed experts of which E_held (experts 0 to E_held - 1) are held here,
k experts per token, and the dtype.

Routing (DeepSeek-V3, ``topk_method`` noaux_tc, ``scoring_func`` sigmoid):
s = sigmoid(x W_r) over all E experts; the selection score is s + b, with b
the ``e_score_correction_bias``; each of the ``N_GROUP`` groups of E /
``N_GROUP`` experts scores the sum of its top 2 selection scores, and the
top ``TOPK_GROUP`` groups are kept; the top k experts by selection score
within the kept groups are chosen; their weights are s (without b),
normalised to sum to 1, times ``ROUTED_SCALING_FACTOR``.  The inputs carry
the routing decision, so the program and the reference share it.

The traffic is one fixed batch: the tokens x, the router W_r and the
bias b are drawn from ``ROUTING_SEED``, not from the run's seed, so every
run routes the same pairs to the same experts and a call's work does not
change from seed to seed.  b ~ N(0, ``BIAS_SCALE``^2) is drawn once, as a
trained router's correction bias is fixed at inference; the loads it
leaves are uneven.  The run's seed draws the held experts' weights.

The output is the held experts' part of the layer: for each token, the sum
over its chosen held experts e of w_e * W_o,e (silu(W_g,e x) * W_i,e x);
zero for a token that chose none of them.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
from jax import lax

from chipbench import precision as _precision

N_GROUP = 8
TOPK_GROUP = 4
ROUTED_SCALING_FACTOR = 2.5
#: the scale of the drawn e_score_correction_bias (assumed)
BIAS_SCALE = 0.05
#: the seed of the batch: tokens, router and bias
ROUTING_SEED = 0
#: tokens the reference computes at once, so that it fits the chip
CHUNK = 2048


def choose(select, k: int):
    """The k experts (ids, (N, k)) that noaux_tc chooses by ``select``."""
    n, e = select.shape
    grouped = select.reshape(n, N_GROUP, e // N_GROUP)
    group_score = lax.top_k(grouped, 2)[0].sum(axis=-1)
    _, top_groups = lax.top_k(group_score, TOPK_GROUP)
    kept = jnp.any(top_groups[..., None] == jnp.arange(N_GROUP), axis=-2)
    masked = jnp.where(kept[..., None], grouped, -jnp.inf).reshape(n, e)
    return lax.top_k(masked, k)[1]


def route(logits, bias, k: int):
    """(ids, weights), each (N, k), of DeepSeek-V3's noaux_tc router."""
    s = jax.nn.sigmoid(logits)
    ids = choose(s + bias, k)
    w = jnp.take_along_axis(s, ids, axis=-1)
    w = w / jnp.sum(w, axis=-1, keepdims=True) * ROUTED_SCALING_FACTOR
    return ids.astype(jnp.int32), w


def make_inputs(shape, key):
    """(x, ids, weights, wg, wi, wo): x ~ N(0, 1), the router W_r ~ N(0,
    1/d) and the bias b ~ N(0, BIAS_SCALE^2) from ``ROUTING_SEED``, routed
    as above; the held experts' weights ~ N(0, 1/fan-in) from ``key``."""
    N, d, m, E, G = (shape[n] for n in ("N", "d", "m", "E", "E_held"))
    dtype = jnp.dtype(shape["dtype"])
    kx, kr, kb = jax.random.split(jax.random.key(ROUTING_SEED), 3)
    x = jax.random.normal(kx, (N, d), dtype)
    logits = _precision.dot(x, jax.random.normal(kr, (d, E), jnp.float32)
                            * d ** -0.5)
    bias = BIAS_SCALE * jax.random.normal(kb, (E,), jnp.float32)
    ids, weights = route(logits, bias, shape["k"])
    kg, ki, ko = jax.random.split(key, 3)

    def expert(key, fan_in, fan_out):
        return (jax.random.normal(key, (G, fan_in, fan_out), dtype)
                * fan_in ** -0.5).astype(dtype)

    return (x, ids, weights.astype(dtype), expert(kg, d, m),
            expert(ki, d, m), expert(ko, m, d))


def _chunk(xs, precision):
    x, ids, weights, wg, wi, wo = xs
    out = jnp.zeros(x.shape, jnp.float32)
    for e in range(wg.shape[0]):
        w = jnp.sum(jnp.where(ids == e, weights, 0.0), axis=-1)
        g = _precision.dot(x, wg[e], precision)
        h = jax.nn.silu(g) * _precision.dot(x, wi[e], precision)
        out = out + w[:, None] * _precision.dot(h, wo[e], precision)
    return out


def reference(shape, inputs, precision="highest"):
    """The held experts' masked, weighted SwiGLU, summed, over every token,
    ``CHUNK`` tokens at a time."""
    x, ids, weights, wg, wi, wo = inputs
    n = x.shape[0]
    chunk = min(CHUNK, n)
    split = lambda a: a.reshape((n // chunk, chunk) + a.shape[1:])
    out = lax.map(lambda c: _chunk(c + (wg, wi, wo), precision),
                  (split(x), split(ids), split(weights)))
    return out.reshape(x.shape)


def flops(shape):
    """The balanced expectation: N * k * E_held / E token-expert pairs,
    three products of d * m, 2 operations per multiply-add."""
    return (shape["N"] * shape["k"] * shape["E_held"] / shape["E"]
            * 6.0 * shape["d"] * shape["m"])


def bytes_moved(shape):
    """x read, the held experts' three weights read, the output written
    once each; the routing ids (int32) and weights read once."""
    item = jnp.dtype(shape["dtype"]).itemsize
    N, d, m = shape["N"], shape["d"], shape["m"]
    return float((2 * N * d + 3 * shape["E_held"] * d * m) * item
                 + N * shape["k"] * (4 + item))
