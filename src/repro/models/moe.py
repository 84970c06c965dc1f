"""Capacity-based top-k Mixture-of-Experts (DeepSeek-V3 / Kimi-K2 style).

Dispatch is scatter-based: per-sequence groups compute position-in-expert
counters (a (B, S*k, E) cumsum — small), then scatter token activations into
an (B, E, C, d) buffer; tokens beyond capacity C are dropped (scatter mode
'drop' with an out-of-range sentinel).  This avoids GShard's (S, E, C)
one-hot dispatch tensor, which is infeasible at 1M-token global batches.

Expert weights are stacked (E, ...) and shard over the "experts" logical
axis (-> mesh "model"); the dispatched buffer shards batch over data and
experts over model, so expert compute is fully parallel.  A second
implementation (MOE_IMPL='onehot') keeps the classic einsum dispatch for
small expert counts — it is both the smoke-test oracle and a point in the
sharding tuner's space.

``impl="grouped"`` is dropless: the tuned grouped SwiGLU kernel
(``repro.kernels.moe``, resolved through the registry) computes the routed
pairs of the experts this layer holds.  Under expert parallelism a layer
holds experts ``expert_offset`` to ``expert_offset + E_held`` of all E
(``RunConfig.moe_expert_offset`` / ``moe_experts_held``): it routes over
all E and computes its own experts' part of the result, which the absent
experts' parts would complete.
"""

from __future__ import annotations

from typing import Any, Dict, Tuple

import jax
import jax.numpy as jnp
from jax import lax

from ..dist.sharding import shard
from .config import ModelConfig
from .layers import apply_mlp, mlp_defs
from .params import ParamDef


def moe_defs(cfg: ModelConfig, experts: int = 0) -> Dict[str, Any]:
    """The layer's parameters; ``experts`` routed experts held (0: all)."""
    d, E, m = cfg.d_model, cfg.num_experts, cfg.moe_d_ff
    held = experts or E
    defs: Dict[str, Any] = {
        "router": ParamDef((d, E), ("embed", "experts"), scale=0.1),
        "wg": ParamDef((held, d, m), ("experts", "embed", "expert_mlp")),
        "wi": ParamDef((held, d, m), ("experts", "embed", "expert_mlp")),
        "wo": ParamDef((held, m, d), ("experts", "expert_mlp", "embed")),
    }
    if cfg.topk_method == "noaux_tc":
        # e_score_correction_bias: set by load balancing, not by gradients
        defs["router_bias"] = ParamDef((E,), ("experts",), init="zeros",
                                       dtype="float32")
    if cfg.num_shared_experts:
        defs["shared"] = mlp_defs(
            cfg, d_ff=cfg.moe_d_ff * cfg.num_shared_experts)
    return defs


def capacity(cfg: ModelConfig, seq_len: int) -> int:
    c = int(cfg.experts_per_token * seq_len * cfg.capacity_factor
            / cfg.num_experts)
    return max(4, -(-c // 4) * 4)         # round up to a multiple of 4


def _noaux_tc_choice(cfg: ModelConfig, select):
    """DeepSeek-V3's group-limited choice: each of ``n_group`` groups of
    experts scores the sum of its top 2 selection scores, the top
    ``topk_group`` groups are kept, and the top k experts by selection
    score within them are chosen.  Returns their ids."""
    *lead, E = select.shape
    grouped = select.reshape(*lead, cfg.n_group, E // cfg.n_group)
    group_score = lax.top_k(grouped, 2)[0].sum(-1)
    _, top_groups = lax.top_k(group_score, cfg.topk_group)
    kept = jnp.any(top_groups[..., None] == jnp.arange(cfg.n_group), axis=-2)
    masked = jnp.where(kept[..., None], grouped, -jnp.inf)
    return lax.top_k(masked.reshape(*lead, E), cfg.experts_per_token)[1]


def _router(cfg: ModelConfig, p, x):
    """Return (weights, indices): (B, S, k) routing weights and expert ids."""
    logits = jnp.einsum("bsd,de->bse", x, p["router"]).astype(jnp.float32)
    if cfg.router_impl == "sigmoid":       # DeepSeek-V3 style
        scores = jax.nn.sigmoid(logits)
        if cfg.topk_method == "noaux_tc":
            # chosen by score + bias, weighted by the score alone
            topi = _noaux_tc_choice(cfg, scores + p["router_bias"])
            topv = jnp.take_along_axis(scores, topi, axis=-1)
        else:
            topv, topi = lax.top_k(scores, cfg.experts_per_token)
        topv = topv / jnp.maximum(topv.sum(-1, keepdims=True), 1e-9)
    else:
        probs = jax.nn.softmax(logits, axis=-1)
        topv, topi = lax.top_k(probs, cfg.experts_per_token)
    return topv * cfg.routed_scaling_factor, topi, logits


def _aux_loss(cfg: ModelConfig, logits, topi) -> jax.Array:
    """Switch-style load-balance auxiliary loss."""
    E = cfg.num_experts
    probs = jax.nn.softmax(logits, axis=-1)           # (B, S, E)
    me = probs.mean(axis=(0, 1))                       # mean router prob
    ce = jnp.mean(
        jax.nn.one_hot(topi, E, dtype=jnp.float32), axis=(0, 1, 2))
    return E * jnp.sum(me * ce)


def _expert_ffn(p, h):
    """h: (B, E, C, d) -> (B, E, C, d); stacked-expert SwiGLU."""
    gate = jax.nn.silu(jnp.einsum("becd,edm->becm", h, p["wg"]))
    up = jnp.einsum("becd,edm->becm", h, p["wi"])
    return jnp.einsum("becm,emd->becd", gate * up, p["wo"])


def _dispatch_scatter(cfg: ModelConfig, p, x, topv, topi):
    """Scatter-based dispatch/combine (production path)."""
    B, S, d = x.shape
    E, k = cfg.num_experts, cfg.experts_per_token
    C = capacity(cfg, S)

    flat_e = topi.reshape(B, S * k)                    # expert id per slot
    onehot = jax.nn.one_hot(flat_e, E, dtype=jnp.int32)      # (B, S*k, E)
    pos_all = jnp.cumsum(onehot, axis=1) - onehot            # pos in expert
    pos = jnp.take_along_axis(
        pos_all, flat_e[..., None], axis=-1)[..., 0]         # (B, S*k)
    # overflow -> index C, dropped by scatter mode 'drop'
    pos = jnp.where(pos < C, pos, C)

    xk = jnp.repeat(x, k, axis=1)                            # (B, S*k, d)

    def scatter_one(buf, e_idx, p_idx, vals):
        return buf.at[e_idx, p_idx].add(vals, mode="drop")

    buf = jnp.zeros((B, E, C, d), x.dtype)
    buf = jax.vmap(scatter_one)(buf, flat_e, pos, xk)
    buf = shard(buf, "batch", "experts", "expert_cap", "embed")

    out_buf = _expert_ffn(p, buf)
    out_buf = shard(out_buf, "batch", "experts", "expert_cap", "embed")

    def gather_one(b, e_idx, p_idx):
        safe = jnp.minimum(p_idx, C - 1)
        vals = b[e_idx, safe]                                # (S*k, d)
        return jnp.where((p_idx < C)[:, None], vals, 0.0)

    gathered = jax.vmap(gather_one)(out_buf, flat_e, pos)    # (B, S*k, d)
    gathered = gathered.reshape(B, S, k, d)
    return jnp.einsum("bskd,bsk->bsd", gathered, topv.astype(x.dtype))


def _dispatch_gather(cfg: ModelConfig, p, x, topv, topi):
    """Pull-based dispatch (EXPERIMENTS.md §Perf B4).

    The scatter path pushes token activations into an (B, E, C, d) buffer;
    with tokens batch-sharded and experts model-sharded, GSPMD realises the
    push as an all-reduce of the full f32 dispatch buffer (~GBs per layer).
    Here we invert the mapping instead: a tiny int32 (B, E, C) slot->token
    index table is scattered (bytes, not activations), and each expert
    shard *gathers* the activations it needs — the only large communication
    left is the token resharding itself.
    """
    B, S, d = x.shape
    E, k = cfg.num_experts, cfg.experts_per_token
    C = capacity(cfg, S)
    T = S * k

    flat_e = topi.reshape(B, T)
    onehot = jax.nn.one_hot(flat_e, E, dtype=jnp.int32)
    pos_all = jnp.cumsum(onehot, axis=1) - onehot
    pos = jnp.take_along_axis(pos_all, flat_e[..., None], axis=-1)[..., 0]
    pos = jnp.where(pos < C, pos, C)                  # overflow -> dropped

    # slot -> flat-token-id table; sentinel T points at a zero row
    def invert(e_idx, p_idx):
        tbl = jnp.full((E, C), T, jnp.int32)
        return tbl.at[e_idx, p_idx].set(jnp.arange(T, dtype=jnp.int32),
                                        mode="drop")
    slot_tok = jax.vmap(invert)(flat_e, pos)          # (B, E, C) int32

    xk = jnp.repeat(x, k, axis=1)                     # (B, T, d)
    xk = jnp.concatenate(
        [xk, jnp.zeros((B, 1, d), x.dtype)], axis=1)  # sentinel row

    def pull(xb, tb):
        return xb[tb]                                 # (E, C, d) gather
    buf = jax.vmap(pull)(xk, slot_tok)
    buf = shard(buf, "batch", "experts", "expert_cap", "embed")

    out_buf = _expert_ffn(p, buf)
    out_buf = shard(out_buf, "batch", "experts", "expert_cap", "embed")

    def gather_one(b, e_idx, p_idx):
        safe = jnp.minimum(p_idx, C - 1)
        vals = b[e_idx, safe]
        return jnp.where((p_idx < C)[:, None], vals, 0.0)

    gathered = jax.vmap(gather_one)(out_buf, flat_e, pos)
    gathered = gathered.reshape(B, S, k, d)
    return jnp.einsum("bskd,bsk->bsd", gathered, topv.astype(x.dtype))


def _dispatch_onehot(cfg: ModelConfig, p, x, topv, topi):
    """Classic einsum dispatch — O(S*E*C) mask; small-E oracle path."""
    B, S, d = x.shape
    E, k = cfg.num_experts, cfg.experts_per_token
    C = capacity(cfg, S)
    onehot = jax.nn.one_hot(topi, E, dtype=jnp.int32)        # (B, S, k, E)
    flat = onehot.reshape(B, S * k, E)
    pos = jnp.cumsum(flat, axis=1) - flat                    # (B, S*k, E)
    in_cap = (pos < C) & (flat > 0)
    cap_oh = jax.nn.one_hot(jnp.where(in_cap, pos, C), C,
                            dtype=x.dtype)                   # (B,S*k,E,C)
    disp = cap_oh * flat.astype(x.dtype)[..., None]
    xk = jnp.repeat(x, k, axis=1)
    buf = jnp.einsum("btec,btd->becd", disp, xk)
    out_buf = _expert_ffn(p, buf)
    gathered = jnp.einsum("btec,becd->btd", disp, out_buf)
    gathered = gathered.reshape(B, S, k, d)
    weights = topv.reshape(B, S, k)
    return jnp.einsum("bskd,bsk->bsd", gathered, weights.astype(x.dtype))


def _experts_grouped(cfg: ModelConfig, p, x, topv, topi, expert_offset):
    """Dropless: the held experts' part of the result, from the grouped
    SwiGLU op, whose tiles ``registry.lookup`` resolves."""
    from ..kernels.moe import moe_experts

    B, S, d = x.shape
    k = cfg.experts_per_token
    out = moe_experts(x.reshape(B * S, d), topi.reshape(B * S, k),
                      topv.reshape(B * S, k), p["wg"], p["wi"], p["wo"],
                      expert_offset=expert_offset,
                      num_experts=cfg.num_experts,
                      interpret=jax.default_backend() != "tpu")
    return out.reshape(B, S, d)


def apply_moe(cfg: ModelConfig, p: Dict[str, Any], x: jax.Array,
              impl: str = "scatter",
              expert_offset: int = 0) -> Tuple[jax.Array, jax.Array]:
    """x: (B, S, d) -> (out, aux_loss).  ``p`` holds experts
    ``expert_offset`` onward, as many as its ``wg`` stacks; only the
    ``grouped`` impl computes a share of the experts."""
    topv, topi, logits = _router(cfg, p, x)
    if impl != "grouped" and p["wg"].shape[0] != cfg.num_experts:
        raise ValueError(f"MoE impl {impl!r} computes all "
                         f"{cfg.num_experts} experts; this layer holds "
                         f"{p['wg'].shape[0]} (use impl='grouped')")
    if impl == "grouped":
        routed = _experts_grouped(cfg, p, x, topv, topi, expert_offset)
    elif impl == "scatter":
        routed = _dispatch_scatter(cfg, p, x, topv, topi)
    elif impl == "gather":
        routed = _dispatch_gather(cfg, p, x, topv, topi)
    elif impl == "onehot":
        routed = _dispatch_onehot(cfg, p, x, topv, topi)
    else:
        raise ValueError(f"unknown MoE impl {impl!r}")
    if cfg.num_shared_experts:
        routed = routed + apply_mlp(p["shared"], x)
    return shard(routed, "batch", "seq", "embed"), _aux_loss(cfg, logits, topi)
