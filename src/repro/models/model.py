"""Decoder assembly: param trees, forward, loss, decode — all families.

The layer stack is ``lax.scan`` over stacked per-layer parameters (HLO size
and 512-device compile time stay flat in depth); heterogeneous stacks (MoE
leading dense layers, Zamba2 super-blocks) are segmented into homogeneous
scans.  ``RunConfig`` carries the execution knobs the sharding tuner
searches over (remat policy, MoE dispatch impl, attention chunking,
scan-vs-unroll).
"""

from __future__ import annotations

import dataclasses
import functools
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp
from jax import lax

from ..dist.sharding import shard
from .config import ModelConfig
from .layers import (apply_attention, apply_mlp, attention_cache_defs,
                     attention_defs, mlp_defs, norm_defs, rms_norm)
from .mla import apply_mla, mla_cache_defs, mla_defs
from .moe import apply_moe, moe_defs
from .params import ParamDef, abstract_params, init_params, stack_defs
from .ssm import apply_mamba, mamba_defs, mamba_state_defs


@dataclasses.dataclass(frozen=True)
class RunConfig:
    """Execution knobs (a point in the sharding tuner's space)."""

    remat: str = "none"              # none | full | dots
    moe_impl: str = "scatter"        # scatter | gather | onehot | grouped
    #: the routed experts this device holds (expert parallelism): experts
    #: moe_expert_offset to moe_expert_offset + moe_experts_held of every
    #: MoE layer; 0 held = all.  A share runs with moe_impl "grouped".
    moe_expert_offset: int = 0
    moe_experts_held: int = 0
    attn_chunk: int = 0              # 0 = unchunked; else KV chunk length
    #: attention sharding mode: grouped | expanded (see layers.apply_attention)
    attn_mode: str = "grouped"
    scan_blocks: bool = True         # lax.scan over layers vs python unroll
    microbatch: int = 1              # gradient-accumulation splits
    #: gradient-accumulation dtype; bfloat16 halves accumulator memory
    #: (gradient compression) — default for the >500B configs
    accum_dtype: str = "float32"
    #: sequence-chunked cross-entropy: logits are materialised (B, chunk, V)
    #: at a time (checkpointed scan).  0 = whole-sequence logits.  Essential
    #: when the vocab does not divide the model axis (logits replicated).
    ce_chunk: int = 0
    #: vocab-chunked LM head: the (d, V) head matmul is issued as V/chunk
    #: column tiles (the serve path derives this from the tuned gemm
    #: BLOCK_N, so a hot-swapped winner changes the lowered step).  0 =
    #: one whole-vocab einsum; ignored unless it divides the vocab exactly.
    head_chunk: int = 0

    def remat_policy(self):
        if self.remat == "none":
            return None
        if self.remat == "full":
            return jax.checkpoint_policies.nothing_saveable
        if self.remat == "dots":
            return jax.checkpoint_policies.dots_with_no_batch_dims_saveable
        raise ValueError(f"unknown remat {self.remat!r}")


DEFAULT_RUN = RunConfig()


# ---------------------------------------------------------------------------
# parameter trees
# ---------------------------------------------------------------------------

def _attn_block_defs(cfg: ModelConfig, ffn: str,
                     run: RunConfig = DEFAULT_RUN) -> Dict[str, Any]:
    d = cfg.d_model
    block: Dict[str, Any] = {"ln1": norm_defs(d), "ln2": norm_defs(d)}
    block["attn"] = mla_defs(cfg) if cfg.use_mla else attention_defs(cfg)
    if ffn == "dense":
        block["mlp"] = mlp_defs(cfg)
    elif ffn == "moe":
        block["moe"] = moe_defs(cfg, run.moe_experts_held)
    else:
        raise ValueError(ffn)
    return block


def _mamba_block_defs(cfg: ModelConfig) -> Dict[str, Any]:
    return {"ln": norm_defs(cfg.d_model), "mamba": mamba_defs(cfg)}


def model_defs(cfg: ModelConfig, run: RunConfig = DEFAULT_RUN) -> Dict[str, Any]:
    """The parameter tree; ``run`` says which routed experts are held."""
    d, V = cfg.d_model, cfg.vocab_size
    defs: Dict[str, Any] = {
        "embed": ParamDef((V, d), ("vocab", "embed"), init="normal",
                          scale=0.02),
        "final_norm": norm_defs(d),
    }
    if not cfg.tie_embeddings:
        defs["head"] = ParamDef((d, V), ("embed", "vocab"))

    if cfg.family == "ssm":
        defs["blocks"] = stack_defs(_mamba_block_defs(cfg), cfg.num_layers)
    elif cfg.family == "hybrid":
        n_mamba, n_attn, _ = cfg.layer_plan()
        per = cfg.hybrid_mamba_per_attn
        n_super = n_attn
        rem = n_mamba - n_super * per
        defs["super_mambas"] = stack_defs(
            stack_defs(_mamba_block_defs(cfg), per), n_super)
        defs["shared_attn"] = _attn_block_defs(cfg, "dense")   # weight-shared
        if rem:
            defs["tail_mambas"] = stack_defs(_mamba_block_defs(cfg), rem)
    elif cfg.is_moe:
        n_dense = cfg.moe_first_dense
        n_moe = cfg.num_layers - n_dense
        if n_dense:
            defs["dense_blocks"] = stack_defs(
                _attn_block_defs(cfg, "dense"), n_dense)
        defs["moe_blocks"] = stack_defs(_attn_block_defs(cfg, "moe", run),
                                        n_moe)
        if cfg.mtp_depth:
            defs["mtp"] = {
                "proj": ParamDef((2 * d, d), (None, "embed")),
                "block": _attn_block_defs(cfg, "moe", run),
                "norm": norm_defs(d),
            }
    else:  # dense / vlm / audio
        defs["blocks"] = stack_defs(_attn_block_defs(cfg, "dense"),
                                    cfg.num_layers)
    return defs


def init_model(cfg: ModelConfig, key: jax.Array, run: RunConfig = DEFAULT_RUN):
    return init_params(model_defs(cfg, run), key, cfg.param_dtype)


def abstract_model(cfg: ModelConfig, run: RunConfig = DEFAULT_RUN):
    return abstract_params(model_defs(cfg, run), cfg.param_dtype)


# ---------------------------------------------------------------------------
# block bodies
# ---------------------------------------------------------------------------

def _attn_block(cfg: ModelConfig, run: RunConfig, p, x, positions,
                ffn: str, cache=None, cache_pos=None):
    """Returns (x, aux_loss, new_cache)."""
    h = rms_norm(x, p["ln1"], cfg.norm_eps)
    if cfg.use_mla:
        a, new_cache = apply_mla(cfg, p["attn"], h, positions,
                                 cache=cache, cache_pos=cache_pos)
    else:
        a, new_cache = apply_attention(cfg, p["attn"], h, positions,
                                       cache=cache, cache_pos=cache_pos,
                                       attn_chunk=run.attn_chunk,
                                       mode=run.attn_mode)
    x = x + a
    h = rms_norm(x, p["ln2"], cfg.norm_eps)
    if ffn == "dense":
        out, aux = apply_mlp(p["mlp"], h), jnp.zeros((), jnp.float32)
    else:
        out, aux = apply_moe(cfg, p["moe"], h, impl=run.moe_impl,
                             expert_offset=run.moe_expert_offset)
    return x + out, aux, new_cache


def _mamba_block(cfg: ModelConfig, p, x, state=None):
    h = rms_norm(x, p["ln"], cfg.norm_eps)
    m, new_state = apply_mamba(cfg, p["mamba"], h, state=state)
    return x + m, new_state


# ---------------------------------------------------------------------------
# stacked-block scan helpers
# ---------------------------------------------------------------------------

def _scan_stack(body, x, stacked_params, run: RunConfig):
    """body(p, x) -> (x, aux); returns (x, aux_sum)."""
    if run.remat_policy() is not None:
        # scan already isolates iterations, so CSE prevention is only needed
        # when the stack is unrolled (e.g. cost-measurement lowerings).
        body = jax.checkpoint(body, policy=run.remat_policy(),
                              prevent_cse=not run.scan_blocks)
    if run.scan_blocks:
        def step(carry, p):
            x, aux = carry
            x, a = body(p, x)
            return (x, aux + a), None
        (x, aux), _ = lax.scan(step, (x, jnp.zeros((), jnp.float32)),
                               stacked_params)
        return x, aux
    n = jax.tree_util.tree_leaves(stacked_params)[0].shape[0]
    aux = jnp.zeros((), jnp.float32)
    for i in range(n):
        p = jax.tree_util.tree_map(lambda a: a[i], stacked_params)
        x, a = body(p, x)
        aux = aux + a
    return x, aux


# ---------------------------------------------------------------------------
# forward / loss
# ---------------------------------------------------------------------------

def embed_inputs(cfg: ModelConfig, params, batch) -> Tuple[jax.Array, jax.Array]:
    if cfg.input_mode == "embeddings":
        x = batch["embeds"].astype(jnp.dtype(cfg.param_dtype))
    else:
        x = jnp.take(params["embed"], batch["tokens"], axis=0)
    x = shard(x, "batch", "seq", "embed")
    B, S = x.shape[:2]
    positions = batch.get("positions")
    if positions is None:
        positions = jnp.broadcast_to(jnp.arange(S, dtype=jnp.int32), (B, S))
    return x, positions


def _head_logits(cfg: ModelConfig, params, x_normed,
                 run: RunConfig = DEFAULT_RUN) -> jax.Array:
    head = params["embed"].T if cfg.tie_embeddings else params["head"]
    V = cfg.vocab_size
    hc = int(run.head_chunk)
    if 0 < hc < V and V % hc == 0:
        # column-tiled head matmul: numerically identical to the single
        # einsum, but the lowering carries the tile width — which is how a
        # tuned gemm BLOCK_N becomes visible in the jitted decode step
        logits = jnp.concatenate(
            [jnp.einsum("bsd,dv->bsv", x_normed,
                        lax.slice_in_dim(head, i * hc, (i + 1) * hc, axis=1))
             for i in range(V // hc)], axis=-1)
    else:
        logits = jnp.einsum("bsd,dv->bsv", x_normed, head)
    if cfg.logit_softcap:
        c = cfg.logit_softcap
        logits = c * jnp.tanh(logits / c)
    return shard(logits, "batch", "seq", "vocab")


def _logits(cfg: ModelConfig, params, x,
            run: RunConfig = DEFAULT_RUN) -> jax.Array:
    return _head_logits(cfg, params,
                        rms_norm(x, params["final_norm"], cfg.norm_eps), run)


def forward_hidden(cfg: ModelConfig, params, batch,
                   run: RunConfig = DEFAULT_RUN
                   ) -> Tuple[jax.Array, jax.Array]:
    """Backbone forward up to (but excluding) the LM head.

    Returns (hidden (B,S,d) after final norm, aux_loss scalar)."""
    x, positions = embed_inputs(cfg, params, batch)
    aux = jnp.zeros((), jnp.float32)

    if cfg.family == "ssm":
        def body(p, x):
            x, _ = _mamba_block(cfg, p, x)
            return x, jnp.zeros((), jnp.float32)
        x, _ = _scan_stack(body, x, params["blocks"], run)

    elif cfg.family == "hybrid":
        shared = params["shared_attn"]

        def super_body(p, x):
            def inner(pm, x):
                x, _ = _mamba_block(cfg, pm, x)
                return x, jnp.zeros((), jnp.float32)
            x, _ = _scan_stack(inner, x, p, dataclasses.replace(
                run, scan_blocks=False))
            x, a, _ = _attn_block(cfg, run, shared, x, positions, "dense")
            return x, a
        x, aux1 = _scan_stack(super_body, x, params["super_mambas"], run)
        aux = aux + aux1
        if "tail_mambas" in params:
            def tail(p, x):
                x, _ = _mamba_block(cfg, p, x)
                return x, jnp.zeros((), jnp.float32)
            x, _ = _scan_stack(tail, x, params["tail_mambas"], run)

    elif cfg.is_moe:
        if "dense_blocks" in params:
            def dense_body(p, x):
                x, a, _ = _attn_block(cfg, run, p, x, positions, "dense")
                return x, a
            x, a = _scan_stack(dense_body, x, params["dense_blocks"], run)
            aux = aux + a

        def moe_body(p, x):
            x, a, _ = _attn_block(cfg, run, p, x, positions, "moe")
            return x, a
        x, a = _scan_stack(moe_body, x, params["moe_blocks"], run)
        aux = aux + a

    else:
        def body(p, x):
            x, a, _ = _attn_block(cfg, run, p, x, positions, "dense")
            return x, a
        x, a = _scan_stack(body, x, params["blocks"], run)
        aux = aux + a

    return rms_norm(x, params["final_norm"], cfg.norm_eps), aux


def forward(cfg: ModelConfig, params, batch,
            run: RunConfig = DEFAULT_RUN) -> Tuple[jax.Array, jax.Array]:
    """Full-sequence forward.  Returns (logits (B,S,V), aux_loss scalar)."""
    x, aux = forward_hidden(cfg, params, batch, run)
    return _head_logits(cfg, params, x, run), aux


def cross_entropy(logits: jax.Array, labels: jax.Array,
                  mask: Optional[jax.Array] = None) -> jax.Array:
    lse = jax.nn.logsumexp(logits.astype(jnp.float32), axis=-1)
    gold = jnp.take_along_axis(logits.astype(jnp.float32),
                               labels[..., None], axis=-1)[..., 0]
    nll = lse - gold
    if mask is not None:
        return (nll * mask).sum() / jnp.maximum(mask.sum(), 1.0)
    return nll.mean()


def _ce_from_hidden(cfg: ModelConfig, params, hidden, labels, mask,
                    ce_chunk: int) -> jax.Array:
    """Cross entropy from post-norm hidden states.

    ``ce_chunk > 0``: sequence-chunked — the (B, chunk, V) logits block is
    transient inside a checkpointed scan, so peak memory never holds the
    full (B, S, V) logits (critical when V does not divide the model axis
    and logits are replicated; a large win even when they shard).
    """
    S = hidden.shape[1]
    if not ce_chunk or S % ce_chunk or S <= ce_chunk:
        logits = _head_logits(cfg, params, hidden)
        return cross_entropy(logits, labels, mask)

    n = S // ce_chunk
    split = lambda t: t.reshape((t.shape[0], n, ce_chunk) + t.shape[2:]) \
        .swapaxes(0, 1)
    hs, ls = split(hidden), split(labels)
    ms = split(mask) if mask is not None else jnp.ones_like(ls, jnp.float32)

    @functools.partial(jax.checkpoint,
                       policy=jax.checkpoint_policies.nothing_saveable)
    def chunk_nll(h, l, m):
        logits = _head_logits(cfg, params, h).astype(jnp.float32)
        lse = jax.nn.logsumexp(logits, axis=-1)
        gold = jnp.take_along_axis(logits, l[..., None], axis=-1)[..., 0]
        return ((lse - gold) * m).sum(), m.sum()

    def body(carry, inp):
        h, l, m = inp
        s, c = chunk_nll(h, l, m)
        return (carry[0] + s, carry[1] + c), None

    (nll_sum, count), _ = lax.scan(
        body, (jnp.zeros((), jnp.float32), jnp.zeros((), jnp.float32)),
        (hs, ls, ms))
    return nll_sum / jnp.maximum(count, 1.0)


def loss_fn(cfg: ModelConfig, params, batch,
            run: RunConfig = DEFAULT_RUN,
            aux_weight: float = 0.01) -> Tuple[jax.Array, Dict[str, jax.Array]]:
    hidden, aux = forward_hidden(cfg, params, batch, run)
    labels = batch["labels"]
    mask = batch.get("loss_mask")
    loss = _ce_from_hidden(cfg, params, hidden, labels, mask, run.ce_chunk)
    metrics = {"ce": loss, "aux": aux}
    total = loss + aux_weight * aux

    if cfg.mtp_depth and "mtp" in params and cfg.input_mode == "tokens":
        # DeepSeek-style multi-token prediction: one extra block predicts
        # token t+2 from [h_t ; embed(label_t)].
        x, positions = embed_inputs(cfg, params, batch)
        emb_next = jnp.take(params["embed"], labels, axis=0)
        h = jnp.concatenate([x, emb_next], axis=-1)
        h = jnp.einsum("bsd,dk->bsk", h, params["mtp"]["proj"])
        h, _, _ = _attn_block(cfg, run, params["mtp"]["block"], h,
                              positions, "moe")
        h = rms_norm(h, params["mtp"]["norm"], cfg.norm_eps)
        mtp_labels = jnp.roll(labels, -1, axis=-1)
        mtp_mask = jnp.ones_like(labels, jnp.float32).at[:, -1].set(0.0)
        mtp_loss = _ce_from_hidden(cfg, params, h, mtp_labels, mtp_mask,
                                   run.ce_chunk)
        metrics["mtp"] = mtp_loss
        total = total + cfg.mtp_loss_weight * mtp_loss

    metrics["loss"] = total
    return total, metrics


# ---------------------------------------------------------------------------
# decode (serve_step)
# ---------------------------------------------------------------------------

def cache_defs(cfg: ModelConfig, batch: int, max_len: int) -> Dict[str, Any]:
    if cfg.family == "ssm":
        return {"blocks": stack_defs(mamba_state_defs(cfg, batch),
                                     cfg.num_layers)}
    if cfg.family == "hybrid":
        n_mamba, n_attn, _ = cfg.layer_plan()
        per = cfg.hybrid_mamba_per_attn
        rem = n_mamba - n_attn * per
        out = {
            "super_mambas": stack_defs(
                stack_defs(mamba_state_defs(cfg, batch), per), n_attn),
            "attn": stack_defs(
                attention_cache_defs(cfg, batch, max_len), n_attn),
        }
        if rem:
            out["tail_mambas"] = stack_defs(
                mamba_state_defs(cfg, batch), rem)
        return out
    one = (mla_cache_defs(cfg, batch, max_len) if cfg.use_mla
           else attention_cache_defs(cfg, batch, max_len))
    if cfg.is_moe:
        out = {"moe_blocks": stack_defs(
            one, cfg.num_layers - cfg.moe_first_dense)}
        if cfg.moe_first_dense:
            out["dense_blocks"] = stack_defs(one, cfg.moe_first_dense)
        return out
    return {"blocks": stack_defs(one, cfg.num_layers)}


def init_cache(cfg: ModelConfig, batch: int, max_len: int):
    return init_params(cache_defs(cfg, batch, max_len), jax.random.PRNGKey(0),
                       cfg.param_dtype)


def abstract_cache(cfg: ModelConfig, batch: int, max_len: int):
    return abstract_params(cache_defs(cfg, batch, max_len), cfg.param_dtype)


def decode_step(cfg: ModelConfig, params, cache, tokens_or_embeds,
                pos, run: RunConfig = DEFAULT_RUN
                ) -> Tuple[jax.Array, Any]:
    """One decode step.  tokens: (B, 1) int32 (or (B, 1, d) embeds);
    pos: scalar int32 current position.  Returns (logits (B, V), cache)."""
    if cfg.input_mode == "embeddings":
        x = tokens_or_embeds.astype(jnp.dtype(cfg.param_dtype))
    else:
        x = jnp.take(params["embed"], tokens_or_embeds, axis=0)
    B = x.shape[0]
    positions = jnp.full((B, 1), pos, dtype=jnp.int32)
    new_cache: Dict[str, Any] = {}

    def scan_attn(block_params, block_cache, x, ffn):
        def step(x, inputs):
            p, c = inputs
            x, _, nc = _attn_block(cfg, run, p, x, positions, ffn,
                                   cache=c, cache_pos=pos)
            return x, nc
        return lax.scan(step, x, (block_params, block_cache))

    def scan_mamba(block_params, block_state, x):
        def step(x, inputs):
            p, s = inputs
            x, ns = _mamba_block(cfg, p, x, state=s)
            return x, ns
        return lax.scan(step, x, (block_params, block_state))

    if cfg.family == "ssm":
        x, nc = scan_mamba(params["blocks"], cache["blocks"], x)
        new_cache["blocks"] = nc

    elif cfg.family == "hybrid":
        shared = params["shared_attn"]

        def super_step(x, inputs):
            pm, sm, ca = inputs

            def inner(x, iv):
                p, s = iv
                x, ns = _mamba_block(cfg, p, x, state=s)
                return x, ns
            x, ns = lax.scan(inner, x, (pm, sm))
            x, _, nca = _attn_block(cfg, run, shared, x, positions, "dense",
                                    cache=ca, cache_pos=pos)
            return x, (ns, nca)
        x, (ns, nca) = lax.scan(
            super_step, x,
            (params["super_mambas"], cache["super_mambas"], cache["attn"]))
        new_cache["super_mambas"], new_cache["attn"] = ns, nca
        if "tail_mambas" in params:
            x, nt = scan_mamba(params["tail_mambas"],
                               cache["tail_mambas"], x)
            new_cache["tail_mambas"] = nt

    elif cfg.is_moe:
        if "dense_blocks" in params:
            x, nc = scan_attn(params["dense_blocks"],
                              cache["dense_blocks"], x, "dense")
            new_cache["dense_blocks"] = nc
        x, nc = scan_attn(params["moe_blocks"], cache["moe_blocks"], x, "moe")
        new_cache["moe_blocks"] = nc

    else:
        x, nc = scan_attn(params["blocks"], cache["blocks"], x, "dense")
        new_cache["blocks"] = nc

    logits = _logits(cfg, params, x, run)[:, 0]
    return logits, new_cache
