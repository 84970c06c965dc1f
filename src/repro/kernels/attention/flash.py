"""Tunable Pallas flash attention (online-softmax, chunked KV).

Beyond-paper case study: the paper predates attention workloads, but its
thesis — tile sizes must be tuned per shape and device — applies directly.
Tunables:

  BLOCK_Q / BLOCK_K    VMEM tiles over query / key dimensions
  (causal, scale are static problem properties, not tunables)

The kernel keeps a running max m, normaliser l and accumulator acc in VMEM
scratch across KV blocks (grid dim 1, 'arbitrary'); Q blocks are parallel.

Causal calls skip the KV blocks that no query of the current query block
can see (``last_visible_block``): such a grid step neither computes nor
fetches, since the K/V index maps name the last visible block again and the
pipeline issues no copy for a block it already holds.  Where Sk >= Sq the
outputs equal, bit for bit, those of a kernel that computes every block: a
fully masked block adds exactly zero to a row that has seen a key, and the
visible blocks keep their order.
"""

from __future__ import annotations

import functools
import math
from typing import Any, Dict

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from ...core.profiles import DeviceProfile, kernel_vmem_limit

Config = Dict[str, Any]

#: the kernel's name in the compiled program and the device trace, the same
#: for every configuration
KERNEL_NAME = "flash_attention"

DEFAULT_CONFIG: Config = {"BLOCK_Q": 256, "BLOCK_K": 512}

_NEG = -1e30

#: full float32 products: Mosaic's default for f32 operands is a single
#: bf16 pass, far from the float32 reference
_PRECISION = jax.lax.Precision.HIGHEST


def last_visible_block(qi, *, sq: int, sk: int, bq: int, bk: int):
    """Index of the last KV block that some query of query block ``qi`` can
    see under the causal mask, whose query block ends align with the KV end.

    ``qi`` is a traced grid index or an array of them; a block of queries
    that see no key at all (only where Sq > Sk) gets block 0.
    """
    # lax.div truncates where // floors; the two differ only below zero,
    # which the clip maps to 0 either way, and truncation lowers to one
    # scalar op where floor division adds sign corrections
    last = jax.lax.div(qi * bq + bq - 1 + (sk - sq), bk)
    return jnp.clip(last, 0, sk // bk - 1)


def causal_block_counts(Sq: int, Sk: int, bq: int, bk: int,
                        causal: bool) -> tuple[int, int]:
    """(KV blocks the kernel computes, grid steps) for one head."""
    nq, nk = Sq // bq, Sk // bk
    total = nq * nk
    if not causal:
        return total, total
    last = last_visible_block(jnp.arange(nq), sq=Sq, sk=Sk, bq=bq, bk=bk)
    return int(jnp.sum(last + 1)), total


def _flash_kernel(q_ref, k_ref, v_ref, o_ref, m_ref, l_ref, acc_ref, *,
                  nk: int, scale: float, causal: bool, sq: int, sk: int,
                  bq: int, bk: int):
    """One (query block, KV block) grid step of the online softmax.

    Causal steps past the query block's last visible KV block do nothing;
    ``_init`` and ``_store`` run at the first and last step regardless.
    """
    qi = pl.program_id(0)
    ki = pl.program_id(1)

    @pl.when(ki == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, _NEG)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    def _accumulate():
        q = q_ref[...].astype(jnp.float32)            # (bq, d)
        k = k_ref[...].astype(jnp.float32)            # (bk, d)
        v = v_ref[...].astype(jnp.float32)            # (bk, d)
        s = jnp.dot(q, k.T, preferred_element_type=jnp.float32,
                    precision=_PRECISION) * scale

        if causal:
            # global positions; query block ends align with KV end (prefix cache)
            q_pos = qi * bq + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 0) \
                + (sk - sq)
            k_pos = ki * bk + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 1)
            s = jnp.where(q_pos >= k_pos, s, _NEG)

        m_prev = m_ref[...]                            # (bq, 1)
        m_cur = jnp.max(s, axis=-1, keepdims=True)
        m_new = jnp.maximum(m_prev, m_cur)
        alpha = jnp.exp(m_prev - m_new)
        p = jnp.exp(s - m_new)
        l_ref[...] = l_ref[...] * alpha + p.sum(axis=-1, keepdims=True)
        acc_ref[...] = acc_ref[...] * alpha + jnp.dot(
            p, v, preferred_element_type=jnp.float32, precision=_PRECISION)
        m_ref[...] = m_new

    if causal:
        pl.when(ki <= last_visible_block(qi, sq=sq, sk=sk, bq=bq, bk=bk))(
            _accumulate)
    else:
        _accumulate()

    @pl.when(ki == nk - 1)
    def _store():
        o_ref[...] = (acc_ref[...] /
                      jnp.maximum(l_ref[...], 1e-30)).astype(o_ref.dtype)


def validate_config(config: Config, Sq: int, Sk: int) -> None:
    bq, bk = config["BLOCK_Q"], config["BLOCK_K"]
    if Sq % bq or Sk % bk:
        raise ValueError(f"({Sq},{Sk}) not divisible by blocks ({bq},{bk})")


def make_flash_attention(Sq: int, Sk: int, D: int,
                         config: Config | None = None, *,
                         causal: bool = True, scale: float | None = None,
                         dtype=jnp.float32, interpret: bool = False):
    """Return fn(q, k, v) -> (Sq, D) attention output (single head)."""
    cfg = dict(DEFAULT_CONFIG)
    cfg.update(config or {})
    validate_config(cfg, Sq, Sk)
    bq, bk = cfg["BLOCK_Q"], cfg["BLOCK_K"]
    nk = Sk // bk
    scale = (D ** -0.5) if scale is None else scale

    kernel = functools.partial(
        _flash_kernel, nk=nk, scale=scale, causal=causal,
        sq=Sq, sk=Sk, bq=bq, bk=bk)
    if causal:
        # a skipped step names the block before it again: no copy is issued
        def kv_index(qi, ki):
            last = last_visible_block(qi, sq=Sq, sk=Sk, bq=bq, bk=bk)
            return jnp.minimum(ki, last), 0
    else:
        def kv_index(qi, ki):
            return ki, 0
    kwargs: Dict[str, Any] = {}
    if not interpret:
        # the chip's VMEM budget, the one the static proofs check against
        kwargs["compiler_params"] = pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary"),
            vmem_limit_bytes=kernel_vmem_limit())
    return pl.pallas_call(
        kernel,
        name=KERNEL_NAME,
        grid=(Sq // bq, nk),
        in_specs=[
            pl.BlockSpec((bq, D), lambda qi, ki: (qi, 0)),
            pl.BlockSpec((bk, D), kv_index),
            pl.BlockSpec((bk, D), kv_index),
        ],
        out_specs=pl.BlockSpec((bq, D), lambda qi, ki: (qi, 0)),
        out_shape=jax.ShapeDtypeStruct((Sq, D), dtype),
        scratch_shapes=[
            pltpu.VMEM((bq, 1), jnp.float32),      # running max m
            pltpu.VMEM((bq, 1), jnp.float32),      # normaliser l
            pltpu.VMEM((bq, D), jnp.float32),      # output accumulator
        ],
        interpret=interpret,
        **kwargs)


# ---------------------------------------------------------------------------
# structural cost model
# ---------------------------------------------------------------------------

def vmem_footprint(config: Config, D: int, elt_bytes: int = 4) -> int:
    bq, bk = config["BLOCK_Q"], config["BLOCK_K"]
    depth = int(config.get("PIPELINE_DEPTH", 2))
    io = depth * (bq * D + 2 * bk * D) * elt_bytes
    scratch = (2 * bq + bq * D) * 4 + bq * D * elt_bytes
    return io + scratch


def analytical_time(config: Config, profile: DeviceProfile,
                    Sq: int, Sk: int, D: int, *, causal: bool = True,
                    elt_bytes: int = 4) -> float:
    bq, bk = config["BLOCK_Q"], config["BLOCK_K"]
    if Sq % bq or Sk % bk:
        return math.inf
    if vmem_footprint(config, D, elt_bytes) > profile.vmem_bytes:
        return math.inf
    mxu = profile.mxu_dim
    def _eff(d):
        return d / (math.ceil(d / mxu) * mxu)
    util = _eff(bq) * _eff(bk) * _eff(D)
    frac = 0.5 if causal else 1.0
    flops = 4.0 * Sq * Sk * D * frac
    # softmax VPU work: ~8 ops per score
    vpu_t = 8.0 * Sq * Sk * frac / (profile.peak_flops / 24.0)
    compute_t = flops / (profile.peak_flops * util) + vpu_t
    steps = (Sq // bq) * (Sk // bk) * (frac if causal else 1.0)
    traffic = (Sq * D + steps * 2 * bk * D + Sq * D) * elt_bytes
    memory_t = traffic / profile.hbm_bw
    bubble = steps * profile.grid_step_overhead
    return max(compute_t, memory_t) + bubble + profile.launch_overhead
