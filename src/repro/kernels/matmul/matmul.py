"""Tunable Pallas GEMM — the paper's matrix-multiplication case study on TPU.

Parameter vocabulary (TPU re-derivation of paper Table IV; see DESIGN.md §2):

  BLOCK_M / BLOCK_N / BLOCK_K   VMEM tile sizes       (paper: M_wg/N_wg/K_wg)
  GRID_ORDER  'mn' | 'nm'       outer-loop traversal  (paper: implicit in
                                workgroup scheduling)
  INNER_STEPS 1|2|4|8           K sub-step unroll     (paper: K_wi unroll)
  ACC_DTYPE   float32|bfloat16  accumulator precision (paper: no analogue —
                                MXU-specific; bf16 accumulation trades
                                accuracy for VMEM, verification catches it
                                when it breaks)
  ACC_IN_OUTPUT True|False      accumulate into the output block instead of a
                                scratch buffer (saves one BMxBN VMEM buffer;
                                requires ACC_DTYPE == out dtype)
  TRANS_A     True|False        A arrives K-major (paper computes A^T B)

Analytic-model-only parameters (affect the TPUAnalyticalEvaluator, not the
kernel build — they model compiler/pipeline choices Pallas fixes for us):
PIPELINE_DEPTH, NBUF_OUT, PACK.  The benchmark space that reproduces the
paper's ">200k configurations" claim includes them; build() ignores them.
"""

from __future__ import annotations

import functools
import math
from typing import Any, Dict

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from ...core.profiles import DeviceProfile, kernel_vmem_limit

Config = Dict[str, Any]

#: the kernel's name in the compiled program and the device trace, the same
#: for every configuration
KERNEL_NAME = "gemm"

DEFAULT_CONFIG: Config = {
    "BLOCK_M": 512, "BLOCK_N": 512, "BLOCK_K": 512,
    "GRID_ORDER": "mn", "INNER_STEPS": 1,
    "ACC_DTYPE": "float32", "ACC_IN_OUTPUT": False, "TRANS_A": False,
}


#: full float32 products: Mosaic's default for f32 operands is a single
#: bf16 pass (normwise error ~2e-3 at 2048^3, far from f32 accuracy)
_PRECISION = lax.Precision.HIGHEST


def _dtype(name: str):
    return jnp.dtype(name)


# ---------------------------------------------------------------------------
# kernel bodies
# ---------------------------------------------------------------------------

def accumulate_k_step(acc_ref, a_ref, b_ref, *, first, inner_steps: int = 1,
                      acc_dtype=jnp.float32, trans_a: bool = False):
    """One K step of a tiled product: ``acc_ref += a @ b`` at HIGHEST, with
    ``acc_ref`` zeroed first where ``first`` (the grid's first K step).

    The GEMM's tile loop, shared with the kernels built on its tiles
    (``kernels/moe/grouped.py``).  ``a_ref`` holds a (BM, BK) block, or
    (BK, BM) when ``trans_a``; ``b_ref`` a (BK, BN) block.
    """

    @pl.when(first)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    a = a_ref[...]
    if trans_a:
        a = a.T                     # block arrives (BK, BM): transpose in VREGs
    b = b_ref[...]
    if inner_steps == 1:
        acc_ref[...] += jnp.dot(a, b, preferred_element_type=acc_dtype,
                                precision=_PRECISION)
    else:
        # K_wi unroll: split the BK dimension into inner_steps sub-dots.
        # On TPU this shortens MXU dependency chains for small blocks.
        step = a.shape[1] // inner_steps
        acc = acc_ref[...]
        for s in range(inner_steps):
            acc += jnp.dot(a[:, s * step:(s + 1) * step],
                           b[s * step:(s + 1) * step, :],
                           preferred_element_type=acc_dtype,
                           precision=_PRECISION)
        acc_ref[...] = acc


def _mm_kernel_scratch(a_ref, b_ref, o_ref, acc_ref, *, nk: int,
                       inner_steps: int, acc_dtype, trans_a: bool):
    """K-accumulation into a VMEM scratch accumulator."""
    accumulate_k_step(acc_ref, a_ref, b_ref, first=pl.program_id(2) == 0,
                      inner_steps=inner_steps, acc_dtype=acc_dtype,
                      trans_a=trans_a)

    @pl.when(pl.program_id(2) == nk - 1)
    def _store():
        o_ref[...] = acc_ref[...].astype(o_ref.dtype)


def _mm_kernel_inplace(a_ref, b_ref, o_ref, *, nk: int, inner_steps: int,
                       acc_dtype, trans_a: bool):
    """K-accumulation directly into the output block (ACC_IN_OUTPUT)."""
    accumulate_k_step(o_ref, a_ref, b_ref, first=pl.program_id(2) == 0,
                      inner_steps=inner_steps, acc_dtype=acc_dtype,
                      trans_a=trans_a)


# ---------------------------------------------------------------------------
# pallas_call builder
# ---------------------------------------------------------------------------

def validate_config(config: Config, M: int, N: int, K: int) -> None:
    bm, bn, bk = config["BLOCK_M"], config["BLOCK_N"], config["BLOCK_K"]
    if M % bm or N % bn or K % bk:
        raise ValueError(f"dims ({M},{N},{K}) not divisible by blocks "
                         f"({bm},{bn},{bk})")
    if bk % config["INNER_STEPS"]:
        raise ValueError("BLOCK_K must divide by INNER_STEPS")
    if config["ACC_IN_OUTPUT"] and config["ACC_DTYPE"] != "float32":
        raise ValueError("ACC_IN_OUTPUT requires float32 accumulation")


def make_matmul(M: int, N: int, K: int, config: Config | None = None,
                out_dtype=jnp.float32, interpret: bool = False):
    """Return fn(a, b) -> a @ b with the given tile configuration.

    ``a`` is (M, K), or (K, M) when TRANS_A (paper's A^T input layout).
    """
    cfg = dict(DEFAULT_CONFIG)
    cfg.update(config or {})
    validate_config(cfg, M, N, K)
    bm, bn, bk = cfg["BLOCK_M"], cfg["BLOCK_N"], cfg["BLOCK_K"]
    trans_a = bool(cfg["TRANS_A"])
    acc_dtype = _dtype(cfg["ACC_DTYPE"])
    nk = K // bk
    gm, gn = M // bm, N // bn

    # grid traversal order: 'mn' = M outer; 'nm' = N outer.  K is always the
    # innermost ("arbitrary") dimension so accumulation steps are consecutive.
    if cfg["GRID_ORDER"] == "mn":
        grid = (gm, gn, nk)
        a_idx = (lambda m, n, k: (k, m)) if trans_a else (lambda m, n, k: (m, k))
        b_idx = lambda m, n, k: (k, n)
        o_idx = lambda m, n, k: (m, n)
    elif cfg["GRID_ORDER"] == "nm":
        grid = (gn, gm, nk)
        a_idx = (lambda n, m, k: (k, m)) if trans_a else (lambda n, m, k: (m, k))
        b_idx = lambda n, m, k: (k, n)
        o_idx = lambda n, m, k: (m, n)
    else:
        raise ValueError(f"bad GRID_ORDER {cfg['GRID_ORDER']!r}")

    a_block = (bk, bm) if trans_a else (bm, bk)
    in_specs = [pl.BlockSpec(a_block, a_idx),
                pl.BlockSpec((bk, bn), b_idx)]
    out_spec = pl.BlockSpec((bm, bn), o_idx)
    out_shape = jax.ShapeDtypeStruct((M, N), out_dtype)

    common = dict(nk=nk, inner_steps=cfg["INNER_STEPS"],
                  acc_dtype=acc_dtype, trans_a=trans_a)
    kwargs: Dict[str, Any] = dict(
        grid=grid, in_specs=in_specs, out_specs=out_spec,
        out_shape=out_shape, interpret=interpret)
    if not interpret:
        # M/N grid dims are embarrassingly parallel; K carries the
        # accumulator dependency.  The VMEM limit is the chip's budget,
        # the one the static proofs check footprints against (the compiler's own
        # default scoped limit is far smaller).
        kwargs["compiler_params"] = pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary"),
            vmem_limit_bytes=kernel_vmem_limit())

    if cfg["ACC_IN_OUTPUT"]:
        kernel = functools.partial(_mm_kernel_inplace, **common)
    else:
        kernel = functools.partial(_mm_kernel_scratch, **common)
        kwargs["scratch_shapes"] = [pltpu.VMEM((bm, bn), acc_dtype)]

    return pl.pallas_call(kernel, name=KERNEL_NAME, **kwargs)


# ---------------------------------------------------------------------------
# structural cost models (feed TPUAnalyticalEvaluator and auto-constraints)
# ---------------------------------------------------------------------------

def vmem_footprint(config: Config, elt_bytes: int = 4,
                   out_bytes: int = 4) -> int:
    """Bytes of VMEM the configuration claims (double-buffered inputs)."""
    cfg = dict(DEFAULT_CONFIG)
    cfg.update(config)
    bm, bn, bk = cfg["BLOCK_M"], cfg["BLOCK_N"], cfg["BLOCK_K"]
    nbuf_in = int(cfg.get("PIPELINE_DEPTH", 2))
    nbuf_out = int(cfg.get("NBUF_OUT", 1))
    acc_bytes = jnp.dtype(cfg["ACC_DTYPE"]).itemsize
    buf = nbuf_in * (bm * bk + bk * bn) * elt_bytes
    out = nbuf_out * bm * bn * out_bytes
    acc = 0 if cfg["ACC_IN_OUTPUT"] else bm * bn * acc_bytes
    return buf + out + acc


def analytical_time(config: Config, profile: DeviceProfile,
                    M: int, N: int, K: int, elt_bytes: int = 4) -> float:
    """Structural pipeline model: max(MXU time, HBM time) per grid step.

    Captures the paper's search-space shape on TPU: VMEM cliff (infeasible),
    MXU misalignment penalties, HBM refetch growth as blocks shrink, pipeline
    ramp overheads for deep grids, and bf16-accumulation speedup.
    """
    cfg = dict(DEFAULT_CONFIG)
    cfg.update(config)
    bm, bn, bk = cfg["BLOCK_M"], cfg["BLOCK_N"], cfg["BLOCK_K"]
    if M % bm or N % bn or K % bk or bk % cfg["INNER_STEPS"]:
        return math.inf
    if cfg["ACC_IN_OUTPUT"] and cfg["ACC_DTYPE"] != "float32":
        return math.inf
    if vmem_footprint(cfg, elt_bytes) > profile.vmem_bytes:
        return math.inf                       # the paper's local-memory cliff

    mxu = profile.mxu_dim
    # MXU utilisation: padding waste for non-multiples of the systolic tile
    def _eff(d: int) -> float:
        return d / (math.ceil(d / mxu) * mxu)
    util = _eff(bm) * _eff(bn) * _eff(min(bk, mxu * 4))
    # TPU MXUs always accumulate in f32; a bf16 accumulator only saves VMEM
    # (already charged in the footprint) plus a small epilogue-cast saving.
    acc_speed = 1.0 if cfg["ACC_DTYPE"] == "float32" else 1.02
    # very deep inner unroll wastes VREGs; mild penalty beyond 4
    unroll_pen = 1.0 + 0.03 * max(0, cfg["INNER_STEPS"] - 4)
    # PACK models sublane packing of the minor dim (1 = none)
    pack_gain = {1: 1.0, 2: 1.06, 4: 1.09}.get(int(cfg.get("PACK", 1)), 1.0)

    flops = 2.0 * M * N * K
    # effective rate never exceeds the physical roofline
    rate = profile.peak_flops * min(
        1.0, util * acc_speed * pack_gain / unroll_pen)
    compute_t = flops / rate

    gm, gn, nk = M // bm, N // bn, K // bk
    steps = gm * gn * nk
    # HBM traffic: every (m,n,k) step streams one A and one B block; the
    # output block is written once per (m,n).  TRANS_A loads are contiguous
    # K-major (slightly cheaper on TPU, matching the paper's preference).
    a_bytes = steps * bm * bk * elt_bytes * (0.96 if cfg["TRANS_A"] else 1.0)
    b_bytes = steps * bk * bn * elt_bytes
    o_bytes = gm * gn * bm * bn * elt_bytes
    memory_t = (a_bytes + b_bytes + o_bytes) / profile.hbm_bw

    depth = int(cfg.get("PIPELINE_DEPTH", 2))
    # pipeline: deeper buffering hides more copy latency (memory side only —
    # the MXU floor is physical); costs VMEM (charged in the footprint).
    overlap = {2: 1.0, 3: 0.97, 4: 0.955}.get(depth, 1.0)
    bubble_t = steps * profile.grid_step_overhead / depth
    t = max(compute_t, memory_t * overlap) + bubble_t \
        + profile.launch_overhead
    return t


def flops(M: int, N: int, K: int) -> float:
    return 2.0 * M * N * K
