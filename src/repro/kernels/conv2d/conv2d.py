"""Tunable Pallas 2D convolution — paper case study 1, TPU-native.

Parameter vocabulary (re-derivation of paper Table II; DESIGN.md §2):

  BLOCK_H / BLOCK_W      output tile per grid step      (paper: X_wg/Y_wg —
                         on TPU the VMEM tile *is* the workgroup)
  SUB_H  1|2|4|8         row-chunking of the tile body  (paper: X_wpt/Y_wpt
                         thread coarsening -> VREG working-set control)
  UNROLL True|False      unroll the filter-tap loops    (paper: UNR)
  HALO_MODE              'materialize' = stage overlapping halo tiles through
                         HBM and convolve in Pallas (paper L$=1/2: explicit
                         local-memory caching with halo); 'xla' = direct
                         lax.conv, hardware-managed caching (paper L$=0)

Analytic-only parameters (pipeline/compiler choices, used by the >3k-config
strategy benchmarks): PAD_W (sublane pad, paper PAD), PIPELINE_DEPTH.

The halo adaptation is the interesting hardware translation: OpenCL threads
cooperatively load a halo into local memory; Pallas BlockSpecs cannot
overlap, so the halo is materialised as overlapping tiles in HBM by a cheap
XLA gather and the kernel streams those tiles through VMEM.  The duplication
factor (1 + 2*hh/BH)(1 + 2*hw/BW) is the TPU form of the paper's
halo-loading overhead, and shrinks as tiles grow — same trade-off, different
memory level.
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
from .ref import conv2d_reference

Config = Dict[str, Any]

#: the kernel's name in the compiled program and the device trace, the same
#: for every configuration
KERNEL_NAME = "conv2d"

DEFAULT_CONFIG: Config = {
    "BLOCK_H": 16, "BLOCK_W": 256, "SUB_H": 1, "UNROLL": True,
    "HALO_MODE": "materialize",
}


# ---------------------------------------------------------------------------
# halo-tile materialisation (the L$ caching strategy, TPU form)
# ---------------------------------------------------------------------------

def _materialise_tiles(image, bh, bw, hh, hw):
    """(H, W) -> (gh, gw, bh + 2*hh, bw + 2*hw) overlapping halo tiles."""
    H, W = image.shape
    gh, gw = -(-H // bh), -(-W // bw)
    hp, wp = gh * bh, gw * bw
    padded = jnp.pad(image, ((hh, hh + hp - H), (hw, hw + wp - W)))

    ii, jj = jnp.meshgrid(jnp.arange(gh), jnp.arange(gw), indexing="ij")

    def slice_tile(i, j):
        return lax.dynamic_slice(padded, (i * bh, j * bw),
                                 (bh + 2 * hh, bw + 2 * hw))

    tiles = jax.vmap(jax.vmap(slice_tile))(ii, jj)
    return tiles, gh, gw


# ---------------------------------------------------------------------------
# kernel body
# ---------------------------------------------------------------------------

def _conv_kernel(tile_ref, filt_ref, o_ref, *, fh: int, fw: int,
                 bh: int, bw: int, sub_h: int, unroll: bool, weight: float):
    # tile_ref: (1, 1, bh + fh - 1, bw + fw - 1) in VMEM; filt_ref:
    # (fh, fw) in SMEM, read one scalar tap at a time.  Windows are static
    # slices: Mosaic refuses dynamic slices of a loaded value.
    tile = tile_ref[0, 0] if unroll else None   # rolled reads the ref
    n_sub = bh // sub_h
    rows = []
    for s in range(n_sub):                      # paper's work-per-thread chunking
        r0 = s * sub_h
        acc = jnp.zeros((sub_h, bw), dtype=jnp.float32)
        if unroll:                              # UNR: fully unrolled taps
            for i in range(fh):
                for j in range(fw):
                    acc += filt_ref[i, j] * tile[r0 + i:r0 + i + sub_h,
                                                 j:j + bw]
        else:                                   # rolled loop over filter rows
            def tap_row(i, acc):
                # a dynamic row offset loads one full-width row at a time
                # (Mosaic cannot prove a multi-row dynamic window
                # aligned); column offsets stay static
                band = [tile_ref[0, 0, pl.ds(r0 + i + r, 1), :]
                        for r in range(sub_h)]
                band = band[0] if sub_h == 1 else jnp.concatenate(band, 0)
                for j in range(fw):
                    acc += filt_ref[i, j] * band[:, j:j + bw]
                return acc
            acc = lax.fori_loop(0, fh, tap_row, acc)
        rows.append(acc)
    out = rows[0] if n_sub == 1 else jnp.concatenate(rows, axis=0)
    o_ref[...] = (weight * out).astype(o_ref.dtype)


# ---------------------------------------------------------------------------
# builder
# ---------------------------------------------------------------------------

def validate_config(config: Config, H: int, W: int, Fh: int, Fw: int) -> None:
    bh, bw = config["BLOCK_H"], config["BLOCK_W"]
    if config["BLOCK_H"] % config["SUB_H"]:
        raise ValueError("BLOCK_H must divide by SUB_H")
    if bh <= 0 or bw <= 0:
        raise ValueError("blocks must be positive")
    if config["HALO_MODE"] not in ("materialize", "xla"):
        raise ValueError(f"bad HALO_MODE {config['HALO_MODE']!r}")


def make_conv2d(H: int, W: int, Fh: int, Fw: int,
                config: Config | None = None, weight: float = 1.0,
                interpret: bool = False):
    """Return fn(image, filt) -> (H, W) convolved output."""
    cfg = dict(DEFAULT_CONFIG)
    cfg.update(config or {})
    validate_config(cfg, H, W, Fh, Fw)

    if cfg["HALO_MODE"] == "xla":
        # L$ = 0: no explicit staging, let XLA/hardware manage locality.
        def xla_conv(image, filt):
            return conv2d_reference(image, filt, weight=weight)
        return xla_conv

    bh, bw = cfg["BLOCK_H"], cfg["BLOCK_W"]
    hh, hw = Fh // 2, Fw // 2
    th, tw = bh + 2 * hh, bw + 2 * hw

    kernel = functools.partial(
        _conv_kernel, fh=Fh, fw=Fw, bh=bh, bw=bw, sub_h=cfg["SUB_H"],
        unroll=bool(cfg["UNROLL"]), weight=weight)

    def conv(image, filt):
        tiles, gh, gw = _materialise_tiles(image, bh, bw, hh, hw)
        kwargs: Dict[str, Any] = {}
        if not interpret:
            # the chip's VMEM budget, the one the static proofs check against
            kwargs["compiler_params"] = pltpu.CompilerParams(
                dimension_semantics=("parallel", "parallel"),
                vmem_limit_bytes=kernel_vmem_limit())
        out = pl.pallas_call(
            kernel,
            name=KERNEL_NAME,
            grid=(gh, gw),
            in_specs=[
                pl.BlockSpec((1, 1, th, tw), lambda i, j: (i, j, 0, 0)),
                pl.BlockSpec(memory_space=pltpu.SMEM),
            ],
            out_specs=pl.BlockSpec((bh, bw), lambda i, j: (i, j)),
            out_shape=jax.ShapeDtypeStruct((gh * bh, gw * bw), image.dtype),
            interpret=interpret,
            **kwargs)(tiles, filt)
        return out[:H, :W]

    return conv


# ---------------------------------------------------------------------------
# structural cost model
# ---------------------------------------------------------------------------

def vmem_footprint(config: Config, Fh: int, Fw: int,
                   elt_bytes: int = 4) -> int:
    cfg = dict(DEFAULT_CONFIG)
    cfg.update(config)
    if cfg["HALO_MODE"] == "xla":
        return 0
    bh, bw = cfg["BLOCK_H"], cfg["BLOCK_W"]
    depth = int(cfg.get("PIPELINE_DEPTH", 2))
    pad_w = int(cfg.get("PAD_W", 0)) * 128
    tile = (bh + Fh - 1) * (bw + Fw - 1 + pad_w) * elt_bytes
    out = bh * bw * elt_bytes
    return depth * tile + 2 * out             # the filter sits in SMEM


def analytical_time(config: Config, profile: DeviceProfile,
                    H: int, W: int, Fh: int, Fw: int,
                    elt_bytes: int = 4) -> float:
    """Pipeline model reproducing the paper's conv search-space shape.

    Convolution taps run on the VPU (8x128 lanes), not the MXU, so the
    compute ceiling is the VPU rate; small filters are memory-bound and big
    filters compute-bound — the paper's Fig. 6 arc.  The two HALO modes
    reproduce Table II's L$ flip: 'xla' (hardware caching) wins for 3x3,
    'materialize' (explicit staging) wins once taps dominate.
    """
    cfg = dict(DEFAULT_CONFIG)
    cfg.update(config)
    bh, bw = cfg["BLOCK_H"], cfg["BLOCK_W"]
    if bh % cfg["SUB_H"]:
        return math.inf
    flops = (1.0 + 2.0 * Fh * Fw) * H * W
    vpu_flops = profile.peak_flops / 24.0       # VPU : MXU throughput ratio

    if cfg["HALO_MODE"] == "xla":
        # generic XLA conv lowering: decent but untiled for this exact shape
        compute_t = flops / (vpu_flops * 0.45)
        memory_t = 2.0 * H * W * elt_bytes / profile.hbm_bw
        return max(compute_t, memory_t) + profile.launch_overhead

    if vmem_footprint(cfg, Fh, Fw, elt_bytes) > profile.vmem_bytes:
        return math.inf
    gh, gw = -(-H // bh), -(-W // bw)
    # VPU efficiency: lane alignment of the minor dim, sublane of rows
    lane_eff = bw / (math.ceil(bw / 128) * 128)
    sub_eff = min(1.0, cfg["SUB_H"] * bh / (math.ceil(bh / 8) * 8) / bh * 8) \
        if bh < 8 else 1.0
    unroll_gain = 1.0 if cfg["UNROLL"] else 0.72   # rolled taps re-slice filter
    subh_pen = 1.0 + 0.02 * max(0, int(math.log2(max(cfg["SUB_H"], 1))))
    eff = 0.85 * lane_eff * sub_eff * unroll_gain / subh_pen
    compute_t = flops / (vpu_flops * eff)

    dup = (1.0 + (Fh - 1) / bh) * (1.0 + (Fw - 1) / bw)
    # read image + write tiles + read tiles + write out
    traffic = H * W * elt_bytes * (1.0 + 2.0 * dup + 1.0)
    memory_t = traffic / profile.hbm_bw

    depth = int(cfg.get("PIPELINE_DEPTH", 2))
    overlap = {2: 1.0, 3: 0.97, 4: 0.96}.get(depth, 1.0)
    bubble_t = gh * gw * profile.grid_step_overhead / depth
    return max(compute_t, memory_t * overlap) + bubble_t \
        + profile.launch_overhead
