"""Tunable Pallas grouped SwiGLU: the routed experts of a Mixture-of-Experts
layer over ragged, data-dependent groups (DeepSeek-V3's expert block).

How many rows each expert gets is known only on the device, after routing.
The op keeps the (token, expert) pairs routed to the experts this chip
holds, orders them by expert, and runs two Pallas kernels on the GEMM's
tiles (``matmul.accumulate_k_step``):

  ``moe_experts_gate_up``  h = silu(x W_g,e) * (x W_i,e)   (rows, m)
  ``moe_experts_down``     y = h W_o,e                      (rows, d)

then a third, ``moe_experts_combine``, adds each pair's ``weight * y`` into
its token's output row (an XLA scatter-add there serialises on the tokens
that repeat, and its time depends on the routing).

Tunables:

  BLOCK_M   the row tile, which is also the padding unit of each group
  BLOCK_N   output column block of both products
  BLOCK_K   contraction block of both products

Groups.  Each expert's rows start on a ``BLOCK_M`` boundary, so every row
tile belongs to one expert; the tile's expert and the number of tiles in
use are scalar-prefetched, and the weight blocks' index maps read them.  A
tile past the last group's end computes nothing (``pl.when``) and names the
blocks of the last computed tile again, so the pipeline issues no copy.

Dropless, in rounds.  The pairs are processed in rounds of at most
``round_rows`` pairs (5/4 of the balanced load, in whole tiles), each
through one fixed-size padded buffer: the first round always runs, and a
``lax.while_loop`` runs as many more as the routed pairs need, none in
practice.  No pair is dropped
whatever the load, and no buffer is sized for the worst case of N * k rows.
(Gathering rows inside the kernel by scalar-prefetched indices would save
the permuted copy of x, but one DMA per row of width BLOCK_K is far below
the DMA engine's efficient size.)

Combine.  Each step of the combine kernel writes a block of tokens, whose
slots mostly hold no pair computed here.  A sort along each block's slots
first puts the live pairs ahead, in token order, and counts them; the
kernel walks that count alone.  HBM holds a float32 (rows, d) array in
tiles of 8 rows, so one row cannot be copied alone: the kernel copies each
pair's aligned 8-row block of y, keeps a ring of ``_RING`` such copies in
flight while it adds earlier pairs, and adds each pair's row, rolled to its
token's sublane, into the token's aligned 8-row group of the output.
"""

from __future__ import annotations

import functools
import math
from typing import Any, Dict, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from ...core.profiles import DeviceProfile, kernel_vmem_limit
from ..matmul.matmul import accumulate_k_step

Config = Dict[str, Any]

#: the kernels' names in the compiled program and the device trace, the
#: same for every configuration
GATE_UP_NAME = "moe_experts_gate_up"
DOWN_NAME = "moe_experts_down"
COMBINE_NAME = "moe_experts_combine"

#: a 1-D int32 or float32 array lies in HBM in tiles of this many entries,
#: so a step's SMEM block of its pairs' rows and weights is whole tiles
_SMEM_TILE = 1024
#: 8-row copies of y the combine kernel keeps in flight
_RING = 16
#: rows in one tile of a float32 array in HBM
_SUBLANES = 8

DEFAULT_CONFIG: Config = {"BLOCK_M": 256, "BLOCK_N": 512, "BLOCK_K": 512}

#: one round takes this share of the balanced load N * k * E_held / E
ROUND_SLACK = 1.25


# ---------------------------------------------------------------------------
# tile counts (host side)
# ---------------------------------------------------------------------------

def grouped_tile_counts(group_sizes: Sequence[int], block_m: int,
                        grid_tiles: int | None = None) -> Tuple[int, int, int]:
    """(tiles computed, tiles skipped, padded rows) of one round whose
    groups hold ``group_sizes`` rows, each padded up to ``block_m``, in a
    grid of ``grid_tiles`` row tiles (the computed ones where None)."""
    computed = sum(-(-int(s) // block_m) for s in group_sizes)
    padded = computed * block_m - sum(int(s) for s in group_sizes)
    grid = computed if grid_tiles is None else grid_tiles
    if grid < computed:
        raise ValueError(f"{computed} tiles do not fit a grid of {grid}")
    return computed, grid - computed, padded


def combine_pair_counts(pair_rows: Sequence[int],
                        k: int) -> Tuple[int, int, int]:
    """(pairs walked, slots skipped, blocks with no pair) of one combine
    call: ``pair_rows`` holds each of the N * k (token, expert) slots' row
    of y, token-major, and -1 for a slot with none in this round.  The
    kernel walks a block's live pairs alone; the slots of the padding
    tokens that fill the last block count as skipped."""
    rows = np.asarray(pair_rows).reshape(-1)
    slots = combine_tokens(k) * k
    blocks = -(-rows.size // slots)
    live = np.zeros(blocks * slots, bool)
    live[:rows.size] = rows >= 0
    per_block = live.reshape(blocks, slots).sum(axis=1)
    walked = int(per_block.sum())
    return walked, blocks * slots - walked, int((per_block == 0).sum())


def round_rows(N: int, k: int, E: int, groups: int, block_m: int) -> int:
    """Pairs one round takes: ``ROUND_SLACK`` times the balanced load, in
    whole tiles, and no more than the pairs that can be routed here."""
    most = N * min(k, groups)
    balanced = N * k * groups / E
    rows = math.ceil(math.ceil(balanced * ROUND_SLACK) / block_m) * block_m
    return max(block_m, min(rows, math.ceil(most / block_m) * block_m))


def grid_tiles(rows: int, groups: int, block_m: int) -> int:
    """Row tiles in one round's grid: the tiles of the worst split of
    ``rows`` pairs over ``groups`` groups, one group taking all but one row
    for each of the others."""
    ones = min(groups - 1, rows - 1)
    return grouped_tile_counts([rows - ones] + [1] * ones, block_m)[0]


# ---------------------------------------------------------------------------
# kernel bodies
# ---------------------------------------------------------------------------

def _gate_up_kernel(group_ref, used_ref, x_ref, wg_ref, wi_ref, h_ref,
                    accg_ref, accu_ref, *, nk: int):
    """One (row tile, column block, K step) of h = silu(x W_g) * (x W_i)."""
    kk = pl.program_id(2)

    @pl.when(pl.program_id(0) < used_ref[0])
    def _compute():
        accumulate_k_step(accg_ref, x_ref, wg_ref, first=kk == 0)
        accumulate_k_step(accu_ref, x_ref, wi_ref, first=kk == 0)

        @pl.when(kk == nk - 1)
        def _store():
            g = accg_ref[...]
            h_ref[...] = (g * jax.nn.sigmoid(g)
                          * accu_ref[...]).astype(h_ref.dtype)


def _down_kernel(group_ref, used_ref, h_ref, wo_ref, y_ref, acc_ref, *,
                 nk: int):
    """One (row tile, column block, K step) of y = h W_o."""
    kk = pl.program_id(2)

    @pl.when(pl.program_id(0) < used_ref[0])
    def _compute():
        accumulate_k_step(acc_ref, h_ref, wo_ref, first=kk == 0)

        @pl.when(kk == nk - 1)
        def _store():
            y_ref[...] = acc_ref[...].astype(y_ref.dtype)


def _grouped_call(body, name: str, *, tiles: int, K: int, N: int,
                  weights: int, bm: int, bn: int, bk: int, dtype,
                  interpret: bool):
    """pallas_call of ``body`` over (row tile, column block, K step): the
    row tiles of a (tiles * bm, K) operand times ``weights`` stacked
    per-expert (E_held, K, N) matrices, each tile against its expert's.
    Takes (tile_group, used, rows, *weights); returns (tiles * bm, N)."""
    nn, nk = N // bn, K // bk

    def steps(t, j, kk, used):
        """The blocks a step reads: a skipped tile (t >= used) names those
        of the last computed step again, so no copy is issued."""
        live = t < used[0]
        return (jnp.minimum(t, jnp.maximum(used[0] - 1, 0)),
                jnp.where(live, j, nn - 1), jnp.where(live, kk, nk - 1))

    def row_index(t, j, kk, group, used):
        t, _, kk = steps(t, j, kk, used)
        return t, kk

    def weight_index(t, j, kk, group, used):
        t, j, kk = steps(t, j, kk, used)
        return group[t], kk, j

    def out_index(t, j, kk, group, used):
        t, j, _ = steps(t, j, kk, used)
        return t, j

    kwargs: Dict[str, Any] = {}
    if not interpret:
        # skipped tiles revisit the last computed tile's output block, so
        # the steps run in order on one core
        kwargs["compiler_params"] = pltpu.CompilerParams(
            dimension_semantics=("arbitrary", "arbitrary", "arbitrary"),
            vmem_limit_bytes=kernel_vmem_limit())
    return pl.pallas_call(
        functools.partial(body, nk=nk),
        name=name,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2,
            grid=(tiles, nn, nk),
            in_specs=[pl.BlockSpec((bm, bk), row_index)]
            + [pl.BlockSpec((None, bk, bn), weight_index)] * weights,
            out_specs=pl.BlockSpec((bm, bn), out_index),
            scratch_shapes=[pltpu.VMEM((bm, bn), jnp.float32)] * weights),
        out_shape=jax.ShapeDtypeStruct((tiles * bm, N), dtype),
        interpret=interpret,
        **kwargs)


def _combine_kernel(count_ref, slots_ref, rows_ref, w_ref, y_hbm, *refs,
                    k: int, accumulate: bool):
    """One block of tokens: out = prev (or zero) + each live pair's
    ``w * y[row]``, added into its token's row.  ``count_ref`` (scalar
    prefetch) holds each block's count of live pairs, ``slots_ref`` (SMEM)
    the block's slots with the live ones first, in slot order, which is
    token order; ``rows_ref`` and ``w_ref`` (SMEM) each slot's row of y and
    weight.  Only the live pairs are walked: a ring of ``_RING`` buffers
    keeps the next pairs' 8-row blocks of y in flight while earlier ones
    are added."""
    if accumulate:
        prev_ref, out_ref, buf, sem = refs
        out_ref[...] = prev_ref[...]
    else:
        out_ref, buf, sem = refs
        out_ref[...] = jnp.zeros_like(out_ref)
    n = count_ref[pl.program_id(0)]
    sublane = lax.broadcasted_iota(jnp.int32, buf.shape[1:], 0)

    def copy(j):
        slot = lax.rem(j, _RING)
        row = rows_ref[slots_ref[j]]
        return pltpu.make_async_copy(y_hbm.at[lax.div(row, _SUBLANES)],
                                     buf.at[slot], sem.at[slot])

    for j in range(_RING - 1):
        pl.when(j < n)(lambda j=j: copy(j).start())

    def add(j, carry):
        @pl.when(j + _RING - 1 < n)
        def _next():
            copy(j + _RING - 1).start()

        copy(j).wait()
        slot = slots_ref[j]
        tok = lax.div(slot, k)
        sub = lax.rem(tok, _SUBLANES)
        # move the pair's row of its block to its token's sublane
        shift = lax.rem(sub - lax.rem(rows_ref[slot], _SUBLANES) + _SUBLANES,
                        _SUBLANES)
        row = pltpu.roll(buf[lax.rem(j, _RING)], shift, 0)
        group = pl.ds(pl.multiple_of(tok - sub, _SUBLANES), _SUBLANES)
        out_ref[group, :] += jnp.where(sublane == sub, w_ref[slot] * row,
                                       0.0)
        return carry

    lax.fori_loop(0, n, add, 0)


def combine_tokens(k: int) -> int:
    """Tokens one step of the combine kernel writes: the fewest whose k
    pairs each fill whole SMEM tiles (128 at k = 8)."""
    return _SMEM_TILE // math.gcd(_SMEM_TILE, k)


def _combine_call(N: int, d: int, k: int, *, accumulate: bool,
                  interpret: bool):
    """pallas_call of the combine: takes each block's count of live pairs
    and its slots, live first (``_live_first``), every pair's row and
    weight (N * k, token-major), y as (rows / 8, 8, d), and with
    ``accumulate`` the output so far, which it updates in place; returns
    (N, d) float32."""
    bt = combine_tokens(k)
    pairs = pl.BlockSpec((bt * k,), lambda t, count: (t,),
                         memory_space=pltpu.SMEM)
    tokens = pl.BlockSpec((bt, d), lambda t, count: (t, 0))
    kwargs: Dict[str, Any] = {}
    if accumulate:
        kwargs["input_output_aliases"] = {5: 0}
    if not interpret:
        kwargs["compiler_params"] = pltpu.CompilerParams(
            dimension_semantics=("arbitrary",),
            vmem_limit_bytes=kernel_vmem_limit())
    return pl.pallas_call(
        functools.partial(_combine_kernel, k=k, accumulate=accumulate),
        name=COMBINE_NAME,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=(N // bt,),
            in_specs=[pairs] * 3 + [pl.BlockSpec(memory_space=pl.ANY)]
            + [tokens] * accumulate,
            out_specs=tokens,
            scratch_shapes=[pltpu.VMEM((_RING, _SUBLANES, d), jnp.float32),
                            pltpu.SemaphoreType.DMA((_RING,))]),
        out_shape=jax.ShapeDtypeStruct((N, d), jnp.float32),
        interpret=interpret,
        **kwargs)


def _live_first(pair_row, k: int):
    """(count, slots) for the combine: each block of ``combine_tokens(k)``
    tokens' count of pairs with a row (>= 0), and its slots with those
    first, in slot order."""
    slots = combine_tokens(k) * k
    live = pair_row.reshape(-1, slots) >= 0
    slot = lax.broadcasted_iota(jnp.int32, live.shape, 1)
    order = lax.sort(jnp.where(live, slot, slot + slots), dimension=1)
    return (jnp.sum(live, axis=1, dtype=jnp.int32),
            lax.rem(order, slots).reshape(-1))


def make_combine(N: int, d: int, k: int, *, interpret: bool = False):
    """Return fn(pair_row, pair_weight, y, out=None) -> (N, d) float32:
    ``out`` (zero where None) plus, for each of the N * k pairs (token-major)
    with a row of ``y`` (``pair_row`` >= 0), ``pair_weight * y[pair_row]``
    in its token's row, each token's pairs added in slot order.  N is a
    whole number of ``combine_tokens(k)``."""
    first, more = (_combine_call(N, d, k, accumulate=acc,
                                 interpret=interpret)
                   for acc in (False, True))

    def fn(pair_row, pair_weight, y, out=None):
        args = _live_first(pair_row, k) + (
            pair_row, pair_weight,
            y.astype(jnp.float32).reshape(-1, _SUBLANES, d))
        return first(*args) if out is None else more(*args, out)

    return fn


# ---------------------------------------------------------------------------
# the op: permute, experts, combine
# ---------------------------------------------------------------------------

def validate_config(config: Config, d: int, m: int, k: int) -> None:
    """Refuse blocks that do not tile the widths, and a width whose combine
    kernel does not fit the kernels' VMEM limit."""
    bm, bn, bk = config["BLOCK_M"], config["BLOCK_N"], config["BLOCK_K"]
    if d % bn or m % bn or d % bk or m % bk:
        raise ValueError(f"d={d}, m={m} not divisible by BLOCK_N={bn} and "
                         f"BLOCK_K={bk}")
    if bm % _SUBLANES:
        raise ValueError(f"BLOCK_M={bm} is not a multiple of {_SUBLANES}")
    if combine_vmem(d, k) > kernel_vmem_limit():
        raise ValueError(f"the combine kernel needs {combine_vmem(d, k)} B "
                         f"of VMEM at d={d}, k={k}; the limit is "
                         f"{kernel_vmem_limit()} B")


def make_moe_experts(N: int, d: int, m: int, E: int, E_held: int, k: int,
                     config: Config | None = None, *, dtype=jnp.float32,
                     interpret: bool = False):
    """Return fn(x, ids, weights, wg, wi, wo, expert_offset=0) -> (N, d).

    ``x`` (N, d); ``ids``, ``weights`` (N, k): each token's experts among
    all E and their weights; ``wg``, ``wi`` (E_held, d, m) and ``wo``
    (E_held, m, d): experts ``expert_offset`` to ``expert_offset + E_held``.
    Each token's output is the weighted sum of its held experts' SwiGLU,
    zero where it chose none of them.
    """
    cfg = dict(DEFAULT_CONFIG)
    cfg.update(config or {})
    validate_config(cfg, d, m, k)
    bm, bn, bk = cfg["BLOCK_M"], cfg["BLOCK_N"], cfg["BLOCK_K"]
    G, P = E_held, N * k
    C = round_rows(N, k, E, G, bm)
    tiles = grid_tiles(C, G, bm)
    R = tiles * bm
    Np = -(-N // combine_tokens(k)) * combine_tokens(k)   # whole blocks
    common = dict(tiles=tiles, bm=bm, bn=bn, bk=bk, dtype=dtype,
                  interpret=interpret)
    gate_up = _grouped_call(_gate_up_kernel, GATE_UP_NAME, K=d, N=m,
                            weights=2, **common)
    down = _grouped_call(_down_kernel, DOWN_NAME, K=m, N=d, weights=1,
                         **common)
    combine = make_combine(Np, d, k, interpret=interpret)

    def fn(x, ids, weights, wg, wi, wo, expert_offset=0):
        with jax.named_scope("repro.moe.permute"):
            local = ids.reshape(P).astype(jnp.int32) - expert_offset
            held = (local >= 0) & (local < G)
            group = jnp.where(held, local, G)           # G: not held here
            # the pairs ordered by expert: sorted position -> pair, and back
            iota = jnp.arange(P, dtype=jnp.int32)
            sorted_group, order = lax.sort((group, iota), num_keys=1)
            _, rank = lax.sort((order, iota), num_keys=1)
            starts, ends = (jnp.searchsorted(sorted_group, jnp.arange(G),
                                             side=side).astype(jnp.int32)
                            for side in ("left", "right"))
            pair_group = jnp.minimum(group, G - 1)
            pad = ((0, Np * k - P),)
            pair_weight = jnp.pad(weights.reshape(P).astype(jnp.float32),
                                  pad)

        def one_round(r, out):
            lo = r * C
            with jax.named_scope("repro.moe.permute"):
                # this round's pairs of each group, padded to whole tiles
                cnt = jnp.maximum(jnp.minimum(ends, lo + C)
                                  - jnp.maximum(starts, lo), 0)
                pad_end = jnp.cumsum((cnt + bm - 1) // bm * bm)
                pad_start = pad_end - (cnt + bm - 1) // bm * bm
                first = jnp.maximum(starts, lo)
                used = (pad_end[-1] // bm).reshape(1)
                row = jnp.arange(R, dtype=jnp.int32)
                row_group = jnp.minimum(
                    jnp.sum(row[:, None] >= pad_end[None, :], axis=1), G - 1)
                pos = first[row_group] + row - pad_start[row_group]
                # a padding row reads some token: its output is never used
                token = order[jnp.minimum(pos, P - 1)] // k
                xs = jnp.take(x, token, axis=0, mode="clip")
                tile = jnp.minimum(jnp.arange(tiles),
                                   jnp.maximum(used[0] - 1, 0))
                tile_group = row_group[tile * bm]
            with jax.named_scope("repro.moe.experts"):
                h = gate_up(tile_group, used, xs, wg, wi)
                y = down(tile_group, used, h, wo)
            with jax.named_scope("repro.moe.combine"):
                live = held & (rank >= lo) & (rank < lo + C)
                pair_row = jnp.where(live, pad_start[pair_group] + rank
                                     - first[pair_group], -1)
                return combine(jnp.pad(pair_row, pad, constant_values=-1),
                               pair_weight, y, out)

        out = one_round(0, None)                  # zero pairs: zero output
        rounds = (ends[-1] + C - 1) // C
        out = lax.fori_loop(1, rounds, one_round, out)
        return out[:N].astype(x.dtype)

    return fn


# ---------------------------------------------------------------------------
# structural cost models
# ---------------------------------------------------------------------------

def combine_vmem(d: int, k: int) -> int:
    """Bytes of VMEM the combine kernel claims, whatever the configuration:
    its ring of 8-row blocks of y, and the double-buffered float32 token
    blocks of the output and of the output so far.  ``make_moe_experts``
    checks it against the kernels' VMEM limit; ``vmem_footprint`` leaves it
    out, since no configuration changes it."""
    return 4 * (_RING * _SUBLANES * d + 2 * 2 * combine_tokens(k) * d)


def vmem_footprint(config: Config, elt_bytes: int = 4) -> int:
    """Bytes of VMEM the larger of the two grouped kernels claims:
    double-buffered row, weight and output blocks, and its float32
    accumulators (the combine's, the same for every configuration, is
    ``combine_vmem``)."""
    bm, bn, bk = config["BLOCK_M"], config["BLOCK_N"], config["BLOCK_K"]

    def one(weights: int) -> int:
        io = 2 * (bm * bk + weights * bk * bn + bm * bn) * elt_bytes
        return io + weights * bm * bn * 4

    return max(one(2), one(1))


def analytical_time(config: Config, profile: DeviceProfile, N: int, d: int,
                    m: int, E: int, E_held: int, k: int,
                    elt_bytes: int = 4) -> float:
    """Structural model at the balanced load: each of the E_held groups
    holds N * k / E rows, padded up to BLOCK_M; max(MXU time, HBM time)
    of both products, plus grid steps and the permute and combine passes
    over x and the output."""
    bm, bn, bk = config["BLOCK_M"], config["BLOCK_N"], config["BLOCK_K"]
    if d % bn or m % bn or d % bk or m % bk:
        return math.inf
    if vmem_footprint(config, elt_bytes) > profile.vmem_bytes:
        return math.inf
    mxu = profile.mxu_dim

    def _eff(n: int) -> float:
        return n / (math.ceil(n / mxu) * mxu)

    tiles = E_held * math.ceil(N * k / E / bm)
    rows = tiles * bm
    util = _eff(bm) * _eff(bn) * _eff(min(bk, mxu * 4))
    compute_t = 6.0 * rows * d * m / (profile.peak_flops * util)
    # rows re-read once per column block; each tile reads its expert's
    # weights whole; h and y written once
    traffic = (rows * d * (m // bn) + 2 * tiles * d * m + rows * m
               + rows * m * (d // bn) + tiles * m * d + rows * d)
    memory_t = traffic * elt_bytes / profile.hbm_bw
    steps = tiles * (m // bn) * (d // bk) + tiles * (d // bn) * (m // bk)
    dispatch_t = (2 * N * d + 2 * rows * d) * elt_bytes / profile.hbm_bw
    return (max(compute_t, memory_t) + steps * profile.grid_step_overhead
            + dispatch_t + 2 * profile.launch_overhead)


def flops(N: int, d: int, m: int, E: int, E_held: int, k: int) -> float:
    """Operations of the balanced load: N * k * E_held / E pairs, three
    products of d * m each."""
    return N * k * E_held / E * 6.0 * d * m
