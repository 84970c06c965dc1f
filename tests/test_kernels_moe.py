"""Pallas grouped SwiGLU experts (the MoE op) vs oracle, its tile counts,
and a search over its space."""

import itertools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels.moe import (MOE_EXPERTS, combine_pair_counts, grid_tiles,
                               grouped_tile_counts, heuristic_config,
                               make_moe_experts, moe_experts,
                               moe_experts_reference, round_rows)
from repro.kernels.moe import grouped
from repro.kernels.moe.grouped import combine_tokens

RNG = np.random.default_rng(15)

#: N tokens of width d, experts of width m, E routed over, 4 held, k each
N, D, M, E, HELD, K = 64, 256, 128, 32, 4, 4
CFG = {"BLOCK_M": 8, "BLOCK_N": 128, "BLOCK_K": 128}


def _experts():
    mk = lambda *s: jnp.asarray(RNG.normal(size=s) / np.sqrt(s[-2]),
                                jnp.float32)
    return mk(HELD, D, M), mk(HELD, D, M), mk(HELD, M, D)


def _tokens(ids):
    x = jnp.asarray(RNG.normal(size=(N, D)), jnp.float32)
    w = jnp.asarray(RNG.uniform(0.1, 1.0, size=ids.shape), jnp.float32)
    return x, jnp.asarray(ids, jnp.int32), w


def _distinct(rows, choices):
    """Each row's K distinct experts drawn from ``choices``."""
    return np.stack([RNG.choice(choices, size=K, replace=False)
                     for _ in range(rows)])


def _routing(case):
    if case == "uniform":                      # ragged by chance
        return _distinct(N, E)
    if case == "empty_groups":                 # held experts 1 and 3 idle
        return _distinct(N, [0, 2] + list(range(HELD, E)))
    if case == "not_whole_tiles":              # 3, 5, 7 and 9 pairs
        ids = _distinct(N, range(HELD, E))
        for expert, count in enumerate((3, 5, 7, 9)):
            ids[RNG.choice(N, size=count, replace=False), expert] = expert
        return ids
    if case == "all_on_one_expert":            # every token picks expert 2
        ids = _distinct(N, range(HELD, E))
        ids[:, 1] = 2
        return ids
    raise ValueError(case)


@pytest.mark.parametrize("offset", [0, 4])
@pytest.mark.parametrize("case", ["uniform", "empty_groups",
                                  "not_whole_tiles", "all_on_one_expert"])
def test_moe_experts_matches_reference(case, offset):
    ids = _routing(case)
    if offset:                         # the same pairs, for experts 4..7
        ids = np.where(ids < HELD, ids + offset,
                       np.where(ids < HELD + offset, ids - offset, ids))
    x, ids, w = _tokens(ids)
    wg, wi, wo = _experts()
    fn = make_moe_experts(N, D, M, E, HELD, K, CFG, interpret=True)
    out = jax.jit(lambda *a: fn(*a, expert_offset=offset))(x, ids, w, wg,
                                                           wi, wo)
    ref = moe_experts_reference(x, ids, w, wg, wi, wo, expert_offset=offset)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=2e-5, atol=2e-5)
    held = (np.asarray(ids) >= offset) & (np.asarray(ids) < offset + HELD)
    untouched = ~held.any(axis=1)
    assert np.all(np.asarray(out)[untouched] == 0)


def test_all_on_one_expert_takes_several_rounds():
    """The dropless case above is not one round: every token's pair with
    expert 2 (N of them) is more than a round holds."""
    assert N > round_rows(N, K, E, HELD, CFG["BLOCK_M"])


def test_public_op_resolves_its_config_through_the_registry(monkeypatch):
    from repro.kernels.moe import ops

    seen = []
    real = ops.lookup
    monkeypatch.setattr(ops, "lookup", lambda kernel, shape, **kw:
                        seen.append(dict(shape)) or real(kernel, shape, **kw))
    x, ids, w = _tokens(_routing("uniform"))
    out = moe_experts(x, ids, w, *_experts(), num_experts=E,
                      interpret=True)
    assert seen == [{"N": N, "d": D, "m": M, "E": E, "E_held": HELD, "k": K,
                     "dtype": "float32"}]
    assert out.shape == (N, D)


def test_no_buffer_is_sized_for_every_pair():
    """The largest array the op makes holds a fraction of N * k * d (traced
    at 1024 tokens, never run): the rounds' buffers are sized for the
    balanced load."""
    n = 1024
    fn = make_moe_experts(n, D, M, E, HELD, K, CFG, interpret=True)
    specs = [jax.ShapeDtypeStruct(s, t) for s, t in [
        ((n, D), jnp.float32), ((n, K), jnp.int32), ((n, K), jnp.float32),
        ((HELD, D, M), jnp.float32), ((HELD, D, M), jnp.float32),
        ((HELD, M, D), jnp.float32)]]
    jaxpr = jax.make_jaxpr(fn)(*specs)

    def sizes(jx):
        for eqn in jx.eqns:
            for var in eqn.outvars:
                yield int(np.prod(var.aval.shape))
            for value in eqn.params.values():
                for sub in value if isinstance(value, tuple) else (value,):
                    if hasattr(sub, "eqns") or hasattr(sub, "jaxpr"):
                        yield from sizes(getattr(sub, "jaxpr", sub))

    assert max(sizes(jaxpr.jaxpr)) <= n * K * D // 4


def _brute_tiles(sizes, bm):
    """Tiles and padded rows, one row at a time."""
    tiles = padded = 0
    for size in sizes:
        rows = 0
        while rows < size:
            tiles += 1
            rows += bm
        padded += rows - size
    return tiles, padded


@pytest.mark.parametrize("sizes,bm", [
    ((0, 0, 0), 8), ((1, 0, 7), 8), ((8, 16, 24), 8), ((9, 17, 1, 0), 8),
    ((1030, 998, 1024, 1013), 256), ((8192, 0, 0, 0), 512),
])
def test_grouped_tile_counts_against_brute_force(sizes, bm):
    computed, skipped, padded = grouped_tile_counts(sizes, bm, grid_tiles=40)
    assert (computed, padded) == _brute_tiles(sizes, bm)
    assert skipped == 40 - computed


@pytest.mark.parametrize("rows,groups,bm", [(16, 3, 8), (24, 4, 8),
                                            (9, 2, 4), (5, 4, 4)])
def test_grid_holds_every_split_of_a_round(rows, groups, bm):
    """The grid is as large as the worst split of a round's rows needs, and
    no larger."""
    worst = max(_brute_tiles(split, bm)[0]
                for split in itertools.product(range(rows + 1), repeat=groups)
                if sum(split) == rows)
    assert grid_tiles(rows, groups, bm) == worst


def test_heuristic_at_the_published_shape():
    assert heuristic_config(32768, 7168, 2048, 256, 8, 8) == {
        "BLOCK_M": 256, "BLOCK_N": 512, "BLOCK_K": 512}
    assert round_rows(32768, 8, 256, 8, 256) == 10240      # 8192 * 5/4


def test_combine_vmem_is_checked_at_build():
    """The combine kernel, which every configuration runs, claims ~18 MB
    of VMEM at the published widths: more than the heuristic's grouped
    kernels and less than the space's largest blocks.  A v5e holds it, and
    a build against a smaller limit is refused before anything compiles."""
    from unittest import mock

    from repro.core.profiles import TPU_V5E
    from repro.kernels.moe import combine_vmem

    shape = {"N": 32768, "d": 7168, "m": 2048, "E": 256, "E_held": 8,
             "k": 8, "dtype": "float32"}
    combine = combine_vmem(7168, 8)
    assert combine == 4 * 7168 * (16 * 8 + 4 * 128)
    assert TPU_V5E.fits_vmem(combine)
    cfg = heuristic_config(32768, 7168, 2048, 256, 8, 8)
    assert MOE_EXPERTS.vmem_footprint(shape, cfg) < combine < max(
        MOE_EXPERTS.vmem_footprint(shape, c)
        for c in MOE_EXPERTS.make_space(shape))
    with mock.patch.object(grouped, "kernel_vmem_limit",
                           lambda: 16 * 2**20):
        with pytest.raises(ValueError, match="combine kernel needs"):
            make_moe_experts(32768, 7168, 2048, 256, 8, 8, cfg)


#: the combine's own cases: k = 8, so blocks of 128 tokens and 1024 slots
CK, CD, CR = 8, 128, 2048               # k, width, rows of y
CBT = combine_tokens(CK)
CBLOCKS = 3


def _combine_rows(case):
    """(N * k,) pair rows, -1 where a slot has no pair, for ``case``."""
    rows = np.full((CBLOCKS, CBT * CK), -1)
    sparse = lambda n: RNG.choice(CBT * CK, size=n, replace=False)
    if case in ("empty_block", "accumulate"):        # block 1 holds none
        for b in (0, 2):
            rows[b, sparse(50)] = RNG.integers(CR, size=50)
    elif case == "every_slot_live":                  # block 0 all live
        rows[0] = RNG.integers(CR, size=CBT * CK)
        rows[2, sparse(9)] = RNG.integers(CR, size=9)
    elif case == "deeper_than_ring":                 # 2 rings and 3, 1 short
        for b, n in enumerate((2 * grouped._RING + 3, grouped._RING - 1,
                               grouped._RING)):
            rows[b, sparse(n)] = RNG.integers(CR, size=n)
    elif case == "all_sublane_offsets":
        # token t's first pair reads a row at sublane t // 8 of its block:
        # every (token, row) pair of sublanes, in blocks 0 and 2
        for b in (0, 2):
            for t in range(64):
                rows[b, (t + 17) * CK + t % CK] = (
                    8 * RNG.integers(CR // 8) + t // 8)
    else:
        raise ValueError(case)
    return rows.reshape(-1)


@pytest.mark.parametrize("case", ["empty_block", "every_slot_live",
                                  "deeper_than_ring", "all_sublane_offsets",
                                  "accumulate"])
def test_combine_against_float64_oracle(case):
    """Each token's output is its earlier output (zero in a first round)
    plus its live pairs' ``w * y[row]``; a token with none keeps it
    exactly."""
    n = CBLOCKS * CBT
    pair_row = _combine_rows(case)
    w = RNG.uniform(0.1, 1.0, size=pair_row.size).astype(np.float32)
    y = RNG.normal(size=(CR, CD)).astype(np.float32)
    prev = (RNG.normal(size=(n, CD)).astype(np.float32)
            if case == "accumulate" else np.zeros((n, CD), np.float32))
    fn = grouped.make_combine(n, CD, CK, interpret=True)
    args = [jnp.asarray(pair_row, jnp.int32), jnp.asarray(w), jnp.asarray(y)]
    if case == "accumulate":
        args.append(jnp.asarray(prev))
    out = np.asarray(jax.jit(fn)(*args))

    ref = prev.astype(np.float64)
    size = np.abs(ref)
    for slot in np.flatnonzero(pair_row >= 0):
        term = np.float64(w[slot]) * y[pair_row[slot]].astype(np.float64)
        ref[slot // CK] += term
        size[slot // CK] += np.abs(term)
    # float32 adds of at most k + 1 terms, each rounded once
    assert np.all(np.abs(out - ref) <= (CK + 1) * np.finfo(np.float32).eps
                  * size)
    untouched = ~(pair_row >= 0).reshape(n, CK).any(axis=1)
    assert untouched.any()
    assert np.array_equal(out[untouched], prev[untouched])


def _brute_pair_counts(pair_rows, k):
    """Pairs walked, slots skipped and empty blocks, one slot at a time."""
    per_block = {}
    slots = combine_tokens(k) * k
    for slot, row in enumerate(pair_rows):
        per_block.setdefault(slot // slots, 0)
        per_block[slot // slots] += row >= 0
    walked = sum(per_block.values())
    return (walked, len(per_block) * slots - walked,
            sum(1 for n in per_block.values() if n == 0))


@pytest.mark.parametrize("k,tokens,live", [
    (8, 128, 0.0), (8, 384, 0.03), (8, 300, 0.5), (8, 256, 1.0),
    (6, 700, 0.1), (4, 100, 0.2),
])
def test_combine_pair_counts_against_brute_force(k, tokens, live):
    rows = np.where(RNG.uniform(size=tokens * k) < live,
                    RNG.integers(1000, size=tokens * k), -1)
    assert combine_pair_counts(rows, k) == _brute_pair_counts(rows, k)


def test_tune_kernel_finds_and_verifies_a_winner(tmp_path):
    """A wall-clock search over the op's space at a small shape: every
    candidate is verified against the oracle, and the winner is one."""
    from repro.core.cache import TuningCache
    from repro.core.evaluators import WallClockEvaluator
    from repro.tune import tune_kernel

    shape = {"N": 32, "d": 256, "m": 256, "E": 8, "E_held": 2, "k": 2,
             "dtype": "float32"}
    out = tune_kernel(MOE_EXPERTS, shape, strategy="random", budget=3,
                      evaluator=WallClockEvaluator(repeats=1, warmup=0,
                                                   verify_outputs=True),
                      cache=TuningCache(str(tmp_path / "tuned.json")),
                      record=False, seed=1, interpret=True)
    assert out.best_config is not None
    assert MOE_EXPERTS.make_space(shape).is_feasible(out.best_config)
    args = MOE_EXPERTS.make_args(shape, np.random.default_rng(0))
    got = MOE_EXPERTS.builder(shape, out.best_config, interpret=True)(*args)
    np.testing.assert_allclose(np.asarray(got),
                               np.asarray(moe_experts_reference(*args)),
                               rtol=2e-5, atol=2e-5)
