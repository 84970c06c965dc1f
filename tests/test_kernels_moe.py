"""Pallas grouped SwiGLU experts (the MoE op) vs oracle, its tile counts,
and a search over its space."""

import itertools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels.moe import (MOE_EXPERTS, grid_tiles, grouped_tile_counts,
                               heuristic_config, make_moe_experts,
                               moe_experts, moe_experts_reference, round_rows)

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
    """The combine kernel, which every configuration runs, claims ~45 MB
    of VMEM at the published widths, more than either grouped kernel at
    any configuration of the space: a v5e holds it, and a build against a
    smaller limit is refused before anything compiles."""
    from unittest import mock

    from repro.core.profiles import TPU_V5E
    from repro.kernels.moe import combine_vmem, grouped

    shape = {"N": 32768, "d": 7168, "m": 2048, "E": 256, "E_held": 8,
             "k": 8, "dtype": "float32"}
    combine = combine_vmem(7168, 8)
    assert combine == 4 * 7168 * (16 * 8 * 8 + 8 + 16 + 4 * 128)
    assert TPU_V5E.fits_vmem(combine)
    assert all(MOE_EXPERTS.vmem_footprint(shape, cfg) < combine
               for cfg in MOE_EXPERTS.make_space(shape))
    cfg = heuristic_config(32768, 7168, 2048, 256, 8, 8)
    with mock.patch.object(grouped, "kernel_vmem_limit",
                           lambda: 16 * 2**20):
        with pytest.raises(ValueError, match="combine kernel needs"):
            make_moe_experts(32768, 7168, 2048, 256, 8, 8, cfg)


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
