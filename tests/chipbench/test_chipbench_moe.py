"""The DeepSeek-V3 MoE configuration's benchmark files: its reference's work
counts and routing, and the ``moe.dispatch_share`` reader."""

from __future__ import annotations

import types

import jax
import numpy as np
import pytest

from chipbench_testlib import ROOT, TINY_SHAPES

from chipbench import cells, trace

MOE = cells.load_module(ROOT / "chipbench/references/deepseek_moe.py")
SHARE = cells.load_module(ROOT / "chipbench/metrics/moe.dispatch_share.py")
PUBLISHED = {"N": 32768, "d": 7168, "m": 2048, "E": 256, "E_held": 8,
             "k": 8, "dtype": "float32"}


@pytest.mark.parametrize("tokens, gflop, gbytes", [(32768, 721.6, 3.29),
                                                   (34816, 766.7, 3.41)])
def test_published_shape_work(tokens, gflop, gbytes):
    """N * k / 32 pairs of 6 * 7168 * 2048 operations; x, the 8 experts'
    float32 weights and the output, plus the routing."""
    shape = dict(PUBLISHED, N=tokens)
    assert MOE.flops(shape) == tokens // 4 * 6 * 7168 * 2048
    assert MOE.flops(shape) / 1e9 == pytest.approx(gflop, abs=0.05)
    assert MOE.bytes_moved(shape) / 1e9 == pytest.approx(gbytes, abs=0.005)


def test_configuration_is_the_published_shape():
    data, _ = cells.load_config("deepseek-v3-moe-f32")
    assert data["shape"] == PUBLISHED
    assert data["n_routed_experts"] == PUBLISHED["E_held"]
    assert data["n_routed_experts_published"] == PUBLISHED["E"]
    assert data["hidden_size"] == PUBLISHED["d"]
    assert data["moe_intermediate_size"] == PUBLISHED["m"]
    assert data["num_experts_per_tok"] == PUBLISHED["k"]
    assert (data["n_group"], data["topk_group"]) == (MOE.N_GROUP,
                                                     MOE.TOPK_GROUP)
    assert data["routed_scaling_factor"] == MOE.ROUTED_SCALING_FACTOR


def _summary(busy_s, kernel_s, events=2):
    return trace.Summary(window_s=1.0, busy_s=busy_s, kernel_s=kernel_s,
                         kernel_events=events, device_ops=[], idle_gaps=[])


def test_dispatch_share_reads_the_trace_summary():
    run = types.SimpleNamespace(trace=_summary(0.8, 0.7))
    assert SHARE.read(run) == pytest.approx(12.5)
    assert SHARE.read(types.SimpleNamespace(trace=None)) is None
    assert SHARE.read(types.SimpleNamespace(
        trace=_summary(0.8, 0.0, events=0))) is None


@pytest.mark.parametrize("seed", [1, 2**31 + 7])
def test_make_inputs_routes_as_deepseek_v3(seed):
    """At most k distinct experts a token, weights summing to the scaling
    factor, all of them in the top TOPK_GROUP of the N_GROUP groups."""
    from chipbench import drive

    shape = TINY_SHAPES["deepseek-v3-moe-f32"]["shape"]
    (x, ids, w, wg, wi, wo), = drive.make_inputs(MOE, shape, seed, 1)
    ids, w = np.asarray(ids), np.asarray(w)
    k, E = shape["k"], shape["E"]
    assert ids.shape == w.shape == (shape["N"], k)
    assert all(len(set(row)) == k for row in ids)
    assert ((ids >= 0) & (ids < E)).all()
    np.testing.assert_allclose(w.sum(axis=1), MOE.ROUTED_SCALING_FACTOR,
                               rtol=1e-5)
    assert (w > 0).all()
    groups = ids // (E // MOE.N_GROUP)
    assert all(len(set(row)) <= MOE.TOPK_GROUP for row in groups)
    assert wg.shape == wi.shape == (shape["E_held"], shape["d"], shape["m"])
    assert wo.shape == (shape["E_held"], shape["m"], shape["d"])
    assert np.asarray(x).std() == pytest.approx(1.0, rel=0.05)


def test_make_inputs_fixes_the_batch_and_seeds_the_experts():
    """Every seed routes the same tokens to the same experts with the same
    weights (the batch comes from ROUTING_SEED); the held experts' weights
    follow the seed.  The drawn bias leaves the loads uneven."""
    from chipbench import drive

    shape = TINY_SHAPES["deepseek-v3-moe-f32"]["shape"]
    (a, b) = (drive.make_inputs(MOE, shape, seed, 1)[0] for seed in (1, 2))
    for i in range(3):                               # x, ids, weights
        np.testing.assert_array_equal(np.asarray(a[i]), np.asarray(b[i]))
    for i in range(3, 6):                            # wg, wi, wo
        assert not np.array_equal(np.asarray(a[i]), np.asarray(b[i]))
    for xs in drive.make_inputs(MOE, shape, 1, 2):  # both input sets
        np.testing.assert_array_equal(np.asarray(xs[1]), np.asarray(a[1]))
    load = np.bincount(np.asarray(a[1]).reshape(-1), minlength=shape["E"])
    assert load.max() > 1.5 * load.mean()


def test_route_keeps_groups_by_their_top_two():
    """One token, 8 groups of 2: the groups with the largest top-2 sums are
    kept even where another group holds the single largest score."""
    logits = np.full((1, 16), -4.0, np.float32)
    logits[0, 0] = 4.0                       # group 0: 4.0 and -4.0
    for g in range(1, 5):                    # groups 1..4: 2.0 and 2.0
        logits[0, 2 * g:2 * g + 2] = 2.0
    ids, w = MOE.route(jax.numpy.asarray(logits), np.zeros(16, np.float32), 8)
    assert sorted(np.asarray(ids)[0]) == list(range(2, 10))
    assert float(np.asarray(w).sum()) == pytest.approx(2.5)
