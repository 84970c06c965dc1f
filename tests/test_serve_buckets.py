"""Shape-bucketed SLO serving: bucket admission/padding, per-bucket
hot-swap isolation via objective-scoped cache keys, deterministic p99
retunes over modeled arrival traces, and the satellite contract that a
hot-swapped config changes the *lowered* decode step (tuned gemm BLOCK_N
-> LM-head vocab tile)."""

import dataclasses

import jax
import numpy as np
import pytest

from repro.configs import get_config
from repro.core import SearchSpace, TPU_V5E, TuningCache, tunable
from repro.core.hlo import fingerprint
from repro.dist.step import apply_kernel_configs, make_serve_step
from repro.models.model import RunConfig, init_cache, init_model
from repro.serve import (BackgroundTuner, BucketedServeEngine, JobStatus,
                         OnlineTuneConfig, Request, ServeEngine,
                         buckets_from_env, modeled_arrival_trace,
                         resolve_kernel_resolutions, trace_evaluator_factory)


@pytest.fixture(scope="module")
def model_setup():
    cfg = get_config("granite-3-2b", smoke=True)
    params = init_model(cfg, jax.random.PRNGKey(0))
    return cfg, params


@pytest.fixture
def cache(tmp_path):
    return TuningCache(str(tmp_path / "cache.json"))


def _seed_exact(cfg, cache, slots, max_len):
    for res in resolve_kernel_resolutions(cfg, slots, max_len,
                                          cache=cache).values():
        cache.record(res.kernel, res.key, res.profile, res.config,
                     1.0, "full", 1, shape=res.shape)


def _ragged_requests(cfg, seed=0):
    """Deterministic synthetic ragged traffic: lengths force distinct
    buckets under buckets=(16, 64)."""
    rng = np.random.default_rng(seed)
    lens = [(3, 6), (4, 8), (10, 40), (20, 30), (2, 10)]   # prompt, new
    return [Request(rid=i,
                    prompt=rng.integers(1, cfg.vocab_size, p).tolist(),
                    max_new_tokens=n)
            for i, (p, n) in enumerate(lens)]


# -- env knob & trace modeling ------------------------------------------------

def test_buckets_from_env(monkeypatch):
    assert buckets_from_env(default=(128,)) == (128,)
    monkeypatch.setenv("REPRO_SERVE_BUCKETS", "512, 128,128,2048")
    assert buckets_from_env() == (128, 512, 2048)
    monkeypatch.setenv("REPRO_SERVE_BUCKETS", "128,banana")
    with pytest.raises(ValueError):
        buckets_from_env()
    monkeypatch.setenv("REPRO_SERVE_BUCKETS", "0,128")
    with pytest.raises(ValueError):
        buckets_from_env()
    monkeypatch.setenv("REPRO_SERVE_BUCKETS", " , ")
    with pytest.raises(ValueError):
        buckets_from_env()


def test_modeled_arrival_trace_deterministic_and_quantized():
    shape = {"Sq": 512, "Sk": 512, "D": 64, "causal": True}
    t1 = modeled_arrival_trace(shape, arrivals=8, min_dim=128)
    t2 = modeled_arrival_trace(shape, arrivals=8, min_dim=128)
    assert t1 == t2 and len(t1) == 8
    assert t1[0]["Sq"] == 512                       # full-bucket arrival
    for s in t1:
        assert s["Sq"] % 128 == 0 and 128 <= s["Sq"] <= 512
        assert s["D"] == 64                         # dims below min_dim untouched
        assert s["causal"] is True                  # non-ints untouched
    assert {s["Sq"] for s in t1} == {512, 256, 384, 128}
    with pytest.raises(ValueError):
        modeled_arrival_trace(shape, arrivals=0)


def test_trace_evaluator_factory_requires_analytical_model():
    class NoModel:
        name = "nm"
        analytical_model = None

    with pytest.raises(ValueError):
        trace_evaluator_factory()(NoModel(), {"N": 64}, TPU_V5E)


# -- admission & padding ------------------------------------------------------

def test_bucket_assignment_and_completion(model_setup, cache):
    cfg, params = model_setup
    # 3 slots: the 16-bucket's three requests decode as one batch; with 2,
    # the refill would run that batch past the bucket's 16 KV rows
    engine = BucketedServeEngine(cfg, params, buckets=(16, 64), slots=3,
                                 cache=cache, online_tune=False)
    try:
        reqs = _ragged_requests(cfg)
        assigned = {r.rid: engine.submit(r) for r in reqs}
        # smallest fitting bucket: prompt+new <= 16 -> 16, else 64
        assert assigned == {0: 16, 1: 16, 2: 64, 3: 64, 4: 16}
        done = engine.run()
        assert {r.rid for r in done} == set(range(5))
        for r in done:
            assert r.done and len(r.output) == r.max_new_tokens
            assert all(0 <= t < cfg.vocab_size for t in r.output)
        assert engine.rejected == []
        # both buckets actually decoded
        assert engine.engines[16].steps_total > 0
        assert engine.engines[64].steps_total > 0
    finally:
        engine.close()


def test_bucketed_padding_matches_single_engine_outputs(model_setup, cache):
    """Padding into a bucket is behavior-neutral: the same request decodes
    the same tokens in a small bucket as in one big single-geometry
    engine."""
    cfg, params = model_setup
    req = lambda: Request(rid=0, prompt=[5, 7, 11], max_new_tokens=6)  # noqa: E731
    single = ServeEngine(cfg, params, slots=2, max_len=64, cache=cache)
    single.submit(ra := req())
    single.run()
    single.close()
    engine = BucketedServeEngine(cfg, params, buckets=(16, 64), slots=2,
                                 cache=cache, online_tune=False)
    try:
        assert engine.submit(rb := req()) == 16     # padded into the SMALL bucket
        engine.run()
        assert rb.output == ra.output
    finally:
        engine.close()


def test_oversized_request_is_rejected(model_setup, cache):
    cfg, params = model_setup
    engine = BucketedServeEngine(cfg, params, buckets=(16,), slots=1,
                                 cache=cache, online_tune=False)
    try:
        big = Request(rid=9, prompt=[1] * 10, max_new_tokens=50)
        assert engine.submit(big) is None
        assert engine.rejected == [big]
        assert engine.run() == []                   # nothing admitted
    finally:
        engine.close()


def test_bucketed_engine_env_buckets(model_setup, cache, monkeypatch):
    cfg, params = model_setup
    monkeypatch.setenv("REPRO_SERVE_BUCKETS", "32,8")
    engine = BucketedServeEngine(cfg, params, slots=1, cache=cache,
                                 online_tune=False)
    try:
        assert engine.buckets == (8, 32)
        assert set(engine.engines) == {8, 32}
    finally:
        engine.close()


# -- per-bucket hot-swap isolation --------------------------------------------

def test_per_bucket_hot_swap_isolation(model_setup, cache):
    """A p99-scoped winner recorded for ONE bucket's geometry swaps into
    exactly that bucket; the sibling bucket and the default-objective
    entries are untouched."""
    cfg, params = model_setup
    for b in (16, 64):
        _seed_exact(cfg, cache, 2, b)               # exact hits: no jobs
    engine = BucketedServeEngine(
        cfg, params, buckets=(16, 64), slots=2, cache=cache,
        online_tune=OnlineTuneConfig(strategy="full", budget=2),
        objective="p99_time")
    try:
        assert engine.tuner.config.objective == "p99_time"
        small, large = engine.engines[16], engine.engines[64]
        # flash_attention geometry carries the bucket bound (Sq=Sk=max_len),
        # so each bucket watches its own cache key; gemm's decode geometry
        # is bucket-independent and would (correctly) swap everywhere
        res = small.kernel_resolutions["flash_attention"]
        before_small = small.kernel_configs["flash_attention"]
        before_large = large.kernel_configs["flash_attention"]
        upgraded = dict(res.config, BLOCK_Q=999)
        # a default-objective write must NOT swap into a p99-watching bucket
        cache.record(res.kernel, res.key, res.profile, upgraded, 0.5,
                     "full", 1, shape=res.shape)
        assert small.kernel_configs["flash_attention"] == before_small
        # the p99-scoped write swaps bucket 16 only
        cache.record(res.kernel, res.key, res.profile, upgraded, 0.4,
                     "full", 1, shape=res.shape, objective="p99_time")
        assert small.kernel_configs["flash_attention"] == upgraded
        assert large.kernel_configs["flash_attention"] == before_large
        assert engine.swap_events[64] == []
    finally:
        engine.close()


# -- deterministic p99 retune over the modeled trace --------------------------

def _bucket_kernel(name="bkt"):
    """Tail-shaped toy kernel: X=8 is fastest at the full bucket but blows
    up on small arrivals; X=2 is steady across the trace (better p99)."""

    def space(shape):
        sp = SearchSpace()
        sp.add_parameter(name="X", values=(2, 8))
        return sp

    def model(shape, cfg, prof):
        n = shape["N"]
        if cfg["X"] == 8:
            return 1e-3 if n >= 512 else 50e-3      # tail-heavy
        return 2e-3                                 # steady

    @tunable(name=name, space=space, heuristic=lambda s: {"X": 2},
             analytical_model=model, register=False)
    def build(shape, config):
        return lambda: config["X"]

    return build


def test_background_p99_retune_over_trace_is_deterministic(tmp_path):
    winners = []
    for i in range(2):
        cache = TuningCache(str(tmp_path / f"c{i}.json"))
        k = _bucket_kernel()
        tuner = BackgroundTuner(cache=cache, config=OnlineTuneConfig(
            strategy="full", objective="p99_time",
            evaluator_factory=trace_evaluator_factory(arrivals=8, seed=3)))
        try:
            job = tuner.submit(k, {"N": 512}, provenance="heuristic")
            assert job is not None and job.objective == "p99_time"
            assert tuner.wait(timeout=30)
            assert job.status is JobStatus.DONE
            entry = cache.get(k.name, k.key_for({"N": 512}), TPU_V5E.name,
                              objective="p99_time")
            assert entry is not None and entry.objective == "p99_time"
            assert entry.config == job.config
            # median at the full bucket would pick X=8; the trace's small
            # arrivals make its tail terrible, so p99 picks the steady X=2
            assert job.config == {"X": 2}
            winners.append((job.config, job.best_time))
        finally:
            tuner.close()
    assert winners[0] == winners[1]


# -- satellite: tuned configs change the lowered step -------------------------

@pytest.fixture(scope="module")
def chunky_setup():
    """Smoke model with a pow2 vocab so gemm BLOCK_N tiles divide it."""
    cfg = get_config("granite-3-2b", smoke=True)
    cfg = dataclasses.replace(cfg, vocab_size=512)
    params = init_model(cfg, jax.random.PRNGKey(0))
    return cfg, params


def test_apply_kernel_configs_derives_head_chunk(chunky_setup):
    cfg, _ = chunky_setup
    run = RunConfig()
    assert apply_kernel_configs(cfg, run, None) is run
    derived = apply_kernel_configs(cfg, run, {"gemm": {"BLOCK_N": 128}})
    assert derived.head_chunk == 128
    # non-dividing / degenerate tiles fall back to the unchunked head
    assert apply_kernel_configs(cfg, run, {"gemm": {"BLOCK_N": 100}}) is run
    assert apply_kernel_configs(cfg, run, {"gemm": {"BLOCK_N": 512}}) is run
    assert apply_kernel_configs(cfg, run, {"gemm": {}}) is run
    # an explicit head_chunk wins over the derived one
    pinned = RunConfig(head_chunk=64)
    assert apply_kernel_configs(cfg, pinned,
                                {"gemm": {"BLOCK_N": 128}}) is pinned


def test_config_swap_changes_lowered_computation(chunky_setup):
    """The satellite contract: two gemm winners with different BLOCK_N
    lower to *different* decode-step computations — while decoding the
    same tokens."""
    cfg, params = chunky_setup
    kv = init_cache(cfg, 2, 16)
    tokens = jax.numpy.zeros((2, 1), jax.numpy.int32)

    def lowered(kernel_configs):
        step = jax.jit(make_serve_step(cfg, RunConfig(), greedy=True,
                                       kernel_configs=kernel_configs))
        return step, jax.jit(step).lower(params, kv, tokens, 0).as_text()

    step_a, text_a = lowered({"gemm": {"BLOCK_N": 128}})
    step_b, text_b = lowered({"gemm": {"BLOCK_N": 256}})
    step_0, text_0 = lowered(None)
    assert fingerprint(text_a) != fingerprint(text_b)
    assert fingerprint(text_a) != fingerprint(text_0)
    # same computation -> same fingerprint (the test isn't noise)
    _, text_a2 = lowered({"gemm": {"BLOCK_N": 128}})
    assert fingerprint(text_a) == fingerprint(text_a2)
    # and the tiling is behavior-neutral: identical greedy tokens
    out_a, _ = step_a(params, kv, tokens, 0)
    out_b, _ = step_b(params, kv, tokens, 0)
    out_0, _ = step_0(params, kv, tokens, 0)
    np.testing.assert_array_equal(np.asarray(out_a), np.asarray(out_0))
    np.testing.assert_array_equal(np.asarray(out_b), np.asarray(out_0))


def test_serve_engine_hot_swap_changes_jitted_step(chunky_setup, cache):
    """End-to-end: a cache write with a different BLOCK_N re-derives the
    engine's jitted step at the swap boundary; a config change that folds
    to the same RunConfig reuses the compiled step."""
    cfg, params = chunky_setup
    _seed_exact(cfg, cache, 2, 16)
    engine = ServeEngine(cfg, params, slots=2, max_len=16, cache=cache,
                         online_tune=OnlineTuneConfig(strategy="full",
                                                      budget=2))
    try:
        res = engine.kernel_resolutions["gemm"]
        base_cfg = dict(res.config)
        base_cfg.pop("BLOCK_N", None)
        cache.record(res.kernel, res.key, res.profile,
                     dict(base_cfg, BLOCK_N=128), 0.5, "full", 1,
                     shape=res.shape)
        step_before = engine._step
        engine.submit(Request(rid=0, prompt=[1, 2], max_new_tokens=2))
        engine.run()
        step_128 = engine._step
        assert step_128 is not step_before          # swap re-derived the step
        # different BLOCK_N -> different derived RunConfig -> new step
        cache.record(res.kernel, res.key, res.profile,
                     dict(base_cfg, BLOCK_N=256), 0.25, "full", 1,
                     shape=res.shape)
        engine.submit(Request(rid=1, prompt=[1, 2], max_new_tokens=2))
        engine.run()
        assert engine._step is not step_128
        # same derived geometry -> memoized step is reused
        cache.record(res.kernel, res.key, res.profile,
                     dict(base_cfg, BLOCK_N=128, INNER_STEPS=9), 0.1,
                     "full", 1, shape=res.shape)
        engine.submit(Request(rid=2, prompt=[1, 2], max_new_tokens=2))
        engine.run()
        assert engine._step is step_128
    finally:
        engine.close()
