"""EvaluationEngine: dedup memo, ask/tell equivalence, early-stop pruning."""

import math

import numpy as np
import pytest

from repro.core import (EngineConfig, EvaluationEngine, Evaluator, KernelSpec,
                        Measurement, ParticleSwarm, SearchSpace,
                        SimulatedAnnealing, Strategy, make_strategy,
                        median_prune_loop)
from repro.core.engine import PHASES
from repro.core.spans import config_arg


def make_space(n_params=4, n_values=4):
    sp = SearchSpace()
    for i in range(n_params):
        sp.add_parameter(name=f"p{i}", values=tuple(range(n_values)))
    return sp


def quadratic(cfg):
    return 1.0 + sum((v - 2) ** 2 for v in cfg.values())


SPEC = KernelSpec(name="stub", build=lambda c: (lambda: None))


class TableEvaluator(Evaluator):
    """Deterministic objective with wallclock-style prune semantics.

    ``measure`` draws ``samples`` identical timing samples through
    :func:`median_prune_loop`, so the engine's prune threshold behaves
    exactly as it does for the real WallClockEvaluator — without timers.
    """

    name = "table"

    def __init__(self, fn, samples=5):
        self.fn = fn
        self.samples = samples
        self.prepare_calls = 0
        self.measure_calls = 0

    def prepare(self, spec, config):
        self.prepare_calls += 1
        return "artifact"

    def measure(self, spec, config, prepared=None, prune_threshold_s=None):
        assert prepared == "artifact", "engine must hand back prepare()'s artifact"
        self.measure_calls += 1
        t = float(self.fn(config))
        if not math.isfinite(t):
            return Measurement(time_s=math.inf, ok=False)
        seq, pruned = median_prune_loop(lambda: t, self.samples,
                                        prune_threshold_s=prune_threshold_s)
        m = Measurement(time_s=float(np.median(seq)), ok=True,
                        detail={"samples": len(seq)})
        if pruned:
            m.detail["pruned"] = True
        return m


def run_engine(strategy, budget, *, fn=quadratic, space=None, seed=0,
               seeds=None, **engine_kwargs):
    space = space or make_space()
    ev = TableEvaluator(fn)
    eng = EvaluationEngine(ev, SPEC, space, EngineConfig(**engine_kwargs))
    res = eng.run(strategy, budget, seed=seed, seeds=seeds)
    return res, eng, ev


# -- dedup memo ---------------------------------------------------------------

def test_dedup_memo_counts_and_reuses():
    # gamma=1 collapses the swarm onto its global best: heavy revisiting
    strat = ParticleSwarm(swarm_size=3, alpha=0.0, beta=0.0, gamma=1.0)
    res, eng, ev = run_engine(strat, 30)
    s = res.extra["engine"]
    assert s["memo_hits"] > 0
    assert s["evaluations"] == s["memo_hits"] + s["unique_configs"]
    # every unique config measured exactly once, none recompiled
    assert ev.measure_calls == s["unique_configs"]
    assert ev.prepare_calls == s["compile_calls"]
    assert s["compile_calls"] == s["unique_configs"]
    assert len(eng.measurements) == s["unique_configs"]


def test_memo_returns_identical_measurement():
    strat = ParticleSwarm(swarm_size=2, alpha=0.0, beta=0.0, gamma=1.0)
    res, eng, _ = run_engine(strat, 20)
    # every trial's time must match the memoised measurement for its config
    for trial in res.trials:
        key = tuple(trial.config[n] for n in ("p0", "p1", "p2", "p3"))
        assert eng.measurements[key].time_s == trial.time


# -- ask/tell equivalence -----------------------------------------------------

@pytest.mark.parametrize("name,kwargs", [
    ("pso", {"swarm_size": 3}),
    ("evolutionary", {"population": 6}),
])
def test_batched_drivers_deterministic_and_budgeted(name, kwargs):
    r1, _, _ = run_engine(make_strategy(name, **kwargs), 40, seed=3)
    r2, _, _ = run_engine(make_strategy(name, **kwargs), 40, seed=3)
    assert [t.time for t in r1.trials] == [t.time for t in r2.trials]
    assert r1.best_config == r2.best_config
    assert r1.evaluations <= 40
    assert r1.best is not None


def test_batched_pso_matches_sequential_quality():
    """PSO through the engine finds the same optimum as a direct run of
    the same walk on an easy seeded space within the same budget."""
    direct = ParticleSwarm(swarm_size=3).run(make_space(), quadratic, 60,
                                             seed=0)
    res, _, _ = run_engine(ParticleSwarm(swarm_size=3), 60, seed=0)
    assert res.best_time == direct.best_time == 1.0


def test_full_search_through_engine_is_exhaustive():
    sp = make_space(n_params=3, n_values=3)
    res, _, ev = run_engine(make_strategy("full"), None, space=sp)
    assert res.evaluations == sp.size()
    assert ev.measure_calls == sp.size()
    assert res.best_time == 1.0


#: warm-start seeds for the pinned trajectories: two usable configs, one
#: with a value outside its parameter's list and a duplicate (both dropped)
PINNED_SEEDS = [{"p0": 2, "p1": 2, "p2": 1, "p3": 0},
                {"p0": 0, "p1": 3, "p2": 2, "p3": 2},
                {"p0": 7, "p1": 0, "p2": 0, "p3": 0},
                {"p0": 2, "p1": 2, "p2": 1, "p3": 0}]

#: (strategy, seeded) -> (trial configs as p0p1p2p3 digits, pinned extras)
#: of ``EvaluationEngine.run`` on make_space() with quadratic, budget 24,
#: seed 11.  Greedy's restart after a cycle that spent the budget is a
#: 25th trial.
PINNED_TRAJECTORIES = {
    ("full", False): (
        "0000 0001 0002 0003 0010 0011 0012 0013 0020 0021 0022 0023 "
        "0030 0031 0032 0033 0100 0101 0102 0103 0110 0111 0112 0113", {}),
    ("full", True): (
        "0000 0001 0002 0003 0010 0011 0012 0013 0020 0021 0022 0023 "
        "0030 0031 0032 0033 0100 0101 0102 0103 0110 0111 0112 0113", {}),
    ("random", False): (
        "3331 1310 3210 0331 0000 1103 2311 2300 3230 2212 0003 0230 "
        "0011 0333 3012 2022 0301 1000 3311 3113 3033 1022 0113 3202", {}),
    ("random", True): (
        "2210 0322 3331 1310 3210 0331 0000 1103 2311 2300 3230 2212 "
        "0003 0230 0011 0333 3012 2022 0301 1000 3311 3113 3033 1022", {}),
    ("annealing", False): (
        "3331 2331 2311 3311 3111 3131 3133 3130 3113 3110 3111 3131 "
        "0131 0031 0011 0010 0031 0231 0232 0231 0221 2221 2321 2231",
        {"accepted_worse": 13, "temperature": 4.0}),
    ("annealing", True): (
        "2210 0322 2220 2210 2211 2231 2230 1230 3230 3233 3230 3213 "
        "3210 3211 3231 0231 0031 0211 0210 0231 0131 0132 0131 0121",
        {"accepted_worse": 13, "temperature": 4.0}),
    ("pso", False): (
        "3331 1310 3210 3331 3330 3011 3131 3330 3130 3101 1330 0322 "
        "3311 3311 1331 2311 3101 2230 1233 3311 2111 3310 2331 2131",
        {"swarm_size": 3, "particle_traces": [
            [5.0, 5.0, 5.0, 8.0, 5.0, 4.0, 4.0, 8.0],
            [8.0, 8.0, 8.0, 8.0, 5.0, 8.0, 5.0, 4.0],
            [7.0, 8.0, 8.0, 6.0, 5.0, 6.0, 4.0, 4.0]]}),
    ("pso", True): (
        "2210 0322 3331 3332 0331 3030 1331 3332 3031 3031 0312 3002 "
        "2232 1332 1312 3222 1231 0122 3012 3333 1121 1232 2033 3121",
        {"swarm_size": 3, "particle_traces": [
            [6.0, 4.0, 5.0, 8.0, 2.0, 2.0, 7.0, 3.0],
            [6.0, 8.0, 4.0, 7.0, 4.0, 4.0, 5.0, 7.0],
            [5.0, 11.0, 8.0, 10.0, 4.0, 6.0, 4.0, 4.0]]}),
    ("greedy", False): (
        "3331 0331 1331 2331 3331 2031 2131 2231 2331 2201 2211 2221 "
        "2231 2220 2222 2223 0222 1222 3222 2022 2122 2322 2202 2212 "
        "1310", {}),
    ("greedy", True): (
        "2210 0322 0210 1210 3210 2010 2110 2310 2200 2220 2230 2221 "
        "2222 2223 0222 1222 3222 2022 2122 2322 2202 2212 2232 2220 "
        "3331", {}),
    ("evolutionary", False): (
        "3331 1310 3210 0331 0000 1103 2311 2300 2210 3311 2311 3211 "
        "2301 3333 2312 2311 2212 3312 1311 2312 0012 3311 2311 2211",
        {"population": 8}),
    ("evolutionary", True): (
        "2210 0322 3331 1310 3210 0331 0000 1103 0312 0211 2231 0332 "
        "3231 3330 3331 2231 2231 3331 2331 3231 3331 0031 2231 2231",
        {"population": 8}),
}


@pytest.mark.parametrize("name,seeded", sorted(PINNED_TRAJECTORIES))
def test_engine_trajectory_pinned(name, seeded):
    """Every strategy's search, as the engine drives it, trial for trial."""
    want_trials, want_extra = PINNED_TRAJECTORIES[name, seeded]
    res, _, _ = run_engine(make_strategy(name), 24, seed=11,
                           seeds=PINNED_SEEDS if seeded else None)
    trials = " ".join("".join(str(t.config[f"p{i}"]) for i in range(4))
                      for t in res.trials)
    assert trials == want_trials
    assert [t.time for t in res.trials] == [quadratic(t.config)
                                            for t in res.trials]
    assert {k: res.extra[k] for k in want_extra} == want_extra


class ReversingPredictor:
    """Ranks every batch back to front, so the engine measures and tells
    each batch in the reverse of the order it was asked."""

    def rank(self, configs, shape, profile):
        return list(range(len(configs), 0, -1))


def test_walk_gets_times_in_asked_order_under_predictor_reordering():
    plain, _, _ = run_engine(ParticleSwarm(swarm_size=3), 24, seed=11)
    res, _, _ = run_engine(ParticleSwarm(swarm_size=3), 24, seed=11,
                           predictor=ReversingPredictor())
    assert res.extra["engine"]["predictor_rank_used"] > 0
    # each particle keeps its own times: the reordering changes only the
    # order of measurement, never the swarm's walk
    assert res.extra["particle_traces"] == plain.extra["particle_traces"]
    key = make_space().config_key
    assert sorted(key(t.config) for t in res.trials) == \
        sorted(key(t.config) for t in plain.trials)


# -- early-stop pruning -------------------------------------------------------

def test_median_prune_loop_semantics():
    samples, pruned = median_prune_loop(lambda: 1.0, 5)
    assert len(samples) == 5 and not pruned
    # above threshold: aborts before completing all repeats
    samples, pruned = median_prune_loop(lambda: 2.0, 5, prune_threshold_s=1.0)
    assert pruned and len(samples) < 5
    # at/below threshold: runs to completion
    samples, pruned = median_prune_loop(lambda: 0.5, 5, prune_threshold_s=1.0)
    assert len(samples) == 5 and not pruned


def test_pruning_never_prunes_incumbent():
    times = {0: 5.0, 1: 3.0, 2: 8.0, 3: 1.0, 4: 9.0}
    sp = SearchSpace().add_parameter(name="T", values=tuple(times))
    res, eng, _ = run_engine(
        make_strategy("full"), None, fn=lambda c: times[c["T"]], space=sp,
        prune_factor=1.5, workers=1)
    by_key = {k[0]: m for k, m in eng.measurements.items()}
    # first config: no incumbent yet -> cannot be pruned
    assert not by_key[0].pruned
    # improving configs (new incumbents) are never pruned
    assert not by_key[1].pruned and not by_key[3].pruned
    # configs beyond k x incumbent are aborted early
    assert by_key[2].pruned and by_key[4].pruned
    assert res.extra["engine"]["pruned"] == 2
    # pruning never corrupts the search outcome
    assert res.best_config == {"T": 3} and res.best_time == 1.0
    assert not eng.measurements[(3,)].pruned


def test_pruned_measurement_never_becomes_best():
    # adversarial: prune threshold k=1 (tightest legal) on a noisy-ish table
    times = {i: 1.0 + 0.5 * i for i in range(8)}
    sp = SearchSpace().add_parameter(name="T", values=tuple(times))
    res, eng, _ = run_engine(
        make_strategy("full"), None, fn=lambda c: times[c["T"]], space=sp,
        prune_factor=1.0, workers=1)
    best_key = (res.best_config["T"],)
    assert not eng.measurements[best_key].pruned


# -- acceptance-mirror: 200-config PSO through the engine --------------------

def test_pso_200_fewer_compiles_than_evaluations():
    res, _, ev = run_engine(make_strategy("pso", swarm_size=6), 200,
                            prune_factor=2.0)
    s = res.extra["engine"]
    assert s["evaluations"] == 200
    assert s["compile_calls"] < s["evaluations"]
    assert s["compile_calls"] == ev.prepare_calls
    assert s["memo_hits"] == 200 - s["unique_configs"]


# -- speculation --------------------------------------------------------------

def test_speculative_prefetch_counts_and_preserves_results():
    direct = SimulatedAnnealing().run(make_space(), quadratic, 30, seed=5)
    res, _, ev = run_engine(SimulatedAnnealing(), 30, seed=5,
                            speculate=3, workers=4)
    # speculation warms compiles but never changes the search trajectory
    assert [t.time for t in res.trials] == [t.time for t in direct.trials]
    s = res.extra["engine"]
    assert s["speculative_compiles"] > 0
    assert s["speculative_hits"] <= s["speculative_compiles"]
    # compile_calls includes speculation; measures only actual evaluations
    assert ev.measure_calls == s["unique_configs"]


# -- failure handling ---------------------------------------------------------

def test_infeasible_configs_never_become_incumbent():
    def fn(cfg):
        return math.inf if cfg["p0"] == 2 else quadratic(cfg)
    res, _, _ = run_engine(make_strategy("full"), None, fn=fn)
    assert res.best_config["p0"] != 2
    assert math.isfinite(res.best_time)


def test_custom_registered_strategy_works_via_fallback():
    class TwoStep(Strategy):
        name = "twostep"

        def walk(self, space, budget, seed=0, seeds=None):
            import random as _random
            rng = _random.Random(seed)
            for _ in range(budget):
                yield [space.sample(rng)]

    res, _, _ = run_engine(TwoStep(), 10)
    assert res.evaluations == 10 and res.best is not None


# -- API plumbing -------------------------------------------------------------

def test_tune_kernel_exposes_engine_stats(tmp_path):
    from repro.core import TuningCache
    from repro.tune import tune_kernel
    out = tune_kernel("gemm", {"M": 512, "N": 512, "K": 512},
                      strategy="pso", budget=30, record=False,
                      cache=TuningCache(str(tmp_path / "c.json")),
                      engine={"workers": 2}, swarm_size=3)
    s = out.engine_stats
    assert s is not None
    assert s["evaluations"] == out.result.evaluations
    assert s["compile_calls"] <= s["evaluations"]
    assert "engine:" in out.report()


def test_engine_config_validation():
    with pytest.raises(ValueError):
        EngineConfig(workers=0)
    with pytest.raises(ValueError):
        EngineConfig(prune_factor=0.5)
    assert EngineConfig().workers >= 1      # None = auto-sized pool


def test_batched_drivers_reject_none_budget():
    # budget=None (exhaustive) is a full-search concept; the other native
    # drivers must fail fast rather than crash mid-search or loop forever
    for name in ("random", "pso", "evolutionary"):
        with pytest.raises(ValueError):
            make_strategy(name).asktell(make_space(), None)


# -- phase spans and counters -------------------------------------------------

def _tiny_wallclock_search(tmp_path):
    """Three GEMM configurations, compiled and timed in interpret mode."""
    from repro.core import TuningCache, WallClockEvaluator
    from repro.tune import tune_kernel
    return tune_kernel("gemm", {"M": 256, "N": 256, "K": 256,
                                "dtype": "float32"},
                       strategy="random", budget=3, record=False,
                       evaluator=WallClockEvaluator(repeats=2),
                       cache=TuningCache(str(tmp_path / "c.json")))


def test_wallclock_search_fills_phase_counters(tmp_path):
    s = _tiny_wallclock_search(tmp_path).engine_stats
    assert s["unique_configs"] == 3
    assert all(s[name] > 0 for name in PHASES), s
    # each phase lies inside the engine's interval around its call; the
    # slack is as_dict's rounding to 6 places
    prepare = (s["args_s"] + s["lower_s"] + s["xla_compile_s"]
               + s["first_call_s"])
    assert 0.9 * s["compile_total_s"] <= prepare <= s["compile_total_s"] + 1e-5
    measure = s["verify_s"] + s["timing_s"]
    assert 0.9 * s["measure_total_s"] <= measure <= s["measure_total_s"] + 1e-5


def _counted_copy_tuner(evaluator, calls, n=64):
    """A copy kernel of length ``n`` with three configurations, its input
    draws and reference runs counted in ``calls``."""
    import jax.numpy as jnp

    from repro.core import Tuner

    def make_args(rng):
        calls["make_args"] += 1
        return (jnp.asarray(rng.normal(size=n), jnp.float32),)

    def reference(x):
        calls["reference"] += 1
        return x

    t = Tuner(evaluator=evaluator)
    t.set_reference(reference)
    t.add_kernel(lambda cfg: (lambda x: x.reshape(-1, cfg["W"]).reshape(n)),
                 name="copy", make_args=make_args, meta={"n": n})
    t.add_parameter("W", [1, 2, 4])
    return t


def _search(tuner):
    # one compile at a time: the first trial draws the inputs
    return tuner.tune(strategy="full", engine={"workers": 1}).engine_stats


def test_wallclock_search_draws_inputs_and_runs_reference_once():
    import collections

    from repro.core import WallClockEvaluator
    ev = WallClockEvaluator(repeats=2, seed=3)
    calls = collections.Counter()
    s = _search(_counted_copy_tuner(ev, calls))
    assert s["unique_configs"] == 3
    assert calls == {"make_args": 1, "reference": 1}
    assert s["inputs_reused"] == 2
    # no trial wrote into the inputs every trial shared
    [x] = ev._fixture.args
    np.testing.assert_array_equal(
        np.asarray(x),
        np.random.default_rng(3).normal(size=64).astype(np.float32))


def test_wallclock_fixture_follows_the_spec_and_the_seed():
    import collections

    from repro.core import WallClockEvaluator
    ev = WallClockEvaluator(repeats=1)
    calls = collections.Counter()
    t = _counted_copy_tuner(ev, calls)
    _search(t)
    # the same spec and seed again: every trial reuses the fixture
    assert _search(t)["inputs_reused"] == 3
    assert calls == {"make_args": 1, "reference": 1}
    # another spec of the same name and shape draws and runs its own
    assert _search(_counted_copy_tuner(ev, calls))["inputs_reused"] == 2
    assert calls == {"make_args": 2, "reference": 2}
    # so does a new shape
    t = _counted_copy_tuner(ev, calls, n=128)
    assert _search(t)["inputs_reused"] == 2
    assert calls == {"make_args": 3, "reference": 3}
    # and a new seed, and the evaluator holds only the newest fixture
    ev.seed = 1
    assert _search(t)["inputs_reused"] == 2
    assert calls == {"make_args": 4, "reference": 4}
    assert ev._fixture.seed == 1 and ev._fixture.spec.meta == {"n": 128}


def test_wallclock_fixture_is_drawn_and_run_once_under_contention():
    """Concurrent prepares (a compile pool, dtune thread workers) draw the
    inputs once, and concurrent measures run the reference once."""
    import sys
    import threading

    import jax.numpy as jnp

    from repro.core import WallClockEvaluator
    calls = {"make_args": 0, "reference": 0}

    def make_args(rng):
        calls["make_args"] += 1
        return (jnp.asarray(rng.normal(size=8), jnp.float32),)

    def reference(x):
        calls["reference"] += 1
        return x

    spec = KernelSpec(name="copy", build=lambda cfg: (lambda x: x),
                      make_args=make_args, reference=reference)
    ev = WallClockEvaluator(repeats=1)
    n = 16
    barrier = threading.Barrier(n)
    artifacts = [None] * n

    def trial(i):
        barrier.wait()
        artifacts[i] = ev.prepare(spec, {"i": i})
        barrier.wait()
        ev.measure(spec, {"i": i}, artifacts[i])

    interval = sys.getswitchinterval()
    sys.setswitchinterval(1e-6)
    try:
        threads = [threading.Thread(target=trial, args=(i,))
                   for i in range(n)]
        for t in threads:
            t.start()
        for t in threads:
            t.join(timeout=60)
    finally:
        sys.setswitchinterval(interval)
    assert not any(t.is_alive() for t in threads)
    assert calls == {"make_args": 1, "reference": 1}
    assert len({id(a.payload.fixture) for a in artifacts}) == 1
    assert sum(a.stats["inputs_reused"] for a in artifacts) == n - 1


def test_wallclock_search_spans_reach_the_profiler_trace(tmp_path):
    import collections
    import glob

    import jax
    from jax.profiler import ProfileData

    options = jax.profiler.ProfileOptions()
    options.python_tracer_level = 0
    options.host_tracer_level = 1
    jax.profiler.start_trace(str(tmp_path / "trace"),
                             profiler_options=options)
    try:
        out = _tiny_wallclock_search(tmp_path)
    finally:
        jax.profiler.stop_trace()
    [path] = glob.glob(str(tmp_path / "trace" / "**" / "*.xplane.pb"),
                       recursive=True)
    spans = [(e.name, dict(e.stats))
             for plane in ProfileData.from_file(path).planes
             if plane.name.startswith("/host:")
             for line in plane.lines for e in line.events
             if e.name.startswith("repro.")]
    counts = collections.Counter(name for name, _ in spans)
    for phase in ("args", "lower", "compile", "first_call", "verify",
                  "timing"):
        assert counts[f"repro.eval.{phase}"] == 3, counts
    assert counts["repro.engine.compile_wait"] == 3
    assert counts["repro.engine.strategy"] >= 2      # an ask and a tell
    # one trial's compile and measure spans join on its configuration
    trials = {config_arg(t.config) for t in out.result.trials}
    for phase in ("repro.eval.compile", "repro.eval.timing",
                  "repro.engine.compile_wait"):
        assert {a["config"] for name, a in spans if name == phase} == trials
