"""Distributed tuning plane: sharding, workers, coordinator, fleet merge.

Covers the dtune subsystem (partition / worker / coordinator), the
TuningCache merge primitive and merge-on-disk save protocol (including
multiprocessing concurrent writers and torn-file recovery), the
default_cache() race fix, the nearest() shape-index memoization, and the
engine's cooperative stop_event.
"""

import dataclasses
import json
import math
import multiprocessing
import os
import threading

import pytest

from repro.core import (EngineConfig, EvaluationEngine, KernelSpec,
                        SearchSpace, TuningCache, make_strategy)
from repro.core.cache import CacheEntry, default_cache
from repro.core.evaluators import Evaluator, Measurement
from repro.dtune import (ISLAND_STRATEGIES, DistributedTuner, Shard,
                         TuningWorker, WorkerSpec, run_workers, shard_space)

SHAPE = {"M": 512, "N": 512, "K": 512}
ANALYTICAL = {"name": "analytical", "noise_sigma": 0.0}


def make_space(n_params=3, n_values=4):
    sp = SearchSpace()
    for i in range(n_params):
        sp.add_parameter(name=f"p{i}", values=tuple(range(n_values)))
    return sp


class CountingEvaluator(Evaluator):
    """Deterministic objective; counts evaluations."""

    name = "counting"

    def __init__(self):
        self.calls = 0

    def prepare(self, spec, config):
        return None

    def measure(self, spec, config, prepared=None, prune_threshold_s=None):
        self.calls += 1
        return Measurement(time_s=1.0 + sum(config.values()), ok=True)


SPEC = KernelSpec(name="stub", build=lambda c: (lambda: None))


# -- partitioning -------------------------------------------------------------

def test_strided_shards_partition_space_exactly():
    space = make_space()
    shards = shard_space(space, 4, "strided")
    seen = {}
    for shard in shards:
        strat = make_strategy(shard.strategy, **shard.strategy_kwargs)
        res = strat.run(space, lambda c: 1.0, budget=None)
        for t in res.trials:
            key = space.config_key(t.config)
            assert key not in seen, \
                f"config visited by shards {seen[key]} and {shard.index}"
            seen[key] = shard.index
    assert len(seen) == space.cardinality()          # union covers everything
    # balanced: strided split sizes differ by at most one
    sizes = [sum(1 for v in seen.values() if v == i) for i in range(4)]
    assert max(sizes) - min(sizes) <= 1


def test_shard_space_validation():
    space = make_space(1, 4)
    with pytest.raises(ValueError, match="at least one shard"):
        shard_space(space, 0)
    with pytest.raises(ValueError, match="unknown shard mode"):
        shard_space(space, 2, "rings")
    with pytest.raises(ValueError, match="full search"):
        shard_space(space, 2, "strided", strategies=["pso"])
    with pytest.raises(ValueError, match="at least one strategy"):
        shard_space(space, 2, "islands", strategies=[])


def test_islands_rotate_strategies_and_seeds():
    shards = shard_space(make_space(), 6, "islands", budget=10, seed=7)
    assert [s.strategy for s in shards] == \
        list(ISLAND_STRATEGIES) + list(ISLAND_STRATEGIES[:2])
    assert len({s.seed for s in shards}) == 6        # all distinct
    assert all(s.budget == 10 for s in shards)


def test_full_search_stride_validation():
    with pytest.raises(ValueError):
        make_strategy("full", offset=2, stride=2)
    with pytest.raises(ValueError):
        make_strategy("full", offset=-1, stride=2)
    with pytest.raises(ValueError):
        make_strategy("full", stride=0)


def test_full_search_asktell_respects_stride():
    space = make_space(2, 4)                         # 16 configs
    eng = EvaluationEngine(CountingEvaluator(), SPEC, space, EngineConfig())
    res = eng.run(make_strategy("full", offset=1, stride=4), None)
    assert res.evaluations == 4                      # 16 / 4


# -- engine stop event --------------------------------------------------------

def test_stop_event_yields_graceful_partial_result():
    space = make_space()
    stop = threading.Event()
    stop.set()                                       # stop before any batch
    eng = EvaluationEngine(CountingEvaluator(), SPEC, space,
                           EngineConfig(stop_event=stop))
    res = eng.run(make_strategy("full"), None)
    assert res.extra["aborted"]["stopped"] is True
    assert res.evaluations == 0 and res.best is None
    assert res.extra["engine"]["aborted"] is True


def test_stop_event_unset_changes_nothing():
    space = make_space()
    eng = EvaluationEngine(CountingEvaluator(), SPEC, space,
                           EngineConfig(stop_event=threading.Event()))
    res = eng.run(make_strategy("full"), None)
    assert "aborted" not in res.extra
    assert res.evaluations == space.cardinality()


# -- workers ------------------------------------------------------------------

def _spec(tmp_path, shard, **kw):
    defaults = dict(kernel="gemm", shape=dict(SHAPE), shard=shard,
                    evaluator=ANALYTICAL,
                    cache_path=str(tmp_path / f"w{shard.index}.json"))
    defaults.update(kw)
    return WorkerSpec(**defaults)


def test_worker_runs_one_shard_and_records(tmp_path):
    shard = Shard(index=0, total=2, mode="strided", strategy="full",
                  strategy_kwargs={"offset": 0, "stride": 2})
    res = TuningWorker(_spec(tmp_path, shard)).run()
    assert res.status == "ok" and res.ok
    assert math.isfinite(res.best_time) and res.evaluations > 0
    private = TuningCache(res.cache_path).load()
    assert len(private) == 1                         # shard winner recorded
    entry = private.get("gemm", "M512_N512_K512_float32", "tpu_v5e")
    assert entry is not None and entry.config == res.best_config


def test_worker_crash_becomes_failed_result(tmp_path):
    shard = Shard(index=0, total=1, mode="strided", strategy="full",
                  strategy_kwargs={"offset": 0, "stride": 1})
    res = TuningWorker(_spec(tmp_path, shard,
                             kernel="no-such-kernel")).run()
    assert res.status == "failed" and not res.ok
    assert "no-such-kernel" in (res.error or "")


def test_worker_stop_event_reports_aborted(tmp_path):
    shard = Shard(index=0, total=1, mode="strided", strategy="full",
                  strategy_kwargs={"offset": 0, "stride": 1})
    stop = threading.Event()
    stop.set()
    res = TuningWorker(_spec(tmp_path, shard), stop_event=stop).run()
    assert res.status == "aborted"
    assert res.best_config is None                   # stopped before work


def test_run_workers_rejects_unknown_driver():
    with pytest.raises(ValueError, match="unknown dtune driver"):
        run_workers([], driver="carrier-pigeon")


def test_process_driver_refuses_device_timing_on_tpu(tmp_path, monkeypatch):
    """One process holds a TPU: forked workers could not time on it."""
    import repro.dtune.worker as worker_mod
    monkeypatch.setattr(worker_mod, "on_tpu", lambda: True)
    shard = Shard(index=0, total=1, mode="strided", strategy="full",
                  strategy_kwargs={"offset": 0, "stride": 1})
    for evaluator in (None, "wallclock", {"name": "wallclock"}):
        with pytest.raises(ValueError, match="one process"):
            run_workers([_spec(tmp_path, shard, evaluator=evaluator)],
                        driver="process")
    # a model-timed fleet touches no device and may still fork
    res = run_workers([_spec(tmp_path, shard)], driver="process",
                      timeout_s=120)
    assert res[0].status == "ok"


def test_evaluator_spec_forms(tmp_path):
    from repro.dtune.worker import resolve_evaluator
    from repro.core import TPUAnalyticalEvaluator
    assert resolve_evaluator(None) is None
    ev = TPUAnalyticalEvaluator()
    assert resolve_evaluator(ev) is ev
    assert resolve_evaluator("analytical").name == ev.name
    assert resolve_evaluator(ANALYTICAL).noise_sigma == 0.0
    with pytest.raises(ValueError, match="'name' key"):
        resolve_evaluator({"noise_sigma": 0.0})
    with pytest.raises(TypeError):
        resolve_evaluator(42)


# -- coordinator --------------------------------------------------------------

def test_distributed_strided_matches_single_process(tmp_path):
    cache = TuningCache(str(tmp_path / "fleet.json"))
    out = DistributedTuner("gemm", SHAPE, n_workers=4, mode="strided",
                           driver="thread", cache=cache,
                           evaluator=ANALYTICAL).run()
    assert out.ok and all(w.status == "ok" for w in out.workers)

    from repro.tune import tune_kernel
    from repro.core import TPUAnalyticalEvaluator
    single = tune_kernel("gemm", SHAPE, strategy="full", budget=10 ** 9,
                         record=False, warm_start=False,
                         evaluator=TPUAnalyticalEvaluator(noise_sigma=0.0))
    # exact partition: fleet winner time == single-process winner time and
    # total fleet evaluations == the full space, split ~evenly
    assert out.best_time == pytest.approx(single.best_time)
    assert out.evaluations == single.result.evaluations
    assert out.per_worker_evaluations <= single.result.evaluations / 3
    # the merged fleet winner is in the shared cache file
    again = TuningCache(cache.path).load()
    entry = again.get("gemm", "M512_N512_K512_float32", "tpu_v5e")
    assert entry is not None
    assert entry.time_s == pytest.approx(out.best_time)
    assert out.merged_keys == ["gemm|M512_N512_K512_float32|tpu_v5e"]


def test_distributed_islands_with_process_driver(tmp_path):
    if "fork" not in multiprocessing.get_all_start_methods():
        pytest.skip("needs fork start method")
    cache = TuningCache(str(tmp_path / "fleet.json"))
    out = DistributedTuner("gemm", SHAPE, n_workers=2, mode="islands",
                           driver="process", budget=8, cache=cache,
                           warm_start=False, evaluator=ANALYTICAL
                           ).run(timeout_s=300)
    assert out.ok
    assert [w.status for w in out.workers] == ["ok", "ok"]
    assert all(w.evaluations == 8 for w in out.workers)
    assert len(TuningCache(cache.path).load()) == 1


def test_distributed_one_worker_failure_does_not_kill_fleet(tmp_path):
    cache = TuningCache(str(tmp_path / "fleet.json"))
    shards = shard_space(make_space(), 2, "strided")
    specs = [
        WorkerSpec(kernel="gemm", shape=dict(SHAPE), shard=shards[0],
                   evaluator=ANALYTICAL,
                   cache_path=str(tmp_path / "w0.json")),
        WorkerSpec(kernel="no-such-kernel", shape=dict(SHAPE),
                   shard=shards[1], evaluator=ANALYTICAL,
                   cache_path=str(tmp_path / "w1.json")),
    ]
    results = run_workers(specs, "thread")
    assert [r.status for r in results] == ["ok", "failed"]
    assert results[0].ok                             # shard 0 still tuned


def test_env_knobs(tmp_path, monkeypatch):
    monkeypatch.setenv("REPRO_DTUNE_WORKERS", "7")
    monkeypatch.setenv("REPRO_DTUNE_MODE", "islands")
    monkeypatch.setenv("REPRO_DTUNE_DRIVER", "process")
    dt = DistributedTuner("gemm", SHAPE,
                          cache=TuningCache(str(tmp_path / "c.json")))
    assert (dt.n_workers, dt.mode, dt.driver) == (7, "islands", "process")
    monkeypatch.setenv("REPRO_DTUNE_WORKERS", "not-a-number")
    dt = DistributedTuner("gemm", SHAPE, mode="strided", driver="thread",
                          cache=TuningCache(str(tmp_path / "c.json")))
    assert dt.n_workers == 4                         # fallback, not a crash


def test_coordinator_rejects_engine_stop_event(tmp_path):
    with pytest.raises(ValueError, match="stop_event"):
        DistributedTuner("gemm", SHAPE,
                         cache=TuningCache(str(tmp_path / "c.json")),
                         engine={"stop_event": threading.Event()})


# -- cache merge --------------------------------------------------------------

def _cache(tmp_path, name="c.json"):
    return TuningCache(str(tmp_path / name))


def test_merge_keeps_best_finite_time_per_key(tmp_path):
    a, b = _cache(tmp_path, "a.json"), _cache(tmp_path, "b.json")
    a.record("k", "s", "p", {"x": 1}, 2.0, "full", 10)
    b.record("k", "s", "p", {"x": 2}, 1.0, "full", 20)
    changed = a.merge(b)
    assert list(changed) == ["k|s|p"]
    e = a.get("k", "s", "p")
    assert e.config == {"x": 2} and e.time_s == 1.0
    assert e.evaluations == 30                       # folded, not replaced
    # the worse entry never overwrites the better one in the other order
    # (count folding alone is not a "changed entry" — no subscriber event)
    assert b.merge(a) == {}
    assert b.get("k", "s", "p").config == {"x": 2}
    assert b.get("k", "s", "p").evaluations == 30


def test_merge_unions_disjoint_keys_and_shapes(tmp_path):
    a, b = _cache(tmp_path, "a.json"), _cache(tmp_path, "b.json")
    a.record("k", "s1", "p", {"x": 1}, 1.0, "full", 1)
    b.record("k", "s2", "p", {"x": 2}, 2.0, "full", 1, shape={"M": 64})
    a.merge(b)
    assert len(a) == 2
    assert a.get("k", "s2", "p").shape == {"M": 64}


def test_merge_adopts_shape_from_loser(tmp_path):
    a, b = _cache(tmp_path, "a.json"), _cache(tmp_path, "b.json")
    a.record("k", "s", "p", {"x": 1}, 1.0, "full", 1)            # no shape
    b.record("k", "s", "p", {"x": 2}, 5.0, "full", 1, shape={"M": 64})
    a.merge(b)
    e = a.get("k", "s", "p")
    assert e.config == {"x": 1} and e.shape == {"M": 64}         # union


def test_merge_is_idempotent(tmp_path):
    a, b = _cache(tmp_path, "a.json"), _cache(tmp_path, "b.json")
    a.record("k", "s", "p", {"x": 1}, 2.0, "full", 10, failures=3)
    b.record("k", "s", "p", {"x": 2}, 1.0, "full", 20, failures=5)
    a.merge(b)
    first = dataclasses.asdict(a.get("k", "s", "p"))
    assert not a.merge(b)                            # no further change
    assert dataclasses.asdict(a.get("k", "s", "p")) == first
    assert first["evaluations"] == 30 and first["failures"] == 8


def test_merge_sanitizes_poisoned_peer(tmp_path):
    a = _cache(tmp_path, "a.json")
    a.record("k", "s", "p", {"x": 1}, 1.0, "full", 1)
    changed = a.merge({"k|bad|p": {"time_s": math.inf, "config": {}},
                       "k|worse|p": "not-an-object",
                       "k|s2|p": {"config": {"x": 9}, "time_s": 2.0,
                                  "strategy": "full", "evaluations": 1,
                                  "timestamp": 0.0}})
    assert list(changed) == ["k|s2|p"]
    assert len(a) == 2                               # poison dropped
    a.save()                                         # strict JSON still OK


def test_merge_from_path_and_errors(tmp_path):
    a, b = _cache(tmp_path, "a.json"), _cache(tmp_path, "b.json")
    b.record("k", "s", "p", {"x": 1}, 1.0, "full", 1)
    b.save()
    assert list(a.merge(b.path)) == ["k|s|p"]
    with pytest.raises(FileNotFoundError):
        a.merge(str(tmp_path / "missing.json"))
    with pytest.raises(TypeError):
        a.merge(42)


def test_merge_fires_subscribers_for_changed_entries_only(tmp_path):
    a, b = _cache(tmp_path, "a.json"), _cache(tmp_path, "b.json")
    a.record("k", "s1", "p", {"x": 1}, 1.0, "full", 1)
    b.record("k", "s1", "p", {"x": 2}, 5.0, "full", 1)   # worse: no event
    b.record("k", "s2", "p", {"x": 3}, 1.0, "full", 1)   # new: event
    events = []
    a.subscribe(lambda key, entry: events.append((key, entry.config)))
    a.merge(b)
    assert events == [("k|s2|p", {"x": 3})]


# -- merge-on-disk save protocol ----------------------------------------------

def test_save_merges_with_concurrent_disk_state(tmp_path):
    path = str(tmp_path / "shared.json")
    first, second = TuningCache(path), TuningCache(path)
    second.load()                                    # loads the empty state
    first.record("k", "s1", "p", {"x": 1}, 1.0, "full", 1)
    first.save()
    # second never saw first's entry; its old-style save would erase it
    second.record("k", "s2", "p", {"x": 2}, 2.0, "full", 1)
    second.save()
    on_disk = TuningCache(path).load()
    assert len(on_disk) == 2                         # both survive
    assert len(second) == 2                          # merged back into memory
    # legacy overwrite is still available explicitly
    second.clear()
    second.save(merge_on_disk=False)
    assert len(TuningCache(path).load()) == 0


def test_save_keeps_best_on_overlapping_key(tmp_path):
    path = str(tmp_path / "shared.json")
    first, second = TuningCache(path), TuningCache(path)
    second.load()
    first.record("k", "s", "p", {"x": 1}, 1.0, "full", 1)
    first.save()
    second.record("k", "s", "p", {"x": 2}, 5.0, "full", 1)   # worse time
    second.save()
    assert TuningCache(path).load().get("k", "s", "p").config == {"x": 1}


def _writer(path, keys, t, barrier):
    cache = TuningCache(path)
    for key in keys:
        cache.record("k", key, "p", {"who": key, "t": t}, t, "full", 1)
    barrier.wait(timeout=60)                         # maximize save overlap
    cache.save()


def test_multiprocessing_concurrent_writers_converge(tmp_path):
    if "fork" not in multiprocessing.get_all_start_methods():
        pytest.skip("needs fork start method")
    ctx = multiprocessing.get_context("fork")
    path = str(tmp_path / "shared.json")
    barrier = ctx.Barrier(2)
    procs = [
        ctx.Process(target=_writer,
                    args=(path, ["only-a", "both"], 1.0, barrier)),
        ctx.Process(target=_writer,
                    args=(path, ["only-b", "both"], 2.0, barrier)),
    ]
    for p in procs:
        p.start()
    for p in procs:
        p.join(timeout=120)
        assert p.exitcode == 0
    merged = TuningCache(path).load()
    assert len(merged) == 3                          # disjoint keys union
    # the overlapping key kept the best finite time, not the last writer
    assert merged.get("k", "both", "p").time_s == 1.0
    assert merged.get("k", "only-a", "p") is not None
    assert merged.get("k", "only-b", "p") is not None


def test_torn_tmp_file_does_not_corrupt_load_or_save(tmp_path):
    path = str(tmp_path / "cache.json")
    cache = TuningCache(path)
    cache.record("k", "s", "p", {"x": 1}, 1.0, "full", 1)
    cache.save()
    # a crashed writer leaves a torn temp sibling + a stale lock file
    with open(str(tmp_path / "cache.json.tmp"), "w") as f:
        f.write('{"torn": ')
    with open(path + ".lock", "w") as f:
        f.write("")
    fresh = TuningCache(path).load()
    assert len(fresh) == 1                           # real file untouched
    fresh.record("k", "s2", "p", {"x": 2}, 2.0, "full", 1)
    fresh.save()                                     # lock path still works
    assert len(TuningCache(path).load()) == 2


def test_save_merge_survives_strict_json_gate(tmp_path):
    """In-memory non-finite entries must still make save() raise (the
    defense-in-depth contract) even on the merge path."""
    path = str(tmp_path / "cache.json")
    cache = TuningCache(path)
    cache.record("k", "s", "p", {"x": 1}, 1.0, "full", 1)
    cache._data["bad"] = {"time_s": math.inf}
    with pytest.raises(ValueError):
        cache.save()


# -- default_cache race -------------------------------------------------------

def test_default_cache_is_one_object_across_threads(tmp_path, monkeypatch):
    monkeypatch.setenv("REPRO_TUNE_CACHE", str(tmp_path / "dc.json"))
    import repro.core.cache as cache_mod
    monkeypatch.setattr(cache_mod, "_default_cache", None)
    results = []
    barrier = threading.Barrier(8)

    def resolver():
        barrier.wait(timeout=30)
        results.append(default_cache())

    threads = [threading.Thread(target=resolver) for _ in range(8)]
    for t in threads:
        t.start()
    for t in threads:
        t.join(timeout=30)
    assert len(results) == 8
    assert all(c is results[0] for c in results)     # one shared object


# -- nearest() memoization ----------------------------------------------------

def test_nearest_uses_memoized_index_and_invalidates(tmp_path):
    cache = _cache(tmp_path)
    cache.record("k", "s64", "p", {"x": 64}, 1.0, "full", 1,
                 shape={"M": 64})
    cache.record("k", "s128", "p", {"x": 128}, 1.0, "full", 1,
                 shape={"M": 128})
    out = cache.nearest("k", {"M": 100}, "p", k=1)
    assert [e.config["x"] for e in out] == [128]
    bucket = cache._shape_index[("k", "p", None)]
    cache.nearest("k", {"M": 70}, "p", k=1)
    assert cache._shape_index[("k", "p", None)] is bucket  # reused, not rebuilt
    cache.record("k", "s96", "p", {"x": 96}, 1.0, "full", 1,
                 shape={"M": 96})                    # put invalidates
    assert cache._shape_index is None
    out = cache.nearest("k", {"M": 100}, "p", k=1)
    assert [e.config["x"] for e in out] == [96]


def test_nearest_returns_copies(tmp_path):
    cache = _cache(tmp_path)
    cache.record("k", "s", "p", {"x": 1}, 1.0, "full", 1, shape={"M": 64})
    first = cache.nearest("k", {"M": 64}, "p", k=1)[0]
    first.config["x"] = 999                          # caller mutates freely
    first.shape["M"] = 0
    again = cache.nearest("k", {"M": 64}, "p", k=1)[0]
    assert again.config == {"x": 1} and again.shape == {"M": 64}


def test_nearest_index_invalidated_by_merge(tmp_path):
    a, b = _cache(tmp_path, "a.json"), _cache(tmp_path, "b.json")
    a.record("k", "s64", "p", {"x": 64}, 1.0, "full", 1, shape={"M": 64})
    assert a.nearest("k", {"M": 90}, "p", k=1)[0].config["x"] == 64
    b.record("k", "s96", "p", {"x": 96}, 1.0, "full", 1, shape={"M": 96})
    a.merge(b)
    assert a.nearest("k", {"M": 90}, "p", k=1)[0].config["x"] == 96


# -- CacheEntry.failures ------------------------------------------------------

def test_failures_field_roundtrip_and_legacy_stability(tmp_path):
    cache = _cache(tmp_path)
    cache.record("k", "s", "p", {"x": 1}, 1.0, "full", 5, failures=2)
    cache.record("k", "s2", "p", {"x": 2}, 1.0, "full", 5)       # zero
    cache.save()
    raw = json.load(open(cache.path))
    assert raw["k|s|p"]["failures"] == 2
    assert "failures" not in raw["k|s2|p"]           # legacy byte-stability
    again = TuningCache(cache.path).load()
    assert again.get("k", "s", "p").failures == 2
    assert again.get("k", "s2", "p").failures == 0


# -- coordinator workdir containment + shared artifact store ------------------

def _dtune_tmpdirs():
    import tempfile as _tempfile
    base = _tempfile.gettempdir()
    return {d for d in os.listdir(base) if d.startswith("repro-dtune-")}


def test_workdir_cleaned_up_on_coordinator_crash(tmp_path, monkeypatch):
    """A crash anywhere between mkdtemp and the merge (driver raising,
    worker fleet terminated) must not leak the private-cache tempdir."""
    from repro.dtune import coordinator as mod

    def explode(*a, **kw):
        raise RuntimeError("fleet terminated")

    monkeypatch.setattr(mod, "run_workers", explode)
    before = _dtune_tmpdirs()
    dt = DistributedTuner("gemm", SHAPE, n_workers=2, driver="thread",
                          cache=TuningCache(str(tmp_path / "c.json")))
    with pytest.raises(RuntimeError, match="fleet terminated"):
        dt.run()
    assert _dtune_tmpdirs() == before                # nothing leaked


def test_workdir_cleaned_up_on_spec_construction_crash(tmp_path, monkeypatch):
    from repro.dtune import coordinator as mod

    def bad_spec(*a, **kw):
        raise TypeError("unpicklable spec")

    monkeypatch.setattr(mod, "WorkerSpec", bad_spec)
    before = _dtune_tmpdirs()
    dt = DistributedTuner("gemm", SHAPE, n_workers=2, driver="thread",
                          cache=TuningCache(str(tmp_path / "c.json")))
    with pytest.raises(TypeError, match="unpicklable"):
        dt.run()
    assert _dtune_tmpdirs() == before


def test_workdir_cleaned_up_on_normal_run(tmp_path):
    before = _dtune_tmpdirs()
    DistributedTuner("gemm", SHAPE, n_workers=2, driver="thread",
                     budget=4, mode="islands",
                     cache=TuningCache(str(tmp_path / "c.json"))).run()
    assert _dtune_tmpdirs() == before


def test_worker_spec_ships_artifact_dir(tmp_path):
    """artifact_dir is plain picklable data; the worker opens its own
    store on it and records compiled artifacts there."""
    import pickle

    from repro.core.artifacts import ArtifactStore

    shard = Shard(index=0, total=1, mode="strided", strategy="full",
                  strategy_kwargs={"offset": 0, "stride": 1})
    spec = _spec(tmp_path, shard, artifact_dir=str(tmp_path / "store"))
    assert pickle.loads(pickle.dumps(spec)).artifact_dir == spec.artifact_dir
    res = TuningWorker(spec).run()
    assert res.status == "ok"
    # the analytical evaluator has no compile phase: nothing persisted,
    # nothing crashed — the plumbing is exercised end to end
    assert len(ArtifactStore(str(tmp_path / "store"))) == 0


def test_distributed_reruns_share_artifact_store(tmp_path):
    """Second fleet run against the warm shared store: every prepare in
    every worker is a store hit — zero fresh compiles fleet-wide."""
    from repro.core import SearchSpace as SS
    from repro.core.artifacts import ArtifactStore
    from repro.core.registry import tunable

    import jax
    import jax.numpy as jnp

    def space(shape):
        sp = SS()
        sp.add_parameter(name="k", values=(1.0, 2.0, 3.0, 4.0))
        return sp

    @tunable(name="dtune-artifact-probe", space=space,
             heuristic=lambda s: {"k": 1.0},
             arg_specs=lambda s: (jax.ShapeDtypeStruct((8, 8), jnp.float32),))
    def probe(shape, config, interpret=True):
        return lambda x: x * float(config["k"])

    store_dir = str(tmp_path / "store")

    def fleet():
        dt = DistributedTuner(
            "dtune-artifact-probe", {"N": 8}, n_workers=2, mode="strided",
            driver="thread", evaluator={"name": "costmodel"},
            artifact_store=store_dir,
            cache=TuningCache(str(tmp_path / "c.json")))
        out = dt.run()
        stats = [w.engine_stats for w in out.workers if w.engine_stats]
        return (sum(s["unique_configs"] for s in stats),
                sum(s["artifact_hits"] for s in stats))

    unique_cold, hits_cold = fleet()
    assert unique_cold == 4
    # each distinct artifact was compiled at most once fleet-wide
    store = ArtifactStore(store_dir)
    assert len(store) == 4 - hits_cold
    unique_warm, hits_warm = fleet()
    assert (unique_warm, hits_warm) == (4, 4)        # zero fresh compiles
