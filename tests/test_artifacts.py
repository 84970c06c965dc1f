"""Persistent compile-artifact cache: fingerprinting, the typed
CompiledArtifact contract, the content-addressed store (concurrent
writers, torn-tmp / stale-lock recovery, corrupted-entry quarantine),
the engine's artifact_hits accounting, the evaluate() deprecation shim,
and the unified REPRO_* env-knob parsing.
"""

import json
import multiprocessing
import os
import threading
import warnings

import jax
import jax.numpy as jnp
import pytest

from repro.core import (EngineConfig, EvaluationEngine, KernelSpec,
                        SearchSpace, make_strategy)
from repro.core.artifacts import (ARTIFACT_FORMAT_VERSION, ArtifactStore,
                                  CompiledArtifact, default_store,
                                  resolve_store, spec_fingerprint)
from repro.core.envknobs import env_bool, env_int, env_str, parse_bool
from repro.core.evaluators import (CostModelEvaluator, Evaluator,
                                   TPUAnalyticalEvaluator)
from repro.core.failures import CompileError
from repro.core.hlo import canonicalize_hlo, fingerprint
from repro.core.tuner import Tuner

# -- fingerprint canonicalization ---------------------------------------------

HLO_A = """HloModule jit_f.123, entry_computation_layout={(f32[8,8]{1,0})->f32[8,8]{1,0}}

ENTRY main {
  %p = f32[8,8]{1,0} parameter(0), metadata={op_name="jit(f)/mul" source_file="a.py" source_line=1}
  ROOT %m = f32[8,8]{1,0} multiply(%p, %p), metadata={op_name="jit(f)/mul"}
}
"""

HLO_B = """HloModule jit_g.456, entry_computation_layout={(f32[8,8]{1,0})->f32[8,8]{1,0}}
ENTRY main {
  %p = f32[8,8]{1,0} parameter(0), metadata={op_name="jit(g)/mul" source_file="b.py" source_line=9}
  ROOT %m = f32[8,8]{1,0} multiply(%p, %p)
}
"""

HLO_C = HLO_B.replace("multiply", "add")


def test_canonicalize_strips_names_metadata_and_whitespace():
    assert canonicalize_hlo(HLO_A) == canonicalize_hlo(HLO_B)
    assert canonicalize_hlo(HLO_B) != canonicalize_hlo(HLO_C)


def test_fingerprint_stable_across_presentation_noise():
    assert fingerprint(HLO_A) == fingerprint(HLO_B)
    assert fingerprint(HLO_A) != fingerprint(HLO_C)
    assert fingerprint(HLO_A).startswith("hlo:")


def test_fingerprint_strips_mlir_module_names_and_locs():
    m1 = 'module @jit_f attributes {x = 1} { func @main() loc("a.py":1:0) }\n#loc1 = loc("a.py":1:0)'
    m2 = 'module @jit_g attributes {x = 1} { func @main() loc("b.py":9:4) }\n#loc2 = loc("b.py":9:4)'
    assert fingerprint(m1) == fingerprint(m2)


def test_fingerprint_of_real_lowerings_ignores_wrapper_identity():
    spec = jax.ShapeDtypeStruct((16, 16), jnp.float32)

    def f(x):
        return (x @ x) * 2.0

    def g(x):
        return (x @ x) * 2.0

    def h(x):
        return (x @ x) * 3.0

    fp_f = fingerprint(jax.jit(f).lower(spec))
    fp_g = fingerprint(jax.jit(g).lower(spec))
    fp_h = fingerprint(jax.jit(h).lower(spec))
    assert fp_f == fp_g                  # same computation, different wrapper
    assert fp_f != fp_h                  # different constant -> different key


def test_fingerprint_rejects_non_module_objects():
    with pytest.raises(TypeError, match="as_text"):
        fingerprint(42)


def test_spec_fingerprint_keys_on_kernel_shape_config():
    a = spec_fingerprint("gemm", {"M": 8}, {"bm": 128})
    assert a == spec_fingerprint("gemm", {"M": 8}, {"bm": 128})
    assert a != spec_fingerprint("gemm", {"M": 16}, {"bm": 128})
    assert a != spec_fingerprint("gemm", {"M": 8}, {"bm": 256})
    assert a != spec_fingerprint("conv", {"M": 8}, {"bm": 128})
    assert a != spec_fingerprint("gemm", {"M": 8}, {"bm": 128}, extra="seed=1")
    assert a.startswith("spec:")


# -- the store ----------------------------------------------------------------

def _artifact(fp="hlo:abc", profile="tpu_v5e", kind="costmodel", flops=1.0):
    return CompiledArtifact(
        kind=kind, fingerprint=fp, profile=profile,
        payload={"flops": flops, "bytes": 2.0, "collective_bytes": 0.0,
                 "compile_s": 0.25},
        stats={"flops": flops}, compile_s=0.25, persistable=True)


def test_store_roundtrip_across_instances(tmp_path):
    root = str(tmp_path / "store")
    ArtifactStore(root).put(_artifact())
    got = ArtifactStore(root).get("costmodel", "hlo:abc", "tpu_v5e")
    assert got is not None and got.from_store
    assert got.compile_s == 0.0                      # the hit pays nothing
    assert got.payload["flops"] == 1.0
    assert got.persistable


def test_store_keys_on_kind_fingerprint_and_profile(tmp_path):
    store = ArtifactStore(str(tmp_path))
    store.put(_artifact())
    assert store.get("costmodel", "hlo:abc", "tpu_v4") is None
    assert store.get("costmodel", "hlo:other", "tpu_v5e") is None
    assert store.get("wallclock", "hlo:abc", "tpu_v5e") is None
    assert store.get("costmodel", "hlo:abc", "tpu_v5e") is not None
    assert len(store) == 1


def test_store_refuses_live_payloads(tmp_path):
    store = ArtifactStore(str(tmp_path))
    live = CompiledArtifact(kind="wallclock", fingerprint="spec:xyz",
                            profile="", payload=lambda: None,
                            persistable=False)
    assert store.put(live) is None
    assert len(store) == 0
    with pytest.raises(TypeError, match="live"):
        live.to_json()


def test_corrupted_entry_is_quarantined_not_fatal(tmp_path):
    store = ArtifactStore(str(tmp_path))
    store.put(_artifact())
    path = store.path_for("costmodel", "hlo:abc", "tpu_v5e")
    with open(path, "w") as f:
        f.write('{"torn": ')
    fresh = ArtifactStore(str(tmp_path))
    assert fresh.get("costmodel", "hlo:abc", "tpu_v5e") is None
    assert fresh.stats.quarantined == 1
    assert os.path.exists(path + ".corrupt")
    assert not os.path.exists(path)
    # and the address is usable again
    assert fresh.put(_artifact()) is not None
    assert fresh.get("costmodel", "hlo:abc", "tpu_v5e") is not None


def test_foreign_format_version_is_quarantined(tmp_path):
    store = ArtifactStore(str(tmp_path))
    store.put(_artifact())
    path = store.path_for("costmodel", "hlo:abc", "tpu_v5e")
    with open(path) as f:
        record = json.load(f)
    record["format"] = ARTIFACT_FORMAT_VERSION + 1
    with open(path, "w") as f:
        json.dump(record, f)
    fresh = ArtifactStore(str(tmp_path))
    assert fresh.get("costmodel", "hlo:abc", "tpu_v5e") is None
    assert fresh.stats.quarantined == 1


def test_mismatched_address_inside_record_is_quarantined(tmp_path):
    store = ArtifactStore(str(tmp_path))
    store.put(_artifact())
    src = store.path_for("costmodel", "hlo:abc", "tpu_v5e")
    dst = store.path_for("costmodel", "hlo:stolen", "tpu_v5e")
    os.replace(src, dst)                 # record claims a different address
    fresh = ArtifactStore(str(tmp_path))
    assert fresh.get("costmodel", "hlo:stolen", "tpu_v5e") is None
    assert fresh.stats.quarantined == 1


def test_torn_tmp_and_stale_lock_do_not_break_store(tmp_path):
    store = ArtifactStore(str(tmp_path))
    store.put(_artifact())
    # a crashed writer leaves a torn temp sibling + a stale lock file
    with open(str(tmp_path / "dead.tmp"), "w") as f:
        f.write('{"torn": ')
    lock = store.path_for("costmodel", "hlo:abc", "tpu_v5e") + ".lock"
    with open(lock, "w") as f:
        f.write("")
    fresh = ArtifactStore(str(tmp_path))
    assert fresh.get("costmodel", "hlo:abc", "tpu_v5e") is not None
    # get_or_compute must acquire the stale lock, see the record, not compute
    calls = []
    art = fresh.get_or_compute("costmodel", "hlo:abc", "tpu_v5e",
                               lambda: calls.append(1) or _artifact())
    assert art.from_store and not calls


def test_get_or_compute_computes_once_and_persists(tmp_path):
    store = ArtifactStore(str(tmp_path))
    calls = []

    def compute():
        calls.append(1)
        return _artifact(fp="hlo:fresh")

    a1 = store.get_or_compute("costmodel", "hlo:fresh", "tpu_v5e", compute)
    a2 = store.get_or_compute("costmodel", "hlo:fresh", "tpu_v5e", compute)
    assert len(calls) == 1
    assert a1.provenance == "fresh" and a2.from_store
    assert ArtifactStore(str(tmp_path)).get(
        "costmodel", "hlo:fresh", "tpu_v5e") is not None


def test_get_or_compute_propagates_compile_errors_uncached(tmp_path):
    store = ArtifactStore(str(tmp_path))

    def boom():
        raise CompileError("nope")

    for _ in range(2):                   # a failure is never cached
        with pytest.raises(CompileError):
            store.get_or_compute("costmodel", "hlo:bad", "tpu_v5e", boom)
    assert len(store) == 0
    assert store.stats.compiles == 2


def test_get_or_compute_threads_compile_at_most_once(tmp_path):
    store = ArtifactStore(str(tmp_path))
    barrier = threading.Barrier(4)
    calls = []
    results = []

    def compute():
        calls.append(1)
        return _artifact(fp="hlo:contended")

    def worker():
        barrier.wait(timeout=30)
        results.append(store.get_or_compute(
            "costmodel", "hlo:contended", "tpu_v5e", compute))

    threads = [threading.Thread(target=worker) for _ in range(4)]
    for t in threads:
        t.start()
    for t in threads:
        t.join(timeout=60)
    assert len(calls) == 1
    assert len(results) == 4
    assert all(r.payload["flops"] == 1.0 for r in results)


def _store_writer(root, fp, barrier, log_path):
    store = ArtifactStore(root)

    def compute():
        with open(log_path, "a") as f:
            f.write("compiled\n")
        return _artifact(fp=fp)

    barrier.wait(timeout=60)             # maximize get_or_compute overlap
    store.get_or_compute("costmodel", fp, "tpu_v5e", compute)
    store.put(_artifact(fp=fp + ":private"))


def test_multiprocessing_concurrent_writers_converge(tmp_path):
    if "fork" not in multiprocessing.get_all_start_methods():
        pytest.skip("needs fork start method")
    ctx = multiprocessing.get_context("fork")
    root = str(tmp_path / "store")
    log_path = str(tmp_path / "compiles.log")
    barrier = ctx.Barrier(2)
    procs = [ctx.Process(target=_store_writer,
                         args=(root, "hlo:shared", barrier, log_path))
             for _ in range(2)]
    for p in procs:
        p.start()
    for p in procs:
        p.join(timeout=120)
        assert p.exitcode == 0
    # the contended artifact compiled exactly once across both processes
    with open(log_path) as f:
        assert len(f.read().splitlines()) == 1
    merged = ArtifactStore(root)
    assert merged.get("costmodel", "hlo:shared", "tpu_v5e") is not None
    assert merged.get("costmodel", "hlo:shared:private",
                      "tpu_v5e") is not None
    assert len(merged) == 2


# -- default_store / resolve_store env gating ---------------------------------

def test_default_store_disabled_unless_enabled(monkeypatch, tmp_path):
    monkeypatch.delenv("REPRO_ARTIFACT_CACHE", raising=False)
    assert default_store() is None
    monkeypatch.setenv("REPRO_ARTIFACT_CACHE", "1")
    monkeypatch.setenv("REPRO_ARTIFACT_DIR", str(tmp_path / "a"))
    store = default_store()
    assert store is not None and store.root == str(tmp_path / "a")
    assert default_store() is store      # singleton while env unchanged
    monkeypatch.setenv("REPRO_ARTIFACT_DIR", str(tmp_path / "b"))
    assert default_store().root == str(tmp_path / "b")
    monkeypatch.setenv("REPRO_ARTIFACT_CACHE", "off")
    assert default_store() is None


def test_default_store_rejects_garbage_enable_values(monkeypatch):
    monkeypatch.setenv("REPRO_ARTIFACT_CACHE", "2")
    with pytest.raises(TypeError, match="REPRO_ARTIFACT_CACHE"):
        default_store()


def test_resolve_store_forms(tmp_path, monkeypatch):
    monkeypatch.delenv("REPRO_ARTIFACT_CACHE", raising=False)
    assert resolve_store(None) is None
    store = ArtifactStore(str(tmp_path))
    assert resolve_store(store) is store
    assert resolve_store(str(tmp_path)).root == str(tmp_path)
    with pytest.raises(TypeError, match="artifact_store"):
        resolve_store(123)


# -- evaluator integration ----------------------------------------------------

def _cost_spec():
    return KernelSpec(
        name="probe",
        build=lambda cfg: (lambda x: x * float(cfg["k"])),
        arg_specs=lambda: (jax.ShapeDtypeStruct((8, 8), jnp.float32),),
        meta={"N": 8})


def test_costmodel_prepare_hits_warm_store(tmp_path):
    spec = _cost_spec()
    ev = CostModelEvaluator()
    ev.artifact_store = ArtifactStore(str(tmp_path))
    fresh = ev.prepare(spec, {"k": 2.0})
    assert fresh.provenance == "fresh" and fresh.compile_s > 0
    assert fresh.profile == ev.profile.name
    # a different process/evaluator sharing the store skips the compile
    ev2 = CostModelEvaluator()
    ev2.artifact_store = ArtifactStore(str(tmp_path))
    hit = ev2.prepare(spec, {"k": 2.0})
    assert hit.from_store and hit.compile_s == 0.0
    assert hit.fingerprint == fresh.fingerprint
    assert ev2.artifact_store.stats.compiles == 0
    # measure prices store hits and fresh compiles identically
    assert (ev2.measure(spec, {"k": 2.0}, hit).time_s
            == ev.measure(spec, {"k": 2.0}, fresh).time_s)
    # a different config lowers to a different address
    other = ev2.prepare(spec, {"k": 3.0})
    assert other.provenance == "fresh"
    assert other.fingerprint != fresh.fingerprint


def test_engine_counts_artifact_hits(tmp_path):
    spec = _cost_spec()
    space = SearchSpace()
    space.add_parameter(name="k", values=(1.0, 2.0, 3.0))

    def run():
        ev = CostModelEvaluator()
        ev.artifact_store = ArtifactStore(str(tmp_path))
        engine = EvaluationEngine(ev, spec, space,
                                  EngineConfig(workers=1))
        result = engine.run(make_strategy("full"), None, seed=0)
        return result.extra["engine"]

    cold = run()
    assert cold["artifact_hits"] == 0
    assert cold["compiles_avoided"] == cold["memo_hits"]
    warm = run()                         # same search against the warm store
    assert warm["artifact_hits"] == warm["unique_configs"] == 3
    assert warm["compiles_avoided"] >= 3


def test_tuner_attaches_store_without_clobbering(tmp_path):
    ev = CostModelEvaluator()
    tuner = Tuner(evaluator=ev, artifact_store=str(tmp_path / "a"))
    assert ev.artifact_store is not None
    assert ev.artifact_store.root == str(tmp_path / "a")
    assert tuner.artifact_store is ev.artifact_store
    # a store the evaluator already carries wins over the tuner's
    tuner2 = Tuner(evaluator=ev, artifact_store=str(tmp_path / "b"))
    assert ev.artifact_store.root == str(tmp_path / "a")
    assert tuner2.artifact_store is ev.artifact_store


def test_base_prepare_returns_typed_no_payload_artifact():
    ev = TPUAnalyticalEvaluator()
    spec = KernelSpec(name="t", build=lambda c: None,
                      analytical_model=lambda c, p: 1e-3)
    art = ev.prepare(spec, {"a": 1})
    assert isinstance(art, CompiledArtifact)
    assert art.provenance == "none" and art.payload is None
    assert not art.persistable
    m = ev.measure(spec, {"a": 1}, art)
    assert m.ok


# -- the evaluate() deprecation shim ------------------------------------------

def test_evaluate_warns_once_per_process(monkeypatch):
    from repro.core import evaluators as mod
    monkeypatch.setattr(mod, "_EVALUATE_DEPRECATION_EMITTED", False)
    ev = TPUAnalyticalEvaluator(noise_sigma=0.0)
    spec = KernelSpec(name="t", build=lambda c: None,
                      analytical_model=lambda c, p: 1e-3)
    with warnings.catch_warnings(record=True) as caught:
        warnings.simplefilter("always")
        m1 = ev.evaluate(spec, {"a": 1})
        m2 = ev.evaluate(spec, {"a": 2})
    deprecations = [w for w in caught
                    if issubclass(w.category, DeprecationWarning)
                    and "prepare" in str(w.message)]
    assert len(deprecations) == 1
    assert m1.ok and m2.ok


def test_objective_path_does_not_warn(monkeypatch):
    from repro.core import evaluators as mod
    monkeypatch.setattr(mod, "_EVALUATE_DEPRECATION_EMITTED", False)
    ev = TPUAnalyticalEvaluator(noise_sigma=0.0)
    spec = KernelSpec(name="t", build=lambda c: None,
                      analytical_model=lambda c, p: 1e-3)
    obj = ev.objective(spec)
    with warnings.catch_warnings(record=True) as caught:
        warnings.simplefilter("always")
        assert obj({"a": 1}) > 0
    assert not [w for w in caught
                if issubclass(w.category, DeprecationWarning)]
    assert mod._EVALUATE_DEPRECATION_EMITTED is False


# -- envknobs -----------------------------------------------------------------

def test_parse_bool_canonical_spellings():
    assert parse_bool(True) is True and parse_bool(False) is False
    for raw in ("1", "true", "On", "YES"):
        assert parse_bool(raw) is True
    for raw in ("0", "false", "Off", "no", ""):
        assert parse_bool(raw) is False


def test_parse_bool_rejects_truthy_coercion():
    # the PR 5 rule: 0 / 'off'-like values must never truthy-coerce
    for bad in (0, 1, 2, "enable", "tru", None, [], object()):
        with pytest.raises(TypeError):
            parse_bool(bad)


def test_compile_cache_placement(monkeypatch):
    """JAX's own JAX_COMPILATION_CACHE_DIR wins; otherwise a fixed path in
    the checkout."""
    from repro.core.envknobs import configure_compile_cache
    before = jax.config.jax_compilation_cache_dir
    try:
        monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", "/elsewhere/cache")
        assert configure_compile_cache() == "/elsewhere/cache"
        assert jax.config.jax_compilation_cache_dir == before
        monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR")
        path = configure_compile_cache()
        root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
        assert path == os.path.join(root, ".jax_cache")
        assert jax.config.jax_compilation_cache_dir == path
    finally:
        jax.config.update("jax_compilation_cache_dir", before)


def test_env_bool(monkeypatch):
    monkeypatch.delenv("REPRO_X", raising=False)
    assert env_bool("REPRO_X", True) is True
    monkeypatch.setenv("REPRO_X", "on")
    assert env_bool("REPRO_X", False) is True
    monkeypatch.setenv("REPRO_X", "garbage")
    with pytest.raises(TypeError, match="REPRO_X"):
        env_bool("REPRO_X")


def test_env_int_warns_and_falls_back(monkeypatch):
    monkeypatch.delenv("REPRO_N", raising=False)
    assert env_int("REPRO_N", 4) == 4
    monkeypatch.setenv("REPRO_N", "7")
    assert env_int("REPRO_N", 4) == 7
    monkeypatch.setenv("REPRO_N", "seven")
    assert env_int("REPRO_N", 4) == 4


def test_env_str_choices(monkeypatch):
    monkeypatch.delenv("REPRO_S", raising=False)
    assert env_str("REPRO_S", "a") == "a"
    monkeypatch.setenv("REPRO_S", "")
    assert env_str("REPRO_S", "a") == "a"
    monkeypatch.setenv("REPRO_S", "b")
    assert env_str("REPRO_S", "a", choices=("a", "b")) == "b"
    monkeypatch.setenv("REPRO_S", "zzz")
    assert env_str("REPRO_S", "a", choices=("a", "b")) == "a"
