"""Tuner facade: verification, device constraints, cache, evaluators."""

import math

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import repro.core.evaluators as evaluators_mod
import repro.core.tuner as tuner_mod
from repro.core import (CostModelEvaluator, TPUAnalyticalEvaluator, Tuner,
                        TuningCache, WallClockEvaluator, TPU_V5E, TPU_V3)
from repro.core.evaluators import KernelSpec, resolve_interpret
from repro.core.profiles import attached_profile, profile_for_kind
from repro.core.verify import VerificationError, assert_trees_close

N = 1024


def _copy_builder(cfg):
    wpt = cfg["WPT"]

    def copy(x):
        return x.reshape(N // wpt, wpt).reshape(N)
    return copy


def _buggy_builder(cfg):
    """WPT=4 silently drops data — verification must catch it."""
    wpt = cfg["WPT"]

    def copy(x):
        if wpt == 4:
            return jnp.concatenate([x[: N // 2], jnp.zeros(N // 2, x.dtype)])
        return x
    return copy


def _make_args(rng):
    return (jnp.asarray(rng.normal(size=N), jnp.float32),)


def test_wallclock_tuner_end_to_end():
    t = Tuner(evaluator=WallClockEvaluator(repeats=2))
    t.set_reference(lambda x: x)
    t.add_kernel(_copy_builder, name="copy", make_args=_make_args)
    t.add_parameter("WPT", [1, 2, 4])
    out = t.tune(strategy="full")
    assert out.best_config is not None
    assert out.failed_fraction == 0.0
    assert "copy" in out.report()


def test_verification_rejects_buggy_config():
    t = Tuner(evaluator=WallClockEvaluator(repeats=1))
    t.set_reference(lambda x: x)
    t.add_kernel(_buggy_builder, name="buggy", make_args=_make_args)
    t.add_parameter("WPT", [1, 2, 4])
    out = t.tune(strategy="full")
    assert out.best_config["WPT"] != 4
    key = out.result.trials
    bad = [tr for tr in key if tr.config["WPT"] == 4]
    assert bad and not bad[0].ok


def test_device_vmem_constraint_auto_imposed():
    t = Tuner(evaluator=WallClockEvaluator(repeats=1), profile=TPU_V3)

    def foot(cfg):
        return cfg["TILE"] * 1024 * 1024          # 1 MiB per TILE unit

    t.add_kernel(_copy_builder, name="c", make_args=_make_args,
                 vmem_footprint=foot)
    t.add_parameter("WPT", [1])
    t.add_parameter("TILE", [1, 8, 64])            # 64 MiB > v3's 16 MiB
    out = t.tune(strategy="full")
    tiles = {tr.config["TILE"] for tr in out.result.trials}
    assert 64 not in tiles                         # filtered pre-evaluation


def test_analytical_evaluator_deterministic_noise():
    spec = KernelSpec(name="k", build=lambda c: (lambda: None),
                      analytical_model=lambda c, p: 1e-3 * c["x"])
    ev = TPUAnalyticalEvaluator(noise_sigma=0.05, seed=3)
    m1 = ev._evaluate(spec, {"x": 2})
    m2 = ev._evaluate(spec, {"x": 2})
    m3 = ev._evaluate(spec, {"x": 3})
    assert m1.time_s == m2.time_s
    assert m1.time_s != m3.time_s


def test_analytical_evaluator_infeasible():
    spec = KernelSpec(name="k", build=lambda c: (lambda: None),
                      analytical_model=lambda c, p: math.inf)
    m = TPUAnalyticalEvaluator()._evaluate(spec, {})
    assert not m.ok and m.time_s == math.inf


def test_cost_model_evaluator_roofline_terms():
    def build(cfg):
        def f(a, b):
            return a @ b
        return f

    spec = KernelSpec(
        name="mm", build=build,
        arg_specs=lambda: (jax.ShapeDtypeStruct((256, 256), jnp.float32),
                           jax.ShapeDtypeStruct((256, 256), jnp.float32)))
    m = CostModelEvaluator(profile=TPU_V5E)._evaluate(spec, {})
    assert m.ok
    assert m.detail["flops"] >= 2 * 256 ** 3 * 0.9
    assert m.detail["compute_t"] > 0


def test_cache_roundtrip(tmp_path):
    path = str(tmp_path / "cache.json")
    c = TuningCache(path)
    assert c.get("k", "s", "p") is None
    c.record("k", "s", "p", {"BM": 128}, 1e-3, "full", 10)
    c.save()
    c2 = TuningCache(path).load()
    e = c2.get("k", "s", "p")
    assert e.config == {"BM": 128} and e.time_s == 1e-3


def test_cache_only_if_better(tmp_path):
    c = TuningCache(str(tmp_path / "c.json"))
    assert c.record("k", "s", "p", {"a": 1}, 2.0, "full", 1)
    assert not c.record("k", "s", "p", {"a": 2}, 3.0, "full", 1)
    assert c.record("k", "s", "p", {"a": 3}, 1.0, "full", 1)
    assert c.get("k", "s", "p").config == {"a": 3}


# -- TPU backend defaults (backend faked: these run on the host) -----------

def _fake_tpu(monkeypatch, kind="TPU v5 lite"):
    monkeypatch.setattr(evaluators_mod, "on_tpu", lambda: True)
    monkeypatch.setattr(tuner_mod, "on_tpu", lambda: True)
    monkeypatch.setattr(tuner_mod, "attached_profile",
                        lambda: profile_for_kind(kind))


def test_interpret_resolves_by_backend(monkeypatch):
    assert resolve_interpret(None) is True          # host: the interpreter
    assert resolve_interpret(False) is False
    _fake_tpu(monkeypatch)
    assert resolve_interpret(None) is False         # TPU: compiled
    with pytest.raises(ValueError, match="TPU backend"):
        resolve_interpret(True)


def test_default_evaluator_times_the_device_on_tpu(monkeypatch):
    _fake_tpu(monkeypatch)
    t = Tuner.from_tunable("gemm", {"M": 256, "N": 256, "K": 256})
    assert isinstance(t.evaluator, WallClockEvaluator)
    # a profile that is not the attached chip's would mislabel timings
    with pytest.raises(ValueError, match="attached chip"):
        Tuner.from_tunable("gemm", {"M": 256, "N": 256, "K": 256},
                           profile=TPU_V3)
    # the model stays available when asked for explicitly
    t = Tuner.from_tunable("gemm", {"M": 256, "N": 256, "K": 256},
                           evaluator=TPUAnalyticalEvaluator())
    assert isinstance(t.evaluator, TPUAnalyticalEvaluator)


def test_host_default_evaluator_is_the_model():
    t = Tuner.from_tunable("gemm", {"M": 256, "N": 256, "K": 256})
    assert isinstance(t.evaluator, TPUAnalyticalEvaluator)


def test_device_kind_lookup():
    assert profile_for_kind("TPU v5 lite") is TPU_V5E
    with pytest.raises(KeyError, match="TPU v9"):
        profile_for_kind("TPU v9")
    with pytest.raises(RuntimeError, match="'cpu'"):
        attached_profile()                          # tests run on the host


@pytest.mark.parametrize("leaf", [np.asarray, jnp.asarray],
                         ids=["numpy", "jax"])
def test_verification_is_normwise(leaf):
    """A long reduction's near-zero outputs carry rounding error on the
    scale of its terms: that passes, an error on that scale fails.  Host
    and device leaves are held to the same rule."""
    ref = np.array([100.0, 1e-3, -50.0], np.float32)
    # 5e-6 of max|ref| passes
    assert_trees_close(leaf(ref + np.float32(5e-4)), leaf(ref))
    with pytest.raises(VerificationError, match="max_abs_err"):
        assert_trees_close(leaf(ref + np.float32(5e-2)), leaf(ref))
    for bad in (np.nan, np.inf):
        with pytest.raises(VerificationError):
            assert_trees_close(leaf(np.array([bad, 1.0], np.float32)),
                               leaf(np.array([0.0, 1.0], np.float32)))


def test_verification_catches_a_fault_when_the_inputs_are_reused():
    """The faulty configuration is the second trial, so its inputs and the
    reference's output are the ones the first trial left."""
    t = Tuner(evaluator=WallClockEvaluator(repeats=1))
    t.set_reference(lambda x: x)
    t.add_kernel(_buggy_builder, name="buggy", make_args=_make_args)
    t.add_parameter("WPT", [1, 4, 2])
    # one compile at a time: the first trial draws the inputs
    out = t.tune(strategy="full", engine={"workers": 1})
    assert [tr.config["WPT"] for tr in out.result.trials] == [1, 4, 2]
    bad = out.result.trials[1]
    assert not bad.ok and bad.failure.error_type == "VerificationFailure"
    assert out.best_config["WPT"] != 4
    # the faulty trial counts too: its verify ran, and failed, on the fixture
    assert out.engine_stats["inputs_reused"] == 2


def test_verification_follows_a_reference_set_after_a_search():
    """A reference set between two searches on one evaluator replaces the
    held reference output: the second search runs the new reference, and a
    configuration that matches only the old one fails."""
    ev = WallClockEvaluator(repeats=1)
    ran = []

    def reference(scale):
        def ref(x):
            ran.append(scale)
            return x * scale
        return ref

    t = Tuner(evaluator=ev)
    t.set_reference(reference(1.0))
    t.add_kernel(lambda cfg: (lambda x: x * cfg["S"]), name="scale",
                 make_args=_make_args)
    t.add_parameter("S", [1.0, 2.0])
    first = t.tune(strategy="full", engine={"workers": 1})
    assert first.best_config == {"S": 1.0} and ran == [1.0]
    t.set_reference(reference(2.0))
    second = t.tune(strategy="full", engine={"workers": 1})
    assert ran == [1.0, 2.0]
    assert second.best_config == {"S": 2.0}
    [old] = [tr for tr in second.result.trials if tr.config["S"] == 1.0]
    assert not old.ok and old.failure.error_type == "VerificationFailure"


@pytest.mark.parametrize("tpu", [False, True])
def test_wallclock_trials_time_one_at_a_time_on_tpu(monkeypatch, tpu):
    """Concurrent engines (dtune thread workers, a retune beside serving)
    share one chip: on a TPU their timing loops run one at a time."""
    import threading
    import time

    from repro.core.evaluators import _CompiledKernel, _Fixture
    monkeypatch.setattr(evaluators_mod, "on_tpu", lambda: tpu)
    lock = threading.Lock()
    active, peak = [0], [0]

    def kernel():
        with lock:
            active[0] += 1
            peak[0] = max(peak[0], active[0])
        time.sleep(0.02)
        with lock:
            active[0] -= 1
        return np.zeros(1)

    ev = WallClockEvaluator(repeats=3, warmup=1)
    spec = KernelSpec(name="sleep", build=lambda cfg: kernel)
    barrier = threading.Barrier(2)

    def trial():
        barrier.wait()
        ev.measure(spec, {}, _CompiledKernel(
            fn=kernel, fixture=_Fixture(spec, 0, ()), out=None,
            compile_s=0.0))
    threads = [threading.Thread(target=trial) for _ in range(2)]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    assert peak[0] == (1 if tpu else 2)


def test_kernel_vmem_limit_follows_the_attached_chip(monkeypatch):
    import repro.core.profiles as profiles_mod
    from repro.core.profiles import kernel_vmem_limit
    # host backend: ahead-of-time compiles target the v5e
    assert kernel_vmem_limit() == TPU_V5E.vmem_bytes
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    monkeypatch.setattr(profiles_mod, "attached_profile", lambda: TPU_V3)
    assert kernel_vmem_limit() == TPU_V3.vmem_bytes
    monkeypatch.setattr(profiles_mod, "attached_profile",
                        lambda: profile_for_kind("TPU v9"))
    with pytest.raises(KeyError, match="TPU v9"):
        kernel_vmem_limit()
