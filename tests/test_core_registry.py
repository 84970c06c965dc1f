"""Tunable-kernel registry: declaration, lookup policies, cache plumbing."""


import pytest

from repro.core import (REGISTRY, AutotunePolicy, KernelRegistry,
                        SearchSpace, TunableKernel, Tuner, TuningCache,
                        default_cache, lookup, resolve, tunable)
from repro.core.cache import _ENV_VAR


def _toy_kernel(name="toy", registry=None, values=(1, 2, 4, 8)):
    """A tiny analytical kernel: time = 1/X, best config is max X."""

    def space(shape):
        sp = SearchSpace()
        sp.add_parameter(name="X", values=values)
        sp.add_constraint(lambda x: shape["N"] % x == 0, ("X",), "N % X")
        return sp

    @tunable(name=name, space=space,
             heuristic=lambda s: {"X": 1},
             analytical_model=lambda s, cfg, prof: 1.0 / cfg["X"],
             registry=registry, register=registry is not None)
    def build(shape, config):
        return lambda: config["X"]

    return build


@pytest.fixture
def registry():
    return KernelRegistry()


@pytest.fixture
def cache(tmp_path):
    return TuningCache(str(tmp_path / "cache.json"))


def test_tunable_decorator_returns_kernel(registry):
    k = _toy_kernel(registry=registry)
    assert isinstance(k, TunableKernel)
    assert registry.get("toy") is k
    assert "toy" in registry and len(registry) == 1
    # the kernel object stays callable with the build signature
    assert k({"N": 8}, {"X": 4})() == 4


def test_duplicate_registration_rejected(registry):
    _toy_kernel(registry=registry)
    with pytest.raises(ValueError, match="already registered"):
        _toy_kernel(registry=registry)
    # explicit replace is allowed
    registry.register(_toy_kernel(registry=None), replace=True)


def test_unknown_kernel_lookup_names_known(registry):
    _toy_kernel(registry=registry)
    with pytest.raises(KeyError, match="toy"):
        registry.get("nope")


def test_resolve_accepts_object_and_name(registry):
    k = _toy_kernel(registry=registry)
    assert resolve(k) is k
    assert resolve("toy", registry) is k


def test_default_shape_key_is_canonical():
    k = _toy_kernel(registry=None)
    assert k.key_for({"b": 2, "a": 1}) == k.key_for({"a": 1, "b": 2})


def test_policy_off_heuristic_on_miss(registry, cache):
    k = _toy_kernel(registry=registry)
    cfg = lookup(k, {"N": 8}, cache=cache, policy="off")
    assert cfg == {"X": 1}                    # declared heuristic
    assert len(cache) == 0                    # no tuning happened


def test_policy_off_returns_cache_hit(registry, cache):
    k = _toy_kernel(registry=registry)
    cache.record(k.name, k.key_for({"N": 8}), "tpu_v5e", {"X": 8},
                 1e-3, "full", 4)
    cfg = lookup(k, {"N": 8}, cache=cache, policy=AutotunePolicy.OFF)
    assert cfg == {"X": 8}


def test_policy_on_miss_tunes_once_then_hits(registry, cache):
    k = _toy_kernel(registry=registry)
    cfg = lookup(k, {"N": 8}, cache=cache, policy="on_miss",
                 strategy="full")
    assert cfg["X"] == 8                      # tuned: 1/X minimised at X=8
    assert len(cache) == 1                    # recorded under the shape key
    # second call is a pure cache hit (policy off would also find it now)
    again = lookup(k, {"N": 8}, cache=cache, policy="off")
    assert again == cfg


def test_policy_always_retunes(registry, cache):
    k = _toy_kernel(registry=registry)
    cache.record(k.name, k.key_for({"N": 8}), "tpu_v5e", {"X": 1},
                 999.0, "full", 1)
    cfg = lookup(k, {"N": 8}, cache=cache, policy="always", strategy="full")
    assert cfg["X"] == 8                      # stale entry was re-tuned over


def test_on_miss_infeasible_shape_falls_back_to_heuristic(registry, cache):
    # N=7 divides none of the X values except 1... values (1,2,4,8): only 1.
    # Use a space with NO feasible point: values (2,4,8) against odd N.
    k = _toy_kernel(registry=registry, values=(2, 4, 8))
    cfg = lookup(k, {"N": 7}, cache=cache, policy="on_miss",
                 strategy="annealing", budget=4)
    assert cfg == {"X": 1}                    # heuristic, not a crash
    assert len(cache) == 0


def test_policy_coerce_rejects_unknown():
    with pytest.raises(ValueError, match="unknown autotune policy"):
        AutotunePolicy.coerce("sometimes")


def test_shape_keyed_entries_are_distinct(registry, cache):
    k = _toy_kernel(registry=registry)
    lookup(k, {"N": 8}, cache=cache, policy="on_miss", strategy="full")
    lookup(k, {"N": 6}, cache=cache, policy="on_miss", strategy="full")
    assert len(cache) == 2
    assert lookup(k, {"N": 6}, cache=cache, policy="off")["X"] == 2


def test_tuner_from_tunable(registry):
    k = _toy_kernel(registry=registry)
    tuner = Tuner.from_tunable(k, {"N": 8})
    out = tuner.tune(strategy="full")
    assert out.best_config == {"X": 8}
    assert out.kernel == "toy"
    # fluent compatibility layer still works on the result
    tuner2 = Tuner.from_tunable(k, {"N": 8})
    tuner2.add_constraint(lambda x: x <= 4, ("X",), "cap")
    assert tuner2.tune(strategy="full").best_config == {"X": 4}


def test_budget_clamped_to_tiny_space_and_reported(registry):
    k = _toy_kernel(registry=registry)          # 4 configs for N=8
    tuner = Tuner.from_tunable(k, {"N": 8})
    out = tuner.tune(strategy="random")          # default budget rule
    assert out.budget == 4                       # card <= 32: swept whole
    assert "budget=4" in out.report()
    out2 = Tuner.from_tunable(k, {"N": 8}).tune(strategy="random",
                                                budget=10_000)
    assert out2.budget == 4                      # explicit budget clamped
    full = Tuner.from_tunable(k, {"N": 8}).tune(strategy="full")
    assert full.budget is None
    assert "budget=exhaustive" in full.report()
    # an explicit budget still caps full enumeration (huge-space escape)
    capped = Tuner.from_tunable(k, {"N": 8}).tune(strategy="full", budget=2)
    assert capped.result.evaluations <= 2 and capped.budget == 2


def test_builtin_kernels_registered():
    for name in ("gemm", "conv2d", "flash_attention"):
        import repro.kernels  # noqa: F401 — registration side effect
        assert name in REGISTRY
        k = REGISTRY.get(name)
        assert k.analytical_model is not None and k.make_args is not None


@pytest.mark.parametrize("name,shape", [
    ("gemm", {"M": 256, "N": 256, "K": 256, "dtype": "float32"}),
    ("flash_attention", {"Sq": 128, "Sk": 128, "D": 64, "causal": True}),
    ("conv2d", {"H": 256, "W": 256, "Fh": 3, "Fw": 3}),
    ("moe_experts", {"N": 32, "d": 256, "m": 256, "E": 8, "E_held": 2,
                     "k": 2, "dtype": "float32"}),
])
def test_builtin_make_args_is_a_function_of_the_seed(name, shape):
    """A wall-clock search draws its inputs once per seed and reuses them
    for every trial: that is sound only if a draw depends on nothing else."""
    import jax
    import numpy as np

    k = resolve(name)
    first = k.make_args(shape, np.random.default_rng(7))
    again = k.make_args(shape, np.random.default_rng(7))
    leaves = jax.tree_util.tree_leaves
    assert len(leaves(first)) == len(leaves(again)) > 0
    for a, b in zip(leaves(first), leaves(again)):
        assert a.dtype == b.dtype
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


def test_cache_env_override_and_clear(tmp_path, monkeypatch):
    target = str(tmp_path / "override" / "db.json")
    monkeypatch.setenv(_ENV_VAR, target)
    c = default_cache()
    assert c.path == target
    c.record("k", "s", "p", {"a": 1}, 1.0, "full", 1)
    c.save()
    assert len(TuningCache(target).load()) == 1
    c.clear(delete_file=True)
    assert len(c) == 0
    import os
    assert not os.path.exists(target)
    # dropping the env var re-resolves to the in-tree default
    monkeypatch.delenv(_ENV_VAR)
    assert default_cache().path != target
