"""Search strategies: paper equations, determinism, invariants."""

import math
import random

import pytest
from hypothesis import given, settings, strategies as st

from repro.core import (FullSearch, ParticleSwarm, RandomSearch, SearchSpace,
                        SimulatedAnnealing, available_strategies,
                        make_strategy, register_strategy)
from repro.core.strategies import Strategy


def make_space(n_params=4, n_values=4):
    sp = SearchSpace()
    for i in range(n_params):
        sp.add_parameter(name=f"p{i}", values=tuple(range(n_values)))
    return sp


def quadratic(cfg):
    # minimum at all-parameters == 2
    return 1.0 + sum((v - 2) ** 2 for v in cfg.values())


def test_full_search_finds_global_optimum():
    sp = make_space()
    r = FullSearch().run(sp, quadratic, budget=None)
    assert r.best_time == 1.0
    assert all(v == 2 for v in r.best_config.values())
    assert r.evaluations == sp.size()


def test_random_search_budget_respected():
    sp = make_space()
    r = RandomSearch().run(sp, quadratic, budget=37, seed=0)
    assert r.evaluations == 37


def test_strategies_deterministic_per_seed():
    sp = make_space()
    for name in ("random", "annealing", "pso", "greedy"):
        r1 = make_strategy(name).run(sp, quadratic, budget=30, seed=7)
        r2 = make_strategy(name).run(sp, quadratic, budget=30, seed=7)
        assert r1.best_config == r2.best_config
        assert [t.time for t in r1.trials] == [t.time for t in r2.trials]


def test_best_is_min_of_trials():
    sp = make_space()
    for name in ("random", "annealing", "pso", "greedy"):
        r = make_strategy(name).run(sp, quadratic, budget=40, seed=3)
        assert r.best_time == min(t.time for t in r.trials if t.ok)
        assert sp.is_feasible(r.best_config)


def test_progress_trace_monotone_nonincreasing():
    sp = make_space()
    r = SimulatedAnnealing().run(sp, quadratic, budget=50, seed=1)
    trace = r.progress_trace()
    assert all(a >= b for a, b in zip(trace, trace[1:]))


def test_annealing_acceptance_equation():
    """P(t,t',T) = 1 if t'<t else exp(-(t'-t)/T) — paper section III-C."""
    # verified indirectly: with cooling disabled and T huge, SA must accept
    # nearly every worse move; with T tiny, nearly none.
    sp = make_space(n_params=2, n_values=8)
    hot = SimulatedAnnealing(temperature=1e6, cooling=False)
    cold = SimulatedAnnealing(temperature=1e-6, cooling=False)
    r_hot = hot.run(sp, quadratic, budget=60, seed=5)
    r_cold = cold.run(sp, quadratic, budget=60, seed=5)
    assert r_hot.extra["accepted_worse"] > r_cold.extra["accepted_worse"]


def test_pso_alpha_beta_gamma_validation():
    with pytest.raises(ValueError):
        ParticleSwarm(alpha=0.5, beta=0.4, gamma=0.4)


def test_pso_respects_budget_and_particle_traces():
    sp = make_space()
    r = ParticleSwarm(swarm_size=3).run(sp, quadratic, budget=31, seed=2)
    assert r.evaluations == 31
    assert len(r.extra["particle_traces"]) == 3


def test_pso_moves_toward_global_best():
    """With gamma=1 every dimension moves to the swarm best."""
    sp = make_space()
    strat = ParticleSwarm(swarm_size=2, alpha=0.0, beta=0.0, gamma=1.0)
    r = strat.run(sp, quadratic, budget=20, seed=0)
    # after the first round all particles sit on the initial global best,
    # so the recorder dedupe means very few unique evaluations happen
    assert r.evaluations <= 20


def test_infeasible_objective_never_becomes_best():
    sp = make_space()

    def obj(cfg):
        if cfg["p0"] == 2:          # poison the true optimum
            return math.inf
        return quadratic(cfg)

    r = FullSearch().run(sp, obj, budget=None)
    assert r.best_config["p0"] != 2
    assert math.isfinite(r.best_time)


def test_evolutionary_strategy():
    """Paper §III-B future work: evolutionary search, pluggable."""
    sp = make_space(n_params=4, n_values=4)
    r = make_strategy("evolutionary", population=8).run(
        sp, quadratic, budget=80, seed=0)
    assert r.evaluations <= 80
    assert sp.is_feasible(r.best_config)
    # must beat the expected quality of a single random draw by a margin
    rr = make_strategy("random").run(sp, quadratic, budget=8, seed=0)
    assert r.best_time <= rr.best_time


def test_evolutionary_deterministic():
    sp = make_space()
    r1 = make_strategy("evolutionary").run(sp, quadratic, budget=40, seed=5)
    r2 = make_strategy("evolutionary").run(sp, quadratic, budget=40, seed=5)
    assert r1.best_config == r2.best_config


def test_registry_pluggable():
    class Fixed(Strategy):
        name = "fixed"

        def walk(self, space, budget, seed=0, seeds=None):
            yield [next(iter(space))]

    if "fixed" not in available_strategies():
        register_strategy("fixed", Fixed)
    r = make_strategy("fixed").run(make_space(), quadratic, budget=1)
    assert r.evaluations == 1
    with pytest.raises(ValueError):
        register_strategy("fixed", Fixed)


@given(seed=st.integers(0, 500), budget=st.integers(5, 60))
@settings(max_examples=15, deadline=None)
def test_property_budget_and_feasibility(seed, budget):
    sp = make_space()
    for name in ("random", "annealing", "pso"):
        r = make_strategy(name).run(sp, quadratic, budget=budget, seed=seed)
        assert r.evaluations <= budget
        if r.best is not None:
            assert sp.is_feasible(r.best_config)
