"""Search strategies: full, random, simulated annealing, PSO (+ extensions).

The four strategies of the paper (section III-B/C/D) with its exact update
equations, plus a pluggable registry so "evolutionary search, gradient
methods, stochastic optimisation or dynamic programming can be evaluated as
part of future work" (paper, end of III-B).  We add one beyond-paper strategy
(greedy coordinate descent) used by the sharding tuner.

Each strategy holds its search once, as a *walk*: a generator that yields
batches of configurations and is sent their objective values.  The
evaluation engine drives every walk through one :class:`AskTellDriver`, and
``Strategy.run`` drives the same walk against a plain objective function.

Objective convention: *lower is better* (execution time in seconds), exactly
like the paper's annealing-energy analogy.  Infeasible / failed measurements
return ``math.inf`` and are recorded but never become the incumbent.
"""

from __future__ import annotations

import dataclasses
import inspect
import itertools
import math
import random
from typing import (Any, Callable, Dict, Generator, List, Optional, Sequence,
                    Tuple)

from .failures import FailureRecord, summarize_failures
from .space import Config, SearchSpace

#: scalar objective function over one config — lower is better.  Renamed
#: from ``Objective``: the *typed* objective identity (median/p99/weighted
#: specs) now lives in :class:`repro.core.metrics.Objective`; strategies
#: only ever see the already-scalarized callable.
ObjectiveFn = Callable[[Config], float]


def accepts_kwarg(fn: Callable, kwarg: str) -> bool:
    """Whether ``fn`` can take ``kwarg`` — shared signature introspection
    for optional-capability probes (extended spaces, interpret mode)."""
    try:
        params = inspect.signature(fn).parameters
    except (TypeError, ValueError):    # builtins / C callables
        return False
    return kwarg in params or any(
        p.kind is inspect.Parameter.VAR_KEYWORD for p in params.values())


def usable_seeds(space: SearchSpace, seeds: Optional[Sequence[Config]],
                 limit: Optional[int] = None) -> List[Config]:
    """Sanitize warm-start seed configs for one search.

    Seeds come from *other* shapes' tuned winners and declared heuristics,
    so each is projected onto this space's parameters (a seed missing a
    parameter, or carrying a value outside the parameter's list, is
    dropped), checked for feasibility, and deduplicated; ``limit`` caps
    how many survive (a seed list must never exhaust the search budget).
    """
    out: List[Config] = []
    seen = set()
    for seed in seeds or ():
        try:
            cfg = {p.name: seed[p.name] for p in space.parameters}
            space.to_indices(cfg)           # value outside the list raises
            key = space.config_key(cfg)
            feasible = space.is_feasible(cfg)
        except (KeyError, ValueError):
            continue
        if not feasible or key in seen:
            continue
        seen.add(key)
        out.append(cfg)
        if limit is not None and len(out) >= limit:
            break
    return out


def project_feasible(space: SearchSpace, config: Config,
                     scan_limit: int = 4096) -> Optional[Config]:
    """Project an arbitrary config onto the nearest feasible space point.

    Two stages, mirroring what :func:`usable_seeds` checks but *repairing*
    instead of dropping: each parameter value is first snapped to its
    nearest in-list value (missing parameter -> first value; numeric ->
    closest by absolute distance; categorical -> first value); if the
    snapped point still violates a constraint, the feasible space is
    scanned (up to ``scan_limit`` points) for the config at minimum
    index-distance from the snapped one.  Returns ``None`` only when no
    feasible point exists within the scan horizon.
    """
    snapped: Config = {}
    for p in space.parameters:
        v = config.get(p.name, p.values[0])
        try:
            p.index_of(v)
        except ValueError:
            numeric = (isinstance(v, (int, float)) and not isinstance(v, bool))
            in_list = [x for x in p.values
                       if isinstance(x, (int, float))
                       and not isinstance(x, bool)]
            v = (min(in_list, key=lambda x: (abs(x - v), x))
                 if numeric and in_list else p.values[0])
        snapped[p.name] = v
    try:
        if space.is_feasible(snapped):
            return snapped
    except KeyError:
        return None
    want = space.to_indices(snapped)
    best: Optional[Config] = None
    best_d = math.inf
    for cfg in itertools.islice(iter(space), scan_limit):
        d = sum(abs(i - j) for i, j in zip(space.to_indices(cfg), want))
        if d < best_d:
            best, best_d = cfg, d
            if d == 0:
                break
    return best


def _sample_avoiding(space: SearchSpace, rng: random.Random, count: int,
                     exclude: Sequence[Config]) -> List[Config]:
    """``sample_unique`` that skips already-seeded configs.

    With no exclusions this is exactly ``sample_unique(rng, count)`` — the
    seedless trial sequence is unchanged.
    """
    if count <= 0:
        return []
    if not exclude:
        return space.sample_unique(rng, count)
    banned = {space.config_key(c) for c in exclude}
    drawn = space.sample_unique(rng, count + len(banned))
    fresh = [c for c in drawn if space.config_key(c) not in banned]
    return fresh[:count]


@dataclasses.dataclass
class Trial:
    """One evaluated configuration."""

    config: Config
    time: float                 # objective score (inf = failed/infeasible);
                                # seconds under time-based objectives
    index: int                  # evaluation order, 0-based
    #: populated (by the evaluation engine) when this trial is a failed
    #: configuration: the structured why — stage, exception type, message
    failure: Optional[FailureRecord] = None
    #: populated (by the evaluation engine) with the structured
    #: :class:`~repro.core.metrics.Metrics` behind this trial — the full
    #: per-repeat sample vector the scalar ``time`` collapsed
    metrics: Optional[Any] = None

    @property
    def ok(self) -> bool:
        return math.isfinite(self.time)


@dataclasses.dataclass
class SearchResult:
    strategy: str
    trials: List[Trial]
    best: Optional[Trial]
    evaluations: int
    #: per-strategy extras (e.g. PSO per-particle traces)
    extra: Dict[str, object] = dataclasses.field(default_factory=dict)
    #: canonical spec of the objective that ranked these trials (set by
    #: the evaluation engine; None from bare ``Strategy.run`` calls,
    #: which are always scalar and therefore default-objective)
    objective: Optional[str] = None

    @property
    def best_time(self) -> float:
        return self.best.time if self.best else math.inf

    @property
    def best_config(self) -> Optional[Config]:
        return self.best.config if self.best else None

    def progress_trace(self) -> List[float]:
        """Best-so-far time after each evaluation (paper Fig. 4 traces)."""
        out, best = [], math.inf
        for t in self.trials:
            best = min(best, t.time)
            out.append(best)
        return out

    def failures(self) -> List[Trial]:
        """The failed/infeasible trials (inf time), in evaluation order."""
        return [t for t in self.trials if not t.ok]

    def failure_summary(self) -> Dict[str, Any]:
        """Aggregate counts by stage/exception type of this run's failures."""
        records = [t.failure for t in self.trials if t.failure is not None]
        summary = summarize_failures(records)
        summary["failed_trials"] = sum(1 for t in self.trials if not t.ok)
        return summary


#: one search as the engine drives it: a generator that yields each batch
#: of configs to measure, is sent that batch's objective values in the
#: order it yielded them, and returns the strategy's ``extra`` dict
Walk = Generator[List[Config], List[float], Optional[Dict[str, object]]]


def _require_budget(strategy: "Strategy", budget: Optional[int]) -> int:
    """Only full search supports budget=None (exhaustive enumeration)."""
    if budget is None:
        raise ValueError(f"strategy {strategy.name!r} requires a finite "
                         "budget (budget=None is full-search only)")
    return budget


class Strategy:
    """Base class; a subclass implements ``walk``, its whole search.

    ``walk(space, budget, seed, seeds)`` is a generator (:data:`Walk`):
    ``times = yield batch`` asks for one non-empty batch of configs, and
    the walk returns its ``extra`` dict when it is done.  Sequential walks
    (annealing, greedy) ask one config at a time, generation-based ones
    (PSO, evolutionary) a whole population.  Every evaluation, a revisit
    included, counts against ``budget``.

    ``seeds`` are optional warm-start candidates: sanitized initial configs
    (transferred nearest-shape winners, heuristics) evaluated before — or,
    for population strategies, as part of — the strategy's own
    exploration.  Seeds consume search budget like any other evaluation.

    :class:`repro.core.engine.EvaluationEngine` drives the walk through
    :meth:`asktell`; :meth:`run` drives the same walk against a plain
    objective function.
    """

    name = "base"

    def walk(self, space: SearchSpace, budget: Optional[int], seed: int = 0,
             seeds: Optional[Sequence[Config]] = None) -> Walk:
        raise NotImplementedError

    def asktell(self, space: SearchSpace, budget: Optional[int],
                seed: int = 0,
                seeds: Optional[Sequence[Config]] = None) -> "AskTellDriver":
        return AskTellDriver(self, space,
                             self.walk(space, budget, seed=seed, seeds=seeds))

    def run(self, space: SearchSpace, objective: ObjectiveFn,
            budget: Optional[int], seed: int = 0,
            seeds: Optional[Sequence[Config]] = None) -> SearchResult:
        """Search against ``objective`` directly.  A revisited config is
        answered from this run's memo, not measured again (CLTune's
        compiled-kernel cache), but still counts against ``budget``."""
        driver = self.asktell(space, budget, seed=seed, seeds=seeds)
        memo: Dict[Tuple, float] = {}
        while batch := driver.ask():
            keys = [space.config_key(cfg) for cfg in batch]
            for cfg, key in zip(batch, keys):
                if key not in memo:
                    memo[key] = float(objective(cfg))
            driver.tell([(cfg, memo[key]) for cfg, key in zip(batch, keys)])
        return driver.result()


class AskTellDriver:
    """One search run with control inverted: the caller pulls batches.

    ``ask()`` returns the walk's next batch, or ``[]`` once the walk has
    returned.  The caller evaluates the batch however it likes — parallel
    compilation, memoisation, early-stop pruning, in any order — and
    reports objective values with ``tell()``, which records the trials in
    the order told.  At the next ``ask()`` the walk gets the batch's values
    in the order it asked for them, matched by config identity, so the
    whole batch must be told by then; a partial tell before an aborted
    search is fine.  ``result()`` is valid once ``ask()`` has returned
    ``[]``.
    """

    def __init__(self, strategy: Strategy, space: SearchSpace, walk: Walk):
        self.strategy = strategy
        self._space = space
        self._walk = walk
        self._trials: List[Trial] = []
        self._best: Optional[Trial] = None
        self._told: Dict[Tuple, float] = {}
        self._asked = False
        self._extra: Optional[Dict[str, object]] = None
        self._step(None)        # to the first batch: bad arguments raise here

    def _step(self, times: Optional[List[float]]) -> None:
        try:
            self._batch = list(self._walk.send(times))
        except StopIteration as done:
            self._batch = []
            self._extra = dict(done.value or {})

    def ask(self) -> List[Config]:
        if self._asked and self._batch:
            try:
                times = [self._told[self._space.config_key(c)]
                         for c in self._batch]
            except KeyError:
                raise RuntimeError("ask() before every config of the last "
                                   "batch was told") from None
            self._step(times)
        self._asked, self._told = True, {}
        return [dict(c) for c in self._batch]

    def tell(self, results: List[Tuple[Config, float]]) -> None:
        for config, time_s in results:
            trial = Trial(config=dict(config), time=float(time_s),
                          index=len(self._trials))
            self._trials.append(trial)
            if trial.ok and (self._best is None
                             or trial.time < self._best.time):
                self._best = trial
            self._told[self._space.config_key(config)] = trial.time

    def result(self) -> SearchResult:
        if self._extra is None:
            raise RuntimeError(
                "result() before the search finished; a caller that aborts "
                "a search assembles its partial result itself (the "
                "EvaluationEngine does, from its tell history)")
        return SearchResult(self.strategy.name, list(self._trials),
                            self._best, len(self._trials),
                            extra=dict(self._extra))

    def close(self) -> None:
        """Stop the walk where it stands (idempotent)."""
        self._walk.close()
        self._batch = []


class FullSearch(Strategy):
    """Exhaustive enumeration of every feasible configuration.

    Warm-start seeds are meaningless here (every feasible config is
    visited anyway) and are ignored.

    ``offset``/``stride`` slice the enumeration for sharded distributed
    search: worker *i* of *n* runs ``FullSearch(offset=i, stride=n)`` and
    the *n* shards partition the feasible space exactly (every config
    visited once, by exactly one worker).
    """

    name = "full"

    def __init__(self, offset: int = 0, stride: int = 1):
        if stride < 1:
            raise ValueError("stride must be >= 1")
        if not 0 <= offset < stride:
            raise ValueError(f"offset must be in [0, stride); got "
                             f"offset={offset} stride={stride}")
        self.offset = offset
        self.stride = stride

    def walk(self, space, budget=None, seed=0, seeds=None) -> Walk:
        configs = itertools.islice(iter(space), self.offset, None,
                                   self.stride)
        if budget is not None:
            configs = itertools.islice(configs, budget)
        while batch := list(itertools.islice(configs, 64)):  # engine-sized
            yield batch


class RandomSearch(Strategy):
    """Uniform sampling of a configurable fraction of the space.

    The whole sample is one batch, maximally overlappable.  Warm-start
    seeds lead it and count toward the budget; random draws fill the
    remainder (seeds excluded from re-draws).
    """

    name = "random"

    def walk(self, space, budget, seed=0, seeds=None) -> Walk:
        budget = _require_budget(self, budget)
        planted = usable_seeds(space, seeds, limit=budget)
        batch = planted + _sample_avoiding(
            space, random.Random(seed), budget - len(planted), planted)
        if batch:
            yield batch
        if len(batch) < budget:
            # the feasible space is smaller than the budget: surface the
            # shortfall instead of silently under-spending
            return {"sample_shortfall": budget - len(batch)}
        return {}


class SimulatedAnnealing(Strategy):
    """Paper section III-C, acceptance probability taken verbatim:

        P(t, t', T) = 1                      if t' < t
                      exp(-(t' - t) / T)     otherwise

    with T the annealing temperature and t, t' the execution times of the
    current and neighbour configuration.  As in CLTune the walk starts from a
    random feasible configuration and runs until ``budget`` configurations
    have been explored; a configuration with no feasible neighbour restarts
    it from a random one.  ``temperature`` is expressed in the objective's
    units scaled by the first measurement, so T={2,4,6} behaves like the
    paper's settings regardless of kernel magnitude; ``cooling`` optionally
    anneals T linearly to ~0 over the run ("probability decreases over time
    as the temperature decreases").
    """

    name = "annealing"

    def __init__(self, temperature: float = 4.0, cooling: bool = True):
        self.temperature = float(temperature)
        self.cooling = cooling

    def walk(self, space, budget, seed=0, seeds=None) -> Walk:
        budget = _require_budget(self, budget)
        rng = random.Random(seed)
        # Warm start: evaluate every seed, then walk from the best of them
        # (transferred nearest-shape winners put the walk straight into a
        # good basin).  Without seeds the walk starts at a random sample.
        times: List[float] = []
        current, t_cur = None, math.inf
        for cfg in usable_seeds(space, seeds, limit=budget):
            t, = yield [cfg]
            times.append(t)
            if current is None or t < t_cur:
                current, t_cur = cfg, t
        if current is None:
            current = space.sample(rng)
            t_cur, = yield [current]
            times.append(t_cur)
        # Temperature scale: the first *finite* measurement, refreshed on
        # dead-end restarts.  Seeding it from an inf (failed) first eval —
        # or keeping a stale basin's scale after a restart — mis-sizes
        # every subsequent acceptance probability.
        scale = next((t for t in times if math.isfinite(t) and t > 0), None)
        done = len(times)
        accepted_worse = 0
        while done < budget:
            nbr = space.random_neighbour(current, rng)
            if nbr is None:
                current = space.sample(rng)
                t_cur, = yield [current]
                done += 1
                if math.isfinite(t_cur) and t_cur > 0:
                    scale = t_cur           # recalibrate to the new basin
                continue
            t_nbr, = yield [nbr]
            done += 1
            if scale is None and math.isfinite(t_nbr) and t_nbr > 0:
                scale = t_nbr               # first finite measurement seen
            # temperature in units of the scale measurement; linear cooling
            frac_done = done / max(budget, 1)
            T = self.temperature * (1.0 - frac_done if self.cooling else 1.0)
            T = max(T, 1e-9)
            if t_nbr < t_cur:
                p = 1.0                                     # always accept better
            elif not math.isfinite(t_nbr):
                p = 0.0                                     # never move into a wall
            else:
                p = math.exp(-((t_nbr - t_cur) / (scale or 1.0)) / T)
            if rng.random() < p:
                if t_nbr >= t_cur:
                    accepted_worse += 1
                current, t_cur = nbr, t_nbr
        return {"accepted_worse": accepted_worse,
                "temperature": self.temperature}


class ParticleSwarm(Strategy):
    """Paper section III-D: modified *discrete* accelerated PSO.

    Velocity-free, per-dimension d update:

        x[i,d] <- eps_d      with probability alpha   (random value)
                  p[i,d]     with probability beta    (particle best)
                  g[d]       with probability gamma   (global best)
                  x[i,d]     otherwise                (stay)

    with alpha + beta + gamma <= 1.  Paper experiments use alpha=0.4, beta=0,
    gamma=0.4, swarm sizes S in {3, 6}.  Generation-synchronous: each batch
    is the whole swarm, and every particle of a generation moves against
    the global best of the generation before.
    """

    name = "pso"

    def __init__(self, swarm_size: int = 3, alpha: float = 0.4,
                 beta: float = 0.0, gamma: float = 0.4,
                 max_repair_tries: int = 32):
        if alpha + beta + gamma > 1.0 + 1e-9:
            raise ValueError("require alpha + beta + gamma <= 1")
        self.swarm_size = swarm_size
        self.alpha, self.beta, self.gamma = alpha, beta, gamma
        self.max_repair_tries = max_repair_tries

    def _move(self, space: SearchSpace, rng: random.Random,
              x: Config, p_best: Config, g_best: Config) -> Config:
        """One per-dimension stochastic move; rejection-repair to feasibility."""
        params = space.parameters
        for _ in range(self.max_repair_tries):
            new: Config = {}
            for param in params:
                r = rng.random()
                if r < self.alpha:
                    new[param.name] = rng.choice(param.values)      # eps_d
                elif r < self.alpha + self.beta:
                    new[param.name] = p_best[param.name]            # local best
                elif r < self.alpha + self.beta + self.gamma:
                    new[param.name] = g_best[param.name]            # global best
                else:
                    new[param.name] = x[param.name]                 # stay
            if space.is_feasible(new):
                return new
        return space.sample(rng)    # repair failed: rerandomise the particle

    def walk(self, space, budget, seed=0, seeds=None) -> Walk:
        budget = _require_budget(self, budget)
        rng = random.Random(seed)
        n = self.swarm_size
        # Warm start: the first particles spawn at the seed configs, the
        # rest randomly — the swarm explores around transferred winners.
        planted = usable_seeds(space, seeds, limit=n)
        xs = planted + [space.sample(rng) for _ in range(n - len(planted))]
        p_best = [dict(x) for x in xs]
        p_time = [math.inf] * n
        g_best: Optional[Config] = None
        g_time = math.inf
        traces: List[List[float]] = [[] for _ in range(n)]
        done = 0
        while (k := min(budget - done, n)) > 0:
            times = yield xs[:k]
            done += k
            for i, t in enumerate(times):
                traces[i].append(t)
                if t < p_time[i]:
                    p_best[i], p_time[i] = dict(xs[i]), t
                if t < g_time:
                    g_best, g_time = dict(xs[i]), t
            if done < budget:
                g = g_best if g_best is not None else xs[0]
                xs = [self._move(space, rng, x, p, g)
                      for x, p in zip(xs, p_best)]
        return {"particle_traces": traces, "swarm_size": n}


class GreedyCoordinateDescent(Strategy):
    """Beyond-paper: cycle through parameters, greedily taking the best value
    of each while holding the others fixed; restart from a random point when
    a full cycle yields no improvement.  Cheap and surprisingly strong on the
    near-separable sharding spaces; included as a pluggable-strategy demo.
    """

    name = "greedy"

    def walk(self, space, budget, seed=0, seeds=None) -> Walk:
        budget = _require_budget(self, budget)
        rng = random.Random(seed)
        done = 0
        # Warm start: descend from the best seed instead of a random point
        current, t_cur = None, math.inf
        for cfg in usable_seeds(space, seeds, limit=budget):
            t, = yield [cfg]
            done += 1
            if current is None or t < t_cur:
                current, t_cur = cfg, t
        if current is None:
            current = space.sample(rng)
            t_cur, = yield [current]
            done += 1
        while done < budget:
            improved = False
            for param in space.parameters:
                if done >= budget:
                    break
                for v in param.values:
                    if v == current[param.name]:
                        continue
                    cand = dict(current)
                    cand[param.name] = v
                    if not space.is_feasible(cand):
                        continue
                    t, = yield [cand]
                    done += 1
                    if t < t_cur:
                        current, t_cur = cand, t
                        improved = True
                    if done >= budget:
                        break
            if not improved:
                current = space.sample(rng)      # random restart
                t_cur, = yield [current]
                done += 1
        return {}


class Evolutionary(Strategy):
    """Genetic algorithm — the paper's named future-work strategy (§III-B).

    Tournament selection, uniform crossover per dimension, per-dimension
    mutation to a random value; elitism keeps the incumbent.  Infeasible
    offspring are repaired by re-sampling.  Each batch is one generation's
    offspring.
    """

    name = "evolutionary"

    def __init__(self, population: int = 8, mutation_rate: float = 0.15,
                 tournament: int = 3, max_repair_tries: int = 32):
        self.population = population
        self.mutation_rate = mutation_rate
        self.tournament = tournament
        self.max_repair_tries = max_repair_tries

    def _offspring(self, space: SearchSpace, rng: random.Random,
                   a: Config, b: Config) -> Config:
        for _ in range(self.max_repair_tries):
            child: Config = {}
            for p in space.parameters:
                v = a[p.name] if rng.random() < 0.5 else b[p.name]
                if rng.random() < self.mutation_rate:
                    v = rng.choice(p.values)
                child[p.name] = v
            if space.is_feasible(child):
                return child
        return space.sample(rng)

    def walk(self, space, budget, seed=0, seeds=None) -> Walk:
        budget = _require_budget(self, budget)
        rng = random.Random(seed)
        # Warm start: seeds join generation 0 (elitism then carries the
        # best transferred config forward until something beats it)
        planted = usable_seeds(space, seeds, limit=self.population)
        batch = planted + [space.sample(rng)
                           for _ in range(self.population - len(planted))]
        pop: List[Config] = []
        fit: List[float] = []
        elite: List[Config] = []
        elite_fit: List[float] = []

        def tourney() -> Config:
            idx = min(rng.sample(range(len(pop)),
                                 min(self.tournament, len(pop))),
                      key=lambda i: fit[i])
            return pop[idx]

        done = 0
        while batch and done < budget:
            batch = batch[:budget - done]
            times = yield batch
            done += len(batch)
            pop, fit = elite + batch, elite_fit + list(times)
            if done >= budget:
                break
            elite_i = min(range(len(pop)), key=lambda i: fit[i])
            elite, elite_fit = [pop[elite_i]], [fit[elite_i]]
            batch = [self._offspring(space, rng, tourney(), tourney())
                     for _ in range(self.population - 1)]
        return {"population": self.population}


# ---------------------------------------------------------------------------
# Registry ("other search methods are easily pluggable into CLTune")
# ---------------------------------------------------------------------------

_REGISTRY: Dict[str, Callable[..., Strategy]] = {
    "full": FullSearch,
    "random": RandomSearch,
    "annealing": SimulatedAnnealing,
    "pso": ParticleSwarm,
    "greedy": GreedyCoordinateDescent,
    "evolutionary": Evolutionary,
}


def register_strategy(name: str, factory: Callable[..., Strategy]) -> None:
    if name in _REGISTRY:
        raise ValueError(f"strategy {name!r} already registered")
    _REGISTRY[name] = factory


def make_strategy(name: str, **kwargs) -> Strategy:
    try:
        factory = _REGISTRY[name]
    except KeyError as e:
        raise KeyError(f"unknown strategy {name!r}; known: {sorted(_REGISTRY)}") from e
    return factory(**kwargs)


def available_strategies() -> Tuple[str, ...]:
    return tuple(sorted(_REGISTRY))
