"""Distributed tuning workers: one shard, one EvaluationEngine.

A :class:`TuningWorker` executes one :class:`~repro.dtune.partition.Shard`
of a distributed search by wrapping the exact same stack a single-process
tune uses — ``Tuner.from_tunable`` → ``EvaluationEngine`` — so every PR 3
fault-tolerance behaviour carries over: a failing config becomes a trial,
a circuit-breaker trip yields a *partial* :class:`WorkerResult` with
``status="aborted"`` instead of killing the job, and only an unexpected
crash in the worker scaffolding itself reports ``status="failed"``.

Everything in :class:`WorkerSpec` is plain data (kernel by registered
name, evaluator by name/kwargs spec, profile by name) so a spec crosses a
process boundary by pickling; each worker records into its own *private*
cache file and the coordinator folds those into the shared cache with
:meth:`TuningCache.merge` afterwards.

Two drivers run a fleet of specs:

* ``thread`` — in-process pool.  Zero setup cost; right for analytical /
  cost-model evaluators (pure Python, cheap) and for tests.  Wall-clock
  measurement in concurrent threads contends for the device: on a TPU
  every trial holds the process-wide ``device_lock``, so the workers'
  compiles overlap but their timings run one at a time; on a host
  backend they run concurrently and each median counts the others'
  work.  It is the only driver that can time kernels on a TPU, which
  one process holds.
* ``process`` — one OS process per worker (``fork`` server where
  available, ``spawn`` otherwise).  True isolation: a worker segfaulting
  in a compiler cannot take the coordinator down; results come back over
  a queue and caches over the filesystem.  Refused for device timing on
  a TPU backend.
"""

from __future__ import annotations

import dataclasses
import logging
import multiprocessing as mp
import traceback
from concurrent.futures import ThreadPoolExecutor
from typing import Any, Dict, List, Mapping, Optional, Union

from ..core.cache import TuningCache
from ..core.evaluators import (Evaluator, WallClockEvaluator, make_evaluator,
                               on_tpu)
from ..core.profiles import get_profile
from ..core.registry import resolve
from ..core.tuner import Tuner
from .partition import Shard

log = logging.getLogger("repro.dtune")

#: evaluator specification forms a WorkerSpec accepts: None (the kernel's
#: default), a make_evaluator name, a {"name": ..., **kwargs} dict, or a
#: live Evaluator instance (thread driver / fork only — not spawn-safe)
EvaluatorSpec = Union[None, str, Mapping[str, Any], Evaluator]


def resolve_evaluator(spec: EvaluatorSpec) -> Optional[Evaluator]:
    """Materialize an evaluator from its picklable spec (None passes
    through: ``Tuner.from_tunable`` picks the kernel's default)."""
    if spec is None or isinstance(spec, Evaluator):
        return spec
    if isinstance(spec, str):
        return make_evaluator(spec)
    if isinstance(spec, Mapping):
        kwargs = dict(spec)
        try:
            name = kwargs.pop("name")
        except KeyError:
            raise ValueError("evaluator spec dict needs a 'name' key; "
                             f"got {dict(spec)!r}") from None
        return make_evaluator(name, **kwargs)
    raise TypeError(f"bad evaluator spec: {spec!r}")


@dataclasses.dataclass
class WorkerSpec:
    """Everything one worker needs, as plain picklable data."""

    kernel: str                             # registered TunableKernel name
    shape: Dict[str, Any]
    shard: Shard
    profile: str = "tpu_v5e"                # DeviceProfile by name
    evaluator: EvaluatorSpec = None
    #: EngineConfig kwargs (workers, prune_factor, max_failures, ...);
    #: the runtime stop event is injected separately, never pickled
    engine: Dict[str, Any] = dataclasses.field(default_factory=dict)
    interpret: Optional[bool] = None
    extended_space: bool = False
    #: private cache file this worker records its shard winner into;
    #: None = don't record (results only travel via WorkerResult)
    cache_path: Optional[str] = None
    #: warm-start seed configs (nearest-shape winners, heuristics)
    seeds: Optional[List[Dict[str, Any]]] = None
    #: root directory of the *shared* compile-artifact store (picklable
    #: path, not a live store): every worker opens its own ArtifactStore
    #: on it, and the store's per-artifact cross-process locks make each
    #: distinct artifact compile at most once fleet-wide.  None = no store.
    artifact_dir: Optional[str] = None
    #: predictor as plain picklable data: None (the REPRO_PREDICTOR env
    #: default), a kind string, or a ``{"kind", "payload"}`` dict carrying
    #: a fleet-trained LearnedPredictor's weights — the coordinator trains
    #: ONE model from the merged cache and ships it to every worker, so
    #: the whole fleet ranks with the same surrogate (never a live object)
    predictor: "str | Dict[str, Any] | None" = None


@dataclasses.dataclass
class WorkerResult:
    """One worker's outcome, as plain data (crosses process boundaries)."""

    index: int
    shard_label: str
    #: "ok" | "aborted" (circuit breaker / stop event, partial result) |
    #: "empty" (no feasible config in the shard) | "failed" (worker crash)
    status: str
    best_config: Optional[Dict[str, Any]] = None
    best_time: float = float("inf")
    evaluations: int = 0
    failures: int = 0                       # failed-config trials
    error: Optional[str] = None             # set when status == "failed"
    cache_path: Optional[str] = None
    engine_stats: Optional[Dict[str, Any]] = None

    @property
    def ok(self) -> bool:
        return self.status in ("ok", "aborted") \
            and self.best_config is not None


class TuningWorker:
    """Run one shard of a distributed search through the standard stack."""

    def __init__(self, spec: WorkerSpec, stop_event: Optional[Any] = None):
        self.spec = spec
        self.stop_event = stop_event

    def run(self) -> WorkerResult:
        spec = self.spec
        shard = spec.shard
        try:
            return self._run()
        except Exception as e:  # noqa: BLE001 — one worker crashing must
            # surface as a failed *result*, not kill the whole fleet
            log.exception("dtune: worker %s crashed", shard.label)
            return WorkerResult(
                index=shard.index, shard_label=shard.label, status="failed",
                error=f"{type(e).__name__}: {e}\n"
                      f"{traceback.format_exc(limit=5)}",
                cache_path=spec.cache_path)

    def _run(self) -> WorkerResult:
        spec = self.spec
        shard = spec.shard
        k = resolve(spec.kernel)
        cache = TuningCache(spec.cache_path) if spec.cache_path else None
        tuner = Tuner.from_tunable(
            k, spec.shape,
            evaluator=resolve_evaluator(spec.evaluator),
            profile=get_profile(spec.profile),
            cache=cache, artifact_store=spec.artifact_dir,
            interpret=spec.interpret,
            extended_space=spec.extended_space)
        engine = dict(spec.engine)
        if self.stop_event is not None:
            engine["stop_event"] = self.stop_event
        outcome = tuner.tune(
            strategy=shard.strategy, budget=shard.budget, seed=shard.seed,
            record_to_cache=spec.cache_path is not None,
            shape_key=k.key_for(spec.shape), engine=engine,
            seeds=spec.seeds or None, predictor=spec.predictor,
            **shard.strategy_kwargs)
        result = outcome.result
        best = result.best
        if result.extra.get("aborted"):
            status = "aborted"
        elif best is None:
            status = "empty"
        else:
            status = "ok"
        return WorkerResult(
            index=shard.index, shard_label=shard.label, status=status,
            best_config=dict(best.config) if best else None,
            best_time=best.time if best else float("inf"),
            evaluations=result.evaluations,
            failures=outcome.failure_summary["failed_trials"],
            cache_path=spec.cache_path,
            engine_stats=result.extra.get("engine"))


# -- drivers -------------------------------------------------------------------

def _times_device(spec: EvaluatorSpec) -> bool:
    """Whether an evaluator spec runs kernels on the device (None is the
    kernel's default, which on a TPU backend is wall-clock timing)."""
    if spec is None or isinstance(spec, WallClockEvaluator):
        return True
    if isinstance(spec, str):
        return spec == WallClockEvaluator.name
    if isinstance(spec, Mapping):
        return spec.get("name") == WallClockEvaluator.name
    return False


def _process_entry(spec: WorkerSpec, queue: "mp.Queue",
                   stop_event: Optional[Any] = None) -> None:
    """Module-level child entry point (picklable under spawn)."""
    result = TuningWorker(spec, stop_event).run()
    queue.put(dataclasses.asdict(result))


def run_workers(specs: List[WorkerSpec], driver: str = "thread", *,
                stop_event: Optional[Any] = None,
                timeout_s: Optional[float] = None) -> List[WorkerResult]:
    """Execute every spec and return results in spec order.

    ``driver="thread"`` runs workers on an in-process pool sized to the
    fleet; ``driver="process"`` forks/spawns one OS process per worker.
    ``stop_event`` (optional) is handed to every worker's engine for
    cooperative early stop; with the process driver it must be a
    ``multiprocessing.Event``.  A worker that crashes, dies, or exceeds
    ``timeout_s`` yields a ``status="failed"`` result — never an
    exception out of this function.
    """
    if driver == "thread":
        with ThreadPoolExecutor(max_workers=max(1, len(specs)),
                                thread_name_prefix="dtune-worker") as pool:
            futures = [pool.submit(TuningWorker(s, stop_event).run)
                       for s in specs]
            return [f.result() for f in futures]
    if driver != "process":
        raise ValueError(f"unknown dtune driver {driver!r}; "
                         "known: 'thread', 'process'")
    if any(_times_device(s.evaluator) for s in specs) and on_tpu():
        raise ValueError(
            "driver='process' cannot time kernels on a TPU: the chip "
            "belongs to one process, and this one already holds it, so a "
            "worker process would fail or hang reaching it; use "
            "driver='thread' for device timing")
    # fork keeps live registry/evaluator state; spawn is the portable
    # fallback and is why WorkerSpec is all-plain-data
    method = "fork" if "fork" in mp.get_all_start_methods() else "spawn"
    ctx = mp.get_context(method)
    queues = [ctx.Queue() for _ in specs]
    procs = []
    for spec, q in zip(specs, queues):
        # NB a stop_event crossing this boundary must be a
        # multiprocessing.Event from a compatible context; a plain
        # threading.Event would fail to pickle under spawn
        p = ctx.Process(target=_process_entry, args=(spec, q, stop_event),
                        name=f"dtune-{spec.shard.label}")
        p.start()
        procs.append(p)
    results: List[WorkerResult] = []
    for spec, p, q in zip(specs, procs, queues):
        shard = spec.shard
        try:
            results.append(WorkerResult(**q.get(timeout=timeout_s)))
        except Exception as e:  # noqa: BLE001 — queue.Empty on timeout,
            # or a child that died before putting anything
            results.append(WorkerResult(
                index=shard.index, shard_label=shard.label, status="failed",
                error=f"worker process yielded no result ({e!r})",
                cache_path=spec.cache_path))
        p.join(timeout=5.0)
        if p.is_alive():
            p.terminate()
            p.join(timeout=5.0)
    return results
