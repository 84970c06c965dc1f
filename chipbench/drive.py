"""The general driver: runs one cell's traffic mix once, then checks it.

A traffic mix is data (``traffic/<name>.json``); its ``kind`` picks one of
the two drivers here, and the rest of the file are its parameters.

- ``apply``: the configuration's public op, as a user calls it, at the
  configuration's ``op_shape`` (its ``shape`` where it has none).  The
  config comes from ``registry.lookup`` with an empty tuned-config cache,
  the op is wrapped once in a named ``jax.jit``, and the window dispatches
  calls back to back, cycling over ``input_sets`` seeded input sets, with
  at most ``in_flight`` calls dispatched and not yet finished: a call waits
  only for the one ``in_flight`` calls before it, and the window ends when
  the last call has finished.
- ``tune``: a device-timed search through ``tune_kernel``, its budget the
  size of the search space.  JAX's persistent compilation cache is on for
  set-up and off from the window's start, so every candidate compiles cold
  in every run.  A timer sets the engine's ``stop_event`` when the window
  closes; a search that ends first is followed by another with the next
  seed.  The searches' seeds come from the mix (``search_seed``), so every
  run walks the same path: which configurations a search visits sets how
  much compiling it does, and ``--seed`` changes only the data.  The
  harness counts the trials itself, from the evaluator's calls (see
  ``TrialCounter``).

Both check what the window produced against the configuration's plain
reference once the window has closed (see ``check_outputs``).
"""

from __future__ import annotations

import collections
import dataclasses
import functools
import importlib
import json
import os
import tempfile
import threading
import time
from typing import Any, Callable, Dict, List, Optional

import jax
import numpy as np

from chipbench import peaks as peaks_mod
from chipbench import trace as trace_mod
from chipbench import work as work_mod


@dataclasses.dataclass
class Run:
    """What one run measured; the per-layer metric readers read it."""

    work: work_mod.Work
    attempted: int
    failed: int
    memory_peak_bytes: int
    #: the cell's end-to-end metrics, by name
    metrics: Dict[str, float]
    #: each number compared, with its limit
    compared: Dict[str, Dict[str, float]] = dataclasses.field(
        default_factory=dict)
    trace: Optional[trace_mod.Summary] = None
    #: the chip's peaks; None off a TPU
    peaks: Optional[peaks_mod.Peaks] = None
    #: apply: host-clock time per call over the window
    call_s: Optional[float] = None
    #: tune: EngineStats summed over the window's searches
    engine: Dict[str, float] = dataclasses.field(default_factory=dict)
    #: tune: the winner's time per call, re-timed after the window
    best_call_s: Optional[float] = None

    @property
    def correct(self) -> bool:
        return bool(self.compared) and all(
            c["value"] <= c["limit"] for c in self.compared.values())


def seed_key(seed: int):
    """A PRNG key from any seed below 2**64."""
    if not 0 <= seed < 2**64:
        raise ValueError(f"seed {seed} is not in [0, 2**64)")
    return jax.random.fold_in(jax.random.key(seed & 0xFFFFFFFF), seed >> 32)


def make_inputs(reference, shape, seed: int, sets: int) -> List[tuple]:
    """``sets`` input sets for ``shape``, made by the reference module on the
    device in one jitted call from ``seed``."""

    def make(key):
        return [tuple(reference.make_inputs(shape, k))
                for k in jax.random.split(key, sets)]

    return jax.block_until_ready(jax.jit(make)(seed_key(seed)))


def rel_err(out, ref) -> float:
    """max |out - ref| / max |ref|: inf where it is not a finite number."""
    out = jax.numpy.asarray(out, jax.numpy.float32)
    err = float(jax.numpy.max(jax.numpy.abs(out - ref))
                / jax.numpy.max(jax.numpy.abs(ref)))
    return err if np.isfinite(err) else float("inf")


def op_shape(config) -> Dict[str, Any]:
    """The shape the public op is called at: ``op_shape``, else ``shape``."""
    return config.get("op_shape", config["shape"])


@functools.lru_cache(maxsize=None)
def _jitted_reference(reference, shape_json: str, precision: str):
    shape = json.loads(shape_json)
    return jax.jit(lambda xs: reference.reference(shape, xs, precision))


def check_outputs(config, reference, pairs, shape) -> Dict[str, Dict[str, float]]:
    """Compare each (inputs, output) pair at ``shape`` with the plain
    reference at the configuration's precision; the number compared is the
    worst pair's."""
    ref = _jitted_reference(reference, json.dumps(shape, sort_keys=True),
                            config["precision"])
    worst = max((rel_err(out, ref(xs)) for xs, out in pairs),
                default=float("inf"))
    return {"max_rel_err": {"value": worst,
                            "limit": float(config["max_rel_err_limit"])}}


def _peak_memory(devices) -> int:
    stats = [d.memory_stats() or {} for d in devices]
    return max(int(s.get("peak_bytes_in_use", 0)) for s in stats)


def _import_op(spec: str) -> Callable:
    module, _, name = spec.partition(":")
    return getattr(importlib.import_module(module), name)


def _calls_in_flight(fn: Callable, inputs: List[tuple], seconds: float,
                     in_flight: int, tracer):
    """Calls of ``fn``, cycling over ``inputs``, dispatched back to back for
    at least ``seconds``, with at most ``in_flight`` of them unfinished: a
    call waits only for the one ``in_flight`` calls before it.  Returns the
    calls made, the seconds until the last had finished, and the last
    output of each input set."""
    outs: List[Any] = [None] * len(inputs)
    pending: collections.deque = collections.deque()
    calls = 0
    t0 = time.perf_counter()
    t_end = t0 + seconds
    while True:
        i = calls % len(inputs)
        with tracer.span("chipbench.apply.call"):
            outs[i] = fn(*inputs[i])
        pending.append(outs[i])
        calls += 1
        if len(pending) >= in_flight:
            with tracer.span("chipbench.apply.wait"):
                pending.popleft().block_until_ready()
        if time.perf_counter() >= t_end:
            break
    with tracer.span("chipbench.apply.wait"):
        jax.block_until_ready(list(pending))
    return calls, time.perf_counter() - t0, outs


def run_apply(cell, devices, seed: int, seconds: float, tracer, say,
              cache_dir: Optional[str], setup_started: float) -> Run:
    from repro.core.cache import TuningCache
    from repro.core.registry import lookup_resolved

    traffic, config = cell.traffic, cell.config
    shape = op_shape(config)
    in_flight = int(traffic["in_flight"])
    inputs = make_inputs(cell.reference, shape, seed,
                         int(traffic["input_sets"]))
    with tempfile.TemporaryDirectory() as tmp:
        resolved = lookup_resolved(
            config["kernel"], config["shape"], policy="off",
            cache=TuningCache(os.path.join(tmp, "tuned_configs.json")))
    cfg = dict(resolved.config)
    say(f"apply {config['kernel']} {shape}: config {cfg} "
        f"({resolved.provenance}), {in_flight} calls in flight")
    op = _import_op(config["op"])
    kwargs = dict(config["op_kwargs"], config=cfg,
                  interpret=jax.default_backend() != "tpu")

    def call(*xs):
        return op(*xs, **kwargs)

    call.__name__ = call.__qualname__ = f"chipbench_{config['kernel']}"
    fn = jax.jit(call)
    for xs in inputs:                      # compile and warm the one shape
        fn(*xs).block_until_ready()

    if tracer.enabled and traffic.get("trace_seconds"):
        seconds = min(seconds, float(traffic["trace_seconds"]))
    with tracer.window() as window:
        t0 = time.perf_counter()
        calls, elapsed, outs = _calls_in_flight(fn, inputs, seconds,
                                                in_flight, tracer)
    call_s = elapsed / calls
    run = Run(work=work_mod.of(cell.reference, shape), attempted=calls,
              failed=0, memory_peak_bytes=_peak_memory(devices),
              metrics={"kernel_us": call_s * 1e6,
                       "setup_s": t0 - setup_started},
              trace=window.summary, call_s=call_s)
    del fn
    run.compared = check_outputs(
        config, cell.reference,
        [(xs, out) for xs, out in zip(inputs, outs) if out is not None], shape)
    return run


def _sum_stats(outcomes) -> Dict[str, float]:
    total: Dict[str, float] = {}
    for o in outcomes:
        for k, v in (o.engine_stats or {}).items():
            if isinstance(v, (int, float)) and not isinstance(v, bool):
                total[k] = total.get(k, 0) + v
    return total


class TrialCounter:
    """The tune mix's trials, counted by the harness: each configuration
    that a search hands the evaluator to measure, or whose compile raises,
    once per search.  Configurations answered without a compile (the
    engine's memo, pruning) never reach the evaluator and are not trials."""

    def __init__(self):
        self.search = 0              # the search now running
        self._seen: set = set()
        self._lock = threading.Lock()   # compiles run on the engine's pool

    def add(self, cfg) -> None:
        with self._lock:
            self._seen.add((self.search, json.dumps(cfg, sort_keys=True)))

    def of(self, search: int) -> int:
        return sum(1 for s, _ in self._seen if s == search)

    @property
    def trials(self) -> int:
        return len(self._seen)


def run_tune(cell, devices, seed: int, seconds: float, tracer, say,
             cache_dir: Optional[str], setup_started: float) -> Run:
    from jax.experimental.compilation_cache import compilation_cache

    from repro.core.cache import TuningCache
    from repro.core.engine import EngineConfig
    from repro.core.evaluators import WallClockEvaluator
    from repro.core.profiles import TPU_V5E, attached_profile
    from repro.core.registry import resolve
    from repro.tune import tune_kernel

    traffic, config = cell.traffic, cell.config
    shape = config["shape"]
    search_seed = int(traffic["search_seed"])
    on_chip = jax.default_backend() == "tpu"
    kernel = resolve(config["kernel"])
    profile = attached_profile() if on_chip else TPU_V5E
    budget = kernel.make_space(shape).size()
    inputs = make_inputs(cell.reference, shape, seed, 1)

    counter = TrialCounter()

    class Evaluator(WallClockEvaluator):
        """The program's evaluator, with the harness's spans around the
        engine's calls into it and its count of the trials."""

        def prepare(self, spec, cfg):
            with tracer.span("chipbench.tune.compile"):
                try:
                    return super().prepare(spec, cfg)
                except Exception:
                    counter.add(cfg)
                    raise

        def measure(self, spec, cfg, prepared=None, prune_threshold_s=None):
            counter.add(cfg)
            with tracer.span("chipbench.tune.measure"):
                return super().measure(spec, cfg, prepared, prune_threshold_s)

    evaluator = Evaluator(seed=seed, **traffic["evaluator"])
    # warm what every trial calls besides its own kernel: the program's
    # reference, which verifies each trial's output at this shape
    args = kernel.make_args(shape, np.random.default_rng(seed))
    jax.block_until_ready(kernel.reference(shape)(*args))
    del args

    hits = []
    jax.monitoring.register_event_listener(
        lambda event, **kw: hits.append(event)
        if event == "/jax/compilation_cache/cache_hits" else None)
    entries_before = _entries(cache_dir)
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    hits_at_start = len(hits)

    stop = threading.Event()
    timer = threading.Timer(seconds, stop.set)
    outcomes = []
    with tempfile.TemporaryDirectory() as tmp:
        cache = TuningCache(os.path.join(tmp, "tuned_configs.json"))
        with tracer.window() as window:
            t0 = time.perf_counter()
            timer.start()
            while not stop.is_set():
                counter.search = len(outcomes)
                with tracer.span("chipbench.tune.search"):
                    outcomes.append(tune_kernel(
                        kernel, shape, strategy=traffic["strategy"],
                        budget=budget, evaluator=evaluator, profile=profile,
                        cache=cache, record=False,
                        seed=search_seed + len(outcomes),
                        extended_space=False,
                        engine=EngineConfig(stop_event=stop)))
            t1 = time.perf_counter()
    timer.cancel()
    timer.join()
    window_hits = len(hits) - hits_at_start

    stats = _sum_stats(outcomes)
    for i, o in enumerate(outcomes):
        s = o.engine_stats or {}
        say(f"search {i} (seed {search_seed + i}): {counter.of(i)} trials "
            f"counted, winner {o.best_config} median "
            f"{o.best_time * 1e6:.1f} us; EngineStats {s}")
    trials = counter.trials
    failed = int(stats.get("compile_failures", 0)
                 + stats.get("measure_failures", 0))
    say(f"compile cache {cache_dir}: {entries_before} entries before the "
        f"window, {_entries(cache_dir)} after; {window_hits} cache hits "
        f"in the window")
    best = min((o for o in outcomes if o.best_config is not None),
               key=lambda o: o.best_time, default=None)
    run = Run(work=work_mod.of(cell.reference, shape), attempted=trials,
              failed=failed, memory_peak_bytes=_peak_memory(devices),
              metrics={"tune_trials_per_s": trials / (t1 - t0),
                       "setup_s": t0 - setup_started},
              trace=window.summary, engine=stats)
    if best is None:
        say("no configuration ran: nothing to check")
        run.compared = check_outputs(config, cell.reference, [], shape)
        return run
    say(f"winner {best.best_config} median {best.best_time * 1e6:.1f} us")
    fn = jax.jit(kernel.builder(shape, best.best_config,
                                interpret=not on_chip))
    out = fn(*inputs[0]).block_until_ready()
    calls, elapsed, _ = _calls_in_flight(
        fn, inputs, float(traffic["retime_seconds"]),
        int(traffic["in_flight"]), tracer)
    run.best_call_s = elapsed / calls
    say(f"winner re-timed: {run.best_call_s * 1e6:.1f} us per call")
    del fn
    run.compared = check_outputs(config, cell.reference, [(inputs[0], out)],
                                 shape)
    return run


def _entries(path: Optional[str]) -> int:
    return len(os.listdir(path)) if path and os.path.isdir(path) else 0


def run(cell, devices, seed: int, seconds: float, trace: bool, say,
        cache_dir: Optional[str], setup_started: float) -> Run:
    """One run of ``cell``; ``setup_started`` is the ``perf_counter`` time
    its set-up began, which ``setup_s`` counts from."""
    drivers = {"apply": run_apply, "tune": run_tune}
    kind = cell.traffic["kind"]
    if kind not in drivers:
        raise ValueError(f"traffic {cell.traffic_name!r}: unknown kind "
                         f"{kind!r}; known: {sorted(drivers)}")
    tracer = trace_mod.Tracer(trace, cell.config.get("kernel_event"))
    return drivers[kind](cell, devices, seed, seconds, tracer, say,
                         cache_dir, setup_started)
