"""Measurement backends for the tuner.

CLTune measures one thing: wall-clock kernel time on the attached OpenCL
device.  This port makes the measurement pluggable because (a) the target
device (TPU v5e) is not the device this container runs on, and (b) beyond the
paper we tune *distributed* configurations whose natural objective is a
compile-time roofline estimate, not a wall-clock sample.

Three evaluators, one interface:

* :class:`WallClockEvaluator`  — jit + block_until_ready median timing; the
  faithful CLTune measurement, used on CPU for small shapes and unchanged on
  a real TPU.
* :class:`CostModelEvaluator`  — ``lower().compile().cost_analysis()`` FLOPs +
  bytes + HLO collective bytes -> roofline time against a DeviceProfile.
* :class:`TPUAnalyticalEvaluator` — a structural VMEM/MXU pipeline model of a
  Pallas kernel (supplied by the kernel's ``analytical_model``), with seeded
  multiplicative noise so that the paper's stochastic-search experiments see
  realistic measurement jitter on this CPU-only container.
"""

from __future__ import annotations

import contextlib
import dataclasses
import hashlib
import math
import threading
import time
import warnings
from typing import Any, Callable, Dict, List, Optional, Tuple

import jax
import numpy as np

from . import spans, verify
from .artifacts import (PROVENANCE_NONE, ArtifactStore, CompiledArtifact,
                        spec_fingerprint)
from .failures import (CompileError, EvaluationError, InfeasibleConfigError,
                       MeasureError, VerificationFailure)
from .hlo import collective_stats, fingerprint
from .metrics import Metrics
from .profiles import DeviceProfile, TPU_V5E
from .space import Config


@dataclasses.dataclass
class KernelSpec:
    """Everything the evaluators may need about one tunable kernel.

    ``build(config)`` returns a jit-able callable implementing the kernel for
    that parameter configuration (the analogue of CLTune recompiling the
    OpenCL source with new ``#define``\\ s).  The remaining fields feed the
    different evaluators and the verification path; only the ones the chosen
    evaluator needs must be provided.
    """

    name: str
    build: Callable[[Config], Callable]
    #: concrete host arguments for wall-clock runs + verification
    make_args: Optional[Callable[[np.random.Generator], Tuple]] = None
    #: abstract args (jax.ShapeDtypeStruct pytree) for lowering-based evaluation
    arg_specs: Optional[Callable[[], Tuple]] = None
    #: structural time model: (config, profile) -> seconds (math.inf = infeasible)
    analytical_model: Optional[Callable[[Config, DeviceProfile], float]] = None
    #: reference oracle taking the same args, for SetReference verification
    reference: Optional[Callable] = None
    #: static metadata (shape key etc.) used by the results cache
    meta: Dict[str, Any] = dataclasses.field(default_factory=dict)


@dataclasses.dataclass
class Measurement:
    """Outcome of evaluating one configuration."""

    time_s: float                       # objective; inf = failed
    ok: bool
    verified: Optional[bool] = None     # None = verification not performed
    compile_s: float = 0.0              # trace+lower+compile cost (also real:
                                        # the paper notes recompilation limits
                                        # tuning throughput)
    error: str = ""
    detail: Dict[str, float] = dataclasses.field(default_factory=dict)
    #: full per-repeat sample vector + derived stats; None on failure or
    #: from legacy backends that only produced a scalar
    metrics: Optional[Metrics] = None

    @property
    def pruned(self) -> bool:
        """True when the measurement was aborted by early-stop pruning."""
        return bool(self.detail.get("pruned", False))

    def as_metrics(self) -> Optional[Metrics]:
        """The structured metrics behind this measurement.  Falls back to a
        single-sample vector built from ``time_s`` for backends that never
        attached one; None for failed measurements (scalarizes to inf)."""
        if not self.ok:
            return None
        if self.metrics is not None:
            return self.metrics
        if not math.isfinite(self.time_s):
            return None
        return Metrics(samples=(self.time_s,), compile_s=self.compile_s)


def median_prune_loop(sample: Callable[[], float], repeats: int,
                      prune_threshold_s: Optional[float] = None,
                      min_samples: int = 1) -> Tuple[List[float], bool]:
    """Collect up to ``repeats`` timing samples with early-stop pruning.

    After each sample the running median is compared against
    ``prune_threshold_s`` (typically ``k × incumbent``); once it exceeds
    the threshold the loop aborts.  Returns ``(samples, pruned)``.  A
    configuration whose samples stay below the threshold can never be
    pruned, so the incumbent — or anything better — survives; real
    timing is noisy, though, so ``min_samples`` guards against a single
    outlier sample aborting a genuinely fast configuration (wall-clock
    measurement passes 2: pruning only ever triggers on a median of at
    least two samples).
    """
    samples: List[float] = []
    for _ in range(max(1, repeats)):
        samples.append(float(sample()))
        if (prune_threshold_s is not None
                and len(samples) >= max(1, min_samples)
                and len(samples) < repeats
                and float(np.median(samples)) > prune_threshold_s):
            return samples, True
    return samples, False


#: module-level flag: the evaluate() deprecation fires once per process,
#: not once per call site (a tuning run calls it thousands of times)
_EVALUATE_DEPRECATION_EMITTED = False


class Evaluator:
    """Interface: ``prepare`` -> :class:`CompiledArtifact` -> ``measure``.

    Evaluation splits into two typed phases for the parallel engine:

    * ``prepare(spec, config)`` — the compilation phase.  Must be safe to
      run concurrently from a worker pool and returns a
      :class:`~repro.core.artifacts.CompiledArtifact` carrying the
      content-address (HLO or spec fingerprint), the device-profile key,
      lowered stats, the measurable payload and its provenance
      (fresh-compile vs persistent-store hit).  The default prepares
      nothing and returns a payload-free artifact with
      ``provenance="none"``.
    * ``measure(spec, config, prepared, prune_threshold_s)`` — the timing
      phase, always serialized by the engine so measurements never
      contend.  ``prune_threshold_s`` enables early-stop pruning where
      the backend supports it.  ``measure`` accepts the artifact from
      *any* provenance; a store-hit artifact measures identically to a
      fresh one (that is the whole point of the store).

    Evaluators that can skip compilation consult ``artifact_store`` (an
    :class:`~repro.core.artifacts.ArtifactStore`, attached by the Tuner
    or set directly; None = no persistence) inside ``prepare``.

    **Failure contract**: a configuration that cannot be evaluated raises
    a typed :class:`~repro.core.failures.EvaluationError` subclass —
    :class:`~repro.core.failures.CompileError` from ``prepare``,
    :class:`~repro.core.failures.MeasureError` (or
    :class:`~repro.core.failures.VerificationFailure`) from ``measure`` —
    carrying the original exception as ``__cause__``.  The evaluation
    engine converts these into ``inf``-time trials with structured
    FailureRecords.  Failed compiles are never persisted to the store.
    Returning a failed :class:`Measurement` from either phase is the
    legacy convention and still tolerated; so are legacy untyped
    artifacts (``_CompiledKernel``, bare cost dicts) reaching
    ``measure`` from code that calls ``prepare`` directly.

    ``evaluate`` — the positional one-call compat shim — is
    **deprecated**: it emits a DeprecationWarning (once per process) and
    routes through the artifact path.  Internal callers (``objective``,
    ``analyze``, the engine) use the prepare/measure pair or the
    non-warning ``_evaluate``.
    """

    name = "base"
    #: persistent compile-artifact store; None disables persistence.
    #: Class-level default so every evaluator has the attribute; the
    #: Tuner attaches a per-run store on the instance.
    artifact_store: Optional[ArtifactStore] = None
    #: the DeviceProfile this evaluator models/measures against, when it
    #: has one (cost-model and analytical evaluators set it).  The engine
    #: reads it (via getattr) to give predictors device context; None
    #: means "no modeled device" (e.g. wall-clock on the host).
    profile: Optional[Any] = None

    def evaluate(self, spec: KernelSpec, config: Config) -> Measurement:
        """Deprecated one-call path; use ``prepare`` + ``measure``
        (or ``objective``) instead."""
        global _EVALUATE_DEPRECATION_EMITTED
        if not _EVALUATE_DEPRECATION_EMITTED:
            _EVALUATE_DEPRECATION_EMITTED = True
            warnings.warn(
                "Evaluator.evaluate(spec, config) is deprecated; use the "
                "typed prepare()/measure() artifact path (or objective()) "
                "instead", DeprecationWarning, stacklevel=2)
        return self._evaluate(spec, config)

    def _evaluate(self, spec: KernelSpec, config: Config) -> Measurement:
        """measure(prepare(...)) with typed errors folded back into failed
        Measurements — so bare objective adapters keep seeing ``inf``
        instead of exceptions.  Not deprecated; not part of the public
        contract."""
        try:
            return self.measure(spec, config, self.prepare(spec, config))
        except EvaluationError as e:
            return _failed(e)

    def prepare(self, spec: KernelSpec, config: Config) -> CompiledArtifact:
        """Concurrent compile phase; default: nothing to prepare."""
        return CompiledArtifact(
            kind=self.name,
            fingerprint=spec_fingerprint(spec.name, spec.meta, config),
            profile="", payload=None, provenance=PROVENANCE_NONE)

    def measure(self, spec: KernelSpec, config: Config,
                prepared: Any = None,
                prune_threshold_s: Optional[float] = None) -> Measurement:
        raise NotImplementedError

    def objective(self, spec: KernelSpec) -> Callable[[Config], float]:
        """Adapt to the strategies' ``Config -> float`` objective."""
        def _obj(config: Config) -> float:
            return self._evaluate(spec, config).time_s
        return _obj


def on_tpu() -> bool:
    """Whether JAX's default backend is a TPU (initializes the backend)."""
    return jax.default_backend() == "tpu"


#: one per process: the chip runs one program at a time, so a host-clock
#: sample taken while another thread's kernel or decode step runs on it
#: would count that work too
_DEVICE_LOCK = threading.RLock()


def device_lock():
    """Held around work run on a TPU whose time is read or contended:
    wall-clock trials (every engine, every dtune thread worker) and serve
    decode steps.  A no-op on a host backend, where timings stay as
    concurrent as the caller makes them."""
    return _DEVICE_LOCK if on_tpu() else contextlib.nullcontext()


def resolve_interpret(interpret: Optional[bool]) -> bool:
    """Whether Pallas kernels build in interpret mode.

    None means: interpret on a host backend, compile for the chip on a
    TPU.  Asking for interpret mode on a TPU raises — a kernel run through
    the interpreter there would be timed and cached as if it were the
    compiled kernel."""
    tpu = on_tpu()
    if interpret is None:
        return not tpu
    if interpret and tpu:
        raise ValueError(
            "interpret=True on a TPU backend: the Pallas interpreter is a "
            "host-side debugging path, not the kernel the chip runs")
    return bool(interpret)


def _failed(err: Exception | str, compile_s: float = 0.0) -> Measurement:
    return Measurement(time_s=math.inf, ok=False, compile_s=compile_s,
                       error=str(err)[:500])


@dataclasses.dataclass
class _Fixture:
    """One search's inputs and its reference's output, kept on the device
    and shared by every trial of one kernel spec at one seed."""

    spec: KernelSpec
    seed: int
    args: Tuple
    ref: Any = None             # the reference's output, once a trial ran it
    lock: threading.Lock = dataclasses.field(default_factory=threading.Lock)

    def reference(self) -> Any:
        """The reference's output on ``args``, run by the first caller."""
        with self.lock:
            if self.ref is None:
                self.ref = self.spec.reference(*self.args)
            return self.ref


@dataclasses.dataclass
class _CompiledKernel:
    """Artifact of WallClockEvaluator.prepare: compiled fn, the fixture
    whose args it runs on, first output."""

    fn: Callable
    fixture: _Fixture
    out: Any
    compile_s: float


class WallClockEvaluator(Evaluator):
    """Median-of-N wall-clock timing of the jitted kernel (CLTune's method).

    ``prepare`` performs the expensive part — building and compiling
    the kernel plus its first call — and returns a
    :class:`CompiledArtifact` whose payload is the live ``_CompiledKernel``
    bundle (compiled fn, concrete args, first output).  A live executable
    does not serialize, so the artifact is *not persistable*: wall-clock
    artifacts never reach the on-disk store and their fingerprint is the
    spec/config content address.  ``measure`` verifies and times serially, optionally aborting
    early once the running median exceeds the prune threshold.

    Each phase is a span of :mod:`repro.core.spans` whose seconds are
    returned beside the result: ``args_s``, ``lower_s``, ``xla_compile_s``
    and ``first_call_s`` in the artifact's ``stats``, ``verify_s`` and
    ``timing_s`` in the measurement's ``detail``.

    The inputs, ``spec.make_args(np.random.default_rng(seed))``, are drawn
    once per spec object and seed, and the reference is run once on them:
    every trial of a search times and verifies against the same fixture,
    held on the device, as CLTune's ``SetReference`` stores one reference
    result.  Another spec (a new search's, or one whose reference was
    replaced) or another seed replaces it, so the evaluator holds one
    fixture at a time.  Kernels are compiled without donated arguments, so
    no trial writes into the shared inputs.  A trial whose prepare found
    the fixture held counts one ``inputs_reused`` in the artifact's
    ``stats``.

    On a TPU every device run here holds :func:`device_lock`, so trials
    from concurrent engines (dtune thread workers, a background retune
    beside a serving loop) are timed one at a time.
    """

    name = "wallclock"

    def __init__(self, repeats: int = 5, warmup: int = 1,
                 verify_outputs: bool = True, seed: int = 0,
                 atol: Optional[float] = None, rtol: Optional[float] = None):
        self.repeats = repeats
        self.warmup = warmup
        self.verify_outputs = verify_outputs
        self.seed = seed
        self.atol, self.rtol = atol, rtol
        self._fixture: Optional[_Fixture] = None
        self._fixture_lock = threading.Lock()

    def _inputs(self, spec: KernelSpec) -> Tuple[_Fixture, bool]:
        """The fixture of ``spec`` at this seed, drawn if it is not the one
        held; and whether it was held.  Concurrent prepares draw it once."""
        with self._fixture_lock:
            if (self._fixture is not None and self._fixture.spec is spec
                    and self._fixture.seed == self.seed):
                return self._fixture, True
            self._fixture = None    # free the old inputs before the draw
            self._fixture = _Fixture(
                spec, self.seed,
                spec.make_args(np.random.default_rng(self.seed)))
            return self._fixture, False

    def prepare(self, spec: KernelSpec, config: Config):
        if spec.make_args is None:
            raise CompileError("WallClockEvaluator requires spec.make_args")
        trial = spans.config_arg(config)
        seconds: Dict[str, float] = {}
        try:
            with spans.phase("repro.eval.args", seconds, "args_s",
                             config=trial):
                fixture, reused = self._inputs(spec)
            args = fixture.args
            # compile outside the device lock (compiles overlap), run the
            # first call under it
            with spans.phase("repro.eval.lower", seconds, "lower_s",
                             config=trial):
                lowered = jax.jit(spec.build(config)).lower(*args)
            with spans.phase("repro.eval.compile", seconds, "xla_compile_s",
                             config=trial):
                fn = lowered.compile()
            with spans.phase("repro.eval.first_call", seconds,
                             "first_call_s", config=trial):
                with device_lock():
                    out = jax.block_until_ready(fn(*args))
        except Exception as e:  # noqa: BLE001 — any build/compile error = failed config
            raise CompileError(f"{type(e).__name__}: {e}") from e
        compile_s = (seconds["lower_s"] + seconds["xla_compile_s"]
                     + seconds["first_call_s"])
        kernel = _CompiledKernel(fn=fn, fixture=fixture, out=out,
                                 compile_s=compile_s)
        return CompiledArtifact(
            kind=self.name,
            fingerprint=spec_fingerprint(spec.name, spec.meta, config,
                                         extra=f"seed={self.seed}"),
            profile="", payload=kernel,
            stats=dict(seconds, compile_s=compile_s,
                       inputs_reused=int(reused)),
            compile_s=compile_s, persistable=False)

    def measure(self, spec: KernelSpec, config: Config,
                prepared=None,
                prune_threshold_s: Optional[float] = None) -> Measurement:
        if prepared is None:
            prepared = self.prepare(spec, config)
        if isinstance(prepared, Measurement):   # prepare already failed
            return prepared
        if isinstance(prepared, CompiledArtifact):
            prepared = prepared.payload         # legacy _CompiledKernel passes as-is
        with device_lock():
            return self._measure_locked(spec, config, prepared,
                                        prune_threshold_s)

    def _measure_locked(self, spec: KernelSpec, config: Config,
                        prepared: _CompiledKernel,
                        prune_threshold_s: Optional[float]) -> Measurement:
        fn, args, out = prepared.fn, prepared.fixture.args, prepared.out
        compile_s = prepared.compile_s
        trial = spans.config_arg(config)
        seconds: Dict[str, float] = {}

        verified: Optional[bool] = None
        if self.verify_outputs and spec.reference is not None:
            try:
                with spans.phase("repro.eval.verify", seconds, "verify_s",
                                 config=trial):
                    verify.assert_trees_close(out,
                                              prepared.fixture.reference(),
                                              atol=self.atol, rtol=self.rtol)
                verified = True
            except Exception as e:  # verification failure => config is invalid
                raise VerificationFailure(
                    f"verification failed: {e}") from e

        try:
            with spans.phase("repro.eval.timing", seconds, "timing_s",
                             config=trial):
                for _ in range(max(0, self.warmup - 1)):
                    jax.block_until_ready(fn(*args))

                def _sample() -> float:
                    t0 = time.perf_counter()
                    jax.block_until_ready(fn(*args))
                    return time.perf_counter() - t0

                samples, pruned = median_prune_loop(
                    _sample, self.repeats,
                    prune_threshold_s=prune_threshold_s, min_samples=2)
            t = float(np.median(samples))
        except Exception as e:  # noqa: BLE001
            raise MeasureError(f"{type(e).__name__}: {e}") from e
        detail = {"min_s": float(np.min(samples)),
                  "max_s": float(np.max(samples)),
                  "samples": float(len(samples)), **seconds}
        if pruned:
            detail["pruned"] = True
        return Measurement(time_s=t, ok=True, verified=verified,
                           compile_s=compile_s, detail=detail,
                           metrics=Metrics(samples=tuple(samples),
                                           compile_s=compile_s))


class CostModelEvaluator(Evaluator):
    """Roofline time from the compiled artifact (no execution).

    time = max(flops / peak, bytes / hbm_bw) + weighted_collective_bytes /
    (ici_links * ici_bw), per chip.  ``chips`` divides flops/bytes when the
    candidate function is a *global* (multi-device) computation lowered on a
    mesh; for single-kernel tuning chips=1.

    ``prepare`` lowers the kernel, content-addresses the lowered module
    (:func:`repro.core.hlo.fingerprint`) and — when an ``artifact_store``
    is attached — answers from the persistent store instead of compiling:
    the expensive ``compile()`` + ``cost_analysis()`` half is skipped and
    the returned :class:`CompiledArtifact` carries ``provenance="store"``
    with ``compile_s=0``.  On a miss it compiles under the store's
    per-artifact cross-process lock (fleet-wide at-most-once) and
    persists the JSON cost payload keyed by (fingerprint,
    ``profile.name``).  Failed compiles raise CompileError and are never
    persisted.  ``measure`` prices the payload against the profile; a
    store-hit payload prices identically to a fresh one.
    """

    name = "costmodel"

    def __init__(self, profile: DeviceProfile = TPU_V5E, chips: int = 1,
                 include_collectives: bool = True):
        self.profile = profile
        self.chips = chips
        self.include_collectives = include_collectives

    @property
    def _artifact_kind(self) -> str:
        # include_collectives changes the payload we extract, so the two
        # variants must not share content addresses
        return self.name if self.include_collectives else f"{self.name}-nocoll"

    def prepare(self, spec: KernelSpec, config: Config) -> CompiledArtifact:
        """Lower, fingerprint, then compile-or-fetch (the parallel phase)."""
        if spec.arg_specs is None:
            raise CompileError("CostModelEvaluator requires spec.arg_specs")
        try:
            t0 = time.perf_counter()
            fn = spec.build(config)
            lowered = jax.jit(fn).lower(*spec.arg_specs())
            fp = fingerprint(lowered)
        except Exception as e:  # noqa: BLE001
            raise CompileError(f"{type(e).__name__}: {e}") from e

        def _compile() -> CompiledArtifact:
            try:
                compiled = lowered.compile()
                cost = compiled.cost_analysis() or {}
            except Exception as e:  # noqa: BLE001
                raise CompileError(f"{type(e).__name__}: {e}") from e
            coll = 0.0
            if self.include_collectives:
                try:
                    coll = collective_stats(compiled.as_text()).weighted_bytes
                except Exception:   # text unavailable on some backends
                    coll = 0.0
            compile_s = time.perf_counter() - t0
            payload = {"flops": float(cost.get("flops", 0.0)),
                       "bytes": float(cost.get("bytes accessed", 0.0)),
                       "collective_bytes": float(coll),
                       "compile_s": compile_s}
            return CompiledArtifact(
                kind=self._artifact_kind, fingerprint=fp,
                profile=self.profile.name, payload=payload,
                stats=dict(payload), compile_s=compile_s, persistable=True)

        if self.artifact_store is not None:
            return self.artifact_store.get_or_compute(
                self._artifact_kind, fp, self.profile.name, _compile)
        return _compile()

    def measure(self, spec: KernelSpec, config: Config,
                prepared=None,
                prune_threshold_s: Optional[float] = None) -> Measurement:
        if prepared is None:
            prepared = self.prepare(spec, config)
        if isinstance(prepared, Measurement):
            return prepared
        if isinstance(prepared, CompiledArtifact):
            compile_s = prepared.compile_s
            prepared = prepared.payload
        else:   # legacy bare cost dict from direct prepare() callers
            compile_s = float(prepared.get("compile_s", 0.0))
        flops, bytes_ = prepared["flops"], prepared["bytes"]
        coll = prepared["collective_bytes"]
        p = self.profile
        compute_t = flops / (self.chips * p.peak_flops)
        memory_t = bytes_ / (self.chips * p.hbm_bw)
        coll_t = coll / (self.chips * p.ici_links * p.ici_bw)
        t = max(compute_t, memory_t) + coll_t + p.launch_overhead
        return Measurement(
            time_s=t, ok=True, compile_s=compile_s,
            detail={"flops": flops, "bytes": bytes_,
                    "collective_bytes": coll,
                    "compute_t": compute_t, "memory_t": memory_t,
                    "collective_t": coll_t},
            metrics=Metrics(samples=(t,), compile_s=compile_s, work=flops))

    def analyze(self, spec: KernelSpec, config: Config) -> Measurement:
        return self._evaluate(spec, config)


class TPUAnalyticalEvaluator(Evaluator):
    """Structural TPU pipeline model + seeded measurement noise.

    The kernel supplies ``analytical_model(config, profile) -> seconds``
    (math.inf for configurations that exceed VMEM or are otherwise
    infeasible on the profile).  We multiply by log-normal noise whose seed
    is derived from the configuration, so repeated evaluation of the same
    point is deterministic — matching how a real timing distribution has a
    per-configuration systematic component plus jitter.

    There is no compile phase: ``prepare`` is the base payload-free
    :class:`CompiledArtifact` (``provenance="none"``), ``measure`` prices
    the model directly and ignores the artifact.  Nothing reaches the
    persistent store — there is nothing worth amortizing.
    """

    name = "analytical"

    def __init__(self, profile: DeviceProfile = TPU_V5E,
                 noise_sigma: float = 0.03, seed: int = 0,
                 repeats: int = 5):
        self.profile = profile
        self.noise_sigma = noise_sigma
        self.seed = seed
        self.repeats = max(1, repeats)

    def _noise_rng(self, config: Config) -> np.random.Generator:
        h = hash((self.seed,) + tuple(sorted(
            (k, str(v)) for k, v in config.items()))) & 0xFFFFFFFF
        return np.random.default_rng(h)

    def _noise(self, config: Config) -> float:
        if self.noise_sigma <= 0:
            return 1.0
        rng = self._noise_rng(config)
        return float(np.exp(rng.normal(0.0, self.noise_sigma)))

    def _noise_samples(self, config: Config, n: int) -> List[float]:
        """n deterministic noise factors; the first is byte-identical to
        :meth:`_noise` (same rng construction, first draw) so the scalar
        ``time_s`` is unchanged by the metrics extension."""
        if self.noise_sigma <= 0:
            return [1.0] * n
        rng = self._noise_rng(config)
        return [float(np.exp(rng.normal(0.0, self.noise_sigma)))
                for _ in range(n)]

    def measure(self, spec: KernelSpec, config: Config,
                prepared=None,
                prune_threshold_s: Optional[float] = None) -> Measurement:
        if spec.analytical_model is None:
            raise CompileError(
                "TPUAnalyticalEvaluator requires spec.analytical_model")
        try:
            t = float(spec.analytical_model(config, self.profile))
        except Exception as e:  # noqa: BLE001
            raise MeasureError(f"{type(e).__name__}: {e}") from e
        if not math.isfinite(t):
            raise InfeasibleConfigError("analytically infeasible (VMEM/limits)")
        noise = self._noise_samples(config, self.repeats)
        samples = tuple(t * n for n in noise)
        return Measurement(time_s=samples[0], ok=True,
                           detail={"model_time_s": t},
                           metrics=Metrics(samples=samples))


class ArrivalTraceEvaluator(Evaluator):
    """Price one configuration against a modeled **arrival trace**.

    SLO tuning measures a config against the traffic *distribution*, not
    one fixed geometry: the sample vector has one entry per traced
    arrival shape (times seeded log-normal jitter), so a p99 objective
    over these metrics is literally "the tail of the modeled trace".
    The first traced shape is the bucket's full (padded) geometry; a
    config must be feasible there, or the whole config raises
    :class:`InfeasibleConfigError`.  A *ragged* arrival the config
    cannot cover (e.g. a block size that does not divide that arrival's
    shape) is not infeasible — serving pads such a request up to the
    bucket bound, so the sample for that arrival is the full-geometry
    cost.  Configs with finer tiles therefore win on ragged tails
    exactly as they do in the real padded serve path.

    ``model(shape, config, profile) -> seconds`` matches the signature of
    a :class:`~repro.core.registry.TunableKernel`'s ``analytical_model``,
    so a kernel's registered model plugs in directly.  ``time_s`` stays
    the median of the trace (the legacy scalar contract); tail objectives
    read the full vector through ``Measurement.metrics``.
    """

    name = "trace"

    def __init__(self, model: Callable[[Dict[str, Any], Config, DeviceProfile],
                                       float],
                 trace, profile: DeviceProfile = TPU_V5E,
                 noise_sigma: float = 0.03, seed: int = 0):
        if not trace:
            raise ValueError("ArrivalTraceEvaluator requires a non-empty trace")
        self.model = model
        self.trace = tuple(dict(s) for s in trace)
        self.profile = profile
        self.noise_sigma = noise_sigma
        self.seed = seed

    def _noise(self, config: Config, index: int) -> float:
        if self.noise_sigma <= 0:
            return 1.0
        # stable digest, NOT hash(): str hashing is per-process randomized
        # and a retune winner must reproduce across processes/hosts
        text = repr((self.seed, index) + tuple(sorted(
            (k, str(v)) for k, v in config.items())))
        h = int.from_bytes(hashlib.sha256(text.encode()).digest()[:4], "big")
        rng = np.random.default_rng(h)
        return float(np.exp(rng.normal(0.0, self.noise_sigma)))

    def measure(self, spec: KernelSpec, config: Config,
                prepared=None,
                prune_threshold_s: Optional[float] = None) -> Measurement:
        samples: List[float] = []
        padded = 0
        full_t: Optional[float] = None
        for i, shape in enumerate(self.trace):
            try:
                t = float(self.model(shape, config, self.profile))
            except Exception as e:  # noqa: BLE001
                raise MeasureError(f"{type(e).__name__}: {e}") from e
            if not math.isfinite(t):
                if full_t is None:
                    # the bucket's own geometry (trace[0]) must work
                    raise InfeasibleConfigError(
                        f"infeasible at bucket geometry {shape!r}")
                # ragged arrival the tiles can't cover: serving pads it
                # up to the bucket bound, so it costs the full geometry
                t = full_t
                padded += 1
            if full_t is None:
                full_t = t
            samples.append(t * self._noise(config, i))
        return Measurement(
            time_s=float(np.median(samples)), ok=True,
            detail={"trace_len": float(len(samples)),
                    "padded_arrivals": float(padded),
                    "min_s": float(np.min(samples)),
                    "max_s": float(np.max(samples))},
            metrics=Metrics(samples=tuple(samples)))


def make_evaluator(name: str, **kwargs) -> Evaluator:
    table = {
        "wallclock": WallClockEvaluator,
        "costmodel": CostModelEvaluator,
        "analytical": TPUAnalyticalEvaluator,
        "trace": ArrivalTraceEvaluator,
    }
    try:
        return table[name](**kwargs)
    except KeyError as e:
        raise KeyError(f"unknown evaluator {name!r}; known: {sorted(table)}") from e
