"""repro.core — the paper's contribution: a generic auto-tuner.

Public API surface (the CLTune analogue):

    from repro.core import Tuner, Parameter, SearchSpace
    from repro.core import WallClockEvaluator, CostModelEvaluator, \
        TPUAnalyticalEvaluator
    from repro.core import make_strategy, TPU_V5E
"""

from .artifacts import (ARTIFACT_FORMAT_VERSION, ArtifactStore,
                        CompiledArtifact, StoreStats, default_store,
                        resolve_store, spec_fingerprint)
from .cache import (CacheEntry, TuningCache, default_cache, shape_distance,
                    split_key)
from .engine import EngineConfig, EngineStats, EvaluationEngine
from .envknobs import env_bool, env_int, env_str, parse_bool
from .evaluators import (ArrivalTraceEvaluator, CostModelEvaluator,
                         Evaluator, KernelSpec, Measurement,
                         TPUAnalyticalEvaluator, WallClockEvaluator,
                         make_evaluator, median_prune_loop)
from .failures import (CompileError, EvaluationError, EvaluationTimeout,
                       FailureRecord, InfeasibleConfigError, MeasureError,
                       RetryPolicy, TransientError, VerificationFailure,
                       summarize_failures)
from .hlo import (CollectiveStats, canonicalize_hlo, collective_stats,
                  count_ops, fingerprint, fusion_stats)
from .metrics import (DEFAULT_OBJECTIVE, Metrics, Objective,
                      default_objective)
from .predict import (PREDICTOR_KINDS, CostModelPredictor,
                      HeuristicPredictor, LearnedPredictor, Predictor,
                      TransferPredictor, make_predictor, resolve_predictor,
                      train_from_cache, training_fingerprint)
from .profiles import (PROFILES, TPU_V3, TPU_V4, TPU_V5E, TPU_V5P,
                       DeviceProfile, get_profile)
from .registry import (REGISTRY, AutotunePolicy, KernelRegistry, Resolution,
                       TunableKernel, default_policy, lookup, lookup_resolved,
                       resolve, transfer_config, tunable)
from .space import Config, Constraint, Parameter, SearchSpace
from .strategies import (AskTellDriver, Evolutionary, FullSearch,
                         GreedyCoordinateDescent, ParticleSwarm,
                         RandomSearch, SearchResult, SimulatedAnnealing,
                         Strategy, Trial,
                         available_strategies, make_strategy,
                         project_feasible, register_strategy, usable_seeds)
from .tuner import Tuner, TuningOutcome
from .verify import VerificationError, assert_trees_close, trees_close

__all__ = [
    "ARTIFACT_FORMAT_VERSION", "ArtifactStore", "CompiledArtifact",
    "StoreStats", "default_store", "resolve_store", "spec_fingerprint",
    "CacheEntry", "TuningCache", "default_cache", "shape_distance",
    "split_key",
    "EngineConfig", "EngineStats", "EvaluationEngine",
    "env_bool", "env_int", "env_str", "parse_bool",
    "ArrivalTraceEvaluator", "CostModelEvaluator", "Evaluator", "KernelSpec",
    "Measurement", "TPUAnalyticalEvaluator", "WallClockEvaluator",
    "make_evaluator", "median_prune_loop",
    "DEFAULT_OBJECTIVE", "Metrics", "Objective", "default_objective",
    "PREDICTOR_KINDS", "CostModelPredictor", "HeuristicPredictor",
    "LearnedPredictor", "Predictor", "TransferPredictor", "make_predictor",
    "resolve_predictor", "train_from_cache", "training_fingerprint",
    "CompileError", "EvaluationError", "EvaluationTimeout", "FailureRecord",
    "InfeasibleConfigError", "MeasureError", "RetryPolicy", "TransientError",
    "VerificationFailure", "summarize_failures",
    "CollectiveStats", "canonicalize_hlo", "collective_stats", "count_ops",
    "fingerprint", "fusion_stats",
    "PROFILES", "TPU_V3", "TPU_V4", "TPU_V5E", "TPU_V5P",
    "DeviceProfile", "get_profile",
    "REGISTRY", "AutotunePolicy", "KernelRegistry", "Resolution",
    "TunableKernel", "default_policy", "lookup", "lookup_resolved",
    "resolve", "transfer_config", "tunable",
    "Config", "Constraint", "Parameter", "SearchSpace",
    "AskTellDriver", "Evolutionary", "FullSearch",
    "GreedyCoordinateDescent", "ParticleSwarm", "RandomSearch",
    "SearchResult", "SimulatedAnnealing",
    "Strategy", "Trial",
    "available_strategies", "make_strategy", "project_feasible",
    "register_strategy", "usable_seeds",
    "Tuner", "TuningOutcome",
    "VerificationError", "assert_trees_close", "trees_close",
]
