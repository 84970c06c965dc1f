"""Static-analyzer section: proven-infeasible pruning + registry hygiene.

CLTune (§III-A) folds device limits into the search space as
auto-generated constraints so provably-invalid configurations are never
compiled.  :mod:`repro.analyze` is that idea as a *static proof*: the
declared ``vmem_footprint`` is evaluated against the device budget
before any compile, and configs it proves over-budget are answered
``inf`` without touching the toolchain.  Two records:

* ``proven_prune`` — the same seeded random search as PR 9's
  ``predict/prune_infeasible`` (extended GEMM space, ``2048^3``,
  TPU_V3's 16 MiB VMEM cliff, budget 96), but with the engine's
  ``proven_checker`` instead of a learned predictor.  The engine is
  driven directly so device feasibility stays the checker's call, not a
  space constraint.  Gates: ``proven_pruned > 0``, compiles saved at
  least match the predictor's 5-of-96 on this trace, and the winner is
  *identical* to the unpruned search (a proof, unlike a prediction,
  carries no survivor hedge — so winner identity must hold exactly).
* ``analyze_clean_registry`` — the ``python -m repro.analyze --strict``
  gate, run in this process over the kernels the package ships (earlier
  bench sections register scratch kernels into this process's registry,
  so those are left out), must find zero errors and zero warnings.  It
  runs in-process because a child interpreter could not reach a chip
  this process already holds.
"""

from __future__ import annotations

import dataclasses

from repro.analyze import analyze_registry, proven_checker
from repro.core import (EngineConfig, EvaluationEngine, KernelSpec,
                        TPUAnalyticalEvaluator, make_strategy)
from repro.core.profiles import TPU_V3
from repro.core.registry import REGISTRY
from repro.kernels.matmul.ops import GEMM
from repro.tune import sharding_autotune  # noqa: F401 — registers sharding_cell

from .common import emit

PRUNE_SHAPE = {"M": 2048, "N": 2048, "K": 2048, "dtype": "float32"}
BUDGET = 96
#: compiles the learned predictor saved on this exact trace (PR 9's
#: ``predict/prune_infeasible`` record) — the static proof must do at
#: least as well, with zero model to train
PREDICTOR_SAVED = 5


def main() -> None:
    # -- proven-infeasible pruning on the TPU_V3 VMEM cliff ----------------
    space = GEMM.make_space(PRUNE_SHAPE, extended=True)
    spec = KernelSpec(
        name="gemm_proven", build=lambda cfg: (lambda: None),
        analytical_model=lambda cfg, prof: GEMM.analytical_model(
            PRUNE_SHAPE, cfg, prof),
        meta=dict(PRUNE_SHAPE))
    evaluator = TPUAnalyticalEvaluator(noise_sigma=0.0, profile=TPU_V3)

    def _run(proven: bool):
        cfg = EngineConfig(workers=4)
        if proven:
            cfg = dataclasses.replace(
                cfg, proven_checker=proven_checker(GEMM, PRUNE_SHAPE,
                                                   TPU_V3))
        eng = EvaluationEngine(evaluator, spec, space, cfg)
        res = eng.run(make_strategy("random"), budget=BUDGET, seed=7)
        return res, res.extra["engine"]

    base_res, base_s = _run(False)
    prov_res, prov_s = _run(True)
    saved = base_s["compile_calls"] - prov_s["compile_calls"]
    ok = (prov_s["proven_pruned"] > 0
          and saved >= PREDICTOR_SAVED
          and prov_res.best_config == base_res.best_config
          and prov_res.best_time == base_res.best_time)
    emit("analyze/proven_prune", prov_res.best_time * 1e6,
         (f"proven_pruned={prov_s['proven_pruned']} compiles "
          f"{base_s['compile_calls']}->{prov_s['compile_calls']} "
          f"(saved {saved}, predictor saved {PREDICTOR_SAVED}), "
          f"winner identical"
          if ok else
          f"proven gate broken: pruned={prov_s['proven_pruned']} "
          f"saved={saved} (need >= {PREDICTOR_SAVED}) winner_match="
          f"{prov_res.best_config == base_res.best_config}"),
         status="ok" if ok else "error",
         config=prov_res.best_config,
         compiles=prov_s["compile_calls"],
         engine=prov_s)

    # -- registry hygiene: the --strict CI gate over the shipped kernels --
    shipped = [n for n in REGISTRY.names()
               if REGISTRY.get(n).build.__module__.startswith("repro.")]
    report = analyze_registry(kernels=shipped)
    counts = report.counts()
    clean = report.exit_code(strict=True) == 0
    emit("analyze/analyze_clean_registry", 0.0,
         (f"shipped registry clean under --strict: "
          f"{counts['info']} info advisories, 0 errors, 0 warnings"
          if clean else f"strict gate failed: counts={counts}"),
         status="ok" if clean else "error")


if __name__ == "__main__":
    main()
