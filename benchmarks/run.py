"""Benchmark driver: one section per paper table/figure.

Each section collects structured :class:`benchmarks.common.Record` rows
(the ``name,us_per_call,derived`` CSV stream is still printed for humans)
and the driver writes one machine-readable ``BENCH_<section>.json`` per
section plus a combined ``BENCH_all.json`` under ``REPRO_BENCH_OUT``
(default ``experiments/bench``).  These are the artifacts CI uploads and
``benchmarks/compare.py`` diffs against the committed baseline.

A section fails when its function raises *or* when any of its emitted
records carries ``status="error"`` — per-record status is propagated, not
inferred from stdout.  Any failed section makes the driver exit 1.

Env knobs:
  REPRO_BENCH_RUNS   statistical runs per strategy (paper: 128; default 16)
  REPRO_BENCH_ONLY   comma-separated subset
                     (conv,gemm,roofline,wallclock,engine,transfer,online,
                      dtune,artifacts,slo,predict,analyze)
  REPRO_BENCH_OUT    output directory for BENCH_*.json
"""

from __future__ import annotations

import json
import os
import sys
import time
import traceback
from typing import Any, Callable, Dict

from repro.core.envknobs import configure_compile_cache

from . import common


def run_section(name: str, fn: Callable[[], Any]) -> Dict[str, Any]:
    """Run one section, collecting records + status into a JSON payload."""
    common.begin_section()
    t0 = time.perf_counter()
    status, error = "ok", None
    try:
        fn()
    except Exception as e:  # noqa: BLE001 — a section must not kill the run
        traceback.print_exc()
        status, error = "error", f"{type(e).__name__}: {e}"
    records = common.end_section()
    bad = [r for r in records if r.status != "ok"]
    if bad and status == "ok":
        status = "error"
        error = f"{len(bad)} error record(s): {', '.join(r.name for r in bad[:5])}"
    return {
        "schema_version": common.SCHEMA_VERSION,
        "section": name,
        "status": status,
        "error": error,
        "runs": common.RUNS,
        "wall_s": round(time.perf_counter() - t0, 3),
        "records": [r.to_json() for r in records],
    }


def write_payload(name: str, payload: Dict[str, Any]) -> str:
    os.makedirs(common.OUT_DIR, exist_ok=True)
    path = os.path.join(common.OUT_DIR, f"BENCH_{name}.json")
    with open(path, "w") as f:
        json.dump(payload, f, indent=2, default=str)
    return path


def main() -> None:
    configure_compile_cache()
    only = os.environ.get("REPRO_BENCH_ONLY", "")
    wanted = set(only.split(",")) if only else None
    from . import (bench_analyze, bench_artifacts, bench_conv, bench_dtune,
                   bench_engine, bench_gemm, bench_online, bench_predict,
                   bench_roofline, bench_slo, bench_transfer,
                   bench_wallclock)
    table = {
        "conv": bench_conv.main,          # paper §V: Figs 4/5/6, Tables II/III
        "gemm": bench_gemm.main,          # paper §VI: Fig 7, Table IV, Fig 9
        "roofline": bench_roofline.main,  # assignment §Roofline (dry-run)
        "wallclock": bench_wallclock.main,
        "engine": bench_engine.main,      # EvaluationEngine: dedup/prune/overlap
        "transfer": bench_transfer.main,  # nearest-shape reuse + warm start
        "online": bench_online.main,      # background retune + config hot-swap
        "dtune": bench_dtune.main,        # sharded workers + fleet cache merge
        "artifacts": bench_artifacts.main,  # compile-artifact store hit rate
        "slo": bench_slo.main,            # bucketed p99 vs worst-case padding
        "predict": bench_predict.main,    # learned surrogate vs warm start
        "analyze": bench_analyze.main,    # static proofs: prune + registry lint
    }
    print("name,us_per_call,derived")
    sections: Dict[str, Dict[str, Any]] = {}
    failed = []
    for name, fn in table.items():
        if wanted and name not in wanted:
            continue
        payload = run_section(name, fn)
        sections[name] = payload
        path = write_payload(name, payload)
        ok = payload["status"] == "ok"
        print(f"section/{name},{payload['wall_s'] * 1e6:.0f},"
              f"{payload['status']}"
              + ("" if ok else f":{payload['error']}"))
        if not ok:
            failed.append(name)
        sys.stdout.flush()
    combined = {"schema_version": common.SCHEMA_VERSION,
                "runs": common.RUNS, "sections": sections}
    path = write_payload("all", combined)
    print(f"# wrote {path} (+ {len(sections)} per-section files)")
    if failed:
        print(f"# FAILED sections: {', '.join(failed)}", file=sys.stderr)
        sys.exit(1)


if __name__ == "__main__":
    main()
