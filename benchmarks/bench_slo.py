"""SLO-serving section: shape-bucketed tail latency vs worst-case padding.

A single :class:`ServeEngine` must provision its geometry for the
largest request it may ever see, so *every* decode step — including the
short requests that dominate real traffic — pays attention over the
worst-case KV cache.  :class:`BucketedServeEngine` admits each request
into the smallest tuned bucket it fits, so short traffic decodes against
short caches.  This section measures that claim and the objective
machinery behind it, on the granite smoke model:

* ``bucketed_p99`` / ``single_p99`` — per-step wall-clock p99 over a
  short-dominated ragged workload.  The single engine runs the same
  requests at the worst-case bound (the largest bucket); the bucketed
  engine's p99 must beat it (record turns ``error`` otherwise, and both
  rows carry ``p99_us`` so ``compare.py --p99-threshold`` gates tail
  growth against the committed baseline).
* ``bucket_admission`` — a mixed workload routes each request to the
  smallest fitting bucket; oversized requests are rejected at admission
  (``failures`` carries ``misrouted``/``silently_truncated``).
* ``p99_retune_winner`` — the shared BackgroundTuner retunes a bucket's
  kernels under ``objective="p99_time"`` over the modeled arrival trace;
  the winner must land under the objective-scoped cache key (invisible
  to a default-objective lookup) and be deterministic across two
  independent engines.
"""

from __future__ import annotations

import os
import tempfile
import time
from typing import List, Optional, Tuple

import jax
import numpy as np

from repro.configs import get_config
from repro.core import TuningCache
from repro.models.model import init_model
from repro.serve import BucketedServeEngine, JobStatus, Request, ServeEngine

from .common import RUNS, emit

SLOTS = 4
SMALL, BIG = 16, 256            # bucket bounds; BIG is the worst-case bound
PROMPT, NEW_TOKENS = 4, 8       # short request: needs 12 positions <= SMALL


def _short_requests(cfg, n: int, seed: int) -> List[Request]:
    rng = np.random.default_rng(seed)
    return [Request(rid=seed * 1000 + i,
                    prompt=rng.integers(1, cfg.vocab_size, PROMPT).tolist(),
                    max_new_tokens=NEW_TOKENS)
            for i in range(n)]


def _timed_run(engine, requests) -> Tuple[int, List[float]]:
    """Serve ``requests``; return (finished, per-step wall seconds).

    Requests go in waves of SLOTS, one batch each: slots share one decode
    position, and refilling mid-batch would run the SMALL bucket past its
    KV rows."""
    finished, durs = 0, []
    for i in range(0, len(requests), SLOTS):
        for r in requests[i:i + SLOTS]:
            engine.submit(r)
        stamps: List[float] = []
        done = engine.run(
            on_step=lambda e, s: stamps.append(time.perf_counter()))
        stamps.append(time.perf_counter())
        durs += [b - a for a, b in zip(stamps, stamps[1:])]
        finished += sum(1 for r in done if r.done)
    return finished, durs


def _p99_us(durs: List[float]) -> float:
    return float(np.percentile(np.asarray(durs, dtype=np.float64), 99) * 1e6)


def main() -> None:
    cfg = get_config("granite-3-2b", smoke=True)
    params = init_model(cfg, jax.random.PRNGKey(0))
    tmpdir = tempfile.mkdtemp(prefix="repro-bench-slo-")
    cache = TuningCache(os.path.join(tmpdir, "slo_cache.json"))
    n_short = SLOTS * min(max(RUNS, 2), 16)

    # -- tail latency: short-dominated traffic, worst-case vs bucketed ----
    # The single engine is provisioned for BIG (it must be able to admit
    # the largest request); the bucketed engine routes the same short
    # traffic into the SMALL bucket, so each of its steps attends over a
    # 16-position KV cache instead of a 256-position one.
    single = ServeEngine(cfg, params, slots=SLOTS, max_len=BIG, cache=cache,
                         online_tune=False)
    bucketed = BucketedServeEngine(cfg, params, buckets=(SMALL, BIG),
                                   slots=SLOTS, cache=cache,
                                   online_tune=False)
    # warm-up: first step per engine compiles the jitted decode step
    _timed_run(single, _short_requests(cfg, SLOTS, seed=9))
    _timed_run(bucketed, _short_requests(cfg, SLOTS, seed=9))
    done_s, durs_s = _timed_run(single, _short_requests(cfg, n_short, seed=1))
    done_b, durs_b = _timed_run(bucketed, _short_requests(cfg, n_short,
                                                          seed=1))
    single.close()
    bucketed.close()
    p99_s, p99_b = _p99_us(durs_s), _p99_us(durs_b)
    served = (done_s == n_short and done_b == n_short)
    win = served and p99_b < p99_s
    emit("slo/bucketed_p99", p99_b,
         (f"bucketed p99 {p99_b:.0f}us vs single-geometry {p99_s:.0f}us "
          f"({p99_s / max(p99_b, 1e-9):.1f}x, {len(durs_b)} steps)"
          if win else
          f"bucketed p99 {p99_b:.0f}us did not beat single {p99_s:.0f}us "
          f"(served {done_b}/{n_short} and {done_s}/{n_short})"),
         status="ok" if win else "error",
         p99_us=p99_b, failures={"p99_losses": int(not win)})
    emit("slo/single_p99", p99_s,
         f"worst-case-provisioned engine, {len(durs_s)} steps at "
         f"max_len={BIG}",
         p99_us=p99_s)

    # -- admission: smallest fitting bucket, oversize rejected ------------
    with BucketedServeEngine(cfg, params, buckets=(SMALL, 64), slots=SLOTS,
                             cache=cache, online_tune=False) as adm:
        short = Request(rid=1, prompt=[5] * 4, max_new_tokens=8)    # 12
        mid = Request(rid=2, prompt=[5] * 20, max_new_tokens=30)    # 50
        huge = Request(rid=3, prompt=[5] * 60, max_new_tokens=30)   # 90
        routed = [adm.submit(short), adm.submit(mid), adm.submit(huge)]
        misrouted = int(routed != [SMALL, 64, None])
        rejected_ok = [r.rid for r in adm.rejected] == [3]
        truncated = int(not rejected_ok)
    emit("slo/bucket_admission", 0.0,
         (f"requests routed to buckets {routed[:2]}, oversize rejected"
          if not (misrouted or truncated) else
          f"admission broke: routed={routed}, "
          f"rejected={[r.rid for r in adm.rejected]}"),
         status="ok" if not (misrouted or truncated) else "error",
         failures={"misrouted": misrouted, "silently_truncated": truncated})

    # -- p99 retune: objective-scoped winner, deterministic ----------------
    def _retune_winner(seed_dir: str) -> Tuple[Optional[dict], bool, bool]:
        bcache = TuningCache(os.path.join(tmpdir, seed_dir, "cache.json"))
        with BucketedServeEngine(
                cfg, params, buckets=(128,), slots=SLOTS, cache=bcache,
                online_tune={"strategy": "full", "budget": 1_000_000}) as eng:
            eng.tuner.wait(timeout=300)
            jobs = [j for j in eng.tuner.jobs.values()
                    if j.kernel == "flash_attention"]
            job = jobs[0] if jobs else None
            if job is None or job.status is not JobStatus.DONE:
                return None, False, False
            scoped = bcache.get(job.kernel, job.key[1], job.profile,
                                objective="p99_time")
            default_view = bcache.get(job.kernel, job.key[1], job.profile)
            ok = (job.objective == "p99_time" and scoped is not None
                  and scoped.objective == "p99_time"
                  and scoped.config == job.config)
            return job.config, ok, default_view is None

    win_a, scoped_a, hidden_a = _retune_winner("retune-a")
    win_b, scoped_b, hidden_b = _retune_winner("retune-b")
    retune_ok = (win_a is not None and win_a == win_b
                 and scoped_a and scoped_b and hidden_a and hidden_b)
    emit("slo/p99_retune_winner", 0.0,
         (f"p99-objective winner {win_a} recorded under obj-scoped key, "
          f"invisible to default-objective lookup, identical across two "
          f"independent retunes"
          if retune_ok else
          f"p99 retune broke: winners {win_a} vs {win_b}, "
          f"scoped=({scoped_a},{scoped_b}) hidden=({hidden_a},{hidden_b})"),
         status="ok" if retune_ok else "error", config=win_a)


if __name__ == "__main__":
    main()
