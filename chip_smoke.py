"""Smoke run of the tuner's main path on one TPU chip.

    python chip_smoke.py [--out DIR]

One process, phases in order; any failed phase exits non-zero:

1. device  — require a TPU and resolve its DeviceProfile from device_kind.
2. kernels — for gemm, flash_attention and conv2d at their registry
   default shapes: compile the heuristic config for the chip (the compiled
   HLO must hold the Pallas kernel), check it against the float32
   reference, then run a short search through ``tune_kernel`` whose
   trials are timed on the device.  Winners go to a fresh cache file
   under ``--out``.
3. serve   — granite-3-2b at its published widths (40 layers, d_model
   2048) with random weights, through ``ServeEngine``: one wave of 4
   requests on 4 slots, each answered with 16 in-vocabulary tokens.

Times printed on the way are informational.  The last line of standard
output is one JSON object naming the device.
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import time
from pathlib import Path

ROOT = Path(__file__).resolve().parent
sys.path.insert(0, str(ROOT / "src"))

KERNELS = ("gemm", "flash_attention", "conv2d")
TUNE_BUDGET = 8
SEED = 0                 # weights, prompts, kernel inputs and the search
SERVE_ARCH = "granite-3-2b"
SLOTS, MAX_LEN, PROMPT_LEN, NEW_TOKENS = 4, 256, 8, 16


def check(cond: bool, msg: str) -> None:
    if not cond:
        raise RuntimeError(msg)


def cache_entries(path: str) -> int:
    return len(os.listdir(path)) if os.path.isdir(path) else 0


def device_phase():
    import jax

    from repro.core.profiles import attached_profile

    profile = attached_profile()       # raises off a TPU, naming the platform
    dev = jax.devices()[0]
    print(f"device: platform={dev.platform} kind={dev.device_kind!r} "
          f"count={len(jax.devices())} -> profile {profile.name}", flush=True)
    return dev, profile


def kernel_phase(name, profile, cache):
    import jax
    import numpy as np

    from repro.core import verify
    from repro.core.registry import resolve
    from repro.tune import tune_kernel

    k = resolve(name)
    shape = dict(k.default_shapes[0])
    cfg = k.heuristic(shape)
    fn = jax.jit(k.builder(shape, cfg, interpret=False))
    args = k.make_args(shape, np.random.default_rng(SEED))
    t0 = time.perf_counter()
    hlo = fn.lower(*args).compile().as_text()
    compile_s = time.perf_counter() - t0
    check("tpu_custom_call" in hlo,
          f"{name}: no tpu_custom_call in the compiled HLO")
    out = jax.block_until_ready(fn(*args))
    ref = k.reference(shape)(*args)
    err = float(np.max(np.abs(np.asarray(out, np.float64)
                              - np.asarray(ref, np.float64))))
    scale = float(np.max(np.abs(np.asarray(ref, np.float64))))
    print(f"{name} {shape}: heuristic {cfg} compiled in {compile_s:.2f} s "
          f"(tpu_custom_call present); max|out-ref|={err:.3e} "
          f"max|ref|={scale:.3e}", flush=True)
    verify.assert_trees_close(out, ref)
    del out, ref, args

    t0 = time.perf_counter()
    outcome = tune_kernel(k, shape, budget=TUNE_BUDGET, profile=profile,
                          cache=cache, interpret=False, seed=SEED,
                          extended_space=False, record=True)
    wall_s = time.perf_counter() - t0
    trials = outcome.result.trials
    summary = outcome.failure_summary
    print(f"{name}: tuned by {outcome.evaluator!r} in {wall_s:.1f} s "
          f"(informational): {len(trials)} trials, "
          f"{summary['failed_trials']} failed {dict(summary['by_type'])}; "
          f"winner {outcome.best_config} median "
          f"{outcome.best_time * 1e6:.1f} us", flush=True)
    check(outcome.evaluator == "wallclock",
          f"{name}: tuned by {outcome.evaluator!r}, not timed on the device")
    check(outcome.best_config is not None,
          f"{name}: no configuration ran: {dict(summary['by_type'])}")
    check(cache.get(k.name, k.key_for(shape), profile.name) is not None,
          f"{name}: winner not recorded in {cache.path}")


def serve_phase(profile, cache):
    import jax
    import numpy as np

    from repro.configs import get_config
    from repro.models.model import init_model
    from repro.serve import Request, ServeEngine

    cfg = get_config(SERVE_ARCH, smoke=False)
    t0 = time.perf_counter()
    params = jax.block_until_ready(init_model(cfg, jax.random.PRNGKey(SEED)))
    print(f"serve: {cfg.name} {cfg.num_layers} layers d_model={cfg.d_model} "
          f"vocab={cfg.vocab_size}, random weights in "
          f"{time.perf_counter() - t0:.1f} s", flush=True)
    engine = ServeEngine(cfg, params, slots=SLOTS, max_len=MAX_LEN,
                         profile=profile, cache=cache, online_tune=False)
    rng = np.random.default_rng(SEED)
    for rid in range(SLOTS):
        prompt = rng.integers(0, cfg.vocab_size, PROMPT_LEN).tolist()
        engine.submit(Request(rid=rid, prompt=prompt,
                              max_new_tokens=NEW_TOKENS))
    stamps = []
    t0 = time.perf_counter()
    done = engine.run(on_step=lambda eng, step: stamps.append(
        time.perf_counter()))
    wall_s = time.perf_counter() - t0
    engine.close()
    first_s = stamps[1] - stamps[0] if len(stamps) > 1 else wall_s
    steady_s = wall_s - (stamps[1] - t0 if len(stamps) > 1 else wall_s)
    tokens = sum(len(r.output) for r in done)
    print(f"serve: {len(done)} requests, {tokens} tokens in {len(stamps)} "
          f"steps, {wall_s:.2f} s (informational): first step (compile + "
          f"run) {first_s:.2f} s, then "
          f"{tokens / steady_s if steady_s > 0 else float('nan'):.1f} "
          f"tokens/s", flush=True)
    check(len(done) == SLOTS, f"serve: {len(done)} of {SLOTS} answered")
    for r in done:
        check(r.done and len(r.output) == NEW_TOKENS,
              f"serve: request {r.rid} done={r.done} "
              f"with {len(r.output)} tokens")
        check(all(0 <= t < cfg.vocab_size for t in r.output),
              f"serve: request {r.rid} has out-of-vocabulary tokens")
    print(f"serve: request 0 -> {done[0].output}", flush=True)


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--out", default=str(ROOT / "experiments" / "chip_smoke"),
                    help="directory for the run's tuned-config cache")
    args = ap.parse_args()

    from repro.core.envknobs import configure_compile_cache

    cache_dir = configure_compile_cache()
    before = cache_entries(cache_dir)
    t_start = time.perf_counter()
    dev, profile = device_phase()

    from repro.core.cache import TuningCache

    out = Path(args.out)
    out.mkdir(parents=True, exist_ok=True)
    cache_path = out / "tuned_configs.json"
    cache_path.unlink(missing_ok=True)         # fresh: this run's winners only
    cache = TuningCache(str(cache_path))
    for name in KERNELS:
        kernel_phase(name, profile, cache)
    serve_phase(profile, cache)

    import jax

    print(f"compile cache {cache_dir}: {before} entries before, "
          f"{cache_entries(cache_dir)} after; total "
          f"{time.perf_counter() - t_start:.1f} s (informational)",
          flush=True)
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(jax.devices())}}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
