"""Serving launcher: batched greedy decoding with continuous batching.

``python -m repro.launch.serve --arch granite-3-2b --requests 8``
"""

from __future__ import annotations

import argparse
import time

import jax
import numpy as np

from repro.configs import get_config
from repro.core.envknobs import configure_compile_cache
from repro.core.evaluators import on_tpu
from repro.core.profiles import TPU_V5E, attached_profile
from repro.models.model import init_model
from repro.serve import Request, ServeEngine


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="granite-3-2b")
    ap.add_argument("--full", action="store_true")
    ap.add_argument("--requests", type=int, default=8)
    ap.add_argument("--slots", type=int, default=4)
    ap.add_argument("--max-new-tokens", type=int, default=16)
    ap.add_argument("--max-len", type=int, default=256)
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args()

    configure_compile_cache()
    # on a TPU, the attached chip's profile (an unknown chip raises); a
    # host run resolves kernel configs for the v5e target
    profile = attached_profile() if on_tpu() else TPU_V5E
    cfg = get_config(args.arch, smoke=not args.full)
    params = init_model(cfg, jax.random.PRNGKey(args.seed))
    engine = ServeEngine(cfg, params, slots=args.slots,
                         max_len=args.max_len, profile=profile)
    rng = np.random.default_rng(args.seed)
    for rid in range(args.requests):
        prompt = rng.integers(1, cfg.vocab_size,
                              size=int(rng.integers(4, 12))).tolist()
        engine.submit(Request(rid=rid, prompt=prompt,
                              max_new_tokens=args.max_new_tokens))
    t0 = time.perf_counter()
    done = engine.run()
    dt = time.perf_counter() - t0
    total_tokens = sum(len(r.output) for r in done)
    print(f"served {len(done)} requests, {total_tokens} tokens "
          f"in {dt:.2f}s ({total_tokens / dt:.1f} tok/s)")
    for r in done[:3]:
        print(f"  req {r.rid}: prompt={r.prompt[:6]}... -> "
              f"output={r.output[:8]}...")


if __name__ == "__main__":
    main()
