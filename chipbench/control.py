"""Readings that a configuration's limit on ``max_rel_err`` is set from.

    python chipbench/control.py --config <name> --seeds 1 2 ... \\
        [--space-sample 24] [--control-seeds 1 2 3]

At the configuration's own shapes, on each seed:

- the program: ``max_rel_err`` of the public op at the heuristic config, at
  the ``op_shape`` the apply cells call it at, and of ``--space-sample``
  configurations drawn from the search space, built by the registry at the
  kernel's ``shape`` (the winners a tune cell can check);
- the control: the plain reference at ``bf16x3``, the nearest precision
  below the configuration's, put in the program's place at both shapes.

The lower reading is the largest program reading, the upper one the
smallest control reading; the limit lies between them.  Needs a TPU, as
``run.py`` does; the functions run anywhere.
"""

from __future__ import annotations

import argparse
import json
import os
import random
import sys
import tempfile
from pathlib import Path

ROOT = Path(__file__).resolve().parents[1]
for _path in (ROOT / "src", ROOT):
    if str(_path) not in sys.path:
        sys.path.insert(0, str(_path))

from chipbench import cells, drive  # noqa: E402


def control_reading(config, reference, seed: int, shape) -> float:
    """``max_rel_err`` of the reference at ``bf16x3`` in the program's place,
    at ``shape``."""
    import jax

    (xs,) = drive.make_inputs(reference, shape, seed, 1)
    out = jax.jit(lambda xs: reference.reference(shape, xs, "bf16x3"))(xs)
    compared = drive.check_outputs(config, reference, [(xs, out)], shape)
    return compared["max_rel_err"]["value"]


def program_functions(config, space_sample: int, sample_seed: int = 0):
    """(label, shape, jitted fn) for the public op at the heuristic config,
    at ``op_shape``, and for ``space_sample`` configurations drawn from the
    kernel's search space, at ``shape``."""
    import jax

    from repro.core.cache import TuningCache
    from repro.core.registry import lookup, resolve

    shape = config["shape"]
    interpret = jax.default_backend() != "tpu"
    kernel = resolve(config["kernel"])
    op = drive._import_op(config["op"])
    with tempfile.TemporaryDirectory() as tmp:
        heuristic = lookup(kernel, shape, policy="off", cache=TuningCache(
            os.path.join(tmp, "tuned_configs.json")))
    fns = [("public op, heuristic", drive.op_shape(config), jax.jit(
        lambda *xs: op(*xs, config=heuristic, interpret=interpret,
                       **config["op_kwargs"])))]
    space = list(kernel.make_space(shape))
    for cfg in random.Random(sample_seed).sample(space, min(space_sample,
                                                            len(space))):
        fns.append((json.dumps(cfg, sort_keys=True), shape,
                    jax.jit(kernel.builder(shape, cfg, interpret=interpret))))
    return fns


def program_readings(config, reference, fns, seed: int):
    """``max_rel_err`` of each program function on ``seed``'s inputs."""
    inputs = {}
    readings = []
    for label, shape, fn in fns:
        key = json.dumps(shape, sort_keys=True)
        if key not in inputs:
            inputs[key] = drive.make_inputs(reference, shape, seed, 1)[0]
        xs = inputs[key]
        compared = drive.check_outputs(config, reference, [(xs, fn(*xs))],
                                       shape)
        readings.append((label, compared["max_rel_err"]["value"]))
    return readings


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--config", required=True)
    ap.add_argument("--seeds", type=int, nargs="+", required=True)
    ap.add_argument("--control-seeds", type=int, nargs="*", default=[])
    ap.add_argument("--space-sample", type=int, default=0)
    args = ap.parse_args(argv)

    from chipbench.run import require_devices

    dev = require_devices(1)[0]
    tag = f"[{dev.platform} {dev.device_kind} x1]"
    config, reference = cells.load_config(args.config)
    fns = program_functions(config, args.space_sample)
    lower, upper = 0.0, float("inf")
    for seed in args.seeds:
        readings = program_readings(config, reference, fns, seed)
        label, worst = max(readings, key=lambda r: r[1])
        lower = max(lower, worst)
        print(f"{tag} program seed {seed}: heuristic {readings[0][1]!r}, "
              f"worst of {len(readings)} {worst!r} ({label})", flush=True)
    shapes = [config["shape"]]
    if drive.op_shape(config) != config["shape"]:
        shapes.append(drive.op_shape(config))
    for seed in args.control_seeds:
        values = [control_reading(config, reference, seed, shape)
                  for shape in shapes]
        upper = min([upper] + values)
        print(f"{tag} control seed {seed}: bf16x3 {values!r} (shape, "
              f"op_shape)", flush=True)
    print(json.dumps({"config": args.config, "lower": lower, "upper": upper,
                      "limit": config["max_rel_err_limit"]}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
