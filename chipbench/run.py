"""Runs one cell of the on-chip benchmark once.

    python chipbench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

The cell, its configuration, traffic mix and metrics come from
``BENCHMARK.json`` at the root of the checkout.  The run refuses to measure
anywhere but on a TPU with as many chips as the cell asks for.  It sets up
(inputs from ``--seed``, compiles of the cell's own shape, JAX's persistent
compilation cache in ``$JAX_COMPILATION_CACHE_DIR``, else
``<checkout>/.jax_cache``), measures for ``--seconds``, then checks what the
window produced against the configuration's plain reference.

Standard output: lines that name the device, then one JSON object: with
``--trace 0`` the cell's end-to-end metrics, with ``--trace 1`` its
per-layer metrics from a profiler trace of the window.  Standard error ends
with each number compared and its limit.
"""

import time

T_START = time.perf_counter()   # set-up is timed from here

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

ROOT = Path(__file__).resolve().parents[1]
for _path in (ROOT / "src", ROOT):
    if str(_path) not in sys.path:
        sys.path.insert(0, str(_path))

from chipbench import cells  # noqa: E402


def require_devices(chips: int):
    """The first ``chips`` TPU devices; exits without a result otherwise."""
    import jax

    devices = jax.devices()
    if devices[0].platform != "tpu" or len(devices) < chips:
        raise SystemExit(
            f"chipbench measures on a TPU only: JAX offers {len(devices)} "
            f"{devices[0].platform} device(s), the cell needs {chips} TPU "
            f"chip(s)")
    return devices[:chips]


def configure_compile_cache(root: Path) -> str:
    """JAX's persistent compilation cache: ``$JAX_COMPILATION_CACHE_DIR``,
    else ``<checkout>/.jax_cache``, a fixed path; every compile is kept."""
    import jax

    path = os.environ.get("JAX_COMPILATION_CACHE_DIR") or str(root / ".jax_cache")
    jax.config.update("jax_compilation_cache_dir", path)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    return path


def parse_args(argv):
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    return ap.parse_args(argv)


def result_line(cell, run, device: dict, trace: bool) -> dict:
    """The last line of standard output, by the benchmark's contract."""
    metrics = {}
    specs = cell.per_layer if trace else cell.end_to_end
    for spec in specs:
        value = (cell.reader(spec)(run) if trace
                 else run.metrics.get(spec["name"]))
        if value is not None:
            metrics[spec["name"]] = {"value": value, "unit": spec["unit"]}
    line = {"correct": run.correct, "attempted": run.attempted,
            "failed": run.failed, "metrics": metrics, "device": device}
    if trace:
        device.update(busy_s=run.trace.busy_s, window_s=run.trace.window_s)
        line["breakdown"] = {"device_ops": run.trace.device_ops,
                             "idle_gaps": run.trace.idle_gaps}
    line["compared"] = run.compared
    return line


def main(argv=None, root: Path = ROOT) -> int:
    args = parse_args(argv)
    cell = cells.load_cell(args.workload, root)
    devices = require_devices(cell.chips)
    cache_dir = configure_compile_cache(root)

    from chipbench import drive, peaks

    dev = devices[0]
    tag = f"[{dev.platform} {dev.device_kind} x{len(devices)}]"

    def say(msg: str) -> None:
        print(f"{tag} {msg}", flush=True)

    say(f"cell {cell.name}: config {cell.config_name}, traffic "
        f"{cell.traffic_name}, seed {args.seed}, {args.seconds} s, "
        f"trace {args.trace}; devices ready {time.perf_counter() - T_START:.1f} "
        f"s into set-up, compile cache {cache_dir}")
    run = drive.run(cell, devices, args.seed, args.seconds, bool(args.trace),
                    say, cache_dir, setup_started=T_START)
    run.peaks = peaks.for_kind(dev.device_kind) if dev.platform == "tpu" \
        else None
    device = {"platform": dev.platform, "kind": dev.device_kind,
              "count": len(devices),
              "memory_peak_bytes": run.memory_peak_bytes}
    line = result_line(cell, run, device, bool(args.trace))
    for name, c in run.compared.items():
        print(f"{tag} compared {name} = {c['value']!r} (limit {c['limit']!r})",
              file=sys.stderr, flush=True)
    say(f"correct={run.correct} attempted={run.attempted} failed={run.failed}")
    print(json.dumps(line), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
