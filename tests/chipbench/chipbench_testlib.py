"""Helpers for the chipbench tests: a copy of the benchmark at tiny shapes,
and runs of ``chipbench/run.py``'s ``main`` on the host's CPU.

The harness refuses to measure off a TPU; these tests step past that check
by replacing ``run.require_devices`` and drive the rest of a run, with the
program's Pallas kernels in interpret mode.
"""

from __future__ import annotations

import contextlib
import io
import json
import shutil
import sys
from pathlib import Path

ROOT = Path(__file__).resolve().parents[2]
for _path in (ROOT / "src", ROOT):
    if str(_path) not in sys.path:
        sys.path.insert(0, str(_path))

_TINY_FLASH = {"Sq": 256, "Sk": 256, "D": 128, "causal": True,
               "dtype": "float32"}

#: shapes small enough for the Pallas interpreter, by configuration name:
#: the kernel's ``shape`` and, where the configuration has one, the
#: ``op_shape`` the public op is called at
TINY_SHAPES = {
    "gemm-2048-f32": {"shape": {"M": 256, "N": 256, "K": 256,
                                "dtype": "float32"}},
    "flash-4096-causal-f32": {"shape": _TINY_FLASH,
                              "op_shape": dict(_TINY_FLASH, H=2, KV=1)},
}

_JAX_FLAGS = ("jax_compilation_cache_dir", "jax_enable_compilation_cache",
              "jax_persistent_cache_min_compile_time_secs",
              "jax_persistent_cache_min_entry_size_bytes")


def tiny_root(tmp_path: Path) -> Path:
    """A checkout-like directory: ``BENCHMARK.json`` and ``chipbench/`` with
    every configuration cut to its tiny shape."""
    root = tmp_path / "checkout"
    root.mkdir()
    shutil.copy(ROOT / "BENCHMARK.json", root / "BENCHMARK.json")
    shutil.copytree(ROOT / "chipbench", root / "chipbench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    bench = json.loads((root / "BENCHMARK.json").read_text())
    for entry in bench["configs"]:
        path = root / entry["file"]
        config = json.loads(path.read_text())
        config.update(TINY_SHAPES[entry["name"]])
        path.write_text(json.dumps(config))
    return root


@contextlib.contextmanager
def restored_jax_config():
    """Put back the JAX settings a run changes, for the tests that follow."""
    import jax
    from jax.experimental.compilation_cache import compilation_cache

    saved = {flag: getattr(jax.config, flag) for flag in _JAX_FLAGS}
    try:
        yield
    finally:
        for flag, value in saved.items():
            jax.config.update(flag, value)
        compilation_cache.reset_cache()


def run_main(monkeypatch, root: Path, workload: str, *, seed: int = 7,
             seconds: float = 1.0, trace: int = 0):
    """``run.main`` on the CPU; returns (exit code, stdout lines, result)."""
    import jax

    from chipbench import run

    monkeypatch.setattr(run, "require_devices",
                        lambda chips: jax.devices()[:chips])
    out = io.StringIO()
    with restored_jax_config(), contextlib.redirect_stdout(out):
        code = run.main(["--workload", workload, "--seed", str(seed),
                         "--seconds", str(seconds), "--trace", str(trace)],
                        root=root)
    lines = out.getvalue().splitlines()
    return code, lines, json.loads(lines[-1])
