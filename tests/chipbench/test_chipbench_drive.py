"""Each traffic mix end to end at a tiny shape, through the harness's own
code, on the CPU with the Pallas kernels in interpret mode; and the
harness's refusal to measure anywhere but on a TPU."""

from __future__ import annotations

import os
import re
import subprocess
import sys

import pytest

from chipbench_testlib import ROOT, run_main, tiny_root

RESULT_KEYS = ["correct", "attempted", "failed", "metrics", "device",
               "compared"]


@pytest.fixture(scope="module")
def root(tmp_path_factory):
    return tiny_root(tmp_path_factory.mktemp("tiny"))


@pytest.mark.parametrize("workload, metric", [
    ("gemm-2048-f32.apply", "kernel_us"),
    ("flash-4096-causal-f32.apply", "kernel_us"),
    ("gemm-2048-f32.tune", "tune_trials_per_s"),
    ("flash-4096-causal-f32.tune", "tune_trials_per_s"),
])
def test_mix_runs_end_to_end(monkeypatch, root, workload, metric):
    code, lines, result = run_main(monkeypatch, root, workload,
                                   seed=2**31 + 12345, seconds=1.5)
    assert code == 0
    assert list(result) == RESULT_KEYS
    assert result["correct"] is True
    assert result["attempted"] > 0 and result["failed"] == 0
    assert set(result["metrics"]) == {metric, "setup_s"}
    assert all(m["value"] > 0 for m in result["metrics"].values())
    assert result["device"] == {"platform": "cpu", "kind": "cpu", "count": 1,
                                "memory_peak_bytes": 0}
    compared = result["compared"]["max_rel_err"]
    assert 0 <= compared["value"] <= compared["limit"]
    assert all(line.startswith("[cpu cpu x1] ") for line in lines[:-1])


def test_tune_prints_the_search_path(monkeypatch, root):
    _, lines, result = run_main(monkeypatch, root, "gemm-2048-f32.tune",
                                seconds=1.5)
    text = "\n".join(lines)
    assert "search 0 (seed 2147484504):" in text and "EngineStats" in text
    assert "winner re-timed" in text
    assert "0 cache hits in the window" in text


def test_tune_counts_its_own_trials(monkeypatch, root):
    """The harness counts a search's trials from the evaluator's calls; on
    a search with no failures and no pruning they are the engine's
    distinct configurations."""
    _, lines, result = run_main(monkeypatch, root, "gemm-2048-f32.tune",
                                seconds=1.5)
    searches = [re.search(r"(\d+) trials counted.*'unique_configs': (\d+)",
                          line) for line in lines if " trials counted" in line]
    assert searches and all(searches)
    assert all(m.group(1) == m.group(2) for m in searches)
    assert result["attempted"] == sum(int(m.group(1)) for m in searches) > 0


def test_traced_apply_line(monkeypatch, root):
    """A --trace 1 line on the CPU: per-layer metrics only, none of them the
    device's (the CPU trace has no TPU plane), and the traced window."""
    code, _, result = run_main(monkeypatch, root, "gemm-2048-f32.apply",
                               seconds=1.0, trace=1)
    assert code == 0 and result["correct"] is True
    assert list(result) == RESULT_KEYS[:5] + ["breakdown", "compared"]
    assert set(result["device"]) >= {"busy_s", "window_s"}
    assert result["device"]["window_s"] > 0.5
    assert set(result["metrics"]) <= {"apply_mfu", "kernel_device_us",
                                      "kernel_roofline",
                                      "device_idle_share.apply"}
    assert "kernel_us" not in result["metrics"]
    assert set(result["breakdown"]) == {"device_ops", "idle_gaps"}


def test_traced_tune_line(monkeypatch, root):
    code, _, result = run_main(monkeypatch, root,
                               "flash-4096-causal-f32.tune", seconds=1.5,
                               trace=1)
    assert code == 0 and result["correct"] is True
    metrics = result["metrics"]
    assert set(metrics) >= {"tune.compile_wait_share",
                            "tune.measure_ms_per_trial", "tune.best_kernel_us"}
    assert 0 < metrics["tune.compile_wait_share"]["value"] <= 100
    assert metrics["tune.compile_wait_share"]["unit"] == "%"


def test_run_refuses_without_a_tpu(tmp_path):
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               JAX_COMPILATION_CACHE_DIR=str(tmp_path / "cache"))
    proc = subprocess.run(
        [sys.executable, "chipbench/run.py", "--workload",
         "gemm-2048-f32.apply", "--seed", "1", "--seconds", "1",
         "--trace", "0"],
        cwd=ROOT, env=env, capture_output=True, text=True, timeout=120)
    assert proc.returncode != 0
    assert "measures on a TPU only" in proc.stderr
    assert not any(line.lstrip().startswith("{")
                   for line in proc.stdout.splitlines())
