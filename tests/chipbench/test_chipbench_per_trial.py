"""The tune cells' phase metrics: each reads the program's counter of one
phase per trial, prints on a traced run, and reads nothing from a program
that keeps no such counter."""

from __future__ import annotations

import pytest

from chipbench_testlib import run_main, tiny_root

from chipbench import cells, drive

#: metric -> the EngineStats field it reads
PHASES = {"tune.args_ms_per_trial": "args_s",
          "tune.lower_ms_per_trial": "lower_s",
          "tune.compile_ms_per_trial": "xla_compile_s",
          "tune.verify_ms_per_trial": "verify_s",
          "tune.timing_ms_per_trial": "timing_s"}


def _run(engine):
    return drive.Run(work=None, attempted=0, failed=0, memory_peak_bytes=0,
                     metrics={}, engine=engine)


@pytest.mark.parametrize("metric", sorted(PHASES))
def test_phase_metric_reads_its_counter_per_trial(metric):
    counter = PHASES[metric]
    for workload in ("gemm-2048-f32.tune", "flash-4096-causal-f32.tune"):
        cell = cells.load_cell(workload)
        [spec] = [m for m in cell.per_layer if m["name"] == metric]
        read = cell.reader(spec)
        assert read(_run({"unique_configs": 4, counter: 0.5,
                          "measure_total_s": 9.0})) == 125.0
        # a program without the counter, and a window without a trial
        assert read(_run({"unique_configs": 4, "measure_total_s": 9.0})) is None
        assert read(_run({"unique_configs": 0, counter: 0.0})) is None


def test_traced_tune_line_splits_compile_and_measure(monkeypatch, tmp_path):
    root = tiny_root(tmp_path)
    code, lines, result = run_main(monkeypatch, root, "gemm-2048-f32.tune",
                                   seconds=1.5, trace=1)
    assert code == 0 and result["correct"] is True
    metrics = result["metrics"]
    assert set(PHASES) <= set(metrics)
    assert all(metrics[m]["value"] > 0 and metrics[m]["unit"] == "ms"
               for m in PHASES)
    measured = sum(metrics[m]["value"] for m in ("tune.verify_ms_per_trial",
                                                 "tune.timing_ms_per_trial"))
    assert 0.9 * metrics["tune.measure_ms_per_trial"]["value"] <= measured
    assert measured <= metrics["tune.measure_ms_per_trial"]["value"] + 1e-3
