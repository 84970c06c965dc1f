"""BENCHMARK.json and the files it names: everything loads by name, from
data, and a new cell is new files plus entries, with no code edited."""

from __future__ import annotations

import json
import re

import pytest

from chipbench_testlib import ROOT

from chipbench import cells

BENCH = json.loads((ROOT / "BENCHMARK.json").read_text())
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")
WORKLOADS = [w["name"] for w in BENCH["workloads"]]


def test_benchmark_has_exactly_the_contract_keys():
    assert set(BENCH) == {"command", "paths", "run_seconds", "configs",
                          "workloads", "end_to_end", "per_layer"}
    assert BENCH["command"] == ["python3", "chipbench/run.py"]
    assert BENCH["paths"] == ["chipbench", "tests/chipbench"]
    assert 1 <= BENCH["run_seconds"] <= 51


def test_names_units_and_entries_are_well_formed():
    metrics = BENCH["end_to_end"] + BENCH["per_layer"]
    names = [e["name"] for e in BENCH["configs"] + BENCH["workloads"] + metrics]
    assert len(names) == len(set(names))
    assert all(NAME.match(n) for n in names)
    for m in metrics:
        assert re.match(r"^[A-Za-z0-9_/%.-]{1,16}$", m["unit"])
        assert m["better"] in ("lower", "higher")
    for m in BENCH["end_to_end"]:
        assert set(m) <= {"name", "unit", "better", "bound", "source",
                          "workloads"}
        assert m["source"] in ("host_clock", "device_trace")
        assert 0.01 <= m["bound"] <= 0.25
    for m in BENCH["per_layer"]:
        assert set(m) <= {"name", "unit", "better", "source", "layer",
                          "moves", "workloads"}
        assert m["moves"] in {e["name"] for e in BENCH["end_to_end"]}
        assert set(m.get("workloads", [])) <= set(WORKLOADS)


@pytest.mark.parametrize("workload", WORKLOADS)
def test_cell_loads_by_name(workload):
    cell = cells.load_cell(workload)
    assert cell.chips == 1
    assert cell.traffic["kind"] in ("apply", "tune")
    assert {m["name"] for m in cell.end_to_end} >= {"setup_s"}
    assert len(cell.end_to_end) >= 2 and cell.per_layer
    for metric in cell.per_layer:
        assert callable(cell.reader(metric))
    for name in ("make_inputs", "reference", "flops", "bytes_moved"):
        assert callable(getattr(cell.reference, name))


@pytest.mark.parametrize("metric", [m["name"] for m in BENCH["per_layer"]])
def test_each_per_layer_metric_is_reported_where_its_moves_metric_is(metric):
    spec = next(m for m in BENCH["per_layer"] if m["name"] == metric)
    for workload in spec["workloads"]:
        reported = {m["name"] for m in cells.load_cell(workload).end_to_end}
        assert spec["moves"] in reported


@pytest.mark.parametrize("config", [c["name"] for c in BENCH["configs"]])
def test_configuration_file_states_what_is_run(config):
    entry = next(c for c in BENCH["configs"] if c["name"] == config)
    data, reference = cells.load_config(config)
    assert entry["file"] == f"chipbench/configs/{config}.json"
    assert data["precision"] == "highest"
    assert 0 < data["max_rel_err_limit"] < 1e-4
    assert data["op"].startswith("repro.kernels.")
    assert reference.flops(data["shape"]) > 0


def test_a_new_cell_is_files_and_entries_only(tmp_path):
    """A throwaway configuration, traffic mix and metric, registered from a
    temporary directory, load through the unchanged code."""
    import shutil

    root = tmp_path / "checkout"
    shutil.copytree(ROOT / "chipbench", root / "chipbench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    bench = json.loads((ROOT / "BENCHMARK.json").read_text())
    config = json.loads((ROOT / "chipbench/configs/gemm-2048-f32.json").read_text())
    config.update(shape={"M": 512, "N": 1024, "K": 256, "dtype": "float32"},
                  reference="throwaway")
    (root / "chipbench/configs/gemm-throwaway.json").write_text(json.dumps(config))
    (root / "chipbench/references/throwaway.py").write_text(
        (ROOT / "chipbench/references/gemm.py").read_text())
    (root / "chipbench/traffic/steady.json").write_text(
        json.dumps({"kind": "apply", "input_sets": 2, "trace_seconds": 1.0}))
    (root / "chipbench/metrics/throwaway_calls.py").write_text(
        "def read(run):\n    return float(run.attempted)\n")
    bench["configs"].append({"name": "gemm-throwaway", "source": "x",
                             "file": "chipbench/configs/gemm-throwaway.json",
                             "reduced": [], "why": "test"})
    bench["workloads"].append({"name": "gemm-throwaway.steady",
                               "config": "gemm-throwaway",
                               "traffic": "steady", "chips": 1, "why": "test"})
    bench["end_to_end"][0]["workloads"].append("gemm-throwaway.steady")
    bench["per_layer"].append({"name": "throwaway_calls", "unit": "calls",
                               "better": "higher", "source": "host_clock",
                               "layer": "public op", "moves": "kernel_us",
                               "workloads": ["gemm-throwaway.steady"]})
    (root / "BENCHMARK.json").write_text(json.dumps(bench))

    cell = cells.load_cell("gemm-throwaway.steady", root)
    assert cell.config["shape"]["N"] == 1024
    assert cell.traffic == {"kind": "apply", "input_sets": 2,
                            "trace_seconds": 1.0}
    assert cell.reference.flops(cell.config["shape"]) == 2.0 * 512 * 1024 * 256
    assert [m["name"] for m in cell.end_to_end] == ["kernel_us", "setup_s"]
    assert [m["name"] for m in cell.per_layer] == ["throwaway_calls"]

    class Run:
        attempted = 5

    assert cell.reader(cell.per_layer[0])(Run()) == 5.0


def test_unknown_names_raise():
    with pytest.raises(KeyError, match="no workload named"):
        cells.load_cell("gemm-2048-f32.nothing")
    with pytest.raises(KeyError, match="no configuration named"):
        cells.load_config("nothing")
