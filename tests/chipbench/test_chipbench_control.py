"""The comparison that decides ``correct`` fails what it must: the control
(the plain reference at bf16x3 in the program's place) and, with the
harness driving a whole run, a timed path broken underneath."""

from __future__ import annotations

import json

import jax
import jax.numpy as jnp
import pytest

from chipbench_testlib import TINY_SHAPES, run_main, tiny_root

from chipbench import cells, control

CONFIGS = sorted(TINY_SHAPES)


@pytest.fixture(scope="module")
def root(tmp_path_factory):
    return tiny_root(tmp_path_factory.mktemp("tiny"))


@pytest.mark.parametrize("config", CONFIGS)
@pytest.mark.parametrize("seed", [1, 2, 3])
def test_control_fails_the_limit(root, config, seed):
    from chipbench import drive

    data, reference = cells.load_config(config, root)
    for shape in (data["shape"], drive.op_shape(data)):
        assert control.control_reading(data, reference, seed, shape) \
            > data["max_rel_err_limit"]


@pytest.mark.parametrize("config", CONFIGS)
def test_program_passes_the_limit(root, config):
    data, reference = cells.load_config(config, root)
    fns = control.program_functions(data, space_sample=2)
    readings = control.program_readings(data, reference, fns, seed=5)
    assert len(readings) == 3
    assert all(value <= data["max_rel_err_limit"] for _, value in readings)


def _unwritten(y):          # the output left as it was allocated
    return jnp.zeros_like(y)


def _half_left_out(y):      # the second half of the rows never computed
    return y.at[y.shape[0] // 2:].set(0)


def _answer_altered(y):     # one answer changed where it is produced
    i = jnp.argmax(jnp.abs(y))
    return y.reshape(-1).at[i].multiply(-1).reshape(y.shape)


FAULTS = [_unwritten, _half_left_out, _answer_altered]


def _broken(fn, fault):
    return lambda *xs, **kw: fault(fn(*xs, **kw))


@pytest.mark.parametrize("fault", FAULTS, ids=lambda f: f.__name__)
@pytest.mark.parametrize("workload", ["gemm-2048-f32.apply",
                                      "flash-4096-causal-f32.apply"])
def test_broken_public_op_is_not_correct(monkeypatch, root, workload, fault):
    from chipbench import drive

    real = drive._import_op
    monkeypatch.setattr(drive, "_import_op",
                        lambda spec: _broken(real(spec), fault))
    code, _, result = run_main(monkeypatch, root, workload, seconds=0.5)
    assert code == 0
    assert result["correct"] is False
    assert result["compared"]["max_rel_err"]["value"] > 0.1


@pytest.mark.parametrize("fault", FAULTS, ids=lambda f: f.__name__)
def test_broken_kernel_under_search_is_not_correct(monkeypatch, tmp_path,
                                                   fault):
    """The program's own verification off, so that the broken kernel wins
    the search and only the harness's check stands between it and a
    correct run."""
    from repro.core.registry import TunableKernel

    root = tiny_root(tmp_path)
    traffic = root / "chipbench/traffic/tune.json"
    mix = json.loads(traffic.read_text())
    mix["evaluator"]["verify_outputs"] = False
    traffic.write_text(json.dumps(mix))
    real = TunableKernel.builder
    monkeypatch.setattr(TunableKernel, "builder",
                        lambda self, *a, **k: _broken(real(self, *a, **k),
                                                      fault))
    code, _, result = run_main(monkeypatch, root, "gemm-2048-f32.tune",
                               seconds=1.0)
    assert code == 0 and result["attempted"] > 0
    assert result["correct"] is False
    assert result["compared"]["max_rel_err"]["value"] > 0.1


def test_rel_err_of_nan_is_inf():
    from chipbench import drive

    ref = jnp.ones((4, 4))
    assert drive.rel_err(ref.at[1, 1].set(jnp.nan), ref) == float("inf")
    assert drive.rel_err(ref, ref) == 0.0
    assert drive.rel_err(jax.numpy.zeros((4, 4)), ref) == 1.0
