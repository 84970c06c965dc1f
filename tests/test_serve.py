"""Serving engine: continuous batching, greedy decode consistency."""

import threading

import jax
import numpy as np
import pytest

from repro.configs import get_config
from repro.models.model import init_model
from repro.serve import Request, ServeEngine


@pytest.fixture(scope="module")
def setup():
    cfg = get_config("granite-3-2b", smoke=True)
    params = init_model(cfg, jax.random.PRNGKey(0))
    return cfg, params


def test_engine_completes_all_requests(setup):
    cfg, params = setup
    engine = ServeEngine(cfg, params, slots=2, max_len=128)
    rng = np.random.default_rng(0)
    reqs = [Request(rid=i,
                    prompt=rng.integers(1, cfg.vocab_size, 5).tolist(),
                    max_new_tokens=6)
            for i in range(5)]          # 5 requests > 2 slots: forces refill
    for r in reqs:
        engine.submit(r)
    done = engine.run()
    assert len(done) == 5
    for r in done:
        assert len(r.output) == 6
        assert all(0 <= t < cfg.vocab_size for t in r.output)


def test_engine_eos_stops_early(setup):
    cfg, params = setup
    engine = ServeEngine(cfg, params, slots=1, max_len=128)
    # every token is EOS -> stops after the first generated token
    engine.submit(Request(rid=0, prompt=[1, 2, 3], max_new_tokens=50,
                          eos_id=None))
    done = engine.run()
    assert done[0].done


def test_engine_rejects_embedding_models():
    cfg = get_config("musicgen-medium", smoke=True)
    with pytest.raises(ValueError):
        ServeEngine(cfg, params=None)


def test_engine_max_steps_returns_unfinished_flagged(setup, caplog):
    """Hitting max_steps must not silently drop in-flight/queued requests:
    they come back flagged done=False (with a logged truncation warning)
    and a subsequent run() resumes them."""
    cfg, params = setup
    engine = ServeEngine(cfg, params, slots=1, max_len=128)
    reqs = [Request(rid=i, prompt=[1, 2, 3], max_new_tokens=6)
            for i in range(2)]          # 2 requests, 1 slot: one stays queued
    for r in reqs:
        engine.submit(r)
    import logging
    with caplog.at_level(logging.WARNING, logger="repro.serve"):
        out = engine.run(max_steps=3)
    # every submitted request is accounted for, none silently dropped
    assert {r.rid for r in out} == {0, 1}
    assert not any(r.done for r in out)
    assert any("max_steps" in rec.message for rec in caplog.records)
    # the engine still holds them: a second run finishes the work
    done = engine.run()
    assert {r.rid for r in done} == {0, 1}
    assert all(r.done and len(r.output) == 6 for r in done)


def test_engine_raises_before_decode_position_passes_max_len(setup):
    """Slots share one decode position; a batch that would write past the
    last KV row raises instead of clamping the write onto that row."""
    cfg, params = setup
    engine = ServeEngine(cfg, params, slots=1, max_len=8)
    # 4 prompt + 5 new tokens = 8 steps: positions 0..7 fit exactly
    engine.submit(Request(rid=0, prompt=[1, 2, 3, 4], max_new_tokens=5))
    (done,) = engine.run()
    assert done.done and len(done.output) == 5
    # the slot is free again, so the next batch restarts at row 0
    engine.submit(Request(rid=1, prompt=[1, 2, 3, 4], max_new_tokens=5))
    assert engine.run()[0].done
    # 4 + 6 = 9 steps cannot fit 8 rows
    engine.submit(Request(rid=2, prompt=[1, 2, 3, 4], max_new_tokens=6))
    with pytest.raises(RuntimeError, match="max_len=8"):
        engine.run()


def _held_by_another_thread(lock) -> bool:
    got = []

    def probe():
        got.append(lock.acquire(blocking=False))
        if got[0]:
            lock.release()
    t = threading.Thread(target=probe)
    t.start()
    t.join()
    return not got[0]


@pytest.mark.parametrize("tpu", [False, True])
def test_decode_steps_hold_the_device_lock_on_tpu(setup, monkeypatch, tpu):
    """On a TPU a background retune times kernels on the chip; decode
    steps take the same lock, so neither's timing counts the other."""
    import repro.core.evaluators as evaluators_mod
    monkeypatch.setattr(evaluators_mod, "on_tpu", lambda: tpu)
    cfg, params = setup
    engine = ServeEngine(cfg, params, slots=1, max_len=16)
    held = []
    step = engine._step

    def probed_step(*args):
        held.append(_held_by_another_thread(evaluators_mod._DEVICE_LOCK))
        return step(*args)
    engine._step = probed_step
    engine.submit(Request(rid=0, prompt=[1, 2], max_new_tokens=2))
    (done,) = engine.run()
    assert done.done and len(held) == 3
    assert held == [tpu] * 3
