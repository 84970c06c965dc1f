"""The reduction from a profiler trace to device busy time, kernel time
and the breakdown: on hand-made events, and on a small trace recorded on a
TPU v5e (``data/``)."""

from __future__ import annotations

import pytest

from chipbench_testlib import ROOT

from chipbench import trace

MS = 1e6    # ns


def test_union_merges_overlaps_and_keeps_order():
    assert trace.union([(5, 6), (0, 2), (1, 3), (3, 4), (8, 9)]) == [
        (0, 4), (5, 6), (8, 9)]
    assert trace.union([(0, 10), (2, 3)]) == [(0, 10)]
    assert trace.union([]) == []


def _spans(window=(0, 10 * MS), calls=()):
    spans = [("chipbench.window", window[0], window[1] - window[0])]
    spans += [("chipbench.apply.call", s, d) for s, d in calls]
    return spans


KERNEL = "%k.1 = f32[8] custom-call(), custom_call_target=\"tpu_custom_call\""


def _op(text, start, dur):
    return ("jit_f/" + text.split(" = ")[0], text, start, dur)


def test_reduce_busy_kernel_and_gaps():
    ops = {"/device:TPU:0": [
        _op(KERNEL, 1 * MS, 2 * MS),
        _op("%copy.1 = f32[8] copy()", 2.5 * MS, 1 * MS),   # overlaps it
        _op(KERNEL, 6 * MS, 2 * MS),
        _op(KERNEL, 9.5 * MS, 1 * MS),    # ends after the host's window
    ]}
    spans = _spans(calls=[(0.5 * MS, 4 * MS), (5 * MS, 4 * MS)])
    s = trace.reduce(ops, spans, "tpu_custom_call")
    assert s.window_s == pytest.approx(0.010)
    # busy time is clipped to the host's window, as the idle gaps are
    assert s.busy_s == pytest.approx(0.0025 + 0.002 + 0.0005)
    assert s.kernel_events == 3
    assert s.kernel_s == pytest.approx(0.005)
    assert s.device_ops[0] == ["jit_f/%k.1", pytest.approx(0.005)]
    assert [name for name, _ in s.device_ops] == ["jit_f/%k.1",
                                                   "jit_f/%copy.1"]
    # idle: [0,1] [3.5,6] [8,9.5] ms; the middle one falls between calls
    assert [g[1] for g in s.idle_gaps] == pytest.approx([0.0025, 0.0015,
                                                         0.001])
    assert [g[0] for g in s.idle_gaps] == [
        "outside the harness's spans", "chipbench.apply.call",
        "chipbench.apply.call"]
    assert trace.idle_pct(s) == pytest.approx(50.0)


def test_busy_time_never_exceeds_the_window():
    """Device ops that start before the window or end after it count only
    inside it: a device busy throughout reads 0% idle, never below."""
    ops = {"/device:TPU:0": [_op(KERNEL, -2 * MS, 7 * MS),
                             _op(KERNEL, 5 * MS, 8 * MS)]}
    s = trace.reduce(ops, _spans(), "tpu_custom_call")
    assert s.busy_s == pytest.approx(s.window_s)
    assert s.idle_gaps == []
    assert trace.idle_pct(s) == pytest.approx(0.0)


def test_reduce_averages_busy_over_chips():
    ops = {"/device:TPU:0": [_op("%k = f32[8] add()", 0, 4 * MS)],
           "/device:TPU:1": [_op("%k = f32[8] add()", 0, 2 * MS)]}
    s = trace.reduce(ops, _spans(), None)
    assert s.busy_s == pytest.approx(0.003)
    assert s.kernel_events == 0 and s.kernel_s == 0


def test_reduce_needs_exactly_one_window():
    with pytest.raises(ValueError, match="0 'chipbench.window' spans"):
        trace.reduce({}, [], None)


def test_idle_pct_of_nothing_is_none():
    assert trace.idle_pct(None) is None
    empty = trace.reduce({}, _spans(), None)
    assert trace.idle_pct(empty) is None


@pytest.mark.parametrize("kernel, device_us", [("gemm", 546.0),
                                                ("flash_attention", 293.5)])
def test_recorded_v5e_trace(kernel, device_us):
    """Ten calls of a public op, each blocked on, traced on a TPU v5e with
    the harness's own spans (gemm 2048^3; flash attention over one head,
    4096 x 4096 x 128, causal)."""
    name = kernel.split("_")[0]
    path = ROOT / f"tests/chipbench/data/{name}_apply_v5e.xplane.pb"
    ops, spans = trace.load(str(path))
    assert list(ops) == ["/device:TPU:0"]
    assert [n for n, _, _ in spans].count("chipbench.apply.call") == 10
    s = trace.reduce(ops, spans, "tpu_custom_call")
    assert s.kernel_events == 10
    assert s.kernel_s / 10 * 1e6 == pytest.approx(device_us, rel=0.01)
    # the device's clock is mapped onto the host's to within about a
    # millisecond, so busy time clipped to the host's window may lose up to
    # that much of the kernels at its edges
    assert 0 <= s.kernel_s - s.busy_s < 1e-3
    assert 0 < s.busy_s < s.window_s
    assert s.device_ops == [[f"jit_chipbench_{kernel}/%chipbench_{kernel}.1",
                             pytest.approx(s.kernel_s)]]
    assert {name for name, _ in s.idle_gaps} <= {
        "chipbench.apply.call", "outside the harness's spans"}
