"""Work counts, the peaks table and the roofline arithmetic, on known
shapes."""

from __future__ import annotations

import types

import pytest

from chipbench_testlib import ROOT

from chipbench import cells, peaks, work

V5E = peaks.for_kind("TPU v5 lite")
GEMM = cells.load_module(ROOT / "chipbench/references/gemm.py")
FLASH = cells.load_module(ROOT / "chipbench/references/flash_attention.py")


def test_peaks_of_v5e_and_unknown_kind():
    assert V5E.flops == 197e12 and V5E.hbm_bw == 819e9
    with pytest.raises(KeyError, match="no peaks for device_kind"):
        peaks.for_kind("TPU v9 imaginary")


def test_gemm_2048_work_and_roofline():
    shape = {"M": 2048, "N": 2048, "K": 2048, "dtype": "float32"}
    w = work.of(GEMM, shape)
    assert w.flops == 2 * 2048**3
    assert w.bytes == 3 * 2048 * 2048 * 4
    seconds, bound = work.roofline_s(w, V5E)
    assert bound == "compute"
    assert seconds == pytest.approx(2 * 2048**3 / 197e12)        # 87.2 us
    assert work.roofline_pct(w, V5E, 1.3e-3) == pytest.approx(6.708, rel=1e-3)
    assert work.flops_pct(w, V5E, 1.3e-3) == pytest.approx(6.708, rel=1e-3)


@pytest.mark.parametrize("causal, factor", [(True, 0.5), (False, 1.0)])
def test_flash_4096_work(causal, factor):
    shape = {"Sq": 4096, "Sk": 4096, "D": 128, "causal": causal,
             "dtype": "float32"}
    w = work.of(FLASH, shape)
    assert w.flops == 4 * 4096 * 4096 * 128 * factor
    assert w.bytes == 4 * 4096 * 128 * 4
    seconds, bound = work.roofline_s(w, V5E)
    assert bound == "compute"
    assert seconds == pytest.approx(w.flops / 197e12)


def test_flash_work_counts_every_query_head():
    """granite-34b's 48 query heads on one key/value head: the operations
    grow with the query heads, K and V are read once."""
    shape = {"H": 48, "KV": 1, "Sq": 4096, "Sk": 4096, "D": 128,
             "causal": True, "dtype": "float32"}
    w = work.of(FLASH, shape)
    assert w.flops == 48 * 0.5 * 4 * 4096 * 4096 * 128
    assert w.bytes == (2 * 48 + 2 * 1) * 4096 * 128 * 4
    assert work.roofline_s(w, V5E) == (pytest.approx(w.flops / 197e12),
                                       "compute")


def test_flash_reference_over_heads_is_head_by_head():
    import jax
    import numpy as np

    one = {"Sq": 64, "Sk": 64, "D": 128, "causal": True, "dtype": "float32"}
    heads = dict(one, H=4, KV=2)
    q, k, v = FLASH.make_inputs(heads, jax.random.key(3))
    assert q.shape == (4, 64, 128) and k.shape == v.shape == (4, 64, 128)
    # query heads 0 and 1 share key/value head 0, heads 2 and 3 head 1
    np.testing.assert_array_equal(k[0], k[1])
    np.testing.assert_array_equal(v[2], v[3])
    assert not np.array_equal(k[1], k[2])
    out = FLASH.reference(heads, (q, k, v))
    for h in range(4):
        np.testing.assert_allclose(
            out[h], FLASH.reference(one, (q[h], k[h], v[h])), rtol=1e-6)


def test_memory_bound_shape():
    shape = {"M": 8, "N": 8192, "K": 8192, "dtype": "float32"}
    w = work.of(GEMM, shape)
    seconds, bound = work.roofline_s(w, V5E)
    assert bound == "memory"
    assert seconds == pytest.approx(w.bytes / 819e9)


def test_kernel_roofline_reads_the_trace_per_call():
    w = work.of(GEMM, {"M": 2048, "N": 2048, "K": 2048, "dtype": "float32"})
    trace = types.SimpleNamespace(kernel_s=10 * 1.2e-3, kernel_events=10)
    run = types.SimpleNamespace(peaks=V5E, trace=trace, attempted=10, work=w)
    assert work.kernel_roofline_pct(run) == pytest.approx(
        100 * w.flops / 197e12 / 1.2e-3)
    assert work.kernel_roofline_pct(types.SimpleNamespace(
        peaks=None, trace=trace, attempted=10, work=w)) is None
    trace.kernel_events = 0
    assert work.kernel_roofline_pct(run) is None
