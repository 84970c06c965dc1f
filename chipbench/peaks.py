"""Published peaks of the chips the benchmark runs on, keyed by ``device_kind``.

Source: Google Cloud documentation, "TPU v5e" (system architecture page):
197 TFLOP/s in bf16, 393 TOP/s in int8, 16 GB of HBM at 819 GB/s per chip.
No float32 peak is published, so every share of a peak here is a share of
the bf16 peak: a float32 kernel can never reach it, and its share reads
low by however many MXU passes its precision costs.

A ``device_kind`` that is not in the table is an error, not a default.
"""

from __future__ import annotations

from typing import Dict, NamedTuple


class Peaks(NamedTuple):
    flops: float    # dense matmul operations per second (bf16)
    hbm_bw: float   # bytes per second
    hbm_bytes: int


PEAKS: Dict[str, Peaks] = {
    "TPU v5 lite": Peaks(flops=197e12, hbm_bw=819e9, hbm_bytes=16 * 10**9),
}


def for_kind(device_kind: str) -> Peaks:
    try:
        return PEAKS[device_kind]
    except KeyError as e:
        raise KeyError(f"no peaks for device_kind {device_kind!r}; "
                       f"known: {sorted(PEAKS)}") from e
