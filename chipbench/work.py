"""Work of one call and its share of the chip's roofline.

The operations and bytes of one call are counted from the shape alone, by
the configuration's reference module (``references/<name>.py``: its
``flops(shape)`` and ``bytes_moved(shape)``), whatever implements the
kernel: a kernel that skips work, or moves more bytes than it needs,
does not change the count.  This module holds the arithmetic on top.
"""

from __future__ import annotations

from typing import NamedTuple, Tuple

from .peaks import Peaks


class Work(NamedTuple):
    flops: float    # operations the algorithm needs for one call
    bytes: float    # bytes it must move to and from HBM at least once


def of(reference, shape) -> Work:
    return Work(float(reference.flops(shape)), float(reference.bytes_moved(shape)))


def roofline_s(work: Work, peaks: Peaks) -> Tuple[float, str]:
    """The least time the chip could take for ``work``, and which bound it."""
    compute_s = work.flops / peaks.flops
    memory_s = work.bytes / peaks.hbm_bw
    if compute_s >= memory_s:
        return compute_s, "compute"
    return memory_s, "memory"


def roofline_pct(work: Work, peaks: Peaks, seconds: float) -> float:
    """Share of the roofline, in percent, reached by a call of ``seconds``."""
    return 100.0 * roofline_s(work, peaks)[0] / seconds


def flops_pct(work: Work, peaks: Peaks, seconds: float) -> float:
    """Operations per second of a call of ``seconds``, in percent of peak."""
    return 100.0 * work.flops / seconds / peaks.flops


def kernel_roofline_pct(run):
    """A run's kernel roofline share: the kernel's device time per call of
    the public op, from the trace.  None where the trace holds no kernel
    event or the chip has no peaks."""
    if (run.peaks is None or run.trace is None or not run.trace.kernel_events
            or not run.attempted):
        return None
    return roofline_pct(run.work, run.peaks, run.trace.kernel_s / run.attempted)
