"""apply_mfu: the operations of one call of the public op over its
host-clock time per call in the window, in percent of the chip's bf16
peak.  It covers the whole call, whatever kernels implement it.
"""

from chipbench import work


def read(run):
    if run.peaks is None or not run.call_s:
        return None
    return work.flops_pct(run.work, run.peaks, run.call_s)
