"""kernel_roofline: the cell's Pallas kernel's share of the chip's roofline,
in percent.

The least time the chip could take for one call of the public op
(``work.roofline_s``: the larger of the operations over peak FLOP/s and the
bytes over HBM bandwidth), over the kernel's device time per call from the
trace: the kernel events that the configuration's ``kernel_event`` names.
"""

from chipbench import work


def read(run):
    return work.kernel_roofline_pct(run)
