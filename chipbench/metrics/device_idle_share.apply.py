"""device_idle_share.apply: percent of the traced window in which no
operation ran on the device, in the cells that call the public op."""

from chipbench import trace


def read(run):
    return trace.idle_pct(run.trace)
