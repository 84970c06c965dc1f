"""device_idle_share.tune: percent of the traced window in which no
operation ran on the device, in the cells that run a search."""

from chipbench import trace


def read(run):
    return trace.idle_pct(run.trace)
