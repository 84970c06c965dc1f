"""kernel_device_us: device time of the kernel per call of the public op.

The summed device durations of the kernel's events in the traced window
(``trace.Summary.kernel_s``), over the calls the window made.
"""


def read(run):
    if run.trace is None or not run.trace.kernel_events or not run.attempted:
        return None
    return run.trace.kernel_s / run.attempted * 1e6
