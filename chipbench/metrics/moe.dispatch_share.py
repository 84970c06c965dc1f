"""moe.dispatch_share: percent of the device's busy time in the traced
window spent outside the cell's Pallas kernels (the configuration's
``kernel_event``): 100 * (busy - kernel) / busy.  For the MoE op, whose
Pallas kernels are the two grouped products and the combine, that is the
XLA work around them: ordering the routed pairs by expert and gathering
their rows of x.  None without a trace, or where the trace holds no
kernel event."""


def read(run):
    summary = run.trace
    if summary is None or not summary.busy_s or not summary.kernel_events:
        return None
    return 100.0 * (summary.busy_s - summary.kernel_s) / summary.busy_s
