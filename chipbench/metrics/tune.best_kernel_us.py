"""tune.best_kernel_us: the window's best trial, rebuilt through the
registry and re-timed by the harness after the window (host clock, calls
dispatched back to back as the apply mix does): a record of what the
search found."""


def read(run):
    if run.best_call_s is None:
        return None
    return run.best_call_s * 1e6
