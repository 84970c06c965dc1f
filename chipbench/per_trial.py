"""The search's phase counters per trial, for the tune cells' metrics.

Each counter is an ``EngineStats`` field that the program adds one phase's
seconds into (the phase is also a ``repro.*`` span of its profiler trace),
summed over the window's searches.
"""


def ms(run, counter: str):
    """Milliseconds of ``counter`` per trial, over ``unique_configs`` as
    ``tune.measure_ms_per_trial`` divides; None where the program keeps no
    such counter or evaluated no configuration."""
    trials = run.engine.get("unique_configs", 0)
    if not trials or counter not in run.engine:
        return None
    return 1000.0 * run.engine[counter] / trials
