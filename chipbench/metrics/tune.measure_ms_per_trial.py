"""tune.measure_ms_per_trial: milliseconds the evaluator's measure phase
took per trial (``EngineStats.measure_total_s`` over ``unique_configs``,
each summed over the window's searches): verification and timing."""


def read(run):
    trials = run.engine.get("unique_configs", 0)
    if not trials:
        return None
    return 1000.0 * run.engine.get("measure_total_s", 0.0) / trials
