"""tune.timing_ms_per_trial: milliseconds per trial that the evaluator spent on
warm-up and timed calls (span ``repro.eval.timing``, counter
``EngineStats.timing_s``)."""

from chipbench import per_trial


def read(run):
    return per_trial.ms(run, "timing_s")
