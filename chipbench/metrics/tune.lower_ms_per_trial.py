"""tune.lower_ms_per_trial: milliseconds per trial that the evaluator spent
tracing and lowering the candidate kernel (span ``repro.eval.lower``,
counter ``EngineStats.lower_s``)."""

from chipbench import per_trial


def read(run):
    return per_trial.ms(run, "lower_s")
