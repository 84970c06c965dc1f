"""tune.args_ms_per_trial: milliseconds per trial that the evaluator spent
building the trial's inputs (span ``repro.eval.args``, counter
``EngineStats.args_s``)."""

from chipbench import per_trial


def read(run):
    return per_trial.ms(run, "args_s")
