"""tune.verify_ms_per_trial: milliseconds per trial that the evaluator spent
verifying the output: the program's reference, the host copy and the
comparison (span ``repro.eval.verify``, counter ``EngineStats.verify_s``)."""

from chipbench import per_trial


def read(run):
    return per_trial.ms(run, "verify_s")
