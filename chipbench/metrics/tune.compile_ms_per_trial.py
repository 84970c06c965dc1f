"""tune.compile_ms_per_trial: milliseconds per trial that the compiler took for
the candidate kernel (span ``repro.eval.compile``, counter
``EngineStats.xla_compile_s``)."""

from chipbench import per_trial


def read(run):
    return per_trial.ms(run, "xla_compile_s")
