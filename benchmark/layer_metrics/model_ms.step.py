"""Device milliseconds a local step under `step/model`: the model's forward
(`jvp(step/model)`) and backward (`transpose(jvp(step/model))`) passes, loss
included; self time, mean over devices (`benchmark/scope_reduce.py`)."""

from benchmark import scope_reduce


def compute(reduction, phases, cell):
    return scope_reduce.ms(reduction, scope_reduce.has("step/model"),
                           cell["steps_per_round"])
