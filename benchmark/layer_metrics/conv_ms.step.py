"""Device milliseconds a local step in `conv` under `step/model`: the
convolutions, forward and both backward ones."""

from benchmark import scope_reduce


def compute(reduction, phases, cell):
    return scope_reduce.ms(reduction, scope_reduce.has("step/model", "conv"),
                           cell["steps_per_round"])
