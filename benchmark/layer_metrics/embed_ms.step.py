"""Device milliseconds a local step in `embed` under `step/model`: the token
table's gather and, backward, the scatter of its gradient."""

from benchmark import scope_reduce


def compute(reduction, phases, cell):
    return scope_reduce.ms(reduction, scope_reduce.has("step/model", "embed"),
                           cell["steps_per_round"])
