"""Device milliseconds a local step in `norm` under `step/model`: masked
batch / layer / group norm, forward and backward."""

from benchmark import scope_reduce


def compute(reduction, phases, cell):
    return scope_reduce.ms(reduction, scope_reduce.has("step/model", "norm"),
                           cell["steps_per_round"])
