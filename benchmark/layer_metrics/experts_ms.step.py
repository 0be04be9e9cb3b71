"""Device milliseconds a local step in the held routed experts' three matrix
multiplications (`moe/experts` under `step/model`), forward, recomputation
and both backward ones."""

from benchmark import scope_reduce_moe


def compute(reduction, phases, cell):
    return scope_reduce_moe.ms(reduction, scope_reduce_moe.any_of("moe/experts"),
                               cell["steps_per_round"])
