"""Device milliseconds a local step in the shared experts (`moe/shared` under
`step/model`): one SwiGLU of width 1536 that every token takes."""

from benchmark import scope_reduce_moe


def compute(reduction, phases, cell):
    return scope_reduce_moe.ms(reduction, scope_reduce_moe.any_of("moe/shared"),
                               cell["steps_per_round"])
