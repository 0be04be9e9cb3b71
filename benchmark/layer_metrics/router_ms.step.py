"""Device milliseconds a local step in the expert layers' routing under
`step/model`: `moe/router` (the float32 scores of all experts, top-k, the
weights) and `moe/dispatch` (grouping the pairs by held expert, the gathers
into and out of the experts' buffer, the weighted combine)."""

from benchmark import scope_reduce_moe


def compute(reduction, phases, cell):
    return scope_reduce_moe.ms(reduction,
                               scope_reduce_moe.any_of("moe/router", "moe/dispatch"),
                               cell["steps_per_round"])
