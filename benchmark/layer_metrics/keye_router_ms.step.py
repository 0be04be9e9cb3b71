"""`router_ms.step` in the Keye cell (the accepted entry lists the Kanana-2
cell alone): device milliseconds a local step in `moe/router` (the float32
softmax over all experts, top-k, the renormalised weights) and `moe/dispatch`
(grouping the pairs by held expert, the gathers into and out of the experts'
buffer, the weighted combine) under `step/model`."""

from benchmark import scope_reduce_keye


def compute(reduction, phases, cell):
    return scope_reduce_keye.ms(reduction,
                                scope_reduce_keye.any_of("moe/router", "moe/dispatch"),
                                cell["steps_per_round"])
