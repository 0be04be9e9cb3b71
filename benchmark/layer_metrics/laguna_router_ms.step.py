"""`router_ms.step` in the Laguna cell (the accepted entry lists the Kanana-2
cell alone): device milliseconds a local step in `moe/router` (the float32
sigmoid over all 256 experts, top-8, the renormalised and scaled weights) and
`moe/dispatch` (grouping the pairs by held expert, the gathers into and out of
the experts' buffer, the weighted combine) under `step/model`."""

from benchmark import scope_reduce_laguna as sr


def compute(reduction, phases, cell):
    return sr.ms(reduction, sr.any_of("moe/router", "moe/dispatch"), cell["steps_per_round"])
