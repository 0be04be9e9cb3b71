"""`experts_ms.step` in the Keye cell (the accepted entry lists the Kanana-2
cell alone): device milliseconds a local step in the held routed experts'
three matrix multiplications (`moe/experts` under `step/model`), forward,
recomputation and both backward ones."""

from benchmark import scope_reduce_keye


def compute(reduction, phases, cell):
    return scope_reduce_keye.ms(reduction, scope_reduce_keye.any_of("moe/experts"),
                                cell["steps_per_round"])
