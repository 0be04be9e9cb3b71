"""`experts_ms.step` in the LFM2 cell (the accepted entry lists the Kanana-2
cell alone): device milliseconds a local step in the held routed experts'
three matrix multiplications (`moe/experts` under `step/model`), forward,
recomputation and both backward ones."""

from benchmark import scope_reduce_lfm2


def compute(reduction, phases, cell):
    return scope_reduce_lfm2.ms(reduction, scope_reduce_lfm2.any_of("moe/experts"),
                                cell["steps_per_round"])
