"""Device milliseconds a local step in the conv mixers under `step/model`:
everything under `shortconv` (the `D -> 3 x channels` and `channels -> D`
projections, the two gates and the depthwise taps), forward, recomputation
and backward."""

from benchmark import scope_reduce_lfm2


def compute(reduction, phases, cell):
    return scope_reduce_lfm2.ms(reduction, scope_reduce_lfm2.any_of("shortconv"),
                                cell["steps_per_round"])
