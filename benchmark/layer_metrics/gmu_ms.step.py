"""Device milliseconds a local step in the gated memory units (`gmu`: the
gate's product, `silu`, the product with another layer's scan output and the
output product), forward, recomputation and backward."""

from benchmark import scope_reduce_phi4flash as sr


def compute(reduction, phases, cell):
    return sr.ms(reduction, sr.any_of("gmu"), cell["steps_per_round"])
