"""Device milliseconds a local step in differential attention's softmaxes and
their combine (`attn`, a sliding layer's `swa`, and `diff`: the difference, the
sub-norm and the constant), forward, recomputation and backward; the
projections are `gqa`'s and not in it."""

from benchmark import scope_reduce_phi4flash as sr


def compute(reduction, phases, cell):
    return sr.ms(reduction, sr.any_of("attn", "swa", "diff"), cell["steps_per_round"])
