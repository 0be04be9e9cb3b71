"""Device milliseconds a local step in grouped-query attention under
`step/model`: `gqa` (the q, k, v and output projections and the per-head
norms), `rope`, and `attn` (scores, softmax, values, block by block),
forward, recomputation and backward."""

from benchmark import scope_reduce_lfm2


def compute(reduction, phases, cell):
    return scope_reduce_lfm2.ms(reduction, scope_reduce_lfm2.any_of("gqa", "rope", "attn"),
                                cell["steps_per_round"])
