"""Device milliseconds a local step in latent attention under `step/model`:
`mla` (the q, latent, key/value and output projections and the latent norm),
`rope`, and `attn` (scores, softmax, values, block by block), forward,
recomputation and backward."""

from benchmark import scope_reduce_moe


def compute(reduction, phases, cell):
    return scope_reduce_moe.ms(reduction, scope_reduce_moe.any_of("mla", "rope", "attn"),
                               cell["steps_per_round"])
