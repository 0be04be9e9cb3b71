"""Device milliseconds a local step in the sparse-attention indexer
(`sparse/index` under `step/model`): its three projections, the key head's
LayerNorm and the turn, and the `relu`-weighted score of every query block
against the keys before it, in float32 at "highest" precision; forward and
recomputation (it has no backward)."""

from benchmark import scope_reduce_keye


def compute(reduction, phases, cell):
    return scope_reduce_keye.ms(reduction, scope_reduce_keye.any_of("sparse/index"),
                                cell["steps_per_round"])
