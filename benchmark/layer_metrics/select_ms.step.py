"""Device milliseconds a local step in the indexer's selection
(`sparse/select` under `step/model`): the exact top-k of each score block
(the k-th largest value found bit by bit, then the cut among equal scores)
and the mask built from it; forward and recomputation."""

from benchmark import scope_reduce_keye


def compute(reduction, phases, cell):
    return scope_reduce_keye.ms(reduction, scope_reduce_keye.any_of("sparse/select"),
                                cell["steps_per_round"])
