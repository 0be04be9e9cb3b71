"""Device milliseconds a local step in the attention the indexer selects for,
under `step/model`: `gqa` (the q, k, v and output projections and the per-head
norms), `rope`, and `attn` (scores, the selection as a mask, softmax, values,
block by block), forward, recomputation and backward.  The indexer's own turn
(`rope` under `sparse/index`) is `indexer_ms.step`'s."""

from benchmark import scope_reduce_keye as sr


def compute(reduction, phases, cell):
    return sr.ms(reduction, sr.outside(sr.any_of("gqa", "rope", "attn"), "sparse/index"),
                 cell["steps_per_round"])
