"""The selected attention's share of the chip's bf16 peak: the FLOPs the two
products need over the SELECTED (query, key) pairs alone
(`benchmark/flops/keye.py` `executed_sparse_attn_step_flops`: 32 heads of 128,
forward once, backward twice; recomputation and masked-out pairs do not
count), for every active client, over the device time under `attn`.  The work
counted is the same whatever computes it: a dense-masked form, which computes
every causal pair, can read at most 43.75 (the share of pairs selected at
8,192 positions), and about 33 while a block's forward is recomputed for its
backward."""

from benchmark import scope_reduce_keye


def compute(reduction, phases, cell):
    return scope_reduce_keye.roofline_pct(reduction, cell, ("attn",),
                                          "executed_sparse_attn_step_flops")
