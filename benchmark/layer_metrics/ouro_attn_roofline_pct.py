"""The looped model's causal attention's share of the chip's bf16 peak: the
FLOPs the two products need over the causal (query, key) pairs
(`benchmark/flops/ouro.py` `executed_attn_step_flops`: 16 heads of 128,
`total_ut_steps` x layers applications, forward once, backward twice;
recomputation and pairs above the diagonal do not count), for every active
client, over the device time under `attn`.  A kernel pair that recomputes its
forward for the backward can read at most three quarters of what its own
products sustain."""

from benchmark import scope_reduce_ouro


def compute(reduction, phases, cell):
    return scope_reduce_ouro.roofline_pct(reduction, cell, ("attn",), "executed_attn_step_flops")
