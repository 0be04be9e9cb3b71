"""Differential attention's share of the chip's bf16 peak: the FLOPs its LEAST
form needs (`benchmark/flops/phi4flash.py` `executed_diff_attn_step_flops`:
for each query pair two score products of 64 and two value products of 128
over the pairs a query sees, forward once, backward twice; recomputation,
masked half-tiles and a split softmax's second score product do not count),
for every active client, over `diff_attn_ms.step`, whatever form ran.  Compute
binds.  A kernel pair that recomputes its forward for the backward can read
at most three quarters of what its own products sustain, and 64-wide score
products fill half of the matrix unit's depth."""

from benchmark import scope_reduce_phi4flash as sr


def compute(reduction, phases, cell):
    return sr.roofline_pct(reduction, cell, ("attn", "swa", "diff"),
                           "executed_diff_attn_step_flops")
