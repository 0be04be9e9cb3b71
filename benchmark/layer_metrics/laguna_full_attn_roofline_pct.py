"""The full layers' attention's share of the chip's bf16 peak: the FLOPs the
two products need over the causal pairs (`benchmark/flops/laguna.py`
`executed_full_attn_step_flops`: two layers, 48 heads of 128, forward once,
backward twice; recomputation and pairs above the diagonal do not count), for
every active client, over the device time under `attn`.  A kernel pair that
recomputes its forward for the backward can read at most three quarters of
what its own products sustain."""

from benchmark import scope_reduce_laguna


def compute(reduction, phases, cell):
    return scope_reduce_laguna.roofline_pct(reduction, cell, ("attn",),
                                            "executed_full_attn_step_flops")
