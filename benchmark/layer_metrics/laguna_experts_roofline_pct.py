"""`experts_roofline_pct` in the Laguna cell: the FLOPs the held routed
experts' matrix multiplications need at full width and at the expected pairs
a token (`benchmark/flops/laguna.py` `executed_routed_step_flops`: forward
once, backward twice; recomputation and tile rows that hold no token do not
count), for every active client, over `laguna_experts_ms.step`, against the
chip's bf16 peak (256 tokens an expert in tiles of 512 rows against [2048,
512] weights: half of a tile's rows hold no token)."""

from benchmark import scope_reduce_laguna


def compute(reduction, phases, cell):
    return scope_reduce_laguna.roofline_pct(reduction, cell, ("moe/experts",),
                                            "executed_routed_step_flops")
