"""`experts_roofline_pct` in the Keye cell (the accepted entry lists the
Kanana-2 cell alone): the FLOPs the held routed experts' matrix
multiplications need at full width and at the expected pairs a token
(`benchmark/flops/keye.py` `executed_routed_step_flops`: forward once,
backward twice; recomputation and tile rows that hold no token do not
count), for every active client, over `keye_experts_ms.step`, against the
chip's bf16 peak (512 rows an expert against [2048, 768] weights)."""

from benchmark import scope_reduce_keye


def compute(reduction, phases, cell):
    return scope_reduce_keye.roofline_pct(reduction, cell, ("moe/experts",),
                                          "executed_routed_step_flops")
