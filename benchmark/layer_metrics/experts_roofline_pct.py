"""The held routed experts' share of the chip's bf16 peak: the FLOPs their
matrix multiplications need at full width and at the expected pairs a token
(`benchmark/flops/<model>.py` `executed_routed_step_flops`: forward once,
backward twice; recomputation and tile rows that hold no token do not
count), for every active client, over `experts_ms.step`.  The matmuls are
compute-bound at these shapes (192+ rows against [2048, 768] weights), so
the bound is the FLOP peak."""

import math

from benchmark import harness, scope_reduce_moe


def compute(reduction, phases, cell):
    per_step = scope_reduce_moe.ms(reduction, scope_reduce_moe.any_of("moe/experts"),
                                   cell["steps_per_round"])
    if not per_step:
        return None
    _, config = harness.load_cell(cell["name"])
    flops = harness.load_module("flops", config["flops"])
    if not hasattr(flops, "executed_routed_step_flops"):
        return None
    fed = config["federation"]
    active = int(math.ceil(fed["frac"] * fed["num_users"]))
    need = active * flops.executed_routed_step_flops(config)
    return 100.0 * need / cell["peak_flops_per_s"] / (per_step / 1e3)
