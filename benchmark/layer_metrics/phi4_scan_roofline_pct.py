"""The selective scan's share of its roofline: the least time the chip could
take for it -- the larger of its elementwise operations over the bf16 peak
and its unavoidable bytes over the memory bandwidth
(`benchmark/flops/phi4flash.py` `executed_scan_step_flops` /
`executed_scan_step_bytes`: forward once, backward twice; recomputation does
not count) -- for every active client, over `phi4_scan_ms.step`.  THE MEMORY
SIDE BINDS: at 8,192 positions, 5,120 channels and a state of 16 a step needs
1.2e10 elementwise operations (0.06 ms at 197 TFLOP/s, a peak the vector units
do not have) and 1.5 GB (1.85 ms at 819 GB/s): it reads `xs`, `dt`, `B`, `C`
and writes `y`.  Whatever implements the scope is held against the same
work: the `jnp` form passes a block's `[blocks, 16, 5120]` state through
memory once a position, which a fused kernel would keep on the chip."""

from benchmark import scope_reduce_phi4flash as sr


def compute(reduction, phases, cell):
    return sr.two_sided_roofline_pct(reduction, cell, ("ssm/scan",),
                                     "executed_scan_step_flops", "executed_scan_step_bytes")
