"""The conv mixers' share of the chip's bf16 peak: the FLOPs their two
projections need at full width (`benchmark/flops/lfm2.py`
`executed_shortconv_step_flops`: forward once, backward twice; recomputation
does not count), for every active client, over `shortconv_ms.step`.  The
products are compute-bound (4,096 rows against [2048, 2048] weights); the
gates and taps between them are memory-bound elementwise work with no FLOPs
counted, so their time is what pulls the share down, and with the
recomputed forward uncounted it cannot pass 75."""

from benchmark import scope_reduce_lfm2


def compute(reduction, phases, cell):
    return scope_reduce_lfm2.roofline_pct(reduction, cell, ("shortconv",),
                                          "executed_shortconv_step_flops")
