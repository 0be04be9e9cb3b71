"""The sliding layers' attention's share of the chip's bf16 peak: the FLOPs
the two products need over the BAND pairs (`benchmark/flops/laguna.py`
`executed_window_step_flops`: three layers, 64 heads of 128, query t against
itself and the 511 keys before it, forward once, backward twice;
recomputation and masked-out pairs inside a visited tile do not count), for
every active client, over the device time under `swa`.  What it can reach at
most: a tile of t keys on a band of 512 holds band pairs for about 512 / (512
+ t) of the pairs the kernels compute (1/2 at tiles of 512, 2/3 at 256, 4/5
at 128), and a kernel pair that recomputes its forward for the backward reads
at most three quarters of what its own products sustain."""

from benchmark import scope_reduce_laguna


def compute(reduction, phases, cell):
    return scope_reduce_laguna.roofline_pct(reduction, cell, ("swa",),
                                            "executed_window_step_flops")
