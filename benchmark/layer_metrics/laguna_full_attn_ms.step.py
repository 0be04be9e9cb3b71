"""Device milliseconds a local step under `attn` in the Laguna cell: the score
/ softmax / value part of the two full-attention layers (48 query heads on 8
key/value heads of 128, every causal pair of a row of 8,192), the band kernels
without a window, forward, recomputation and backward."""

from benchmark import scope_reduce_laguna as sr


def compute(reduction, phases, cell):
    return sr.ms(reduction, sr.any_of("attn"), cell["steps_per_round"])
