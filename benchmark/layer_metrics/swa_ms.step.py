"""Device milliseconds a local step under `swa`: the score / softmax / value
part of the three sliding layers (64 query heads on 8 key/value heads of 128,
a window of 512 on rows of 8,192), the `band_attn_fwd` / `band_attn_bwd`
kernels, forward, recomputation and backward."""

from benchmark import scope_reduce_laguna as sr


def compute(reduction, phases, cell):
    return sr.ms(reduction, sr.any_of("swa"), cell["steps_per_round"])
