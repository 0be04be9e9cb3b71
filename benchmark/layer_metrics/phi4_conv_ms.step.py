"""Device milliseconds a local step in the Mamba-1 mixers' depthwise causal
convolution (`ssm/conv`: four shifted taps, the bias and `silu` over the 5,120
inner channels), forward, recomputation and backward."""

from benchmark import scope_reduce_phi4flash as sr


def compute(reduction, phases, cell):
    return sr.ms(reduction, sr.any_of("ssm/conv"), cell["steps_per_round"])
