"""Device milliseconds a local step in the Mamba-1 mixers under `step/model`:
everything under `ssm` (the in- and out-projections, `W_x` and `W_dt`, the
depthwise convolution, the selective scan, the skip and the gate), forward,
recomputation and backward."""

from benchmark import scope_reduce_phi4flash as sr


def compute(reduction, phases, cell):
    return sr.ms(reduction, sr.any_of("ssm"), cell["steps_per_round"])
