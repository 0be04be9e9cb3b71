"""Device milliseconds a local step under `gqa` and `rope` in the Laguna cell:
every layer's q, k, v, gate and output projections, the gate's sigmoid and
product, and both kinds' rotary turns, forward, recomputation and backward;
the score / softmax / value parts are `swa_ms.step` and
`laguna_full_attn_ms.step`."""

from benchmark import scope_reduce_laguna as sr


def compute(reduction, phases, cell):
    return sr.ms(reduction, sr.any_of("gqa", "rope"), cell["steps_per_round"])
