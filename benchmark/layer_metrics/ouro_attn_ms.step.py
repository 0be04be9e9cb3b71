"""Device milliseconds a local step in the looped model's attention, under
`loop/pass`: `gqa` (the q, k, v and output projections), `rope`, and `attn`
(scores, softmax, values: the `gq_attn_fwd` / `gq_attn_bwd` kernels at
128-wide heads in groups of one), all `total_ut_steps` x layers applications,
forward, recomputation and backward."""

from benchmark import scope_reduce_ouro as sr


def compute(reduction, phases, cell):
    return sr.ms(reduction, sr.any_of("gqa", "rope", "attn"), cell["steps_per_round"])
