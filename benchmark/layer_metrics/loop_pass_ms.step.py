"""Device milliseconds a local step under `loop/pass` (inside `step/model`):
the layer stack's `total_ut_steps` passes on the shared weights -- every
layer application's four norms, attention (`gqa`, `rope`, `attn` lie inside
it) and SwiGLU -- forward, recomputation and backward."""

from benchmark import scope_reduce_ouro


def compute(reduction, phases, cell):
    return scope_reduce_ouro.ms(reduction, scope_reduce_ouro.any_of("loop/pass"),
                                cell["steps_per_round"])
