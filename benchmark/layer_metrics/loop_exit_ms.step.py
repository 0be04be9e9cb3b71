"""Device milliseconds a local step under `loop/exit` (inside `step/model`):
the final norm after each pass, the exit gate, the exit distribution, the
mixing of the passes' losses under it and its entropy, forward and
backward."""

from benchmark import scope_reduce_ouro


def compute(reduction, phases, cell):
    return scope_reduce_ouro.ms(reduction, scope_reduce_ouro.any_of("loop/exit"),
                                cell["steps_per_round"])
