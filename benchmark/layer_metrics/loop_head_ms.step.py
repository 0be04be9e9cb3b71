"""Device milliseconds a local step under `loop/head` (inside `step/model`):
the head's product over the whole vocabulary and the cross entropy of every
pass's read-out, block by block of (pass, position) rows, forward,
recomputation and backward."""

from benchmark import scope_reduce_ouro


def compute(reduction, phases, cell):
    return scope_reduce_ouro.ms(reduction, scope_reduce_ouro.any_of("loop/head"),
                                cell["steps_per_round"])
