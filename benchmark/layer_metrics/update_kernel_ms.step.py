"""Device milliseconds a local step in the Pallas fused-SGD kernel itself:
the `custom-call` events under `update/kernel` (`name="fused_sgd"`)."""

from benchmark import scope_reduce


def compute(reduction, phases, cell):
    return scope_reduce.ms(reduction, scope_reduce.kernel_call,
                           cell["steps_per_round"])
