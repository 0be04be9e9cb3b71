"""Device milliseconds a local step under `step/batch`: index slices, the
batch's gather, augmentation and normalisation, the LM's window slices."""

from benchmark import scope_reduce


def compute(reduction, phases, cell):
    return scope_reduce.ms(reduction, scope_reduce.has("step/batch"),
                           cell["steps_per_round"])
