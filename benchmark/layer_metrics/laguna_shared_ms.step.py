"""`shared_ms.step` in the Laguna cell (the accepted entry lists the Kanana-2
cell alone): device milliseconds a local step in the shared expert
(`moe/shared` under `step/model`), one SwiGLU of width 512 that every token
takes, four expert layers."""

from benchmark import scope_reduce_laguna as sr


def compute(reduction, phases, cell):
    return sr.ms(reduction, sr.any_of("moe/shared"), cell["steps_per_round"])
