"""`experts_ms.step` in the Laguna cell (the accepted entry lists the Kanana-2
cell alone): device milliseconds a local step in `moe/experts`, the sixteen
held experts' SwiGLUs of width 512 on the tiles of (token, expert) pairs in
use, forward, recomputation and backward, four expert layers."""

from benchmark import scope_reduce_laguna as sr


def compute(reduction, phases, cell):
    return sr.ms(reduction, sr.any_of("moe/experts"), cell["steps_per_round"])
