"""Host seconds the program's own compilations took: the spans
`compile/trace` + `compile/lower` + `compile/backend` (a cache hit's load
included) whose parents reach a span of the program (a set-up span or a
`stage` / `dispatch` / `fetch` phase).  The benchmark's own programs (weights,
the plain reference) compile under no span and do not count; no window holds
a compilation (`window_compiles` is part of `correct`), so this is set-up's
(`benchmark/setup_reduce.py`)."""

from benchmark import setup_reduce


def compute(reduction, phases, cell):
    return setup_reduce.value(lambda t: t["own_compile_s"])
