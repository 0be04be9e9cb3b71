"""Programs of the program's own that the persistent compile cache did not
serve: `compile/backend` spans with `cache` = `miss` whose parents reach a span
of the program.  0 on a second run of an unchanged tree; above 0 where a
program's key moved (a line under a Mosaic kernel's call stack, the scope
version) or its entry was dropped (the cache's size cap)
(`benchmark/setup_reduce.py`)."""

from benchmark import setup_reduce


def compute(reduction, phases, cell):
    return setup_reduce.value(lambda t: t["own_misses"])
