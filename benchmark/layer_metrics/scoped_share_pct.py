"""Share of the device's self time, in the traced stretch, that lies under a
scope of the program's vocabulary (`benchmark/scope_reduce.py`): the
instruction's own, or, for one the compiler made without a name (relayout
copies, pads, the pieces of a concatenate), the nearest named neighbour's.
The rest ran outside every scope and no per-scope metric counts it.  The
`benchmark: device ms a round by scope` line of a traced run says how much of
the share is own and how much a neighbour's."""

from benchmark import scope_reduce


def compute(reduction, phases, cell):
    table = scope_reduce.table() if reduction else None
    if not table:
        return None
    scoped = scope_reduce.seconds(table, lambda r: r[0] != scope_reduce.UNSCOPED)
    return 100.0 * scoped / table["total_s"]
