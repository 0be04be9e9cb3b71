"""Device milliseconds a round outside the local steps' loop that the round
program spends on its own: `round/gather` (the cohort's shards, the level and
width tables) plus `round/aggregate` (count masks, counted sums, the psum, the
division into the new global)."""

from benchmark import scope_reduce

_gather = scope_reduce.has("round/gather")
_aggregate = scope_reduce.has("round/aggregate")


def compute(reduction, phases, cell):
    return scope_reduce.ms(reduction, lambda r: _gather(r) or _aggregate(r))
