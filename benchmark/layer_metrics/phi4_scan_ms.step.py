"""Device milliseconds a local step in the selective scan (`ssm/scan`:
`ops.layers.selective_scan`, the decays, the blocks' runs, the chunks' carried
state and the read by `C`), forward, recomputation and backward."""

from benchmark import scope_reduce_phi4flash as sr


def compute(reduction, phases, cell):
    return sr.ms(reduction, sr.any_of("ssm/scan"), cell["steps_per_round"])
