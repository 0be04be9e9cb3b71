"""Host milliseconds a round spends staging (the program's `PhaseTimer` phase
`stage`: slot ids, placement-cache lookups, parameter commit), per traced
round."""


def compute(reduction, phases, cell):
    if not phases:
        return None
    return 1e3 * sum(p.get("stage", 0.0) for p in phases) / len(phases)
