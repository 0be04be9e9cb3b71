"""Host milliseconds a round spends in the driver: the program's `PhaseTimer`
phases `sample` + `dispatch`, per traced round."""


def compute(reduction, phases, cell):
    if not phases:
        return None
    return 1e3 * sum(p.get("sample", 0.0) + p.get("dispatch", 0.0)
                     for p in phases) / len(phases)
