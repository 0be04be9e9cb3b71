"""Device-busy milliseconds per local SGD step of the vmapped client slots:
`device_ms.round` over the local steps a round holds (the aggregation's share
is in it; PERF.md section 3)."""


def compute(reduction, phases, cell):
    if not reduction:
        return None
    return 1e3 * reduction["busy_s"] / reduction["rounds"] / cell["steps_per_round"]
