"""Device-busy milliseconds a traced round: the union of the intervals in
which an operation ran, mean over the devices."""


def compute(reduction, phases, cell):
    if not reduction:
        return None
    return 1e3 * reduction["busy_s"] / reduction["rounds"]
