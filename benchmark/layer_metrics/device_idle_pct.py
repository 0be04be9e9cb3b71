"""Share of the traced stretch in which no operation ran on a device, mean
over the devices."""


def compute(reduction, phases, cell):
    if not reduction:
        return None
    return 100.0 * reduction["idle_share"]
