"""Host seconds of set-up spent splitting and staging the population: the
program's spans `setup/split` (`make_splits`) + `setup/stage` (`stage`: every
user's train stack onto the device, the eval operands)
(`benchmark/setup_reduce.py`)."""

from benchmark import setup_reduce


def compute(reduction, phases, cell):
    return setup_reduce.value(lambda t: t["seconds"]["setup/split"]
                              + t["seconds"]["setup/stage"])
