"""Host seconds of set-up spent on the dataset: the program's span
`setup/dataset` (`fetch_dataset`, `process_dataset`, the norm statistics),
inside `FedExperiment.__init__` (`benchmark/setup_reduce.py`)."""

from benchmark import setup_reduce


def compute(reduction, phases, cell):
    return setup_reduce.value(lambda t: t["seconds"]["setup/dataset"])
