"""Host seconds of set-up spent building the experiment without its dataset:
the program's span `setup/experiment` (all of `FedExperiment.__init__`) less
`setup/dataset`: the model, the mesh, the engines, the evaluator and what the
constructor does between them (`benchmark/setup_reduce.py`)."""

from benchmark import setup_reduce


def compute(reduction, phases, cell):
    return setup_reduce.value(lambda t: t["seconds"]["setup/experiment"]
                              - t["seconds"]["setup/dataset"])
