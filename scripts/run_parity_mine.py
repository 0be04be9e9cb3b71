#!/usr/bin/env python
"""This-framework sides of the round-2 trajectory-parity runs (VERDICT r1
item 4), all in ONE process (a chip belongs to one process at a time).
Mirrors scripts/run_parity_ref.sh seed-for-seed."""

import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from heterofl_tpu.analysis import compare_reference as cr


def main():
    for s in (0, 1, 2):
        print(f"=== CIFAR resnet18 mine seed {s} ===", flush=True)
        cr.main(["--data", "CIFAR10", "--model", "resnet18", "--hidden", "64,128",
                 "--users", "100", "--frac", "0.1", "--rounds", "25",
                 "--local_epochs", "1", "--n_train", "2000", "--n_test", "1000",
                 "--seed", str(s), "--skip", "reference",
                 "--out", f"/tmp/PARITY_MINE_CIFAR_S{s}.json"])
    for s in (0, 1, 2):
        print(f"=== MNIST conv non-iid mine seed {s} ===", flush=True)
        cr.main(["--data", "MNIST", "--model", "conv", "--hidden", "64,128,256,512",
                 "--users", "100", "--frac", "0.1", "--split", "non-iid-2",
                 "--rounds", "25", "--local_epochs", "5", "--n_train", "2000",
                 "--n_test", "1000", "--seed", str(s), "--skip", "reference",
                 "--out", f"/tmp/PARITY_MINE_MNIST_NONIID_S{s}.json"])
    print("=== ALL_MINE_DONE ===", flush=True)


if __name__ == "__main__":
    main()
