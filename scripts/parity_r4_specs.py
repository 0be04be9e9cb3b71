"""Single source of truth for the round-3/4 mine-side campaign run specs.

The campaign runner (run_parity_r3_mine.py) imports RUNS and run_one from
here, so artifact names, seeds, and round counts have one owner.  Artifacts land in /tmp/PARITY_R3_MINE_*.json (written
atomically by compare_reference) and finished runs are skipped, so a killed
campaign resumes where it left off.
"""

import os

MNIST_ARGS = ["--data", "MNIST", "--model", "conv", "--hidden", "64,128,256,512",
              "--users", "100", "--frac", "0.1", "--rounds", "100",
              "--local_epochs", "5", "--n_train", "2000", "--n_test", "1000",
              "--skip", "reference"]
CIFAR_ARGS = ["--data", "CIFAR10", "--model", "resnet18", "--hidden", "64,128",
              "--users", "100", "--frac", "0.1", "--rounds", "100",
              "--local_epochs", "1", "--n_train", "2000", "--n_test", "1000",
              "--skip", "reference"]

# (family, name, args, artifact path) in pairing-priority order: families
# alternate so every finished run immediately pairs with an existing ref
# artifact even when a slow CPU fallback only gets through a prefix
RUNS = []
for _s in (0, 1, 2):
    RUNS.append(("mnist", f"MNIST conv non-iid mine seed {_s}",
                 MNIST_ARGS + ["--split", "non-iid-2", "--seed", str(_s)],
                 f"/tmp/PARITY_R3_MINE_MNIST_NONIID_S{_s}.json"))
    RUNS.append(("cifar", f"CIFAR resnet18 mine seed {_s}",
                 CIFAR_ARGS + ["--seed", str(_s)],
                 f"/tmp/PARITY_R3_MINE_CIFAR_S{_s}.json"))
RUNS += [
    ("modes", "MNIST dynamic a1-e1 mine",
     MNIST_ARGS + ["--model_split", "dynamic", "--mode", "a1-e1", "--seed", "0"],
     "/tmp/PARITY_R3_MINE_DYNAMIC_S0.json"),
    ("modes", "MNIST interp a1-b9 mine",
     MNIST_ARGS + ["--mode", "a1-b9", "--seed", "0"],
     "/tmp/PARITY_R3_MINE_INTERP_A1B9_S0.json"),
    ("modes", "MNIST interp a5-e5 mine",
     MNIST_ARGS + ["--mode", "a5-e5", "--seed", "0"],
     "/tmp/PARITY_R3_MINE_INTERP_A5E5_S0.json"),
]


def run_one(cr_main, name, args, out, extra_args=(), log=print):
    """Run one campaign through ``compare_reference.main`` unless its artifact
    already exists.  Returns True if the run executed."""
    if os.path.exists(out):
        log(f"=== skip {name} (artifact exists) ===")
        return False
    log(f"=== {name} ===")
    cr_main(list(args) + list(extra_args) + ["--out", out])
    return True
