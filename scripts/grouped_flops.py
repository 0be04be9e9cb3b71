#!/usr/bin/env python
"""Compiled-program FLOP account: masked vs rate-grouped engine at the
flagship config (VERDICT r4 item 1 'done' bar).

The round-4 roofline (MEASUREMENTS.md) derived ~72.7 TFLOP/round for the
masked strategy vs ~18.6 for ideal dense per-level execution analytically;
this script asks XLA itself via :func:`heterofl_tpu.staticcheck.audit.
flop_account` -- the SAME implementation the staticcheck FLOP-budget audit
runs, so there is one source of truth for the level FLOP numbers (the
analytic shares come from ``fed.core.level_flop_shares``, which also drives
the grouped engine's slices row allocation).  CPU-safe: nothing is
executed, only compiled.  Prints one JSON line; run under
JAX_PLATFORMS=cpu (see tests/conftest.py).

MFU column: set PEAK_FLOPS (hardware peak in FLOP/s, e.g. 1.97e14
for one v5e chip in bf16, as `benchmark/peaks.json` has it, x devices) and
the account gains `mfu`: the ideal round seconds at peak per engine (flops /
peak) -- divide by a measured round time (`round_s` of a benchmark cell) to
get achieved utilisation.

Usage: [SMALL=1] [PEAK_FLOPS=...] python scripts/grouped_flops.py
       (SMALL=1: test widths)
"""

import json
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import numpy as np

from heterofl_tpu import config as C
from heterofl_tpu.data import fetch_dataset, label_split_masks, split_dataset, stack_client_shards
from heterofl_tpu.parallel import make_mesh
from heterofl_tpu.staticcheck.audit import flop_account


def main():
    small = os.environ.get("SMALL") == "1"
    users, n_train = (20, 2000) if small else (100, 50000)
    cfg = C.default_cfg()
    cfg["control"] = C.parse_control_name(f"1_{users}_0.1_iid_fix_a1-b1-c1-d1-e1_bn_1_1")
    cfg["data_name"], cfg["model_name"], cfg["synthetic"] = "CIFAR10", "resnet18", True
    cfg["compute_dtype"] = "bfloat16"
    cfg = C.process_control(cfg)
    if small:
        cfg["resnet"] = {"hidden_size": [8, 16, 16, 16]}
    cfg["classes_size"] = 10

    ds = fetch_dataset("CIFAR10", synthetic=True, seed=0,
                       synthetic_sizes={"train": n_train, "test": 100})
    rng = np.random.default_rng(0)
    split, lsplit = split_dataset(ds, users, "iid", rng)
    x, y, m = stack_client_shards(ds["train"].data, ds["train"].target,
                                  split["train"], list(range(users)))
    lm = label_split_masks(lsplit, users, 10)
    data = (x, y, m, lm)
    mesh = make_mesh(1, 1)

    # active set: the expected mix, 2 clients per level (fix-mode rate vector
    # is level-blocked: users [0..U/5) are level a, etc.)
    rates_vec = np.asarray(cfg["model_rate"], np.float64)
    user_idx = []
    for r in sorted(set(rates_vec), reverse=True):
        user_idx += list(np.where(rates_vec == r)[0][:2])
    user_idx = np.asarray(user_idx, np.int32)

    t0 = time.time()
    account = flop_account(cfg, data, mesh, user_idx, rates_vec[user_idx])
    mfu = None
    try:
        peak = float(os.environ.get("PEAK_FLOPS") or 0) or None
    except ValueError:
        print(f"grouped_flops: ignoring malformed PEAK_FLOPS="
              f"{os.environ['PEAK_FLOPS']!r}", file=sys.stderr)
        peak = None
    if peak:
        # the FLOP-time floor per engine; divide by a MEASURED round time
        # to get achieved MFU
        mfu = {"peak_flops": peak,
               "ideal_round_sec_at_peak": {
                   "masked": account["masked_flops_per_round"] / peak,
                   "grouped": account["grouped_flops_per_round"] / peak},
               "note": "mfu = ideal_round_sec_at_peak / measured_round_sec"}
    print(json.dumps({
        "config": f"CIFAR10 resnet18 {cfg['resnet']['hidden_size']} "
                  f"{users}u/10a a1-e1, batch {cfg['batch_size']['train']}, "
                  f"local_epochs {cfg['num_epochs']['local']}, bf16",
        **account,
        **({"mfu": mfu} if mfu else {}),
        "compile_sec": round(time.time() - t0, 1),
    }), flush=True)


if __name__ == "__main__":
    main()
