#!/usr/bin/env python
"""This-framework sides of the round-3 convergence-grade trajectory campaigns
(VERDICT r2 item 3), mirroring scripts/run_parity_r3_ref.sh run-for-run.
One process so a TPU run claims the chip once; on CPU set JAX_PLATFORMS=cpu.

Usage: run_parity_r3_mine.py [mnist|cifar|modes]  (default: all, in the
pairing-priority order of parity_r4_specs.RUNS).  Finished artifacts are
skipped, so a killed campaign resumes where it left off.  On CPU hosts the
engine uses the im2col conv lowering (numerically equivalent, measured 3.7x
faster there -- MEASUREMENTS.md round 4).
"""

import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))

from heterofl_tpu.analysis import compare_reference as cr
from parity_r4_specs import RUNS, run_one


def main():
    only = sys.argv[1] if len(sys.argv) > 1 else None
    # im2col is the CPU-host lowering (3.7x there, MEASUREMENTS.md round 4);
    # on a TPU host the default direct conv is the right one, so gate on the
    # platform jax actually selects (ADVICE r4).  default_backend() performs
    # the device claim, which this campaign process needs anyway.
    import jax

    extra = ("--conv_impl", "im2col") if jax.default_backend() == "cpu" else ()
    for family, name, args, out in RUNS:
        if only in (None, family):
            run_one(cr.main, name, args, out, extra_args=extra,
                    log=lambda m: print(m, flush=True))
    print("=== ALL_R3_MINE_DONE ===", flush=True)


if __name__ == "__main__":
    main()
