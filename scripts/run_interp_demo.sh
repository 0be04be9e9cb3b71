#!/bin/bash
# End-to-end interpolation figure through this framework's own pipeline
# (VERDICT r2 item 4): make.py grid -> train_classifier_fed ->
# test_classifier_fed -> summary profiles -> process.py, small scale on
# synthetic MNIST.  Produces output_interp/result.csv and
# output_interp/fig/interp_Global-Accuracy.png.
#
# Usage: bash scripts/run_interp_demo.sh [OUTDIR]  (default ./output_interp)
set -eu
cd /root/repo
OUT=${1:-output_interp}
MODES="a1,b1,a1-b9,a3-b7,a5-b5,a7-b3,a9-b1"
OVERRIDE='{"num_epochs": {"global": 30, "local": 2}, "conv": {"hidden_size": [16, 32]}, "batch_size": {"train": 10, "test": 50}}'
ENV() {
  env JAX_PLATFORMS=cpu PYTHONPATH=/root/repo "$@"
}
# JSON kept single-quoted INSIDE the value: the generated grid scripts re-eval
# this string, and unquoted {...} would hit bash brace expansion and split into
# two words, failing argparse (advisor r3, medium).
EXTRA="--output_dir $OUT --synthetic_sizes '{\"train\":4000,\"test\":1000}' --override '$OVERRIDE'"

# 1. grids (one job per line, wait barriers -> sequential on this box)
ENV python -m heterofl_tpu.analysis.make --run train --model conv --fed 1 \
  --data_split_mode iid --modes "$MODES" --synthetic --round 1 --extra "$EXTRA" > /dev/null
ENV python -m heterofl_tpu.analysis.make --run test --model conv --fed 1 \
  --data_split_mode iid --modes "$MODES" --synthetic --round 1 --extra "$EXTRA" > /dev/null

# 2. train + test every grid point (the generated scripts run the entry
#    points; PYTHONPATH/env comes from this shell)
ENV bash train_conv_iid.sh
ENV bash test_conv_iid.sh

# 3. per-level profiler bundles (x axis = measured params ratio)
ENV python - "$OUT" <<'EOF'
import json, sys
from heterofl_tpu import config as C
from heterofl_tpu.analysis.summary import make_summary

cfg = C.default_cfg()
cfg["data_name"], cfg["model_name"] = "MNIST", "conv"
cfg = C.process_control(cfg)
cfg["conv"] = {"hidden_size": [16, 32]}
cfg["classes_size"], cfg["data_shape"] = 10, [28, 28, 1]
make_summary(cfg, rates=[1.0, 0.5, 0.25, 0.125, 0.0625], output_dir=sys.argv[1])
EOF

# 4. aggregate + figures
ENV python -m heterofl_tpu.analysis.process --output_dir "$OUT"
ls -l "$OUT"/result.csv "$OUT"/fig/
