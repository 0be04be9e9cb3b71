#!/bin/bash
# Round-5 non-iid calibration (VERDICT r4 item 4): the reference against
# itself at the PARITY_R3_MNIST_NONIID config on extra seeds 3-5, to measure
# the ref-vs-ref seed band that the +4.5pp mine-vs-ref mean gap must be
# compared against.  nice'd below the CIFAR campaign on this single core.
set -u
cd /root/repo
for s in 3 4 5; do
  out=/tmp/PARITY_R5_REF_MNIST_NONIID_S$s.json
  if [ ! -f "$out" ]; then
    echo "=== MNIST conv non-iid ref seed $s $(date -u +%H:%M:%S) ==="
    env JAX_PLATFORMS=cpu PYTHONPATH=/root/repo \
      nice -n 12 python -u -m heterofl_tpu.analysis.compare_reference \
        --data MNIST --model conv --hidden 64,128,256,512 --users 100 --frac 0.1 \
        --split non-iid-2 --rounds 100 --local_epochs 5 --n_train 2000 --n_test 1000 \
        --seed $s --skip mine --out "$out" 2>&1 | tail -2
  else
    echo "skip seed $s"
  fi
  # persist the ref curve into the repo so the seed band survives a /tmp
  # wipe (this is the CAMPAIGN's side effect; the assemble summarizer only
  # reads -- ADVICE r5 item 4)
  [ -f "$out" ] && python - "$out" "PARITY_R5_REF_MNIST_NONIID_S$s.json" <<'PYEOF'
import json, sys
src, dst = sys.argv[1], sys.argv[2]
with open(src) as fin:
    curve = json.load(fin).get("reference_acc") or []
if curve:
    with open(dst, "w") as fout:
        json.dump({"reference_acc": curve}, fout)
PYEOF
done
echo "=== R5_REF_SEEDS_DONE $(date -u +%H:%M:%S) ==="
