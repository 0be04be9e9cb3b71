#!/bin/bash
# Transformer/WikiText2 trajectory-parity runs (round 2): chained after the
# vision campaign (single-core box).  Both sides run in one invocation per
# seed; reference hyperparameters (SGD lr 0.1, batch_rows 100, ref
# utils.py:195-206) at reduced bptt for CPU budget.
set -u
cd /root/repo
# Wait for the vision campaign's sentinel, but never forever: if the chain
# upstream died without printing it, start anyway after the deadline (the LM
# runs are independent of the vision artifacts).
deadline=$(( $(date +%s) + ${PARITY_LM_WAIT_S:-28800} ))
while ! { [ -s /tmp/PARITY_MINE_MNIST_NONIID_S2.json ] \
          || grep -q ALL_MINE_DONE /tmp/parity_mine.log 2>/dev/null; }; do
  if [ "$(date +%s)" -ge "$deadline" ]; then
    echo "=== WAIT_TIMEOUT: starting LM runs without the vision sentinel ==="
    break
  fi
  sleep 60
done
for s in 0 1 2; do
  echo "=== WikiText2 transformer parity seed $s $(date -u +%H:%M:%S) ==="
  env JAX_PLATFORMS=cpu PYTHONPATH=/root/repo \
    python -u -m heterofl_tpu.analysis.compare_reference \
      --model transformer --data WikiText2 --users 100 --frac 0.1 \
      --rounds 15 --n_train 100000 --n_test_tokens 20000 --batch_rows 100 \
      --bptt 32 --emb 64 --layers 2 --lr 0.1 --seed $s \
      --out /tmp/PARITY_LM_S$s.json 2>&1 | tail -1
done
echo "=== ALL_LM_DONE $(date -u +%H:%M:%S) ==="
