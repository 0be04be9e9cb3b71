#!/bin/bash
# Torch-reference sides of the round-2 trajectory-parity runs (VERDICT r1
# item 4). Sequential: single-core box. Writes /tmp/PARITY_REF_*.json and a
# progress log. Detach with nohup; takes a few hours.
set -u
cd /root/repo
RUN() {
  env JAX_PLATFORMS=cpu PYTHONPATH=/root/repo \
    python -u -m heterofl_tpu.analysis.compare_reference "$@"
}
for s in 0 1 2; do
  echo "=== CIFAR resnet18 ref seed $s $(date -u +%H:%M:%S) ==="
  RUN --data CIFAR10 --model resnet18 --hidden 64,128 --users 100 --frac 0.1 \
      --rounds 25 --local_epochs 1 --n_train 2000 --n_test 1000 --seed $s \
      --skip mine --out /tmp/PARITY_REF_CIFAR_S$s.json 2>&1 | tail -1
done
for s in 0 1 2; do
  echo "=== MNIST conv non-iid ref seed $s $(date -u +%H:%M:%S) ==="
  RUN --data MNIST --model conv --hidden 64,128,256,512 --users 100 --frac 0.1 \
      --split non-iid-2 --rounds 25 --local_epochs 5 --n_train 2000 --n_test 1000 \
      --seed $s --skip mine --out /tmp/PARITY_REF_MNIST_NONIID_S$s.json 2>&1 | tail -1
done
echo "=== ALL_REF_DONE $(date -u +%H:%M:%S) ==="
