#!/bin/bash
# Round-3 convergence-grade trajectory campaigns, torch-reference side
# (VERDICT r2 item 3): 100 rounds x 3 seeds for CIFAR-ResNet18 and
# MNIST-conv non-iid, plus one dynamic-mode and two interpolated-mode
# (a1-b9, a5-e5) campaigns on MNIST-conv.  Sequential, nice'd to idle
# priority (single-core box shared with the build).  Writes
# /tmp/PARITY_R3_REF_*.json; detach with nohup, takes hours.
set -u
cd /root/repo
RUN() {
  env JAX_PLATFORMS=cpu PYTHONPATH=/root/repo \
    nice -n 19 python -u -m heterofl_tpu.analysis.compare_reference "$@"
}
# MNIST first: cheap rounds, gives early full-length artifacts
for s in 0 1 2; do
  echo "=== MNIST conv non-iid ref seed $s $(date -u +%H:%M:%S) ==="
  RUN --data MNIST --model conv --hidden 64,128,256,512 --users 100 --frac 0.1 \
      --split non-iid-2 --rounds 100 --local_epochs 5 --n_train 2000 --n_test 1000 \
      --seed $s --skip mine --out /tmp/PARITY_R3_REF_MNIST_NONIID_S$s.json 2>&1 | tail -1
done
echo "=== MNIST_REF_DONE $(date -u +%H:%M:%S) ==="
# dynamic + interpolation modes (ref make.py:55-66), one seed each
echo "=== MNIST dynamic a1-e1 ref $(date -u +%H:%M:%S) ==="
RUN --data MNIST --model conv --hidden 64,128,256,512 --users 100 --frac 0.1 \
    --split iid --rounds 100 --local_epochs 5 --n_train 2000 --n_test 1000 \
    --model_split dynamic --mode a1-e1 \
    --seed 0 --skip mine --out /tmp/PARITY_R3_REF_DYNAMIC_S0.json 2>&1 | tail -1
echo "=== MNIST interp a1-b9 ref $(date -u +%H:%M:%S) ==="
RUN --data MNIST --model conv --hidden 64,128,256,512 --users 100 --frac 0.1 \
    --split iid --rounds 100 --local_epochs 5 --n_train 2000 --n_test 1000 \
    --mode a1-b9 \
    --seed 0 --skip mine --out /tmp/PARITY_R3_REF_INTERP_A1B9_S0.json 2>&1 | tail -1
echo "=== MNIST interp a5-e5 ref $(date -u +%H:%M:%S) ==="
RUN --data MNIST --model conv --hidden 64,128,256,512 --users 100 --frac 0.1 \
    --split iid --rounds 100 --local_epochs 5 --n_train 2000 --n_test 1000 \
    --mode a5-e5 \
    --seed 0 --skip mine --out /tmp/PARITY_R3_REF_INTERP_A5E5_S0.json 2>&1 | tail -1
echo "=== MODES_REF_DONE $(date -u +%H:%M:%S) ==="
for s in 0 1 2; do
  echo "=== CIFAR resnet18 ref seed $s $(date -u +%H:%M:%S) ==="
  RUN --data CIFAR10 --model resnet18 --hidden 64,128 --users 100 --frac 0.1 \
      --rounds 100 --local_epochs 1 --n_train 2000 --n_test 1000 --seed $s \
      --skip mine --out /tmp/PARITY_R3_REF_CIFAR_S$s.json 2>&1 | tail -1
done
echo "=== ALL_R3_REF_DONE $(date -u +%H:%M:%S) ==="
