#!/bin/bash
# CPU form of the mine-side trajectory runs.
# Waits for the torch-reference script to finish (single-core box), then
# delegates to run_parity_mine.py -- the single source of truth for the
# run matrix -- on the virtual CPU backend (the compile cache goes where
# utils/compile_cache.py puts it, or where JAX_COMPILATION_CACHE_DIR says).
set -u
cd /root/repo
# wait on the ref script's LAST output artifact (robust to where its log
# was redirected), or its conventional log sentinel
while ! { [ -s /tmp/PARITY_REF_MNIST_NONIID_S2.json ] \
          || grep -q ALL_REF_DONE /tmp/parity_ref.log 2>/dev/null; }; do sleep 60; done
env JAX_PLATFORMS=cpu PYTHONPATH=/root/repo \
  python -u scripts/run_parity_mine.py
