"""The two driver-facing contracts: bench.py's single JSON line and
__graft_entry__'s compile/dry-run hooks."""

import pytest

import json
import os
import subprocess
import sys

import numpy as np

# runs bench.py / dryrun children with multi-minute timeouts (fast gate excludes this module)
pytestmark = pytest.mark.slow

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def test_bench_emits_schema_json():
    env = dict(os.environ)
    env.update({"BENCH_CPU": "1", "BENCH_USERS": "5", "BENCH_SYNTH_N": "100",
                "BENCH_ROUNDS": "1", "BENCH_HIDDEN": "4,8,8,8",
                "PYTHONPATH": REPO})
    out = subprocess.run([sys.executable, os.path.join(REPO, "bench.py")],
                         capture_output=True, text=True, timeout=420, env=env)
    assert out.returncode == 0, out.stderr[-2000:]
    line = out.stdout.strip().splitlines()[-1]
    rec = json.loads(line)
    assert set(rec) >= {"metric", "value", "unit", "vs_baseline"}
    assert rec["unit"] == "rounds/sec" and rec["value"] > 0
    # degraded runs (here: BENCH_HIDDEN shrink) must NOT claim comparability
    # to the 10 rps north star (VERDICT r4 item 5)
    assert rec["vs_baseline"] is None
    assert rec["extra"]["degraded"].startswith("hidden-shrink")
    assert np.isfinite(rec["extra"]["final_loss"])


def _metric_lines(stdout):
    out = []
    for line in stdout.strip().splitlines():
        try:
            rec = json.loads(line)
        except json.JSONDecodeError:
            continue
        if isinstance(rec, dict) and "metric" in rec:
            out.append(rec)
    return out


def test_bench_without_tpu_or_cpu_mode_fails():
    """No TPU and no BENCH_CPU=1: bench.py exits non-zero and prints no
    record -- a measurement path that finds no chip never falls back."""
    env = dict(os.environ)
    env.pop("BENCH_CPU", None)
    env.update({"JAX_PLATFORMS": "cpu", "BENCH_USERS": "5",
                "BENCH_SYNTH_N": "100", "BENCH_ROUNDS": "1",
                "BENCH_HIDDEN": "4,8,8,8", "PYTHONPATH": REPO})
    out = subprocess.run([sys.executable, os.path.join(REPO, "bench.py")],
                         capture_output=True, text=True, timeout=420, env=env)
    assert out.returncode != 0
    assert "no TPU" in out.stderr
    assert not _metric_lines(out.stdout)


def test_bench_failure_is_a_failure():
    """A run that crashes exits non-zero with its traceback and no record:
    there is no synthetic rc-0 line any more."""
    env = dict(os.environ)
    env.update({"BENCH_CPU": "1", "BENCH_HIDDEN": "bogus",
                "PYTHONPATH": REPO})
    out = subprocess.run([sys.executable, os.path.join(REPO, "bench.py")],
                         capture_output=True, text=True, timeout=420, env=env)
    assert out.returncode != 0
    assert "Traceback" in out.stderr
    assert not _metric_lines(out.stdout)


def test_graft_entry_contract():
    import jax

    import __graft_entry__ as g

    fn, args = g.entry()
    loss, score = jax.jit(fn)(*args)
    assert np.isfinite(float(loss))
    assert score.shape[-1] == 10
    g.dryrun_multichip(2)
    g.dryrun_multichip(8)  # 2-D mesh path (4 clients x 2 data)
