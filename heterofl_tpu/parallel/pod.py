"""Pod-scale probe (ISSUE 17): run the fused grouped-slices superstep on a
REAL multi-process ``jax.distributed`` CPU mesh and prove the pod
contracts without TPU hardware.

The probe is the shared engine behind three consumers:

* ``tests/test_pod.py`` -- the bitwise acceptance gate: a 2-process run
  must produce params AND per-round metrics bit-identical to the
  single-process run of the same program (``slice_align`` pins the same
  host-aligned level partition on both sides), with
  :func:`~..staticcheck.wire.dcn_axes_of` classifying the clients axis as
  DCN from the real process grid and the traced program carrying exactly
  ONE dense reduction per training round, zero reshards.
* CI (``tier1.yml``) -- the distributed smoke step drives the same child.

Each child process joins the distributed runtime (coordinator on process
0), builds the (clients, data) mesh over ALL global devices, trains a
K-round fused slices superstep, times a second superstep dispatch, writes
a sharded checkpoint (timed per process), and classifies the traced
program's collectives.  Process 0 persists params/metrics as ``.npz`` for
the bitwise comparison; every process writes its own timing JSON.
"""

from __future__ import annotations

import json
import os
import socket
import subprocess
import sys
import time
from typing import Any, Dict, List, Optional

#: default probe shape: 2 levels so a 2-process mesh hosts one level per
#: process block; 8 users on an 8-row clients axis
PROBE_CONTROL = "1_8_0.5_iid_fix_a1-b1_bn_1_1"
PROBE_USERS = 8


def probe_cfg(control: str = PROBE_CONTROL) -> Dict[str, Any]:
    """The small CPU probe config (mirrors the test suite's ``small_cfg``:
    tiny conv, synthetic MNIST)."""
    from .. import config as C

    cfg = C.default_cfg()
    cfg["control"] = C.parse_control_name(control)
    cfg["data_name"] = "MNIST"
    cfg["model_name"] = "conv"
    cfg = C.process_control(cfg)
    cfg["conv"] = {"hidden_size": [8, 16]}
    cfg["classes_size"] = 10
    return cfg


def probe_data(cfg: Dict[str, Any], users: int = PROBE_USERS):
    """Deterministic synthetic population stacks -- every process builds
    the same host arrays (seed 0), committed to the mesh by staging."""
    import numpy as np

    from ..data import (fetch_dataset, label_split_masks, split_dataset,
                        stack_client_shards)

    ds = fetch_dataset(cfg["data_name"], synthetic=True, seed=0,
                       synthetic_sizes={"train": 400, "test": 100})
    rng = np.random.default_rng(0)  # staticcheck: allow(no-fresh-rng): probe harness data seed, not an engine stream
    split, lsplit = split_dataset(ds, users, cfg["data_split_mode"], rng,
                                  classes_size=10)
    x, y, m = stack_client_shards(ds["train"].data, ds["train"].target,
                                  split["train"], list(range(users)))
    lm = label_split_masks(lsplit, users, 10)
    return x, y, m, lm


def _schedules(cfg, epoch0: int, k: int, num_active: int):
    """Host sampling/rate streams, identical on every process (the same
    folded keys the driver consumes)."""
    import numpy as np

    import jax

    from ..fed.core import round_users

    host_key = jax.random.key(0)
    users = np.stack([
        np.asarray(round_users(jax.random.fold_in(host_key, epoch0 + r),  # staticcheck: allow(no-asarray): host schedule assembly in the probe harness
                               cfg["num_users"], num_active))
        for r in range(k)])
    rates = np.asarray(cfg["model_rate"], np.float32)[users]  # staticcheck: allow(no-asarray): host schedule assembly in the probe harness
    return users, rates


def child_main(out_dir: str, k: int = 4, num_active: int = 4,
               align: int = 0) -> Dict[str, Any]:
    """Runs INSIDE a (possibly distributed) subprocess."""
    import numpy as np

    import jax
    import jax.numpy as jnp

    from ..models import make_model
    from ..utils.checkpoint import (dense_from_blocks, is_shard_marker,
                                    load_checkpoint_sharded,
                                    save_checkpoint_sharded)
    from .mesh import initialize_distributed, make_mesh
    from .staging import commit_global, host_fetch
    from ..staticcheck.jaxpr_walk import find_reshards
    from ..staticcheck.wire import dcn_axes_of, program_wire
    from jax.sharding import NamedSharding, PartitionSpec as P

    initialize_distributed()
    pid, n_proc = jax.process_index(), jax.process_count()
    out: Dict[str, Any] = {"process": pid, "processes": n_proc,
                           "devices": len(jax.devices()), "k": k}

    cfg = dict(probe_cfg(), level_placement="slices", strict_placement=True)
    if align:
        cfg["slice_align"] = align
    data_host = probe_data(cfg)
    mesh = make_mesh(len(jax.devices()), 1)
    from .grouped import GroupedRoundEngine

    g = GroupedRoundEngine(cfg, mesh)
    mode, _ = g._fused_layout()
    assert mode == "slices", f"probe needs the slices layout, got {mode}"
    out["slices"] = {str(r): [int(lo), int(hi)]
                     for r, (lo, hi) in g._slices.items()}

    data = tuple(jnp.asarray(a) for a in data_host)  # staticcheck: allow(no-asarray): once-per-run probe staging
    users, rates = _schedules(cfg, 1, k, num_active)
    params = make_model(cfg).init(jax.random.key(0))
    host_key = jax.random.key(0)

    # superstep 1: the probe payload (also the compile warmup)
    p, pend = g.train_superstep(params, host_key, 1, k, users, rates, data)
    ms = pend.fetch()
    # superstep 2: steady-state timing from the updated params
    users2, rates2 = _schedules(cfg, 1 + k, k, num_active)
    t0 = time.perf_counter()  # staticcheck: allow(no-wallclock): probe timing at dispatch boundaries, outside any trace
    p, pend2 = g.train_superstep(p, host_key, 1 + k, k, users2, rates2, data)
    pend2.fetch()
    dt = time.perf_counter() - t0  # staticcheck: allow(no-wallclock): probe timing at dispatch boundaries, outside any trace
    out["rounds_per_sec"] = k / dt
    out["superstep_s"] = dt

    # wire classification against the REAL process grid (aot.py's idiom)
    dcn_axes = dcn_axes_of(mesh)
    out["dcn_axes"] = list(dcn_axes)
    per_dev = None
    for (kk, pd, md, *_rest) in list(g._superstep_progs):
        if kk == k and md == "slices":
            per_dev = pd
    assert per_dev is not None, "slices superstep program not compiled"
    prog = g._superstep_prog(k, per_dev, "slices")
    sched_aval = jax.ShapeDtypeStruct((k, per_dev * mesh.shape["clients"]),
                                      np.int32)
    data_avals = tuple(jax.ShapeDtypeStruct(a.shape, a.dtype) for a in data)
    traced = prog.trace(params, host_key, np.int32(1), sched_aval,
                        *data_avals)
    wire = program_wire(traced.jaxpr, mesh,
                        dcn_axes=dcn_axes if dcn_axes else None)
    reshards = find_reshards(traced.jaxpr)
    out["wire"] = {kk: wire[kk] for kk in
                   ("train_bytes_per_round", "eval_bytes_total",
                    "other_bytes", "dcn_bytes")}
    out["reshards"] = len(reshards)
    out["dcn_one_reduction"] = bool(
        n_proc <= 1 or (wire["dcn_bytes"] == wire["train_bytes_per_round"]
                        and wire["other_bytes"] == 0))

    # per-process checkpoint write: the live blob (replicated params ->
    # header write + barrier) AND a clients-sharded leaf exercising the
    # per-process shard files
    host_params = {n: host_fetch(v) for n, v in p.items()}
    ck = os.path.join(out_dir, "ckpt", "probe.ckpt")
    os.makedirs(os.path.dirname(ck), exist_ok=True)
    t0 = time.perf_counter()  # staticcheck: allow(no-wallclock): probe timing at dispatch boundaries, outside any trace
    save_checkpoint_sharded(ck, {"epoch": k, "params": host_params})
    out["ckpt_write_s"] = time.perf_counter() - t0  # staticcheck: allow(no-wallclock): probe timing at dispatch boundaries, outside any trace

    rng = np.random.default_rng(7)  # staticcheck: allow(no-fresh-rng): synthetic checkpoint payload, not an engine stream
    resid_host = rng.normal(size=(mesh.shape["clients"], 32)).astype(
        np.float32)
    resid = commit_global(resid_host, NamedSharding(mesh, P("clients")))
    cks = os.path.join(out_dir, "ckpt", "probe_sharded.ckpt")
    t0 = time.perf_counter()  # staticcheck: allow(no-wallclock): probe timing at dispatch boundaries, outside any trace
    save_checkpoint_sharded(cks, {"epoch": k, "resid": resid})
    out["ckpt_shard_write_s"] = time.perf_counter() - t0  # staticcheck: allow(no-wallclock): probe timing at dispatch boundaries, outside any trace
    loaded = load_checkpoint_sharded(cks)
    back = loaded["resid"]
    if is_shard_marker(back):
        back = dense_from_blocks(back)
    out["sharded_ckpt_ok"] = bool(np.array_equal(np.asarray(back),  # staticcheck: allow(no-asarray): probe result check
                                                 resid_host))

    if pid == 0:
        np.savez(os.path.join(out_dir, "params.npz"), **host_params)
        flat = {f"r{r}_{name}": np.asarray(v)  # staticcheck: allow(no-asarray): probe result persistence
                for r, md in enumerate(ms) for name, v in md.items()}
        np.savez(os.path.join(out_dir, "metrics.npz"), **flat)
    with open(os.path.join(out_dir, f"pod_result_p{pid}.json"), "w") as f:
        json.dump(out, f, sort_keys=True)
    return out


def _free_port() -> int:
    s = socket.socket()
    s.bind(("127.0.0.1", 0))
    port = s.getsockname()[1]
    s.close()
    return port


def run_pod_probe(out_dir: str, n_processes: int = 2,
                  local_devices: int = 4, k: int = 4, num_active: int = 4,
                  align: int = 0, timeout_s: int = 900) -> List[Dict[str, Any]]:
    """Spawn ``n_processes`` probe children over a shared coordinator and
    return their result dicts (index = process id).  ``n_processes=1``
    runs the single-process reference (no distributed runtime); pass
    ``align=<pod process count>`` there to pin the SAME host-aligned level
    partition the pod run takes -- the bitwise comparison needs identical
    slice boundaries.  Every child is forced onto host CPU devices, so the
    probe is safe to launch from a process that holds a chip."""
    os.makedirs(out_dir, exist_ok=True)
    env = dict(os.environ)
    # every child is forced onto host devices: a chip belongs to one
    # process, and this probe's processes never ask for it
    env["JAX_PLATFORMS"] = "cpu"
    env["XLA_FLAGS"] = (env.get("XLA_FLAGS", "")
                        + f" --xla_force_host_platform_device_count="
                          f"{local_devices}").strip()
    repo = os.path.dirname(os.path.dirname(
        os.path.dirname(os.path.abspath(__file__))))
    env["PYTHONPATH"] = repo + (os.pathsep + env["PYTHONPATH"]
                                if env.get("PYTHONPATH") else "")
    # the reference n_processes=1 run joins the distributed runtime too:
    # the gloo collectives layer fixes the reduction ASSOCIATION by global
    # device rank, so a 1-process gloo run is bit-identical to the
    # N-process one -- XLA's in-process allreduce associates differently
    # (1-2 f32 ULPs), which is exactly the gap the bitwise gate closes
    env["JAX_COORDINATOR_ADDRESS"] = f"127.0.0.1:{_free_port()}"
    env["JAX_NUM_PROCESSES"] = str(n_processes)
    argv = [sys.executable, "-m", "heterofl_tpu.parallel.pod", out_dir,
            "--k", str(k), "--active", str(num_active)]
    if align:
        argv += ["--align", str(align)]
    procs = []
    for i in range(n_processes):
        e = dict(env)
        e["JAX_PROCESS_ID"] = str(i)
        procs.append(subprocess.Popen(argv, env=e, text=True,
                                      stdout=subprocess.PIPE,
                                      stderr=subprocess.PIPE))
    outs = []
    for i, pr in enumerate(procs):
        try:
            so, se = pr.communicate(timeout=timeout_s)
        except subprocess.TimeoutExpired:
            for p2 in procs:
                p2.kill()
            raise RuntimeError(f"pod probe process {i} timed out after "
                               f"{timeout_s}s")
        if pr.returncode != 0:
            raise RuntimeError(f"pod probe process {i} failed "
                               f"(rc={pr.returncode}):\n{se[-3000:]}")
        outs.append((so, se))
    results = []
    for i in range(n_processes):
        with open(os.path.join(out_dir, f"pod_result_p{i}.json")) as f:
            results.append(json.load(f))
    return results


def bitwise_match(dir_a: str, dir_b: str) -> Dict[str, Any]:
    """Compare two probe output dirs' ``params.npz`` + ``metrics.npz``
    bit for bit.  Returns ``{"match": bool, "mismatches": [...]}``."""
    import numpy as np

    mismatches = []
    for fname in ("params.npz", "metrics.npz"):
        a = np.load(os.path.join(dir_a, fname))
        b = np.load(os.path.join(dir_b, fname))
        if sorted(a.files) != sorted(b.files):
            mismatches.append(f"{fname}: key sets differ")
            continue
        for kk in a.files:
            if not np.array_equal(a[kk], b[kk]):
                mismatches.append(f"{fname}:{kk}")
    return {"match": not mismatches, "mismatches": mismatches}


if __name__ == "__main__":
    import argparse

    ap = argparse.ArgumentParser()
    ap.add_argument("out_dir")
    ap.add_argument("--k", type=int, default=4)
    ap.add_argument("--active", type=int, default=4)
    ap.add_argument("--align", type=int, default=0)
    a = ap.parse_args()
    res = child_main(a.out_dir, k=a.k, num_active=a.active, align=a.align)
    print(json.dumps(res, sort_keys=True))
