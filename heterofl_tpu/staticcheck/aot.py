"""AOT v4-128 multi-host audit (ISSUE 17): classify the flagship grouped
slices fused superstep against a REAL pod topology's process grid.

The fake-mesh entries in audit.py prove the wire model holds when the
clients axis is *declared* cross-host; this module proves the same against
an actual ``v4-128`` device grid -- 64 megacore chips over 16 hosts, the
ROADMAP's >=10 rounds/sec target topology -- where
:func:`~.wire.dcn_axes_of` derives the DCN axes from each device's
``process_index`` instead of an override.  The engine's host-aligned
slices partition (``_clients_row_chunks``) sees the same grid, so the
audit exercises the exact placement a pod run would take.

One process per chip: a process that describes a TPU topology loads the
TPU's library and keeps it until it exits, so everything runs in a
SUBPROCESS under a hard timeout, and neither child ever claims a device --
the ``tpu`` child compiles for a topology that is DESCRIBED, not attached
(the staticcheck CLI, its only caller, has pinned ``JAX_PLATFORMS=cpu``
before it spawns, and the child inherits that), the ``cpu`` child is forced
onto host devices:

* child ``tpu``: ``jax.experimental.topologies.get_topology_desc`` for
  v4-128, mesh over the topology devices, trace + AOT-lower the fused
  slices program, classify DCN from the real process grid.
* child ``cpu`` (fallback): 64 forced host devices in 1 process -- the
  same program and mesh SHAPE, with ``dcn_axes=("clients",)`` supplied
  explicitly (recorded as synthetic).

Results land in ``report.config["aot_v4128"]`` ONLY -- never as a program
entry -- so the ratchet baseline stays stable across environments where
the TPU path is (un)available.  The audit fails only on an actual budget
violation from a child that RAN; unavailability is recorded, not fatal.
"""

from __future__ import annotations

import json
import os
import subprocess
import sys
from typing import Any, Dict, Optional

#: v4-128: 4x4x8 chip grid, megacore (one device per chip), 4 chips/host
V4128 = {"name": "v4-128", "topology_name": "v4:4x4x8",
         "chip_config_name": "megacore",
         "chips_per_host_bounds": (2, 2, 1), "devices": 64, "processes": 16}


def _child_payload(mode: str, flagship: bool) -> Dict[str, Any]:
    """Runs INSIDE the subprocess: build the mesh (topology or forced-host
    CPU), trace the fused grouped-slices superstep, price + classify its
    collectives, attempt the AOT lowering.  Returns a plain JSON-able
    dict; any exception is caught by the __main__ wrapper."""
    import numpy as np

    import jax

    from ..fed.core import level_byte_table
    from ..parallel import GroupedRoundEngine
    from ..parallel.grouped import _bucket_pow2
    from ..utils.optim import make_traced_lr_fn
    from .audit import _ceil_div, _sds, default_audit_cfg
    from .jaxpr_walk import find_reshards
    from .wire import dcn_axes_of, program_wire

    from jax.sharding import Mesh

    cfg = default_audit_cfg(flagship)
    out: Dict[str, Any] = {"mode": mode, "flagship": flagship}
    if mode == "tpu":
        from jax.experimental import topologies

        topo = topologies.get_topology_desc(
            V4128["name"], platform="tpu",
            topology_name=V4128["topology_name"],
            chip_config_name=V4128["chip_config_name"],
            chips_per_host_bounds=V4128["chips_per_host_bounds"],
            num_slices=1)
        devices = list(topo.devices)
        synthetic_dcn = None
    else:
        devices = list(jax.devices())
        synthetic_dcn = ("clients",)  # 1 process: declare the split
    n_dev = len(devices)
    mesh = Mesh(np.array(devices).reshape(n_dev, 1), ("clients", "data"))
    out["devices"] = n_dev
    out["processes"] = len({getattr(d, "process_index", 0) for d in devices})

    grp = GroupedRoundEngine(dict(cfg, level_placement="slices",
                                  strict_placement=True), mesh)
    grp._lr_fn = make_traced_lr_fn(cfg)
    mode_got, _ = grp._fused_layout()
    if mode_got != "slices":
        raise RuntimeError(f"fused layout refused slices on the {mode} "
                           f"mesh: {mode_got}")
    bt = level_byte_table(cfg)
    wire_top = bt[max(bt)]["wire_bytes"]
    k = 8
    per_level = 2
    need = max(_ceil_div(per_level, grp._slices[r][1] - grp._slices[r][0])
               for r in grp.levels)
    per_dev = _bucket_pow2(need)
    prog = grp._superstep_prog(k, per_dev, "slices")

    # params/key are real host values (init runs on the local CPU backend);
    # the data operands are avals only -- nothing is placed on the topology
    from ..models import make_model

    params = make_model(cfg).init(jax.random.key(0))
    key = jax.random.key(0)
    U = cfg["num_users"]
    from ..data import fetch_dataset, split_dataset, stack_client_shards, \
        label_split_masks

    ds = fetch_dataset(cfg["data_name"], synthetic=True, seed=0,
                       synthetic_sizes={"train": 2000 if flagship else 400,
                                        "test": 100})
    rng = np.random.default_rng(0)
    split, lsplit = split_dataset(ds, U, "iid", rng, classes_size=10)
    x, y, m = stack_client_shards(ds["train"].data, ds["train"].target,
                                  split["train"], list(range(U)))
    lm = label_split_masks(lsplit, U, 10)
    data = tuple(_sds(a.shape, a.dtype) for a in (x, y, m, lm))

    traced = prog.trace(params, key, np.int32(1),
                        _sds((k, per_dev * n_dev)), *data)
    jaxpr = traced.jaxpr
    dcn_axes = dcn_axes_of(mesh)
    out["real_dcn_axes"] = list(dcn_axes)
    out["synthetic_dcn_axes"] = synthetic_dcn is not None
    wire = program_wire(jaxpr, mesh,
                        dcn_axes=dcn_axes if dcn_axes else synthetic_dcn)
    reshards = find_reshards(jaxpr)
    out["dcn_axes"] = wire["dcn_axes"]
    out["dcn_bytes_per_round"] = wire["dcn_bytes"]
    out["train_bytes_per_round"] = wire["train_bytes_per_round"]
    out["budget_bytes"] = wire_top
    out["reshards_jaxpr"] = len(reshards)
    out["dcn_ok"] = (wire["dcn_bytes"] == wire_top
                     and wire["other_bytes"] == 0 and not reshards)
    try:
        prog.lower(params, key, np.int32(1),
                   _sds((k, per_dev * n_dev)), *data)
        out["lowered"] = True
    except Exception as e:  # recorded, not fatal: the wire audit above decides ok
        out["lowered"] = False
        out["lower_error"] = f"{type(e).__name__}: {e}"[:300]
    out["ok"] = bool(out["dcn_ok"])
    return out


def _spawn(mode: str, flagship: bool, timeout_s: int) -> Dict[str, Any]:
    env = dict(os.environ)
    # the cpu child needs 64 host devices to lay out the v4-128-shaped
    # mesh; the tpu child only describes a topology (see module docstring)
    if mode == "cpu":
        env["JAX_PLATFORMS"] = "cpu"
        env["XLA_FLAGS"] = (env.get("XLA_FLAGS", "")
                            + " --xla_force_host_platform_device_count=64").strip()
    try:
        proc = subprocess.run(
            [sys.executable, "-m", "heterofl_tpu.staticcheck.aot", mode]
            + (["--flagship"] if flagship else []),
            capture_output=True, text=True, timeout=timeout_s, env=env)
    except subprocess.TimeoutExpired:
        return {"mode": mode, "available": False,
                "reason": f"timed out after {timeout_s}s"}
    if proc.returncode != 0:
        return {"mode": mode, "available": False,
                "reason": (proc.stderr or proc.stdout or "")[-400:]}
    try:
        return {"available": True, **json.loads(proc.stdout.strip().splitlines()[-1])}
    except Exception as e:
        return {"mode": mode, "available": False,
                "reason": f"unparseable child output ({e}): "
                          f"{proc.stdout[-200:]}"}


def aot_v4128_check(flagship: bool = False, tpu_timeout_s: int = 120,
                    cpu_timeout_s: int = 420) -> Dict[str, Any]:
    """Best-effort v4-128 AOT audit: try the real TPU topology first, fall
    back to the 64-device CPU mesh with a declared DCN axis.  Always
    returns a record for ``report.config["aot_v4128"]``; ``ok`` is absent
    when no child could run (environment, not regression)."""
    res = _spawn("tpu", flagship, tpu_timeout_s)
    if not res.get("available"):
        fb = _spawn("cpu", flagship, cpu_timeout_s)
        fb["tpu_unavailable_reason"] = res.get("reason", "")[:400]
        return fb
    return res


if __name__ == "__main__":
    mode = sys.argv[1] if len(sys.argv) > 1 else "cpu"
    flagship = "--flagship" in sys.argv
    try:
        print(json.dumps(_child_payload(mode, flagship)))
    except Exception as e:  # noqa: BLE001 - parent records the reason
        print(f"{type(e).__name__}: {e}", file=sys.stderr)
        sys.exit(1)
