"""``python -m heterofl_tpu.chaos.drill`` -- run a driver under a fault
plan and assert the recovery contract (ISSUE 15).

The drill is the chaos harness's executable spec, shared verbatim by the
CLI and the tests:

* **kill drills** (:func:`run_kill_drill`): run a small synthetic
  federation uninterrupted, then run it again with a
  :class:`~heterofl_tpu.chaos.FaultInjector` killing at the planned
  driver boundaries (plus optional checkpoint-byte corruptions applied
  between the kill and the resume), resuming a FRESH experiment from disk
  after every kill.  Contract: the recovered run's final params are
  **bitwise identical** to the uninterrupted run's -- every per-round
  stream is keyed by (host key, epoch), so a replay from any checkpoint
  generation lands on the same trajectory.
* **poison drills** (:func:`run_poison_drill`): NaN-poison a drawn
  (round, uid) client update and prove the run completes without human
  intervention -- either the in-program quarantine gate zeroes the
  contribution (``mode='quarantine'``), or the watchdog's
  ``action='rollback'`` restores the last good generation and replays
  with a salted cohort stream (``mode='rollback'``).
  :func:`pick_poison_uid` chooses a uid that IS drawn at the poisoned
  round but is NOT drawn by any retry's salted stream, so the rollback
  recovery is deterministic, not probabilistic.

Exit code 0 iff every drilled contract holds; the report is one JSON
object on stdout (``--json``) or a human summary.
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import time
from typing import Any, Dict, List, Optional

_REPO = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def _pin_cpu() -> None:
    """Force a multi-device virtual CPU platform BEFORE jax initialises
    (the staticcheck __main__ convention: the drill never claims an
    accelerator)."""
    os.environ["JAX_PLATFORMS"] = "cpu"
    flags = os.environ.get("XLA_FLAGS", "")
    if "xla_force_host_platform_device_count" not in flags:
        os.environ["XLA_FLAGS"] = \
            (flags + " --xla_force_host_platform_device_count=8").strip()


def drill_cfg(out_dir: str, **over) -> Dict[str, Any]:
    """The drill's small synthetic federation (the tests' _driver_cfg
    shape): 8 users, two rate levels, tiny conv widths, 4 rounds."""
    from .. import config as C

    cfg = C.default_cfg()
    cfg["control"] = C.parse_control_name("1_8_0.5_iid_fix_a1-b1_bn_1_1")
    cfg["data_name"] = "MNIST"
    cfg["model_name"] = "conv"
    cfg["synthetic"] = True
    cfg["synthetic_sizes"] = {"train": 80, "test": 40}
    cfg["output_dir"] = out_dir
    cfg["override"] = {"num_epochs": {"global": 4, "local": 1},
                       "conv": {"hidden_size": [4, 8]},
                       "batch_size": {"train": 10, "test": 20},
                       # the drill's contracts NEED the shared epoch-keyed
                       # sampling stream ('prp', the default): the legacy
                       # 'perm' numpy stream is stateful, so a resumed run
                       # could not replay bitwise and pick_poison_uid
                       # could not predict the K=1 draws -- pinned
                       # explicitly so a default change cannot silently
                       # break the drill
                       "sampler": "prp",
                       "superstep_rounds": 2, "eval_interval": 2, **over}
    return C.process_control(cfg)


def _final_params(result) -> Dict[str, Any]:
    import numpy as np

    return {k: np.asarray(v) for k, v in result["params"].items()}


def _params_equal(a: Dict[str, Any], b: Dict[str, Any]) -> bool:
    import numpy as np

    return set(a) == set(b) and all(
        a[k].shape == b[k].shape and np.array_equal(a[k], b[k],
                                                    equal_nan=True)
        for k in a)


def _run_once(cfg: Dict[str, Any], seed: int, injector=None):
    from ..entry.common import FedExperiment
    from ..utils.compile_cache import no_persistent_cache

    # fresh compiles only (no_persistent_cache docstring): in-process
    # kill -> resume with programs deserialized from a warm cache trips
    # the known XLA:CPU donation bug into nondeterministic params
    with no_persistent_cache():
        exp = FedExperiment(cfg, seed)
        exp.chaos = injector
        return exp, exp.run("Global-Accuracy")


def run_kill_drill(plan, cfg_over: Dict[str, Any], out_root: str,
                   seed: int = 0, max_resumes: int = 8) -> Dict[str, Any]:
    """One kill-plan drill: reference run, then kill/corrupt/resume until
    completion; asserts bitwise-equal final params.  ``plan`` is a
    :class:`~heterofl_tpu.chaos.FaultPlan` (poison field ignored here)."""
    from ..chaos import ChaosKill, FaultInjector, corrupt_blob
    from ..utils.checkpoint import checkpoint_path, generation_path

    t0 = time.time()
    cfg_ref = drill_cfg(os.path.join(out_root, "ref"), **cfg_over)
    _, ref = _run_once(cfg_ref, seed)
    ref_params = _final_params(ref)

    cfg_ch = drill_cfg(os.path.join(out_root, "chaos"), **cfg_over)
    injector = FaultInjector(plan)
    resumes, corruptions, applied_corrupt = 0, [], False
    while True:
        cfg_run = dict(cfg_ch, resume_mode=0 if resumes == 0 else 1)
        try:
            exp, res = _run_once(cfg_run, seed, injector)
            break
        except ChaosKill as ck:
            # a real kill -9 frees the process; the in-process simulation
            # must free the dead run's device state explicitly -- the
            # traceback's frame cycle otherwise keeps the killed run's
            # donated buffers alive into the resume, which trips the
            # repo's known XLA:CPU deserialized-executable donation bug
            # (MEASUREMENTS.md Round 10) into nondeterministic params on
            # a warm compile cache
            ck.__traceback__ = None
            import gc

            gc.collect()
            resumes += 1
            if resumes > max_resumes:
                raise RuntimeError(
                    f"kill drill did not converge after {max_resumes} "
                    f"resumes (last kill: {ck})")
            if not applied_corrupt and plan.corrupt:
                # corruptions land between the kill and the resume: the
                # resume must fall back loudly to an older generation
                applied_corrupt = True
                from .. import config as C

                tag = C.make_model_tag(seed, cfg_ch)
                for c in plan.corrupt:
                    p = generation_path(
                        checkpoint_path(cfg_ch["output_dir"], tag,
                                        c["which"]), c["generation"])
                    if os.path.exists(p):
                        corruptions.append(corrupt_blob(p, c["mode"]))
    chaos_params = _final_params(res)
    ok = _params_equal(ref_params, chaos_params)
    return {"drill": "kill", "ok": ok,
            "plan": {"kills": plan.kills, "corrupt": plan.corrupt},
            "kills_fired": injector.fired, "resumes": resumes,
            "corruptions": corruptions,
            "bitwise_equal": ok,
            "wall_sec": round(time.time() - t0, 2)}


def pick_poison_uid(cfg: Dict[str, Any], seed: int, round_: int,
                    max_retries: int = 3) -> Optional[int]:
    """A uid drawn in round ``round_``'s cohort under the base stream but
    NOT drawn by that round under ANY of the first ``max_retries`` salted
    retry streams -- so a rollback recovery deterministically dodges the
    poison on its first replay (and every later one)."""
    import math

    import jax
    import numpy as np

    from ..fed.core import superstep_user_schedule
    from ..fed.sampling import resolve_sampler_cfg
    from ..obs.watchdog import RETRY_SALT
    from ..sched import resolve_schedule_cfg

    sched = resolve_schedule_cfg(cfg)
    samp = resolve_sampler_cfg(cfg).kind
    users = cfg["num_users"]
    active = int(math.ceil(cfg["frac"] * users))

    def row(key):
        r = np.asarray(superstep_user_schedule(key, round_, 1, users, active,
                                               schedule=sched, sampler=samp))
        return {int(u) for u in r[0] if u >= 0}

    base = jax.random.key(seed)
    orig = row(base)
    key = base
    retry_rows = []
    for n in range(1, max_retries + 1):
        key = jax.random.fold_in(key, RETRY_SALT + n)
        retry_rows.append(row(key))
    # prefer a uid absent from EVERY retry draw; dodging the FIRST retry
    # alone is already sufficient (a clean first replay completes the run,
    # so later salted streams never execute)
    for u in sorted(orig):
        if all(u not in rr for rr in retry_rows):
            return u
    for u in sorted(orig):
        if u not in retry_rows[0]:
            return u
    return None


def _read_log(cfg: Dict[str, Any], tag: str) -> List[Dict[str, Any]]:
    path = os.path.join(cfg["output_dir"], "runs", f"train_{tag}",
                        "log.jsonl")
    if not os.path.exists(path):
        return []
    return [json.loads(line) for line in open(path)]


def run_poison_drill(mode: str, cfg_over: Dict[str, Any], out_root: str,
                     seed: int = 0, poison_round: int = 3,
                     max_retries: int = 3) -> Dict[str, Any]:
    """One poison drill: NaN-poison a drawn (round, uid) update and prove
    the run completes -- ``mode='quarantine'`` via the in-program gate,
    ``mode='rollback'`` via watchdog auto-rollback (telemetry on,
    zero-backoff for the drill).  Returns the contract report including
    the rollback MTTR (trip -> first replayed train record)."""
    import numpy as np

    if mode not in ("quarantine", "rollback"):
        raise ValueError(f"Not valid poison drill mode: {mode!r} "
                         f"('quarantine' or 'rollback')")
    t0 = time.time()
    base_cfg = drill_cfg(os.path.join(out_root, mode), **cfg_over)
    uid = pick_poison_uid(base_cfg, seed, poison_round,
                          max_retries=max_retries)
    if uid is None:
        raise RuntimeError(
            f"no dodgeable poison uid at round {poison_round}: every "
            f"cohort member recurs in all {max_retries} salted redraws "
            f"(grow num_users or lower frac)")
    over = dict(cfg_over, chaos_poison=[[poison_round, int(uid)]])
    if mode == "quarantine":
        over["quarantine"] = "on"
    else:
        over["telemetry"] = "on"
        over["watchdog"] = {"action": "rollback", "max_retries": max_retries,
                            "backoff": 0.0}
    cfg = drill_cfg(os.path.join(out_root, mode), **over)
    exp, res = _run_once(cfg, seed)
    params = _final_params(res)
    finite = all(bool(np.all(np.isfinite(v))) for v in params.values())
    log = _read_log(cfg, exp.tag)
    report: Dict[str, Any] = {
        "drill": f"poison-{mode}", "poison": [poison_round, int(uid)],
        "final_params_finite": finite,
        "wall_sec": round(time.time() - t0, 2)}
    if mode == "quarantine":
        quarantined = sum(int(r.get("quarantined") or 0) for r in log
                          if r.get("tag") == "obs"
                          and r.get("event") == "probes")
        report["quarantined_total"] = quarantined
        report["ok"] = finite and quarantined >= 1
    else:
        trips = [r for r in log if r.get("tag") == "obs"
                 and r.get("event") == "watchdog"]
        recoveries = [r for r in log if r.get("tag") == "recovery"]
        report["trips"] = len(trips)
        report["recoveries"] = len(recoveries)
        report["escalated_to_abort"] = False  # run() raised otherwise
        mttr = None
        if trips and recoveries:
            t_trip = trips[0]["t"]
            after = [r["t"] for r in log if r.get("tag") == "train"
                     and r["t"] > recoveries[-1]["t"]]
            if after:
                mttr = round(min(after) - t_trip, 3)
        report["mttr_sec"] = mttr
        report["ok"] = finite and len(recoveries) >= 1
    return report


def run_smoke(out_root: str, json_out: bool = False) -> int:
    """The CI smoke: ONE kill plan (die before the 2nd checkpoint write,
    bitwise resume) + ONE poison plan (rollback recovery), tiny widths."""
    from ..chaos import resolve_fault_plan

    reports = []
    plan = resolve_fault_plan({"kills": [{"point": "checkpoint", "at": 2}]})
    reports.append(run_kill_drill(plan, {}, os.path.join(out_root, "kill")))
    reports.append(run_poison_drill("rollback", {},
                                    os.path.join(out_root, "poison")))
    ok = all(r["ok"] for r in reports)
    out = {"smoke": True, "ok": ok, "drills": reports}
    print(json.dumps(out) if json_out
          else "\n".join(f"[{'ok' if r['ok'] else 'FAIL'}] {r['drill']}: "
                         + json.dumps({k: v for k, v in r.items()
                                       if k not in ('drill', 'ok')})
                         for r in reports))
    return 0 if ok else 1


def main(argv=None) -> int:
    parser = argparse.ArgumentParser(
        prog="python -m heterofl_tpu.chaos.drill",
        description="chaos drill: kill/corrupt/poison a driver run and "
                    "assert the recovery contract")
    parser.add_argument("--plan", default=None,
                        help="JSON fault plan: {kills: [{point, at}], "
                             "corrupt: [{which, mode, generation}], "
                             "poison: [[round, uid]]}")
    parser.add_argument("--poison-mode", default="rollback",
                        choices=("quarantine", "rollback"),
                        help="recovery mechanism for poison drills")
    parser.add_argument("--strategy", default="masked",
                        choices=("masked", "grouped"))
    parser.add_argument("--store", default="eager",
                        choices=("eager", "stream"))
    parser.add_argument("--superstep", type=int, default=2)
    parser.add_argument("--rounds", type=int, default=4)
    parser.add_argument("--seed", type=int, default=0)
    parser.add_argument("--out", default=None,
                        help="work dir (default: a tempdir)")
    parser.add_argument("--smoke", action="store_true",
                        help="the CI smoke: one kill + one rollback poison")
    parser.add_argument("--json", action="store_true")
    args = parser.parse_args(argv)
    _pin_cpu()
    # NOTE: deliberately no enable_persistent_cache() here -- every drill
    # sub-run compiles fresh inside no_persistent_cache() (_run_once)
    out_root = args.out or os.path.join(
        os.environ.get("TMPDIR", "/tmp"), f"chaos_drill_{os.getpid()}")
    if args.smoke:
        return run_smoke(out_root, json_out=args.json)
    over = {"strategy": args.strategy, "client_store": args.store,
            "superstep_rounds": args.superstep,
            "num_epochs": {"global": args.rounds, "local": 1}}
    from ..chaos import resolve_fault_plan

    plan = resolve_fault_plan(json.loads(args.plan) if args.plan
                              else {"kills": [{"point": "superstep",
                                               "at": 2}]})
    reports = []
    if plan.kills or plan.corrupt:
        reports.append(run_kill_drill(plan, over,
                                      os.path.join(out_root, "kill"),
                                      seed=args.seed))
    if plan.poison is not None:
        # the plan's poison rounds drive the drill; each pair drills
        # independently so one report names one contract
        for r, _u in [tuple(p) for p in plan.poison.tolist()]:
            reports.append(run_poison_drill(
                args.poison_mode, over,
                os.path.join(out_root, f"poison_r{r}"), seed=args.seed,
                poison_round=int(r)))
    ok = all(r["ok"] for r in reports) and bool(reports)
    out = {"ok": ok, "drills": reports}
    print(json.dumps(out) if args.json else
          "\n".join(f"[{'ok' if r['ok'] else 'FAIL'}] {r['drill']}: "
                    + json.dumps({k: v for k, v in r.items()
                                  if k not in ('drill', 'ok')})
                    for r in reports))
    return 0 if ok else 1


if __name__ == "__main__":
    sys.exit(main())
