#!/usr/bin/env python
"""Headline benchmark: federated rounds/sec on the BASELINE.json config --
100-client CIFAR10 ResNet-18, 5-level heterogeneity a1-b1-c1-d1-e1, 10 active
clients x 5 local epochs x 50 steps per round, full HeteroFL semantics
(masked widths, Scaler, sBN-free local BN, label masks, counted-average
aggregation), all inside one jitted round program.

One process, one device claim.  `python bench.py` needs a TPU: without one
it exits non-zero and prints no record.  It prints one refined JSON line per
timed round -- {"metric", "value", "unit", "vs_baseline"}, vs_baseline being
rounds/sec relative to the 10 rounds/sec north star (BASELINE.json; the
reference itself publishes no wall-clock numbers) -- take the last.
BENCH_CPU=1 is the explicit CPU mode of the tests and of debugging: every
record it prints carries `degraded` and a null vs_baseline, never a number
comparable to the device metric.

Env knobs: BENCH_ROUNDS (timed rounds, default 5), BENCH_USERS (default 100),
BENCH_SYNTH_N (train images, default 50000), BENCH_CPU=1 (above; tiny widths
unless BENCH_REALWIDTH=1 keeps the flagship widths and cuts the per-client
data instead),
BENCH_STRATEGY=masked|grouped (primary engine), BENCH_SUPERSTEP=K to fuse K
rounds per compiled dispatch (train_superstep; phases amortize per round),
BENCH_BOTH=0/1 to disable/force the second-strategy record in
extra.strategies (default: on except in CPU mode),
BENCH_WIRE_CODEC=dense|int8|signsgd|topk (ISSUE 8) to compress the
aggregation payload inside the fused superstep (extra.wire then records the
measured compressed bytes/round and ratio_vs_dense next to the analytic
per-codec frontier, all from fed.core.level_codec_byte_table -- the same
table staticcheck budgets by equality), BENCH_FETCH_EVERY=K to batch the
D2H metric fetch, BENCH_EVAL_INTERVAL=E to
run the sBN+eval cadence every E rounds -- the primary record then uses the
EVAL-FUSED superstep (eval inside the compiled scan, ISSUE 4) and
extra.strategies carries `<engine>+eval-fused` vs `<engine>+eval-host`
rows, the host row paying the PR 2 clamp (dispatch windows shortened to
min(K, E)) plus a host `eval` phase per window.

BENCH_SCENARIO=<tokens> (ISSUE 9): the scheduler scenario matrix --
comma/plus separated tokens of {uniform, markov, trace, deadline,
buffered} building cfg['schedule'] (heterofl_tpu/sched/): markov/trace =
replayable on/off availability (p_on .7 / p_off .3), deadline = straggler
local-step truncation (min_frac .25), buffered = buffered-async
staleness-weighted aggregation (needs BENCH_SUPERSTEP>1).  Scenario runs
draw cohorts host-side through the one sampling stream and record
per-round participation stats + rounds/sec into extra.scenario.

BENCH_SAMPLER=prp|perm (ISSUE 11): the population sampler behind the one
sampling stream (cfg['sampler'], heterofl_tpu/fed/sampling.py) -- 'prp'
(default) is the O(active) pseudorandom-permutation index-map draw, 'perm'
the legacy full-permutation stream.  Every record carries extra.sampler: the
kind plus a host draw microbench of BOTH samplers at this run's population
(seconds per [1, A] schedule draw, prp-vs-perm speedup) -- at
BENCH_POPULATION=1e6 this is the O(U log U) -> O(active) acceptance
measurement.  The two samplers are DIFFERENT streams: the bench refuses to
record against a newest BENCH_r*.json drawn under the other sampler unless
BENCH_ALLOW_STREAM_CHANGE=1 (trajectory re-baseline must be deliberate).

BENCH_POPULATION=N (ISSUE 6): a population axis.  The federation grows to N
synthetic users (up to 1e6) WITHOUT densifying per-user stacks: users window
onto the shared synthetic sample pool via data.partition.span_population
(O(N) metadata) and the engines stream each dispatch's sampled cohort
through the ClientStore + stage_cohort pipeline, prefetching dispatch i+1's
cohort while dispatch i computes (heavy-traffic sampling: BENCH_ACTIVE
clients/round, default 10, round after round out of N users).
extra.population records the store metadata bytes, peak host RSS
(ru_maxrss) and the prefetched/sync staging counts -- with extra.phases'
`stage` row this is the stage-time-and-RSS-stay-flat-in-population
evidence.  The bench REFUSES to record a population run whose timed
dispatches fell back to synchronous staging unless BENCH_ALLOW_SYNC_STAGE=1
(the warmup dispatch is inherently synchronous and exempt);
BENCH_STREAM_SYNC=1 forces the sync path (the refusal's test hook).
Population runs pin eval off and the second-strategy record off by default,
and are labelled degraded (a different workload than the 100-user
flagship).

MFU (ISSUE 5): extra.mfu reports the analytic FLOPs/round from
fed.core.level_flop_table (expected over the uniform active-client draw)
and, when BENCH_PEAK_FLOPS is set (the hardware peak in FLOP/s, e.g.
2.75e14 for one v4 chip in bf16 x devices), the achieved model FLOP
utilisation mfu = flops_per_round * rounds_per_sec / peak.
BENCH_STEP_AB=1 additionally records the fused-epilogue vs reference-chain
step A/B into extra.step_ab: both measured with the shared procedure plus
the optimized-HLO scan-body kernel counts of the primary engine's hot
program (cfg['fused_update'] on vs off; the staticcheck step-body budget
gates the same counts).

BENCH_TELEMETRY=1 (ISSUE 10): the runtime-telemetry A/B -- one measure
with cfg['telemetry']='on' (in-program health probes riding the metrics
fetch, a TraceRecorder writing trace.json + events.jsonl under
BENCH_TRACE_DIR, default ./obs_trace) against one with telemetry off,
recorded into extra.obs with the overhead percentage, the last round's
probe record and the trace artifact path.  The watchdog (warn mode) runs
over every fetched round's probes; if it FIRED the A/B is refused --
extra.obs carries the trip evidence instead of on/off numbers, because a
rounds/sec figure measured through a diverging run is not a telemetry
overhead.  Needs BENCH_SUPERSTEP>1 for the grouped strategy; ignored in
population mode (the A/B measures the eager flagship program).

BENCH_ARMS=E (ISSUE 14): the experiment-arms multiplexer A/B -- ONE E-arm
fused superstep program vs E serial solo runs on equal per-arm devices,
into extra.arms (aggregate arm-rounds/sec both ways, speedup, compile
counts, peak RSS).  BENCH_ARMS_PLACEMENT=mesh (default when the device
count divides: each arm on its own mesh rows, executing concurrently) or
vmap (batched per device).  Needs BENCH_SUPERSTEP>1; skipped under
population/scenario/codec knobs.

BENCH_CHAOS=1 (ISSUE 15): the fault-tolerance drill measurements -- one
watchdog-rollback poison drill (seeded NaN client update, auto-recovery)
and one quarantine poison drill on the drill's small synthetic
federation, recorded into extra.chaos: rollback-recovery MTTR (trip ->
first replayed train record) and wall clock, trip/recovery counts, and
the quarantined-client count.  If the rollback recovery ESCALATES to
abort the record is refused -- extra.chaos carries the escalation
evidence instead of an MTTR, because a recovery time measured through a
run that needed human intervention is not a recovery time.

BENCH_POD=1 (ISSUE 17): the 2-process pod probe -- a real jax.distributed
CPU mesh (gloo collectives) runs the fused grouped-slices superstep with
the levels host-aligned on disjoint processes, recording per-process
rounds/sec + checkpoint-write times, the DCN classification from the real
process grid, and the bitwise-vs-single-process gate into extra.pod.
Refused when STATICCHECK.json reports a failed multi-host DCN budget
audit (extra.wire also carries the analytic per-link ICI-vs-DCN split
per strategy either way).

BENCH_LEDGER=1 (ISSUE 12): the population-observatory A/B -- one measure
with telemetry='hist' (cohort histograms riding the metrics fetch) PLUS a
host-side ClientLedger folded O(active) per fetch from the recomputed
schedule rows, against one with both off, recorded into extra.obs.ledger
(overhead percentage, resident ledger bytes + bytes/user, coverage, the
last hist record).  ledger.npz and the per-fetch {"tag":"ledger"} summary
lines land under BENCH_TRACE_DIR (default ./obs_trace) for
`python -m heterofl_tpu.obs.report`.  Unlike BENCH_TELEMETRY this runs in
population mode too -- BENCH_POPULATION=1e6 IS the acceptance scale for
the <= ~32 bytes/user resident bound.  Needs BENCH_SUPERSTEP>1 (the
schedule re-draw covers superstep dispatches); a fired warn-mode watchdog
refuses the record.

'value' is like-for-like across strategies: the average per-round seconds
over timed rounds EXCLUDING rounds that compiled a fresh program shape
(grouped slot-bucket compiles, superstep shape changes; detected via
engine.program_cache_size() growth), inverted to rounds/sec.
extra.compile_cache carries persistent-cache hit/miss counts so recompiles
are visible in the artifact.

Diagnosability: every stage (imported / devices acquired / data staged /
compile done / round k/N) is stamped on stderr, and a refined JSON line is
printed after EVERY timed round, so a run killed mid-way still leaves a real
measurement and the place it stopped.
"""

import json
import os
import sys
import time

_REPO = os.path.dirname(os.path.abspath(__file__))


def _load_staticcheck():
    """Summarise the STATICCHECK.json artifact (the staticcheck auditor's
    program report, ISSUE 3) for ``extra.staticcheck``: audit status, per-
    program peak temp bytes from ``memory_analysis()``, lint finding count.
    None when the artifact is absent/unreadable -- the bench still runs,
    but a FRESH failing audit makes the bench refuse to record (see main).

    ``stale`` flags an artifact older than the newest package source file:
    a stale green artifact proves nothing about the current tree (the
    record says so instead of implying a guarantee), and a stale FAILING
    artifact no longer blocks a tree that may already be fixed -- rerun
    ``python -m heterofl_tpu.staticcheck`` to refresh either way."""
    path = os.path.join(_REPO, "STATICCHECK.json")
    try:
        with open(path) as f:
            rec = json.load(f)
        artifact_mtime = os.path.getmtime(path)
    except (OSError, json.JSONDecodeError):
        return None
    newest_src = 0.0
    for dirpath, dirnames, filenames in os.walk(os.path.join(_REPO, "heterofl_tpu")):
        dirnames[:] = [d for d in dirnames if d != "__pycache__"]
        for fn in filenames:
            if fn.endswith(".py"):
                try:
                    newest_src = max(newest_src,
                                     os.path.getmtime(os.path.join(dirpath, fn)))
                except OSError:
                    pass
    progs = rec.get("programs") or {}
    mem = {name: (p.get("memory") or {}).get("temp_size_in_bytes")
           for name, p in progs.items()}
    # ratchet summary (ISSUE 7): ratchet_ok is None when the artifact was
    # produced without --diff-baseline; a checked-and-regressed ratchet
    # blocks recording the same way a failing audit does (see main)
    ratchet = rec.get("ratchet") or {}
    # DCN budget status (ISSUE 17): the multi-host program entries' wire
    # findings plus the AOT v4-128 record -- BENCH_POD refuses to record
    # pod numbers against a failed DCN budget audit.  None when the
    # artifact predates the multi-host matrix.
    mh_findings = [f for name, p in progs.items() if name.endswith("/mh")
                   for f in (p.get("findings") or [])]
    aot = (rec.get("config") or {}).get("aot_v4128") or {}
    dcn_audit_ok = None
    if any(name.endswith("/mh") for name in progs):
        dcn_audit_ok = (not mh_findings
                        and aot.get("ok", True) is not False)
    return {"ok": bool(rec.get("ok")),
            "dcn_audit_ok": dcn_audit_ok,
            "stale": newest_src > artifact_mtime,
            "generated_at": rec.get("generated_at"),
            "programs_audited": len(progs),
            "lint_findings": len(rec.get("lint") or []),
            "ratchet_ok": (bool(ratchet.get("ok"))
                           if ratchet.get("checked") else None),
            "ratchet_regressions": len(ratchet.get("regressions") or []),
            "program_temp_bytes": {k: v for k, v in mem.items() if v}}


def _latest_bench_record():
    """The newest committed BENCH_r*.json (by round number), or None: the
    baseline the sampling-stream comparability gate (ISSUE 11) checks this
    run's sampler kind against.  The loaded record carries its path under
    ``_path`` for the refusal message."""
    import re

    best, best_n = None, -1
    try:
        names = os.listdir(_REPO)
    except OSError:
        return None
    for fn in names:
        m = re.fullmatch(r"BENCH_r(\d+)\.json", fn)
        if m and int(m.group(1)) > best_n:
            best_n, best = int(m.group(1)), fn
    if best is None:
        return None
    try:
        with open(os.path.join(_REPO, best)) as f:
            rec = json.load(f)
    except (OSError, json.JSONDecodeError):
        return None
    if not isinstance(rec, dict):
        return None
    rec["_path"] = best
    return rec


def main():
    t_start = time.time()

    def hb(stage):
        # stage-stamped heartbeat on stderr: the LAST stamp says where a
        # killed or failed run stopped (device claim vs data staging vs
        # compile vs round k)
        print(f"bench: {stage} t=+{time.time() - t_start:.1f}s",
              file=sys.stderr, flush=True)

    # CPU mode defaults to the short-shard data volume (explicit BENCH_*
    # sizes win): it exists to exercise the program, not to time it
    cpu_mode = os.environ.get("BENCH_CPU") == "1"
    realwidth = os.environ.get("BENCH_REALWIDTH") == "1"
    if cpu_mode:
        os.environ["JAX_PLATFORMS"] = "cpu"
    from heterofl_tpu.utils.compile_cache import (enable_persistent_cache,
                                                  install_cache_counters)

    enable_persistent_cache()

    hb("importing jax")
    import jax
    import jax.numpy as jnp
    import numpy as np

    if os.environ.get("JAX_PLATFORMS") == "cpu":
        jax.config.update("jax_platforms", "cpu")
    from heterofl_tpu import config as C
    from heterofl_tpu.data import fetch_dataset, label_split_masks, split_dataset, stack_client_shards
    from heterofl_tpu.fed.core import round_users
    from heterofl_tpu.models import make_model
    from heterofl_tpu.parallel import (MetricsPipeline, PendingMetrics, PhaseTimer,
                                       RoundEngine, make_mesh)
    # persistent-compile-cache visibility (ISSUE 2 satellite): hit/miss
    # counts land in extra.compile_cache so a superstep recompile (a new
    # program shape per K) is attributable instead of silently eating the
    # ~40s flagship compile
    cache_counters = install_cache_counters()

    # staticcheck gate (ISSUE 3 satellite): a bench record against a tree
    # whose program audit FAILED would launder a known-broken round program
    # into the trajectory -- refuse (still one JSON line, rc 0) unless the
    # operator explicitly overrides.  An absent artifact does not block, and
    # a STALE one (older than the newest package source) neither blocks nor
    # vouches -- extra.staticcheck carries the stale flag either way.
    staticcheck = _load_staticcheck()
    if staticcheck is not None \
            and (not staticcheck["ok"] or staticcheck["ratchet_ok"] is False) \
            and not staticcheck["stale"] \
            and os.environ.get("BENCH_SKIP_STATICCHECK") != "1":
        what = ("a failing program audit" if not staticcheck["ok"]
                else "a regressed baseline ratchet")
        print(json.dumps({
            "metric": "federated_rounds_per_sec_cifar10_resnet18_a1-e1_100c",
            "value": 0.0, "unit": "rounds/sec", "vs_baseline": None,
            "extra": {"error": f"STATICCHECK.json reports {what}; refusing "
                               f"to record a bench run. Rerun `python -m "
                               f"heterofl_tpu.staticcheck --diff-baseline` "
                               f"(or set BENCH_SKIP_STATICCHECK=1 to "
                               f"override).",
                      "staticcheck": staticcheck},
        }), flush=True)
        return

    # sampling-stream comparability gate (ISSUE 11): a prp record landing
    # next to a perm baseline (or vice versa) compares two different seeded
    # trajectories as if they were one series -- refuse unless the operator
    # explicitly acknowledges the re-baseline.  Records before ISSUE 11
    # carry no extra.sampler and drew the legacy permutation stream.
    sampler_kind = os.environ.get("BENCH_SAMPLER", "") or "prp"
    if sampler_kind not in ("perm", "prp"):
        print(f"bench: ignoring unknown BENCH_SAMPLER={sampler_kind!r} "
              f"(one of perm|prp)", file=sys.stderr)
        sampler_kind = "prp"
    prev_bench = _latest_bench_record()
    if prev_bench is not None \
            and os.environ.get("BENCH_ALLOW_STREAM_CHANGE") != "1":
        prev_kind = ((prev_bench.get("extra") or {}).get("sampler") or {}) \
            .get("kind", "perm")
        if prev_kind != sampler_kind:
            print(json.dumps({
                "metric": "federated_rounds_per_sec_cifar10_resnet18_a1-e1_100c",
                "value": 0.0, "unit": "rounds/sec", "vs_baseline": None,
                "extra": {"error": f"sampling-stream change: this run draws "
                                   f"sampler={sampler_kind!r} but the newest "
                                   f"committed bench record "
                                   f"({prev_bench.get('_path')}) was drawn "
                                   f"under {prev_kind!r} -- every seeded "
                                   f"trajectory differs, so the records are "
                                   f"not comparable.  Set "
                                   f"BENCH_ALLOW_STREAM_CHANGE=1 to record "
                                   f"the deliberate re-baseline.",
                          "sampler": {"kind": sampler_kind,
                                      "previous_kind": prev_kind}},
            }), flush=True)
            return

    hb("claiming devices")
    devs = jax.devices()
    platform = devs[0].platform
    hb(f"devices acquired: {len(devs)}x {platform}")
    if platform != "tpu" and not cpu_mode:
        # a measurement path that finds no chip fails; it never falls back
        sys.exit(f"bench: no TPU (jax found {platform!r}); set BENCH_CPU=1 "
                 f"for the explicit, degraded CPU mode")

    # Both CPU modes keep the flagship's 100-user/10-active federation
    # structure (VERDICT r4 item 5); the tiny one shrinks widths, the
    # real-width one shrinks only per-round data volume.
    users = int(os.environ.get("BENCH_USERS", "100"))
    # BENCH_POPULATION=N (ISSUE 6): grow the federation to N streaming users
    try:
        population = int(float(os.environ.get("BENCH_POPULATION", "0") or 0))
    except ValueError:
        print(f"bench: ignoring malformed BENCH_POPULATION="
              f"{os.environ['BENCH_POPULATION']!r}", file=sys.stderr)
        population = 0
    if population:
        users = population
    n_train = int(os.environ.get("BENCH_SYNTH_N",
                                 "2000" if (cpu_mode or realwidth) else "50000"))
    timed_rounds = int(os.environ.get("BENCH_ROUNDS",
                                      "1" if realwidth else "2" if cpu_mode else "5"))

    cfg = C.default_cfg()
    cfg["control"] = C.parse_control_name(f"1_{users}_0.1_iid_fix_a1-b1-c1-d1-e1_bn_1_1")
    cfg["data_name"] = "CIFAR10"
    cfg["model_name"] = "resnet18"
    cfg["sampler"] = sampler_kind  # ISSUE 11 (validated by process_control)
    cfg["synthetic"] = True
    # bf16 matmul/conv operands with f32 accumulation: the TPU MXU recipe.
    cfg["compute_dtype"] = os.environ.get("BENCH_DTYPE", "bfloat16")
    cfg = C.process_control(cfg)

    hidden = os.environ.get("BENCH_HIDDEN")
    degraded = None
    if hidden:  # debug-only shrink, e.g. BENCH_HIDDEN=8,16,16,16
        cfg["resnet"] = {"hidden_size": [int(h) for h in hidden.split(",")]}
        degraded = f"hidden-shrink-{hidden}"  # never comparable to baseline
    elif platform == "cpu" and realwidth:
        # flagship widths and federation structure; only the per-client data
        # volume (and with it local steps/round) is cut so a single core can
        # finish -- slow but the right program
        degraded = "cpu-real-width-short-shards"
        cfg["num_epochs"] = dict(cfg["num_epochs"], local=1)
    elif platform == "cpu":
        # tiny widths: the CPU mode exercises the program, it times nothing
        cfg["resnet"] = {"hidden_size": [8, 16, 16, 16]}
        degraded = "cpu-tiny-width"
    if population:
        # a different federation (N users, fixed 10-client cohorts) -- never
        # comparable to the 100-user 10 rps north star
        degraded = f"population-{population}" + (f"+{degraded}" if degraded else "")
    if platform == "cpu":
        # XLA:CPU executes the client-vmapped grouped conv catastrophically
        # (measured 3.7x round slowdown); the numerically-identical im2col
        # lowering is the right default off-TPU (MEASUREMENTS.md round 4)
        cfg["conv_impl"] = os.environ.get("BENCH_CONV_IMPL", "im2col")

    ds = fetch_dataset("CIFAR10", synthetic=True, seed=0,
                       synthetic_sizes={"train": n_train, "test": 1000})
    store = None
    pop_stats = {"prefetched": 0, "sync": 0}
    pop_prefetch = os.environ.get("BENCH_STREAM_SYNC") != "1"
    if population:
        # streaming population (ISSUE 6): users window onto the shared
        # synthetic pool -- O(population) metadata, no [U, ...] stacks, the
        # flagship per-user shard volume (500 samples) regardless of N
        from heterofl_tpu.data import span_population
        from heterofl_tpu.parallel import ClientStore

        cfg["client_store"] = "stream"
        shard = min(int(os.environ.get("BENCH_POP_SHARD", "500")), n_train)
        starts, sizes = span_population(n_train, population, shard)
        store = ClientStore.from_spans(ds["train"].data, ds["train"].target,
                                       starts, sizes, 10)
        split = lsplit = None
        x = np.zeros((0, shard), np.int8)  # population mode never stacks
        lm = None
        if os.environ.get("BENCH_EVAL_INTERVAL"):
            print("bench: BENCH_EVAL_INTERVAL ignored in population mode "
                  "(local eval is O(population); the axis measures staging)",
                  file=sys.stderr)
            os.environ["BENCH_EVAL_INTERVAL"] = "0"
    else:
        rng = np.random.default_rng(0)
        split, lsplit = split_dataset(ds, users, "iid", rng)
        x, y, m = stack_client_shards(ds["train"].data, ds["train"].target, split["train"],
                                      list(range(users)))
        lm = label_split_masks(lsplit, users, 10)
    cfg["classes_size"] = 10
    model = make_model(cfg)
    params = model.init(jax.random.key(0))
    mesh = make_mesh(len(devs), 1)
    # BENCH_STRATEGY=grouped: rate-grouped dense per-level programs
    # (parallel/grouped.py) instead of the masked full-width engine -- the
    # on-device A/B for the ~3.9x FLOP reduction (MEASUREMENTS.md roofline)
    strategy = os.environ.get("BENCH_STRATEGY", "masked")
    rates_vec = np.asarray(cfg["model_rate"], np.float32)
    # BENCH_WIRE_CODEC (ISSUE 8): compress the aggregation payload inside
    # the fused round (heterofl_tpu/compress/).  Lossy codecs need the
    # fused superstep (the grouped K=1 path has no single global psum), so
    # a codec without BENCH_SUPERSTEP>1 falls back to dense with a note --
    # the bench must still print its one JSON line.
    wire_codec = os.environ.get("BENCH_WIRE_CODEC", "dense") or "dense"
    try:
        from heterofl_tpu.compress import resolve_codec_cfg

        wire_codec, _ = resolve_codec_cfg({"wire_codec": wire_codec})
    except ValueError as e:
        print(f"bench: ignoring BENCH_WIRE_CODEC: {e}", file=sys.stderr)
        wire_codec = "dense"
    try:
        _superstep_env = int(os.environ.get("BENCH_SUPERSTEP") or 1)
    except ValueError:
        _superstep_env = 1  # env_int warns + defaults later; keep its rule
    if wire_codec != "dense" and _superstep_env <= 1:
        print(f"bench: BENCH_WIRE_CODEC={wire_codec} needs BENCH_SUPERSTEP>1 "
              f"(compression lives in the fused superstep); falling back to "
              f"dense", file=sys.stderr)
        wire_codec = "dense"
    cfg["wire_codec"] = wire_codec

    # BENCH_SCENARIO (ISSUE 9): scheduler scenario matrix -- comma/plus
    # separated tokens of {uniform, markov, trace, deadline, buffered}
    # building cfg['schedule'] (markov availability p_on=.7/p_off=.3,
    # deadline min_frac=.25, buffered-async staleness .5).  Scenario runs
    # draw their cohorts HOST-side through the one sampling stream
    # (fed.core.superstep_user_schedule) so participation is countable, and
    # extra.scenario records the per-round active-slot statistics next to
    # the run's rounds/sec.
    scenario_raw = os.environ.get("BENCH_SCENARIO", "") or ""
    scenario_tokens = [t.strip() for t in scenario_raw.replace("+", ",").split(",")
                       if t.strip()]
    sched_cfg = {}
    for t in scenario_tokens:
        if t == "uniform":
            continue
        if t in ("markov", "trace"):
            # 'trace' records/replays the markov-generated availability
            # matrix -- the replayable-trace path with a built-in source
            sched_cfg.update({"kind": "markov",
                              "markov": {"p_on": 0.7, "p_off": 0.3,
                                         "length": 64, "seed": 0}})
        elif t == "deadline":
            sched_cfg["deadline"] = {"min_frac": 0.25}
        elif t == "buffered":
            sched_cfg["aggregation"] = "buffered"
        else:
            print(f"bench: ignoring unknown BENCH_SCENARIO token {t!r}",
                  file=sys.stderr)
    if sched_cfg.get("aggregation") == "buffered" and _superstep_env <= 1:
        print("bench: BENCH_SCENARIO buffered needs BENCH_SUPERSTEP>1 (the "
              "staleness buffer rides the fused scan carry); dropping the "
              "buffered token", file=sys.stderr)
        sched_cfg.pop("aggregation")
    sched_spec = None
    if sched_cfg:
        from heterofl_tpu.sched import resolve_schedule_cfg

        cfg["schedule"] = sched_cfg
        sched_spec = resolve_schedule_cfg(cfg)
    part_stats = {"filled": []}

    def track_participation(us):
        """Count filled (id >= 0) slots per drawn round -- the scenario's
        participation record."""
        if sched_spec is not None:
            part_stats["filled"].extend(
                (np.asarray(us) >= 0).sum(axis=1).tolist())

    def make_engine(strat, cfg_over=None):
        c = cfg if not cfg_over else dict(cfg, **cfg_over)
        if strat == "grouped":
            from heterofl_tpu.parallel import GroupedRoundEngine

            return GroupedRoundEngine(c, mesh)
        return RoundEngine(model, c, mesh)

    engine = make_engine(strategy)
    if population:
        data = None
        hb(f"population store built ({population} users, "
           f"{store.metadata_nbytes} metadata bytes; strategy {strategy})")
    else:
        data = (jnp.asarray(x), jnp.asarray(y), jnp.asarray(m), jnp.asarray(lm))
        hb(f"data staged + engine built (strategy {strategy})")

    if population:
        # heavy-traffic sampling: a bounded cohort per round, drawn from the
        # whole population round after round (frac*N would melt any host)
        n_active = int(os.environ.get("BENCH_ACTIVE", "10"))
    else:
        n_active = int(np.ceil(cfg["frac"] * users))
    # MFU account (ISSUE 5): analytic FLOPs per round from the ONE level
    # FLOP source of truth (fed.core.level_flop_table -- the same table the
    # staticcheck FLOP budget and scripts/grouped_flops.py consume),
    # expected over the uniform active-client draw; BENCH_PEAK_FLOPS (the
    # hardware peak in FLOP/s) turns it into achieved utilisation.
    from heterofl_tpu.fed.core import level_flop_table

    flop_table = level_flop_table(cfg)
    # wire account (ISSUE 7): the dense bytes-on-the-wire per fused round
    # from the analytic byte table (the SAME table the staticcheck wire
    # budget enforces by equality against the traced psum operands) -- the
    # recorded dense baseline the compressed-aggregation frontier lands
    # against.  Both strategies' fused rounds join ONE global reduction of
    # the level-a footprint (sums + count masks, f32); the per-level rows
    # are the sliced payloads of the grouped engine's K=1 per-level psums.
    from heterofl_tpu.compress import LOSSY_CODECS
    from heterofl_tpu.fed.core import level_byte_table, level_codec_byte_table
    from heterofl_tpu.staticcheck.wire import (codec_round_wire,
                                               dense_round_wire, link_split)

    byte_table = level_byte_table(cfg)
    top_rate = max(byte_table)
    dense_payload = byte_table[top_rate]["wire_bytes"]
    # per-codec compressed bytes/round from the SAME table staticcheck
    # budgets by equality against the traced psum operand avals (ISSUE 8:
    # no second bytes formula); `codecs` is the analytic frontier, the
    # per-strategy rows record what THIS run's engines actually moved
    # (both strategies' fused rounds reduce at the level-a footprint)
    n_dev_wire = mesh.shape["clients"]
    codec_bytes = {c: level_codec_byte_table(cfg, c, n_leaves=len(params))[top_rate]
                   for c in LOSSY_CODECS}

    def strategy_wire():
        if wire_codec == "dense":
            return dense_round_wire(byte_table[top_rate]["param_bytes"],
                                    n_dev_wire)
        return codec_round_wire(wire_codec, codec_bytes[wire_codec],
                                dense_payload, n_dev_wire)

    wire_extra = {
        "source": "fed.core.level_byte_table + level_codec_byte_table",
        "unit": "bytes/round",
        "codec": wire_codec,
        "per_level_wire_bytes": {f"{r:g}": v["wire_bytes"]
                                 for r, v in sorted(byte_table.items(),
                                                    reverse=True)},
        "codecs": {c: codec_round_wire(c, b, dense_payload, n_dev_wire)
                   for c, b in sorted(codec_bytes.items())},
        "strategies": {s: strategy_wire() for s in ("masked", "grouped")},
        # per-link ICI-vs-DCN split (ISSUE 17 satellite): the same
        # analytic payload priced per bidirectional-ring link -- all-ICI
        # at this run's process layout, plus the 2-process pod-probe
        # projection where the host-aligned slices placement puts exactly
        # h links on DCN (staticcheck.wire.link_split)
        "link_split": {s: {
            "this_run": link_split(
                dense_payload if wire_codec == "dense"
                else codec_bytes[wire_codec],
                n_dev_wire, jax.process_count()),
            "pod_2proc": link_split(
                dense_payload if wire_codec == "dense"
                else codec_bytes[wire_codec],
                n_dev_wire, 2),
        } for s in ("masked", "grouped")},
    }
    shard_n = store.shard_max if population else x.shape[1]
    local_steps = cfg["num_epochs"]["local"] * int(
        np.ceil(shard_n / cfg["batch_size"]["train"]))
    flops_per_round = n_active * local_steps * float(
        np.mean([flop_table[float(r)] for r in rates_vec]))
    try:
        peak_flops = float(os.environ.get("BENCH_PEAK_FLOPS") or 0) or None
    except ValueError:
        print(f"bench: ignoring malformed BENCH_PEAK_FLOPS="
              f"{os.environ['BENCH_PEAK_FLOPS']!r}", file=sys.stderr)
        peak_flops = None

    def mfu_extra(rps):
        out = {"analytic_flops_per_round": flops_per_round,
               "source": "fed.core.level_flop_table",
               "peak_flops": peak_flops}
        if peak_flops:
            out["mfu"] = round(flops_per_round * rps / peak_flops, 6)
        return out

    # stage/dispatch/compute/fetch attribution for every timed round, plus
    # BENCH_FETCH_EVERY>1 to pipeline the D2H metric fetch behind the next
    # round's dispatch (parallel/staging.py; default 1 = synchronous parity)
    timer = PhaseTimer()

    def env_int(name, default):
        try:
            return max(1, int(os.environ.get(name) or default))
        except ValueError:
            print(f"bench: ignoring malformed {name}={os.environ[name]!r}",
                  file=sys.stderr)
            return default

    # clamp to >=1 so the emitted fetch_every matches what the pipeline
    # actually does (MetricsPipeline clamps internally too)
    fetch_every = env_int("BENCH_FETCH_EVERY", 1)
    # BENCH_SUPERSTEP=K: fuse K rounds into one lax.scan program
    # (train_superstep) -- each timed dispatch then covers K rounds and the
    # phase breakdown is amortized per round (the ISSUE 2 acceptance metric)
    superstep = env_int("BENCH_SUPERSTEP", 1)
    # BENCH_EVAL_INTERVAL=E (ISSUE 4 satellite): sBN+eval cadence.  0 = off.
    try:
        eval_iv = max(0, int(os.environ.get("BENCH_EVAL_INTERVAL", "0") or 0))
    except ValueError:
        print(f"bench: ignoring malformed BENCH_EVAL_INTERVAL="
              f"{os.environ['BENCH_EVAL_INTERVAL']!r}", file=sys.stderr)
        eval_iv = 0
    evaluator = fused_ev = eval_local = eval_global = eval_sbn = None
    if eval_iv:
        # staged through the driver's own assembly (entry.common) so the
        # benched eval operands are laid out exactly as the driver commits
        from heterofl_tpu.entry.common import stage_eval_operands
        from heterofl_tpu.parallel.evaluation import Evaluator

        eval_sbn, eval_local, eval_global = stage_eval_operands(
            cfg, ds["train"], ds["test"], split["test"], lm)
        evaluator = Evaluator(model, cfg, mesh, seed=0)
        fused_ev = evaluator.fused(sbn_batches=eval_sbn, local_eval=eval_local,
                                   global_eval=eval_global)
    pipe = MetricsPipeline(fetch_every)
    base_key = jax.random.key(0)

    # sampler microbench (ISSUE 11): the host draw cost of ONE [1, A] round
    # schedule under BOTH samplers at THIS run's population, through the
    # very stream the run consumes (fed.core.superstep_user_schedule).  At
    # BENCH_POPULATION=1e6 this is the acceptance measurement: perm pays
    # the O(U log U) permutation, prp the O(active) index map.
    from heterofl_tpu.fed.core import superstep_user_schedule

    def _draw_sec(kind, reps=3):
        superstep_user_schedule(base_key, 1, 1, users, n_active,
                                sampler=kind)  # warm the dispatch caches
        best = float("inf")
        for i in range(reps):
            t0 = time.time()
            superstep_user_schedule(base_key, 2 + i, 1, users, n_active,
                                    sampler=kind)
            best = min(best, time.time() - t0)
        return best

    hb(f"sampler microbench (kind {sampler_kind}, {users} users)")
    _draw = {k: _draw_sec(k) for k in ("prp", "perm")}
    sampler_extra = {
        "kind": sampler_kind,
        "users": users,
        "num_active": n_active,
        "draw_sec": {k: round(v, 6) for k, v in _draw.items()},
        "speedup_prp_vs_perm": round(_draw["perm"] / max(_draw["prp"], 1e-9),
                                     2),
        "source": "fed.core.superstep_user_schedule([1, A] draw, best of 3)",
    }
    hb(f"sampler draw: prp {_draw['prp']:.4f}s perm {_draw['perm']:.4f}s "
       f"({sampler_extra['speedup_prp_vs_perm']}x)")

    # population mode (ISSUE 6): per-engine prefetched cohorts -- dispatch
    # i+1's cohort stages while dispatch i's scanned program computes
    _pop_cohorts = {}

    def stage_pop(eng, strat, epoch0, k_disp, tmr):
        from heterofl_tpu.fed.core import superstep_rate_schedule

        with tmr.phase("sample"):
            us = superstep_user_schedule(base_key, epoch0, k_disp, users,
                                         n_active, schedule=sched_spec,
                                         sampler=sampler_kind)
        track_participation(us)
        if strat == "grouped":
            rates = superstep_rate_schedule(base_key, epoch0, k_disp, cfg, us)
            return eng.stage_cohort(store, us, rates, timer=tmr)
        return eng.stage_cohort(store, us, timer=tmr)

    def dispatch(eng, strat, params, i, tmr, rng_, eval_mode=None, k_disp=None):
        """One timed dispatch: a single round (superstep==1) or a fused
        K-round superstep -- with BENCH_EVAL_INTERVAL, either eval-fused
        (the mask rides the compiled scan) or host-loop (eval dispatched
        between windows under tmr.phase('eval'), PR 2 semantics).  Returns
        (params, PendingMetrics)."""
        k_disp = k_disp or superstep
        if store is not None:
            # streaming population: cohort staged ahead (prefetch depth 1);
            # the warmup dispatch (i=0) is inherently synchronous and exempt
            # from the sync-fallback refusal
            epoch0 = 1 + i * k_disp
            coh = _pop_cohorts.pop((id(eng), i), None)
            if coh is None:
                if i > 0:
                    pop_stats["sync"] += 1
                coh = stage_pop(eng, strat, epoch0, k_disp, tmr)
            else:
                pop_stats["prefetched"] += 1
            params, pending = eng.train_superstep(
                params, base_key, epoch0, k_disp, timer=tmr, cohort=coh)
            if pop_prefetch and i < timed_rounds:
                # the final timed dispatch has no successor; staging a
                # cohort for it would bill a full host gather + device
                # commit to the last round and never consume it
                _pop_cohorts[(id(eng), i + 1)] = stage_pop(
                    eng, strat, epoch0 + k_disp, k_disp, tmr)
            return params, pending
        if k_disp > 1:
            epoch0 = 1 + i * k_disp
            mask = None
            if eval_mode == "fused":
                mask = tuple((epoch0 + j) % eval_iv == 0 for j in range(k_disp))
                if not any(mask):
                    mask = None
            if strat == "grouped":
                with tmr.phase("sample"):
                    us = superstep_user_schedule(base_key, epoch0, k_disp,
                                                 users, n_active,
                                                 schedule=sched_spec,
                                                 sampler=sampler_kind)
                track_participation(us)
                params, pending = eng.train_superstep(
                    params, base_key, epoch0, k_disp, us, rates_vec[us], data,
                    timer=tmr, eval_mask=mask,
                    fused_eval=fused_ev if mask else None)
            else:
                us = None
                if sched_spec is not None:
                    # scenario runs take the host-drawn schedule (same
                    # stream as the in-jit draw) so participation is
                    # countable per round
                    with tmr.phase("sample"):
                        us = superstep_user_schedule(base_key, epoch0,
                                                     k_disp, users, n_active,
                                                     schedule=sched_spec,
                                                     sampler=sampler_kind)
                    track_participation(us)
                params, pending = eng.train_superstep(
                    params, base_key, epoch0, k_disp, data, user_schedule=us,
                    num_active=n_active, timer=tmr, eval_mask=mask,
                    fused_eval=fused_ev if mask else None)
        else:
            if sched_spec is not None:
                epoch = 1 + i
                with tmr.phase("sample"):
                    user_idx = np.asarray(round_users(
                        jax.random.fold_in(base_key, epoch), users, n_active,
                        avail=sched_spec.avail_row(epoch),
                        sampler=sampler_kind))
                track_participation(user_idx[None])
            elif sampler_kind == "perm":
                # the drivers' legacy numpy K=1 stream (reference parity)
                user_idx = rng_.permutation(users)[:n_active].astype(np.int32)
            else:
                with tmr.phase("sample"):
                    user_idx = np.asarray(round_users(
                        jax.random.fold_in(base_key, 1 + i), users, n_active,
                        sampler=sampler_kind))
            if strat == "grouped":
                params, pending = eng.train_round(
                    params, user_idx, rates_vec[user_idx], data, 0.1,
                    jax.random.key(i), timer=tmr, async_metrics=True)
            else:
                params, ms = eng.train_round(params, jax.random.key(i), 0.1,
                                             user_idx, data, timer=tmr)
                pending = PendingMetrics(ms)
        if eval_mode == "host":
            # the PR 2 host-loop eval: one host eval round-trip per window
            # CONTAINING an eval epoch (for eval_iv <= K the clamp makes
            # that the window's last round; for eval_iv > K windows the
            # cadence doesn't divide, the eval lands at the window end --
            # same round-trip count per eval_iv rounds, which is what the
            # A/B measures)
            epoch0_w = 1 + i * k_disp
            if any((epoch0_w + j) % eval_iv == 0 for j in range(k_disp)):
                epoch = epoch0_w + k_disp - 1
                # sync the train window FIRST so the `eval` phase row
                # measures the eval round-trip itself, not the async train
                # compute the eval's first D2H would otherwise absorb
                with tmr.phase("compute"):
                    jax.block_until_ready(params)
                with tmr.phase("eval"):
                    bn = evaluator.sbn_stats(params, *eval_sbn)
                    evaluator.eval_users(params, bn, *eval_local, epoch=epoch)
                    evaluator.eval_global(params, bn, *eval_global, epoch=epoch)
        return params, pending

    def last_loss(fetched):
        """Superstep fetches return a list of per-round dicts (or the
        train/eval dict when eval-fused); take the latest round's sums."""
        if isinstance(fetched, dict) and "train" in fetched:
            fetched = fetched["train"]
        return fetched[-1] if isinstance(fetched, list) else fetched

    def steady_stats(rsec, compile_flags):
        """Like-for-like 'value' statistic for BOTH strategies (ADVICE r5
        item 1): the average per-round seconds EXCLUDING rounds that
        compiled a fresh program (grouped slot-bucket compiles, superstep
        shape changes), falling back to all rounds when every timed round
        compiled.  Detected via engine.program_cache_size() growth."""
        steady = [t for t, c in zip(rsec, compile_flags) if not c] or list(rsec)
        return sum(steady) / len(steady)

    def summarize(rsec, compile_flags, compile_s, tmr, phases0, rounds_done,
                  k_disp=None):
        steady_avg = steady_stats(rsec, compile_flags)
        n_compile = sum(bool(c) for c in compile_flags)
        return {
            "value": round(1.0 / steady_avg, 4),
            "round_sec_avg": round(sum(rsec) / len(rsec), 3),
            "round_sec_best": round(min(rsec), 3),
            "round_sec_steady_avg": round(steady_avg, 3),
            # rounds that compiled a fresh shape, ALWAYS reported -- when
            # every round compiled the steady avg falls back to all rounds
            # and the next flag says so instead of hiding the recompiles
            "compile_rounds": n_compile,
            "steady_excludes_compile_rounds": n_compile < len(rsec),
            "compile_sec": round(compile_s, 1),
            "rounds_timed": rounds_done,
            # per-ROUND amortized host phases: one stage+dispatch+fetch
            # cycle serves all rounds of a dispatch window (an eval-host
            # record additionally carries the per-window `eval` phase)
            "phases": {k: round(v, 4)
                       for k, v in sorted(tmr.amortized(
                           phases0, rounds_done * (k_disp or superstep)).items())},
        }

    def measure(strat, eng, params0, tmr, hb_prefix="", on_round=None,
                eval_mode=None):
        """Warmup + timed loop: THE single measurement procedure, shared by
        the primary strategy (``on_round`` handles its pipelined fetch and
        refined per-round emits), the alternate-strategy record, and the
        eval-fused vs eval-host rows -- one copy, so every like-for-like
        claim compares identical procedures.  ``eval_mode`` (with
        BENCH_EVAL_INTERVAL): 'fused' rides the eval mask inside the
        superstep; 'host' clamps dispatch windows to min(K, E) and pays the
        host eval round-trip per window (the PR 2 semantics).  Returns
        (summary, ctx) where ctx carries rsec/flags/compile_s/phases0/ms."""
        k_disp = superstep
        if eval_mode == "fused" and superstep == 1:
            eval_mode = "host"  # nothing to fuse into at K=1
        if eval_mode == "host" and eval_iv:
            k_disp = min(superstep, eval_iv)
        rng_ = np.random.default_rng(0)
        part_start = len(part_stats["filled"])  # this measure()'s own draws
        t0 = time.time()
        p, pending = dispatch(eng, strat, params0, 0, tmr, rng_,
                              eval_mode=eval_mode, k_disp=k_disp)
        jax.block_until_ready(p)
        warm_ms = last_loss(pending.fetch())
        compile_s = time.time() - t0
        # phases are reported RELATIVE to this snapshot so the breakdown
        # shows steady-state cost, not the warmup compile in 'dispatch'
        phases0 = tmr.snapshot()
        hb(f"{hb_prefix}compile done ({compile_s:.1f}s incl. warmup dispatch)")
        ctx = {"compile_s": compile_s, "phases0": phases0, "k_disp": k_disp,
               "rsec": [], "flags": [], "ms": warm_ms, "ms_round": 0}
        for r in range(1, timed_rounds + 1):
            size0 = eng.program_cache_size()
            t0 = time.time()
            p, pending = dispatch(eng, strat, p, r, tmr, rng_,
                                  eval_mode=eval_mode, k_disp=k_disp)
            with tmr.phase("compute"):
                jax.block_until_ready(p)
            ctx["rsec"].append((time.time() - t0) / k_disp)
            ctx["flags"].append(eng.program_cache_size() > size0)
            if on_round is not None:
                on_round(r, pending, ctx)
            else:
                with tmr.phase("fetch"):
                    ctx["ms"] = last_loss(pending.fetch())
            hb(f"{hb_prefix}round {r}/{timed_rounds} done "
               f"({ctx['rsec'][-1]:.2f}s/round)")
        summary = summarize(ctx["rsec"], ctx["flags"], compile_s, tmr, phases0,
                            timed_rounds, k_disp=k_disp)
        # scenario participation of THIS measure's draws only (warmup +
        # timed dispatches of this strategy/mode) -- without the slice the
        # second-strategy and eval-host records would pollute the primary
        # record's statistics
        ctx["participation"] = list(part_stats["filled"][part_start:])
        if eval_mode is not None:
            summary["eval_mode"] = eval_mode
            summary["rounds_per_dispatch"] = k_disp
        return summary, ctx

    step_ab = {}  # filled by the BENCH_STEP_AB pass; emitted when non-empty
    obs_ab = {}   # filled by the BENCH_TELEMETRY pass; emitted when non-empty
    arms_ab = {}  # filled by the BENCH_ARMS pass (ISSUE 14)
    chaos_ab = {}  # filled by the BENCH_CHAOS pass (ISSUE 15)
    pod_ab = {}   # filled by the BENCH_POD pass (ISSUE 17)

    def emit(ctx, rounds_done, strategies=None):
        # a degraded (non-flagship-volume / wrong-platform) run must not
        # pretend to be comparable to the 10 rps north star (VERDICT r4
        # item 5): vs_baseline is null unless this is the real program.
        # With BENCH_FETCH_EVERY>1 the loss lags the timed round by up to K
        # rounds; final_loss_round marks which round it belongs to so a
        # mid-run kill's salvaged line is not silently stale.
        ms = ctx["ms"]
        loss = float(np.asarray(ms["loss_sum"]).sum() / np.asarray(ms["n"]).sum())
        pop_extra = {}
        if population:
            import resource

            pop_extra["population"] = {
                "users": population, "active_clients": n_active,
                "shard_size": store.shard_max,
                "store_metadata_bytes": store.metadata_nbytes,
                "rss_max_kb": resource.getrusage(resource.RUSAGE_SELF).ru_maxrss,
                "prefetched_stages": pop_stats["prefetched"],
                "sync_stages": pop_stats["sync"]}
        dt = steady_stats(ctx["rsec"], ctx["flags"])
        rps = 1.0 / dt
        scenario_extra = {}
        if sched_cfg:
            filled = ctx.get("participation") or part_stats["filled"]
            scenario_extra["scenario"] = {
                "schedule": scenario_tokens,
                "config": sched_cfg,
                "participation": {
                    "slots_per_round": n_active,
                    "rounds_sampled": len(filled),
                    "mean_active": (round(float(np.mean(filled)), 3)
                                    if filled else None),
                    "min_active": int(min(filled)) if filled else None,
                    "max_active": int(max(filled)) if filled else None,
                },
                "rounds_per_sec": round(rps, 4),
            }
        summary = summarize(ctx["rsec"], ctx["flags"], ctx["compile_s"], timer,
                            ctx["phases0"], rounds_done,
                            k_disp=ctx.get("k_disp"))
        del summary["value"]  # the top-level "value" IS this number
        cache_dir = os.environ.get("JAX_COMPILATION_CACHE_DIR")
        print(json.dumps({
            "metric": "federated_rounds_per_sec_cifar10_resnet18_a1-e1_100c",
            "value": round(rps, 4),
            "unit": "rounds/sec",
            "vs_baseline": None if degraded else round(rps / 10.0, 4),
            "extra": {"round_sec": round(dt, 3),
                      **summary,
                      "devices": len(devs), "platform": platform,
                      "active_clients": n_active, "users": users,
                      "n_train": n_train, "final_loss": round(loss, 4),
                      "strategy": strategy,
                      "sampler": sampler_extra,
                      "mfu": mfu_extra(rps),
                      "wire": wire_extra,
                      "compile_cache": {
                          "enabled": bool(cache_dir),
                          "requests": cache_counters["requests"],
                          "hits": cache_counters["hits"],
                          "misses": cache_counters["requests"] - cache_counters["hits"]},
                      **({"staticcheck": staticcheck} if staticcheck else {}),
                      **({"superstep_rounds": superstep} if superstep != 1 else {}),
                      **({"eval_interval": eval_iv} if eval_iv else {}),
                      **({"fetch_every": fetch_every,
                          "final_loss_round": ctx["ms_round"]} if fetch_every != 1 else {}),
                      **pop_extra,
                      **scenario_extra,
                      **({"strategies": strategies} if strategies else {}),
                      **({"step_ab": step_ab} if step_ab else {}),
                      **({"obs": obs_ab} if obs_ab else {}),
                      **({"arms": arms_ab} if arms_ab else {}),
                      **({"chaos": chaos_ab} if chaos_ab else {}),
                      **({"pod": pod_ab} if pod_ab else {}),
                      **({"degraded": degraded} if degraded else {})},
        }), flush=True)

    # primary strategy: a refined JSON line lands after EVERY timed round so
    # a mid-run kill still leaves a real measurement.  'value' is the
    # LIKE-FOR-LIKE statistic for both strategies (ADVICE r5 item 1):
    # per-round steady average excluding fresh-compile
    # rounds (extra.round_sec_avg/_best/_steady_avg carry the full picture).
    hb("compiling (warmup dispatch)")

    def pop_sync_refused():
        """The population-axis refusal (ISSUE 6) covers the per-round
        salvage emits too: once a timed dispatch staged synchronously,
        every refined line would measure serialised staging,
        not just the final summary."""
        return (population and pop_stats["sync"]
                and os.environ.get("BENCH_ALLOW_SYNC_STAGE") != "1")

    def on_round(r, pending, ctx):
        with timer.phase("fetch"):
            # tag with the last ROUND the dispatch covered, not the dispatch
            # index: final_loss_round documents which round the (possibly
            # deferred) loss belongs to, and one dispatch is K rounds
            due = pipe.push(r * ctx.get("k_disp", superstep), pending)
        if due:
            ctx["ms_round"], ctx["ms"] = due[-1][0], last_loss(due[-1][1])
        if not pop_sync_refused():
            emit(ctx, r)

    primary_summary, ctx = measure(strategy, engine, params, timer,
                                   on_round=on_round,
                                   eval_mode="fused" if eval_iv else None)
    due = pipe.flush()
    if due and not pop_sync_refused():
        # deferred-fetch tail: re-emit with the final round's loss
        ctx["ms_round"], ctx["ms"] = due[-1][0], last_loss(due[-1][1])
        emit(ctx, timed_rounds)

    # population-mode staging contract (ISSUE 6): a record whose timed
    # dispatches staged SYNCHRONOUSLY measures serialised staging, not the
    # double-buffered pipeline -- refuse to record it as the population
    # axis unless the operator explicitly overrides
    if pop_sync_refused():
        print(json.dumps({
            "metric": "federated_rounds_per_sec_cifar10_resnet18_a1-e1_100c",
            "value": 0.0, "unit": "rounds/sec", "vs_baseline": None,
            "extra": {"error": f"{pop_stats['sync']} timed dispatch(es) fell "
                               f"back to SYNCHRONOUS cohort staging; the "
                               f"population axis measures the prefetched "
                               f"pipeline (set BENCH_ALLOW_SYNC_STAGE=1 to "
                               f"record anyway)",
                      "population": {"users": population,
                                     "prefetched_stages": pop_stats["prefetched"],
                                     "sync_stages": pop_stats["sync"]}},
        }), flush=True)
        return

    def try_measure(strat, hb_prefix, eval_mode=None):
        """An extra record must never kill the primary one."""
        hb(f"{hb_prefix}building engine")
        try:
            s, _ = measure(strat, make_engine(strat),
                           model.init(jax.random.key(0)), PhaseTimer(),
                           hb_prefix=hb_prefix, eval_mode=eval_mode)
            return s
        except Exception as e:
            print(f"bench: extra record {hb_prefix.strip()} failed: {e!r}",
                  file=sys.stderr)
            return {"error": repr(e)}

    # both-strategy record (ISSUE 2 satellite): measure the OTHER engine on
    # the same config so the grouped engine's small-width FLOP reduction
    # lands in the BENCH_*.json trajectory, not only in scripts/
    # grouped_flops.py.  Skipped in CPU mode; BENCH_BOTH=0 disables, =1 forces.
    # With BENCH_EVAL_INTERVAL the strategies dict carries eval-fused vs
    # eval-host rows per engine (ISSUE 4 satellite) -- the A/B that shows
    # the last per-eval-window host round-trip disappearing.
    both_default = "0" if (cpu_mode or realwidth or population) else "1"
    both = os.environ.get("BENCH_BOTH", both_default) == "1"
    alt = "grouped" if strategy != "grouped" else "masked"
    strategies = {}
    if eval_iv:
        # key each row by the mode that actually RAN (measure() degrades
        # fused->host at superstep==1, where there is no scan to fuse into)
        pmode = primary_summary.get("eval_mode", "fused")
        strategies[f"{strategy}+eval-{pmode}"] = primary_summary
        if pmode == "fused":
            strategies[f"{strategy}+eval-host"] = try_measure(
                strategy, f"[{strategy}/eval-host] ", eval_mode="host")
        if both:
            alt_fused = try_measure(alt, f"[{alt}/eval-fused] ",
                                    eval_mode="fused")
            amode = alt_fused.get("eval_mode", "fused")
            strategies[f"{alt}+eval-{amode}"] = alt_fused
            if amode == "fused":
                strategies[f"{alt}+eval-host"] = try_measure(
                    alt, f"[{alt}/eval-host] ", eval_mode="host")
    elif both:
        strategies[strategy] = primary_summary
        strategies[alt] = try_measure(alt, f"[{alt}] ")
    if strategies:
        emit(ctx, timed_rounds, strategies=strategies)

    # BENCH_STEP_AB=1 (ISSUE 5): fused-epilogue vs reference-op-chain step
    # A/B -- both arms measured with the SAME shared procedure (plain train
    # windows; eval rides the primary record, not this one), plus the
    # optimized-HLO scan-body kernel counts in both modes.  The counted
    # program is the engine's K=1 hot program at the bench shapes (masked:
    # the one-round train program; grouped: the full-width level-a span
    # program) -- its LOCAL-STEP scan body is the same step body the
    # K-round superstep scans, and the same body the staticcheck budget
    # gates; the record labels which program was lowered.  Failures never
    # kill the primary record.
    if os.environ.get("BENCH_STEP_AB") == "1" and population:
        print("bench: BENCH_STEP_AB ignored in population mode (the step "
              "A/B lowers the eager-data programs)", file=sys.stderr)
    elif os.environ.get("BENCH_STEP_AB") == "1":
        try:
            from heterofl_tpu.staticcheck.jaxpr_walk import scan_body_kernel_count

            psds = jax.tree_util.tree_map(
                lambda v: jax.ShapeDtypeStruct(v.shape, v.dtype), dict(params))

            def body_counts(fused):
                eng = make_engine(strategy, {"fused_update": fused})
                lr0 = np.float32(0.1)
                if strategy == "grouped":
                    from heterofl_tpu.parallel.grouped import _bucket_pow2

                    slots = _bucket_pow2(1) * len(devs)
                    sds = jax.ShapeDtypeStruct((slots,), np.int32)
                    low = eng._level_prog(1.0, slots).lower(
                        psds, base_key, lr0, sds, *data)
                    prog_name = "grouped/span/level-1/k1"
                else:
                    fix = (eng.fix_rates,) if eng.fix_rates is not None else ()
                    slots = users + ((-users) % len(devs))
                    sds = jax.ShapeDtypeStruct((slots,), np.int32)
                    low = eng._build_train().lower(
                        psds, base_key, lr0, sds, sds, *(data + fix))
                    prog_name = "masked/k1"
                return {"program": prog_name,
                        **scan_body_kernel_count(low.compile().as_text())}

            hb("[step-ab] measuring fused vs reference epilogue")
            ab_fused, _ = measure(strategy, make_engine(strategy),
                                  model.init(jax.random.key(0)), PhaseTimer(),
                                  hb_prefix="[step-ab/fused] ")
            ab_ref, _ = measure(strategy,
                                make_engine(strategy, {"fused_update": False}),
                                model.init(jax.random.key(0)), PhaseTimer(),
                                hb_prefix="[step-ab/reference] ")
            kf, kr = body_counts(True), body_counts(False)
            step_ab.update({
                "fused": ab_fused,
                "reference": ab_ref,
                "speedup": round(ab_ref["round_sec_steady_avg"]
                                 / ab_fused["round_sec_steady_avg"], 4),
                "scan_body_kernels": {
                    "fused": kf, "reference": kr,
                    "fusion_drop_pct": round(
                        100.0 * (1.0 - kf["fusions"] / max(1, kr["fusions"])), 1)},
            })
        except Exception as e:
            step_ab.update({"error": repr(e)})
            print(f"bench: step A/B failed: {e!r}", file=sys.stderr)
        emit(ctx, timed_rounds, strategies=strategies or None)

    # BENCH_TELEMETRY=1 (ISSUE 10): the runtime-telemetry on-vs-off A/B --
    # both arms measured with the SAME shared procedure; the ON arm carries
    # the in-program health probes through every fetch, feeds them to a
    # warn-mode watchdog, and records the run's Chrome trace.  A fired
    # watchdog REFUSES the record: a rounds/sec number measured through a
    # diverging run is not a telemetry overhead.
    if os.environ.get("BENCH_TELEMETRY") == "1" and population:
        print("bench: BENCH_TELEMETRY ignored in population mode (the A/B "
              "measures the eager flagship program)", file=sys.stderr)
    elif os.environ.get("BENCH_TELEMETRY") == "1" \
            and strategy == "grouped" and superstep <= 1:
        print("bench: BENCH_TELEMETRY with the grouped strategy needs "
              "BENCH_SUPERSTEP>1 (the probes live in the fused superstep); "
              "skipping the A/B", file=sys.stderr)
    elif os.environ.get("BENCH_TELEMETRY") == "1":
        try:
            from heterofl_tpu.obs import resolve_telemetry_cfg, split_probes
            from heterofl_tpu.obs.trace import TraceRecorder
            from heterofl_tpu.obs.watchdog import Watchdog

            trace_dir = os.environ.get("BENCH_TRACE_DIR") \
                or os.path.join(os.getcwd(), "obs_trace")
            rec = TraceRecorder(trace_dir)
            tel_timer = PhaseTimer()
            tel_timer.trace = rec  # phases file onto the run timeline
            wd = Watchdog(resolve_telemetry_cfg({"telemetry": "on"}).watchdog)
            tel_state = {"probes": None, "round": 0}

            def tel_on_round(r, pending, ctx2):
                with tel_timer.phase("fetch"):
                    out = pending.fetch()
                if isinstance(out, dict) and "train" in out:
                    rounds_l, probes = out["train"], out.get("obs")
                else:  # the K=1 train_round path: raw obs_ leaves in ms
                    clean, probes = split_probes(out, len(devs))
                    rounds_l = [clean]
                ctx2["ms"] = rounds_l[-1]
                for j, pr in enumerate(probes or []):
                    msr = rounds_l[j] if j < len(rounds_l) else rounds_l[-1]
                    n_j = float(np.asarray(msr["n"]).sum())
                    loss_j = (float(np.asarray(msr["loss_sum"]).sum()) / n_j
                              if n_j > 0 else None)
                    tel_state["round"] += 1
                    tel_state["probes"] = pr
                    rec.instant("probes", cat="obs",
                                args={"round": tel_state["round"],
                                      "loss": loss_j, **pr})
                    wd.check(tel_state["round"], probes=pr, loss=loss_j)

            hb("[obs] telemetry on-vs-off A/B")
            try:
                on_sum, _on_ctx = measure(
                    strategy, make_engine(strategy, {"telemetry": "on"}),
                    model.init(jax.random.key(0)), tel_timer,
                    hb_prefix="[obs/on] ", on_round=tel_on_round)
            finally:
                # a failed ON arm must still leave its trace on disk --
                # that trace is the artifact that explains the failure
                trace_path = rec.close()
            off_sum, _ = measure(strategy, make_engine(strategy),
                                 model.init(jax.random.key(0)), PhaseTimer(),
                                 hb_prefix="[obs/off] ")
            if wd.fired:
                obs_ab.update({
                    "error": "watchdog fired during the telemetry measure; "
                             "refusing to record the on-vs-off A/B",
                    "watchdog_fired": wd.fired[:8],
                    "trace": trace_path})
            else:
                obs_ab.update({
                    "on": on_sum, "off": off_sum,
                    "overhead_pct": round(
                        100.0 * (on_sum["round_sec_steady_avg"]
                                 / off_sum["round_sec_steady_avg"] - 1.0), 2),
                    "probes_last": tel_state["probes"],
                    "watchdog_fired": [],
                    "trace": trace_path})
        except Exception as e:
            obs_ab.update({"error": repr(e)})
            print(f"bench: telemetry A/B failed: {e!r}", file=sys.stderr)
        emit(ctx, timed_rounds, strategies=strategies or None)

    # BENCH_LEDGER=1 (ISSUE 12): the population-observatory A/B -- the ON
    # arm runs telemetry='hist' (cohort histograms in the fetch) and folds
    # a host-side ClientLedger O(active) per fetch from the re-drawn
    # schedule rows (the host twin of the in-jit draw: bit-identical by
    # the sampler-stream contract); the OFF arm is the plain engine.  A
    # fired warn-mode watchdog refuses the record, like BENCH_TELEMETRY.
    # Works in population mode -- 1e6 users IS the bytes/user acceptance
    # measurement -- but needs BENCH_SUPERSTEP>1 (the schedule re-draw
    # addresses whole superstep dispatches).
    if os.environ.get("BENCH_LEDGER") == "1" and superstep <= 1:
        print("bench: BENCH_LEDGER needs BENCH_SUPERSTEP>1 (the per-fetch "
              "ledger fold re-draws superstep schedule rows); skipping",
              file=sys.stderr)
    elif os.environ.get("BENCH_LEDGER") == "1":
        try:
            from heterofl_tpu.obs import resolve_telemetry_cfg
            from heterofl_tpu.obs.ledger import ClientLedger
            from heterofl_tpu.obs.watchdog import Watchdog

            trace_dir = os.environ.get("BENCH_TRACE_DIR") \
                or os.path.join(os.getcwd(), "obs_trace")
            os.makedirs(trace_dir, exist_ok=True)
            ledger = ClientLedger(
                users, sorted({float(r) for r in cfg["model_rate"]},
                              reverse=True))
            wd = Watchdog(resolve_telemetry_cfg({"telemetry": "hist"})
                          .watchdog)
            led_state = {"round": 0, "hist": None}
            led_jsonl_path = os.path.join(trace_dir, "ledger.jsonl")
            led_jsonl = open(led_jsonl_path, "w")

            def led_on_round(r, pending, ctx2):
                out = pending.fetch()
                rounds_l, probes = out["train"], out.get("obs") or []
                ctx2["ms"] = rounds_l[-1]
                epoch0 = 1 + r * superstep
                us = superstep_user_schedule(base_key, epoch0, superstep,
                                             users, n_active,
                                             schedule=sched_spec,
                                             sampler=sampler_kind)
                a = us.shape[1]
                for j, msr in enumerate(rounds_l):
                    s = ledger.update(epoch0 + j, us[j],
                                      np.asarray(msr["rate"])[:a],
                                      np.asarray(msr["loss_sum"])[:a],
                                      np.asarray(msr["n"])[:a])
                    led_jsonl.write(json.dumps({"tag": "ledger", **s}) + "\n")
                led_jsonl.flush()
                for j, pr in enumerate(probes):
                    msr = rounds_l[j]
                    n_j = float(np.asarray(msr["n"]).sum())
                    loss_j = (float(np.asarray(msr["loss_sum"]).sum()) / n_j
                              if n_j > 0 else None)
                    led_state["round"] += 1
                    led_state["hist"] = {n: v for n, v in pr.items()
                                         if n.startswith("hist_")}
                    wd.check(led_state["round"], probes=pr, loss=loss_j)

            hb("[ledger] observatory on-vs-off A/B")
            try:
                led_on, _ = measure(
                    strategy, make_engine(strategy, {"telemetry": "hist"}),
                    model.init(jax.random.key(0)), PhaseTimer(),
                    hb_prefix="[ledger/on] ", on_round=led_on_round)
            finally:
                led_jsonl.close()
            led_off, _ = measure(strategy, make_engine(strategy),
                                 model.init(jax.random.key(0)), PhaseTimer(),
                                 hb_prefix="[ledger/off] ")
            npz_path = ledger.save(os.path.join(trace_dir, "ledger.npz"))
            if wd.fired:
                obs_ab["ledger"] = {
                    "error": "watchdog fired during the ledger measure; "
                             "refusing to record the on-vs-off A/B",
                    "watchdog_fired": wd.fired[:8],
                    "ledger_npz": npz_path}
            else:
                obs_ab["ledger"] = {
                    "on": led_on, "off": led_off,
                    "overhead_pct": round(
                        100.0 * (led_on["round_sec_steady_avg"]
                                 / led_off["round_sec_steady_avg"] - 1.0), 2),
                    "users": users,
                    "ledger_bytes": ledger.nbytes,
                    "bytes_per_user": round(ledger.nbytes / users, 3),
                    "coverage": round(ledger.seen / users, 6),
                    "participations": int(ledger.count.sum()),
                    "hist_last": led_state["hist"],
                    "watchdog_fired": [],
                    "ledger_npz": npz_path,
                    "ledger_jsonl": led_jsonl_path}
        except Exception as e:
            obs_ab["ledger"] = {"error": repr(e)}
            print(f"bench: ledger A/B failed: {e!r}", file=sys.stderr)
        emit(ctx, timed_rounds, strategies=strategies or None)

    # BENCH_ARMS=E (ISSUE 14): the experiment-arms multiplexer A/B -- ONE
    # E-arm fused superstep program vs E SERIAL solo runs, both through the
    # shared measure() procedure on equal per-arm device resources.  The
    # default placement lays the arms over a dedicated mesh axis
    # (make_mesh(n_arms=E): each arm's federation on its own device rows,
    # executing concurrently -- the mesh-filling story); BENCH_ARMS_
    # PLACEMENT=vmap forces the batched-per-device layout instead (the two
    # are bitwise-identical per arm, tests/test_arms.py).  The serial
    # baseline runs ONE arm on the per-arm submesh -- E sequential such
    # runs is the reference's process-grid shape with the compile already
    # amortized, so the steady-state speedup under-counts the reference's
    # per-process compile (reported separately via compile_sec).  Records
    # aggregate ARM-rounds/sec both ways, program/compile counts and RSS
    # into extra.arms.  Skipped in population mode and under scenario/
    # codec knobs (the A/B measures the plain dense program).
    bench_arms = env_int("BENCH_ARMS", 0)
    if bench_arms:
        if population or sched_cfg or wire_codec != "dense":
            print("bench: BENCH_ARMS ignored with population/scenario/codec "
                  "knobs (the A/B measures the plain dense program)",
                  file=sys.stderr)
        elif superstep <= 1:
            print("bench: BENCH_ARMS needs BENCH_SUPERSTEP>1 (arms ride "
                  "the fused superstep); skipping the A/B", file=sys.stderr)
        else:
            import resource

            try:
                E = bench_arms
                n_dev_total = len(devs)
                placement = os.environ.get("BENCH_ARMS_PLACEMENT") or \
                    ("mesh" if n_dev_total % E == 0
                     and n_dev_total >= E else "vmap")
                if placement not in ("mesh", "vmap"):
                    print(f"bench: unknown BENCH_ARMS_PLACEMENT="
                          f"{placement!r}; using mesh", file=sys.stderr)
                    placement = "mesh"
                if placement == "mesh":
                    sub_clients = n_dev_total // E
                    arms_mesh = make_mesh(sub_clients, 1, n_arms=E)
                    solo_mesh = make_mesh(sub_clients, 1)
                else:
                    sub_clients = mesh.shape["clients"]
                    arms_mesh = mesh
                    solo_mesh = mesh
                hb(f"arms A/B: E={E} placement={placement} "
                   f"({E}x{sub_clients} of {n_dev_total} devices)")
                solo_eng = RoundEngine(model, dict(cfg), solo_mesh)
                serial_sum, _ = measure("masked", solo_eng,
                                        model.init(jax.random.key(0)),
                                        PhaseTimer(),
                                        hb_prefix="arms-serial ")
                rss_serial = resource.getrusage(
                    resource.RUSAGE_SELF).ru_maxrss
                arms_eng = RoundEngine(model, dict(cfg, arms=E), arms_mesh)
                p0 = model.init(jax.random.key(0))
                p_stack = jax.tree_util.tree_map(
                    lambda v: jnp.stack([v] * E), p0)

                # the fetch must charge THIS measure()'s timer, not the
                # already-summarized primary pass's (the serial baseline
                # pays fetch through measure's own tmr -- like-for-like)
                arms_tmr = PhaseTimer()

                def arms_fetch(r, pending, ctx):
                    with arms_tmr.phase("fetch"):
                        out = pending.fetch()
                    a0 = out["arms"][0]
                    ctx["ms"] = a0["train"][-1] if isinstance(a0, dict) \
                        else a0[-1]

                arms_sum, _ = measure("masked", arms_eng, p_stack,
                                      arms_tmr,
                                      hb_prefix=f"arms-E{E} ",
                                      on_round=arms_fetch)
                rss_arms = resource.getrusage(resource.RUSAGE_SELF).ru_maxrss
                agg_arms = E / arms_sum["round_sec_steady_avg"]
                agg_serial = 1.0 / serial_sum["round_sec_steady_avg"]
                arms_ab.update({
                    "E": E, "placement": placement,
                    "mesh": {"arms": E if placement == "mesh" else 0,
                             "clients_per_arm": sub_clients,
                             "total_devices": n_dev_total},
                    "one_program": arms_sum,
                    "serial_per_arm": serial_sum,
                    "aggregate_arm_rounds_per_sec": round(agg_arms, 4),
                    "serial_aggregate_arm_rounds_per_sec":
                        round(agg_serial, 4),
                    "speedup": round(agg_arms / agg_serial, 4),
                    # one compiled program + one warmup dispatch serve all
                    # E arms; the reference's process grid compiles E times
                    "compile_count": {"one_program": 1, "serial_runs": E},
                    "compile_sec": {
                        "one_program": arms_sum["compile_sec"],
                        "serial_per_run": serial_sum["compile_sec"]},
                    # ru_maxrss is the process PEAK (monotonic): the delta
                    # after the arms pass bounds its extra footprint
                    "rss_max_kb": {"after_serial": rss_serial,
                                   "after_arms": rss_arms},
                })
            except Exception as e:
                arms_ab.update({"error": repr(e)})
                print(f"bench: arms A/B failed: {e!r}", file=sys.stderr)
            emit(ctx, timed_rounds, strategies=strategies or None)

    # BENCH_CHAOS=1 (ISSUE 15): the fault-tolerance drill measurements --
    # a watchdog-rollback poison drill (seeded NaN, auto-recovery MTTR)
    # and a quarantine poison drill, on the drill's small synthetic
    # federation (its own programs; the flagship measure above is
    # untouched).  An escalation to abort REFUSES the record: a recovery
    # that needed human intervention has no MTTR.
    if os.environ.get("BENCH_CHAOS") == "1":
        try:
            import tempfile

            from heterofl_tpu.chaos.drill import run_poison_drill
            from heterofl_tpu.obs.watchdog import WatchdogError

            hb("[chaos] rollback + quarantine poison drills")
            chaos_root = tempfile.mkdtemp(prefix="bench_chaos_")
            try:
                roll = run_poison_drill(
                    "rollback", {}, os.path.join(chaos_root, "rollback"))
            except WatchdogError as e:
                chaos_ab.update({
                    "error": "rollback recovery escalated to abort; "
                             "refusing to record an MTTR",
                    "escalation": repr(e)})
            else:
                quar = run_poison_drill(
                    "quarantine", {}, os.path.join(chaos_root, "quarantine"))
                chaos_ab.update({
                    "rollback": {
                        "ok": roll["ok"], "poison": roll["poison"],
                        "trips": roll["trips"],
                        "recoveries": roll["recoveries"],
                        "mttr_sec": roll["mttr_sec"],
                        "wall_sec": roll["wall_sec"]},
                    "quarantine": {
                        "ok": quar["ok"], "poison": quar["poison"],
                        "quarantined_total": quar["quarantined_total"],
                        "wall_sec": quar["wall_sec"]},
                })
        except Exception as e:
            chaos_ab.update({"error": repr(e)})
            print(f"bench: chaos drills failed: {e!r}", file=sys.stderr)
        emit(ctx, timed_rounds, strategies=strategies or None)

    # BENCH_POD=1 (ISSUE 17): the 2-process pod probe -- a REAL
    # jax.distributed CPU mesh (gloo collectives) runs the fused
    # grouped-slices superstep with levels on disjoint processes, recorded
    # into extra.pod: per-process rounds/sec + checkpoint-write times, the
    # DCN classification from the real process grid (exactly one dense
    # reduction per training round), and the bitwise gate vs the
    # 1-process gloo reference.  A failed multi-host DCN budget audit
    # REFUSES the numbers: pod rounds/sec against an unaudited wire
    # contract would launder broken placement into the trajectory.
    if os.environ.get("BENCH_POD") == "1":
        if staticcheck is not None \
                and staticcheck.get("dcn_audit_ok") is False:
            pod_ab.update({
                "error": "STATICCHECK.json reports a failed multi-host DCN "
                         "budget audit; refusing to record pod numbers. "
                         "Rerun `python -m heterofl_tpu.staticcheck "
                         "--aot-v4128`."})
        else:
            try:
                import tempfile

                from heterofl_tpu.parallel.pod import (bitwise_match,
                                                       run_pod_probe)

                hb("[pod] 2-process distributed probe + 1-process reference")
                pod_root = tempfile.mkdtemp(prefix="bench_pod_")
                ref_dir = os.path.join(pod_root, "ref")
                pod_dir = os.path.join(pod_root, "pod")
                ref = run_pod_probe(ref_dir, n_processes=1,
                                    local_devices=8, k=4, align=2)
                pod = run_pod_probe(pod_dir, n_processes=2,
                                    local_devices=4, k=4)
                match = bitwise_match(pod_dir, ref_dir)
                pod_ab.update({
                    "processes": pod[0]["processes"],
                    "devices": pod[0]["devices"],
                    "k": pod[0]["k"],
                    "rounds_per_sec": round(pod[0]["rounds_per_sec"], 4),
                    "ref_rounds_per_sec": round(ref[0]["rounds_per_sec"], 4),
                    "ckpt_write_s": [round(r["ckpt_write_s"], 4)
                                     for r in pod],
                    "ckpt_shard_write_s": [round(r["ckpt_shard_write_s"], 4)
                                           for r in pod],
                    "dcn_axes": pod[0]["dcn_axes"],
                    "wire": pod[0]["wire"],
                    "reshards": pod[0]["reshards"],
                    "dcn_one_reduction": pod[0]["dcn_one_reduction"],
                    "bitwise_vs_single_process": match["match"],
                })
                if not match["match"]:
                    pod_ab.update({
                        "error": "2-process run is NOT bitwise-identical "
                                 "to the 1-process reference",
                        "mismatches": match["mismatches"][:20]})
            except Exception as e:
                pod_ab.update({"error": repr(e)})
                print(f"bench: pod probe failed: {e!r}", file=sys.stderr)
        emit(ctx, timed_rounds, strategies=strategies or None)


if __name__ == "__main__":
    main()
