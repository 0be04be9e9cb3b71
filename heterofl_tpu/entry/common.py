"""Shared experiment driver for all entry points.

Mirrors the reference's L5 structure (ref train_classifier_fed.py:37-96):
CLI flags auto-derived from cfg keys + ``--control_name``; per-seed
experiment loop; per-round train -> sBN recalibration -> Local/Global eval ->
scheduler step -> checkpoint + best-pivot copy.  The compute path is the
jitted :class:`~heterofl_tpu.parallel.RoundEngine`; only user sampling,
logging and checkpointing live on the host.
"""

from __future__ import annotations

import argparse
import datetime
import functools
import json
import math
import os
import time
import warnings
from contextlib import nullcontext
from typing import Any, Dict, List, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from .. import config as C
from ..chaos import resolve_poison_cfg
from ..compress import resolve_codec_cfg
from ..obs import (resolve_ledger_cfg, resolve_quarantine_cfg,
                   resolve_telemetry_cfg, spans, split_probes)
from ..obs.ledger import ClientLedger
from ..obs.trace import recorder_from
from ..obs.watchdog import (RETRY_SALT, Watchdog, WatchdogError,
                            WatchdogRollback)
from ..data import (
    bptt_windows,
    stack_windows,
    fetch_dataset,
    label_split_masks,
    process_dataset,
    split_dataset,
    stack_client_shards,
    stack_client_token_rows,
)
from ..fed.core import (arm_stream_keys, round_rates, round_users,
                        superstep_rate_schedule, superstep_user_schedule,
                        validate_width_geometry)
from ..fed.sampling import ScheduleCommitment, resolve_sampler_cfg
from ..multi import resolve_arms_cfg
from ..sched import resolve_schedule_cfg
from ..models import make_model
from ..parallel import (ClientStore, MetricsPipeline, PendingMetrics,
                        PhaseTimer, RoundEngine, make_mesh)
from ..parallel.evaluation import Evaluator
from ..utils.compile_cache import enable_persistent_cache
from ..utils import (
    Logger,
    checkpoint_path,
    copy_best,
    dense_from_blocks,
    is_shard_marker,
    make_scheduler,
    resume,
    save_checkpoint,
    save_checkpoint_sharded,
    summarize_sums,
)
from ..utils.optim import PlateauScheduler


# ---------------------------------------------------------------------------
# CLI (ref train_classifier_fed.py:20-30: every cfg key is a flag)
# ---------------------------------------------------------------------------

def build_cli(description: str) -> argparse.ArgumentParser:
    parser = argparse.ArgumentParser(description=description)
    for k, v in C.DEFAULT_CFG.items():
        if v is None or isinstance(v, (dict, list)):
            parser.add_argument(f"--{k}", default=None, type=str,
                                help=f"JSON override (default {json.dumps(v)})")
        elif isinstance(v, bool):
            parser.add_argument(f"--{k}", default=None, type=int)
        else:
            parser.add_argument(f"--{k}", default=None, type=type(v))
    parser.add_argument("--control_name", default=None, type=str)
    return parser


def cfg_from_args(args: argparse.Namespace) -> Dict[str, Any]:
    cfg = C.default_cfg()
    for k, v in C.DEFAULT_CFG.items():
        val = getattr(args, k, None)
        if val is None:
            continue
        if v is None:
            # None-default flags: JSON containers/null parse, anything else
            # stays a raw string (paths like "123" must not become ints)
            try:
                parsed = json.loads(val)
            except json.JSONDecodeError:
                parsed = val
            cfg[k] = parsed if isinstance(parsed, (dict, list, type(None))) else val
        elif isinstance(v, (dict, list)):
            cfg[k] = json.loads(val)
        elif isinstance(v, bool):
            cfg[k] = bool(val)
        else:
            cfg[k] = val
    if getattr(args, "control_name", None) and args.control_name != "None":
        cfg["control"] = C.parse_control_name(args.control_name)
    return cfg


# ---------------------------------------------------------------------------
# multi-host resume consistency (ISSUE 17 satellite: tested directly)
# ---------------------------------------------------------------------------

def check_multihost_resume(blob: Optional[Dict[str, Any]]) -> int:
    """Verify every process resumed the SAME checkpoint state and return
    the agreed epoch.

    Sharded checkpoints load through the shared filesystem (the header
    names every process's shard file), so hosts given per-host LOCAL
    ``output_dir``\\ s diverge immediately: hosts 1..k see no blob (or a
    stale one) while process 0 resumes -- and the runs silently split into
    different round counts.  A cross-host broadcast of process 0's epoch
    catches that before any training dispatch.  No-op (returns this
    process's epoch) on a single-process runtime."""
    mine = int(blob.get("epoch", 0) if blob else 0)
    if jax.process_count() <= 1:
        return mine
    from jax.experimental import multihost_utils

    epoch0 = int(multihost_utils.broadcast_one_to_all(jnp.int32(mine)))
    if mine != epoch0:
        raise RuntimeError(
            f"resume state differs across hosts (process 0 at epoch "
            f"{epoch0}, this host at {mine}): output_dir must be a "
            f"shared filesystem for multi-host resume")
    return epoch0


def _restore_params(blob_params: Dict[str, Any]) -> Dict[str, Any]:
    """Checkpointed params -> device trees: shard-blocks markers (written
    by a multi-process run) densify from the merged block set first, so a
    blob restores onto ANY process count."""
    return {k: jnp.asarray(dense_from_blocks(v) if is_shard_marker(v) else v)
            for k, v in blob_params.items()}


# ---------------------------------------------------------------------------
# data staging for the engines
# ---------------------------------------------------------------------------

def _batch_array(x: np.ndarray, b: int, pad_value=0) -> Tuple[np.ndarray, np.ndarray]:
    """[N, ...] -> ([S, b, ...], weights [S, b]) padding the tail."""
    n = x.shape[0]
    s = math.ceil(n / b)
    pad = s * b - n
    w = np.concatenate([np.ones(n, np.float32), np.zeros(pad, np.float32)])
    if pad:
        x = np.concatenate([x, np.full((pad,) + x.shape[1:], pad_value, x.dtype)])
    return x.reshape((s, b) + x.shape[1:]), w.reshape(s, b)


def stage_local_eval(xu: np.ndarray, yu: np.ndarray, mu: np.ndarray,
                     batch_size: int) -> Tuple[np.ndarray, np.ndarray, np.ndarray]:
    """Per-user test shards ``[U, N, ...]`` -> batched ``[U, S, B, ...]``
    (tail padded with zero-weight samples): THE Local-eval operand layout,
    shared by the driver and the staticcheck eval-fused audit so their
    committed operands cannot drift apart."""
    u, n = xu.shape[0], xu.shape[1]
    b = min(batch_size, n)
    s = math.ceil(n / b)
    pad = s * b - n
    if pad:
        xu = np.concatenate([xu, np.zeros((u, pad) + xu.shape[2:], xu.dtype)], 1)
        yu = np.concatenate([yu, np.zeros((u, pad), yu.dtype)], 1)
        mu = np.concatenate([mu, np.zeros((u, pad), np.float32)], 1)
    return (xu.reshape(u, s, b, *xu.shape[2:]), yu.reshape(u, s, b),
            mu.reshape(u, s, b))


def stage_eval_operands(cfg, train_set, test_set, test_split, lm):
    """THE vision eval-operand assembly -- ``(sbn_batches, local_eval,
    global_eval)`` exactly as the driver commits them -- shared by
    :meth:`FedExperiment.stage` and the staticcheck eval-fused audit, so
    the audited operand layout cannot drift from the driver's."""
    users = cfg["num_users"]
    sbn = _batch_array(train_set.data, cfg["batch_size"]["train"])
    b = cfg["batch_size"]["test"]
    xg, wg = _batch_array(test_set.data, b)
    yg, _ = _batch_array(test_set.target, b)
    xu, yu, mu = stack_client_shards(test_set.data, test_set.target,
                                     test_split, list(range(users)))
    local = stage_local_eval(xu, yu, mu, b) + (lm,)
    return sbn, local, (xg, yg, wg)


def _maybe_compute_norm_stats(cfg: Dict[str, Any], dataset: Dict[str, Any]) -> None:
    """Datasets without a DATASET_STATS entry get per-channel stats computed
    from the train split (cached; ref utils.py:218-228 ``make_stats``)."""
    from ..data.datasets import DATASET_STATS

    if cfg.get("norm_stats") or cfg["data_name"] in DATASET_STATS:
        return
    if not hasattr(dataset["train"], "data"):
        return
    from ..data.stats import dataset_stats

    mean, std = dataset_stats(cfg["data_name"], dataset["train"].data, cfg["data_dir"])
    cfg["norm_stats"] = (tuple(float(x) for x in mean), tuple(float(x) for x in std))


def _first_round_is_setup(method):
    """The one call of a round method an experiment makes while
    ``_first_round_done`` is false -- the compile-bearing one -- runs under
    the set-up span ``setup/first_round`` (obs/spans.py); every later call
    pays one attribute test."""
    @functools.wraps(method)
    def wrapped(self, *args, **kwargs):
        if self._first_round_done:
            return method(self, *args, **kwargs)
        with spans.span("setup/first_round", self.phase_timer):
            return method(self, *args, **kwargs)
    return wrapped


class FedExperiment:
    """One federated experiment (one seed): owns the data staging, engine,
    evaluator, logger and checkpoint loop."""

    #: experiment arms (ISSUE 14) need the multiplexed driver loop --
    #: :class:`ArmsExperiment` flips this; the base loop refuses loudly
    _arms_capable = False

    def __init__(self, cfg: Dict[str, Any], seed: int):
        self.tag = C.make_model_tag(seed, cfg)
        # staging/dispatch telemetry (parallel/staging.py PhaseTimer)
        self.phase_timer = PhaseTimer()
        self.tracer = None  # obs.trace.TraceRecorder, attached by run()
        built = len(spans.RECORD.spans)
        try:
            with spans.span("setup/experiment", self.phase_timer):
                self._build(cfg, seed)
        finally:
            # construction's set-up and compile spans (obs/spans.py), for
            # the run's recorder: its timeline begins with them
            self._built_spans = spans.RECORD.spans[built:]

    def _build(self, cfg: Dict[str, Any], seed: int):
        """All of construction, under ``setup/experiment``."""
        self.cfg = cfg
        self.seed = seed
        self.kind = "transformer" if cfg["model_name"] in C.LM_MODEL_NAMES else "vision"
        self.rng = np.random.default_rng(seed)
        self.host_key = jax.random.key(seed)

        with spans.span("setup/dataset", self.phase_timer):
            dataset = fetch_dataset(cfg["data_name"], cfg["data_dir"], synthetic=cfg["synthetic"],
                                    seed=seed, synthetic_sizes=cfg.get("synthetic_sizes"),
                                    subset=cfg.get("subset", "label"))
            self.cfg, self.dataset = process_dataset(cfg, dataset)
            cfg = self.cfg
            _maybe_compute_norm_stats(cfg, self.dataset)
        with spans.span("setup/model", self.phase_timer):
            self.model = make_model(cfg)
            validate_width_geometry(self.model, cfg)
        n_data = max(1, cfg["mesh"].get("data", 1))
        n_clients = cfg["mesh"].get("clients", 0) or None
        # arms mesh axis (ISSUE 14): cfg['mesh']['arms'] = E lays each
        # experiment arm on its own device rows (the 'experiments' mesh
        # dimension); 0/absent keeps the (clients, data) mesh and the
        # vmap arms placement
        n_arms_axis = max(1, int(cfg["mesh"].get("arms", 1) or 1))
        # a cfg['mesh'] the devices cannot honour raises here; only
        # clients == 0 means "use all devices"
        with spans.span("setup/engine", self.phase_timer):
            self.mesh = make_mesh(n_clients, n_data, n_arms=n_arms_axis)
            self.engine = RoundEngine(self.model, cfg, self.mesh)
            self.evaluator = Evaluator(self.model, cfg, self.mesh, seed=seed)
        self.scheduler = make_scheduler(cfg)
        self.num_active = int(np.ceil(cfg["frac"] * cfg["num_users"]))
        if not 0 <= self.num_active <= cfg["num_users"]:
            # round_users would raise the same on the first draw; failing
            # at construction names the config knob instead of a mid-run
            # sampling error (ISSUE 11 satellite)
            raise ValueError(
                f"frac={cfg['frac']} draws num_active={self.num_active} "
                f"outside [0, num_users={cfg['num_users']}]")
        # population sampler (ISSUE 11, fed/sampling.py): 'prp' = O(active)
        # index-map draw (default), 'perm' = the legacy full-permutation
        # stream.  sample_horizon != None turns on schedule commitment:
        # superstep N+1's cohort draws from superstep N-horizon's FETCHED
        # state, which keeps the streaming prefetch overlap legal for
        # output-dependent samplers (stateless samplers are bit-identical
        # under commitment -- contract-tested).
        self.sampler_spec = resolve_sampler_cfg(cfg)
        self._commitment = (ScheduleCommitment(self.sampler_spec.horizon)
                            if self.sampler_spec.committed else None)
        self._ss_dispatched = 0  # streaming superstep dispatch counter
        self._ss_fetched = 0     # ... and its fetched-state twin
        self._round_times: List[float] = []  # steady-state round durations (ETA)
        self._first_round_done = False
        self._first_round_time = None  # the compile-bearing first dispatch
        # async metric fetch (parallel/staging.py): per-round metric sums
        # stay on device and are drained every cfg['metrics_fetch_every']
        # rounds (eval boundaries flush)
        fetch_every = int(cfg.get("metrics_fetch_every", 1) or 1)
        eval_iv = max(1, int(cfg.get("eval_interval", 1) or 1))
        self.eval_interval = eval_iv
        if cfg.get("strategy", "masked") not in ("masked", "sliced", "grouped"):
            raise ValueError(f"Not valid strategy: {cfg.get('strategy')!r}")
        # streaming client store (ISSUE 6): the population lives as an
        # O(1)-per-user index (parallel/staging.ClientStore) and only each
        # superstep's sampled cohort is materialised + prefetched
        store_mode = cfg.get("client_store", "eager") or "eager"
        if store_mode not in ("eager", "stream"):
            raise ValueError(f"Not valid client_store: {store_mode!r}")
        self.streaming = store_mode == "stream"
        self.stream_prefetch = bool(cfg.get("stream_prefetch", True))
        self.store: Optional[ClientStore] = None
        # prefetched (epoch0, k, StagedCohort) queue, up to
        # cfg['stream_prefetch_depth'] supersteps ahead (ISSUE 8 satellite)
        self._next_cohorts: List[Tuple[int, int, Any]] = []
        self._prefetch_depth = C.resolve_prefetch_depth(cfg)
        self._stream_sync_warned = False
        if self.streaming and cfg.get("strategy") == "sliced":
            raise ValueError(
                "client_store='stream' needs a mesh-native strategy "
                "('masked' or 'grouped'): the cohort pipeline stages "
                "through the engines' superstep programs")
        # wire codec (ISSUE 8): validated loudly here so a typo'd codec
        # never runs a silently-dense experiment; the lossy codecs need the
        # engines' single-global-psum programs
        self.wire_codec, self.error_feedback = resolve_codec_cfg(cfg)
        if isinstance(self.wire_codec, dict) \
                and cfg.get("strategy") != "grouped":
            raise ValueError(
                "a per-level wire_codec map needs strategy='grouped' (its "
                "fused superstep compresses each level's sliced payload "
                "under that level's codec); the other strategies have no "
                "levels to assign codecs to")
        if self.wire_codec != "dense":
            if cfg.get("strategy") == "sliced":
                raise ValueError(
                    f"wire_codec={self.wire_codec!r} needs a mesh-native "
                    f"strategy ('masked' or 'grouped'): the sliced debug "
                    f"twin aggregates on the host, there is no psum to "
                    f"compress")
            if cfg.get("strategy") == "grouped" \
                    and int(cfg.get("superstep_rounds", 1) or 1) <= 1 \
                    and store_mode != "stream":
                raise ValueError(
                    f"wire_codec={self.wire_codec!r} with the grouped "
                    f"strategy needs the fused superstep (superstep_rounds "
                    f"> 1 or client_store='stream'): the K=1 "
                    f"host-orchestrated path reduces per level and has no "
                    f"single global psum to compress")
        # fused multi-round superstep (ISSUE 2) with the sBN+eval phase
        # folded into the scan (ISSUE 4): K rounds per compiled program,
        # eval windows no longer clamp K.  Most knob combinations are now
        # expressible in-jit; the remaining conflicts fail LOUDLY here.
        self.superstep_rounds = max(1, int(cfg.get("superstep_rounds", 1) or 1))
        if self.superstep_rounds > 1:
            K = self.superstep_rounds
            if cfg.get("strategy") == "sliced":
                raise ValueError(
                    "superstep_rounds>1 needs a mesh-native engine "
                    "(strategy 'masked' or 'grouped'); 'sliced' is the "
                    "host-orchestrated debug twin")
            if fetch_every != 1 and fetch_every % K:
                raise ValueError(
                    f"metrics_fetch_every={fetch_every} conflicts with "
                    f"superstep_rounds={K}: a superstep fetches its metrics "
                    f"exactly once per K rounds (use 1 for synchronous fetch "
                    f"or exactly {K}; larger multiples would defer metrics "
                    f"past the superstep's checkpoint)")
            if isinstance(self.scheduler, PlateauScheduler):
                # ISSUE 4 relaxation: Plateau IS expressible now -- the LR is
                # constant within a superstep (staged scalar, not the traced
                # schedule) and steps on the fused eval metrics at superstep
                # boundaries.  That needs every eval to land on the FINAL
                # round of its superstep and the metrics fetched before the
                # next superstep dispatches.
                if eval_iv % K:
                    raise ValueError(
                        f"ReduceLROnPlateau with superstep_rounds={K} needs "
                        f"eval boundaries on superstep boundaries "
                        f"(eval_interval % superstep_rounds == 0, got "
                        f"eval_interval={eval_iv}): a mid-superstep eval "
                        f"would require an LR step inside the compiled scan")
                if fetch_every > K:
                    raise ValueError(
                        f"ReduceLROnPlateau feeds on each superstep's eval "
                        f"metrics before the next superstep dispatches; "
                        f"metrics_fetch_every={fetch_every} would defer them "
                        f"(use 1 or {K})")
            if fetch_every > K:
                # ISSUE 6 satellite: deferring fetch past the superstep
                # boundary makes pivot_fresh (run()) never true -- the
                # best-checkpoint copy silently stops updating.  Every
                # comparable knob conflict fails loudly; so does this one.
                raise ValueError(
                    f"metrics_fetch_every={fetch_every} exceeds "
                    f"superstep_rounds={K}: each superstep's eval metrics "
                    f"would be deferred past its checkpoint, silently "
                    f"disabling best-checkpoint tracking (pivot never "
                    f"fresh); use 1 or {K}")
            if eval_iv % K and K % eval_iv:
                # legal (the mask is data for the driver, structure for the
                # compiler) but worth a loud note: each distinct mask pattern
                # compiles its own K-round program (~40s at flagship scale)
                warnings.warn(
                    f"eval_interval={eval_iv} and superstep_rounds={K} are "
                    f"mutually non-divisible: the eval mask cycles through "
                    f"{math.lcm(eval_iv, K) // K} patterns, each compiling "
                    f"its own superstep program (cached and bounded, but "
                    f"expensive); align one to a multiple of the other to "
                    f"avoid the extra compiles")
            # the superstep pipeline counts PUSHES (one per superstep of K
            # rounds), so fetch_every=m*K defers m whole supersteps
            self.metrics_pipe = MetricsPipeline(max(1, fetch_every // K))
        else:
            self.metrics_pipe = MetricsPipeline(fetch_every)
            if self.streaming and fetch_every > 1:
                # streaming routes superstep_rounds=1 through the (k=1)
                # superstep path, whose pivot needs a synchronous fetch --
                # same silent best-checkpoint disable as fetch > K above
                raise ValueError(
                    f"metrics_fetch_every={fetch_every} with "
                    f"client_store='stream' at superstep_rounds=1 would "
                    f"defer each round's eval metrics past its checkpoint "
                    f"(best-checkpoint pivot never fresh); use 1")
            if self.metrics_pipe.fetch_every > eval_iv:
                # evaluate() drains the pipeline, so batches never grow past
                # the eval interval -- say so instead of silently
                # under-delivering
                warnings.warn(
                    f"metrics_fetch_every={self.metrics_pipe.fetch_every} exceeds "
                    f"eval_interval={eval_iv}: each eval boundary flushes the metric "
                    f"pipeline, so the effective fetch batch is eval_interval rounds")
        # client scheduler (ISSUE 9, heterofl_tpu/sched/): validated loudly
        # here so scenario configs fail at construction, not mid-run.  The
        # lockstep default changes nothing (bit-identical engines).
        self.sched_spec = resolve_schedule_cfg(cfg)
        if not self.sched_spec.lockstep and cfg.get("strategy") == "sliced":
            raise ValueError(
                "schedule scenarios (trace/markov availability, deadline, "
                "buffered aggregation) need a mesh-native strategy "
                "('masked' or 'grouped'): the sliced debug twin replays the "
                "reference host loop")
        if self.sched_spec.buffered:
            if self.wire_codec != "dense":
                raise ValueError(
                    "schedule aggregation='buffered' cannot combine with a "
                    "lossy wire_codec yet: both add a scan carry with its "
                    "own donation/checkpoint contract -- pick one per "
                    "experiment")
            if cfg.get("strategy") == "grouped" \
                    and self.superstep_rounds <= 1 and not self.streaming:
                raise ValueError(
                    "schedule aggregation='buffered' with the grouped "
                    "strategy needs the fused superstep (superstep_rounds "
                    "> 1 or client_store='stream'): the K=1 "
                    "host-orchestrated path combines in its own program "
                    "and has no scan carry to buffer")
        # sampled/rolling eval cohort (ISSUE 9 satellite): O(eval_cohort)
        # Local eval for streaming populations; loud cross-field checks
        self.eval_cohort = C.resolve_eval_cohort(cfg)
        if self.eval_cohort is not None:
            if not self.streaming:
                raise ValueError(
                    "eval_cohort needs client_store='stream': the eager "
                    "store already densifies the population, so its local "
                    "eval is O(num_users) either way")
            if self.kind != "vision":
                raise ValueError(
                    "eval_cohort samples the per-user Local eval, which "
                    "only vision experiments run (LM evaluates Global "
                    "only)")
        # runtime telemetry (ISSUE 10, heterofl_tpu/obs/): in-program health
        # probes + watchdog + run tracing -- validated loudly here so a
        # telemetry config that cannot run fails at construction
        self.obs_spec = resolve_telemetry_cfg(cfg)
        if self.obs_spec.probes:
            if cfg.get("strategy") == "sliced":
                raise ValueError(
                    "telemetry='on' needs a mesh-native strategy ('masked' "
                    "or 'grouped'): the sliced debug twin replays the "
                    "reference host loop and has no in-program round core "
                    "to probe")
            if cfg.get("strategy") == "grouped" \
                    and self.superstep_rounds <= 1 and not self.streaming:
                raise ValueError(
                    "telemetry='on' with the grouped strategy needs the "
                    "fused superstep (superstep_rounds > 1 or client_store="
                    "'stream'): the K=1 path splits the round across L+1 "
                    "host-orchestrated programs with no shared round core "
                    "to probe")
        self.watchdog = Watchdog(self.obs_spec.watchdog) \
            if (self.obs_spec.probes and self.obs_spec.watchdog is not None) \
            else None
        # client-update quarantine (ISSUE 15): validated loudly here so a
        # quarantine config that cannot run fails at construction.  The
        # gate lives in the engines' round cores -- the sliced debug twin
        # replays the reference host loop and has no core to gate in.
        self.quarantine = resolve_quarantine_cfg(cfg)
        if self.quarantine.enabled and cfg.get("strategy") == "sliced":
            raise ValueError(
                "quarantine needs a mesh-native strategy ('masked' or "
                "'grouped'): the sliced debug twin replays the reference "
                "host loop and has no in-program round core to gate")
        if resolve_poison_cfg(cfg) is not None \
                and cfg.get("strategy") == "sliced":
            raise ValueError(
                "chaos_poison needs a mesh-native strategy ('masked' or "
                "'grouped'): the sliced debug twin has no in-program "
                "update to poison")
        # durable generational checkpoints (ISSUE 15): rotation depth
        self.checkpoint_keep = C.resolve_checkpoint_keep(cfg)
        # rollback budget bookkeeping (watchdog action='rollback'):
        # attempts since the last CLEAN checkpoint write -- a completed
        # superstep + checkpoint proves recovery, resetting the budget
        self._rollback_attempts = 0
        # chaos fault injector (heterofl_tpu/chaos/): attached by the
        # drill harness; None (always, outside drills) = zero-cost checks
        self.chaos = None
        # population-observatory ledger (ISSUE 12, obs/ledger.py): a
        # host-side per-client record updated O(active) at each metrics
        # fetch -- never a program change, so it composes with every
        # telemetry mode.  Cross-field conflicts fail loudly here.
        self.ledger_spec = resolve_ledger_cfg(cfg)
        self.ledger = None
        if self.ledger_spec.enabled:
            if cfg.get("strategy") == "sliced":
                raise ValueError(
                    "ledger='on' needs a mesh-native strategy ('masked' or "
                    "'grouped'): the sliced debug twin replays the "
                    "reference host loop, whose metrics never ride the "
                    "fetch path the ledger folds from")
            if cfg.get("data_placement") == "sharded":
                raise ValueError(
                    "ledger='on' needs replicated (or streaming) data "
                    "placement: the sharded slot packing re-orders metric "
                    "rows by owning device, dropping the schedule-order "
                    "uid alignment the O(active) fold consumes")
            self.ledger = ClientLedger(
                cfg["num_users"],
                sorted({float(r) for r in cfg["model_rate"]}, reverse=True))
        # experiment arms (ISSUE 14, heterofl_tpu/multi/): the base driver
        # runs ONE trajectory -- a multiplexed cfg must go through the
        # ArmsExperiment loop (per-arm checkpoints/logs/Plateau state),
        # which python -m heterofl_tpu.multi.sweep drives
        self.arms_spec = resolve_arms_cfg(cfg)
        if self.arms_spec is not None and not self._arms_capable:
            raise ValueError(
                "cfg['arms'] needs the multiplexed driver loop: run the "
                "sweep front-end (python -m heterofl_tpu.multi.sweep) or "
                "construct entry.common.ArmsExperiment directly -- the "
                "single-trajectory FedExperiment loop cannot thread "
                "per-arm checkpoints/logs")
        if self.arms_spec is not None:
            if cfg.get("strategy") == "sliced":
                raise ValueError(
                    "arms need a mesh-native strategy ('masked' or "
                    "'grouped'): the sliced debug twin replays the "
                    "reference host loop one trajectory at a time")
            if self.ledger_spec.enabled:
                raise ValueError(
                    "ledger='on' cannot combine with arms yet: the "
                    "O(active) fold consumes ONE sampling stream's cohort "
                    "rows, and each arm draws its own (a ROADMAP "
                    "follow-on)")
            if self.obs_spec.trace_dir:
                raise ValueError(
                    "trace_dir cannot combine with arms yet: the "
                    "multiplexed loop does not build the TraceRecorder, "
                    "so the trace would be silently empty (a ROADMAP "
                    "follow-on; per-arm probes/watchdog DO run)")
            # arms-mesh multi-process runs are supported since ISSUE 17:
            # staging commits through commit_global (GSPMD NamedSharding
            # assembly) and the checkpoint path writes per-process shard
            # files for non-addressable leaves (save_checkpoint_sharded)
        self._eval_widx = None  # rolling Local-eval window currently staged
        self._fused = None  # FusedEval, built on first eval-bearing superstep
        self.alt_engine = None
        if cfg.get("strategy") == "sliced":
            from ..fed.sliced import SlicedFederation

            self.alt_engine = SlicedFederation(cfg)
        elif cfg.get("strategy") == "grouped":
            from ..parallel.grouped import GroupedRoundEngine

            with spans.span("setup/engine", self.phase_timer):
                self.alt_engine = GroupedRoundEngine(cfg, self.mesh)

    # -- staging -------------------------------------------------------

    def make_splits(self):
        with spans.span("setup/split", self.phase_timer):
            return split_dataset(self.dataset, self.cfg["num_users"],
                                 self.cfg["data_split_mode"], self.rng,
                                 classes_size=self.cfg["classes_size"])

    def _place(self, data):
        """Train stacks onto devices per ``cfg['data_placement']``."""
        if self.cfg.get("data_placement") == "sharded" and self.alt_engine is None:
            from ..parallel import shard_client_data

            return shard_client_data(self.mesh, data)
        return tuple(jnp.asarray(a) for a in data)

    def stage(self, data_split, label_split):
        with spans.span("setup/stage", self.phase_timer):
            self._stage(data_split, label_split)

    def _stage(self, data_split, label_split):
        cfg = self.cfg
        U = cfg["num_users"]
        if self.streaming:
            # ISSUE 6: no [U, ...] densification -- the population is an
            # O(1)-per-user index over the raw arrays, and train cohorts
            # materialise per superstep (stage_cohort + prefetch).  Eval
            # operands stage LAZILY on the first eval: local (per-user)
            # eval is the one remaining O(U) surface, so runs that never
            # evaluate (population benches) never pay it.
            tr = self.dataset["train"]
            with spans.span("setup/stage/train", self.phase_timer):
                if self.kind == "vision":
                    self.store = ClientStore.from_split(
                        tr.data, tr.target, data_split["train"], label_split,
                        cfg["classes_size"])
                else:
                    self.store = ClientStore.from_split(
                        tr.token, None, data_split["train"], label_split,
                        cfg["num_tokens"], kind="lm")
            self.train_data = None
            self._eval_split = (data_split["test"], label_split)
            self._eval_staged = False
            return
        if self.kind == "vision":
            tr = self.dataset["train"]
            with spans.span("setup/stage/train", self.phase_timer):
                x, y, m = stack_client_shards(tr.data, tr.target, data_split["train"], list(range(U)))
                lm = label_split_masks(label_split, U, cfg["classes_size"])
                self.train_data = self._place((x, y, m, lm))
            # sBN recalibration batches over the whole train set, per-user
            # local eval shards, batched global test set -- the shared
            # assembly (audit/bench stage the same layout)
            with spans.span("setup/stage/eval", self.phase_timer):
                self.sbn_batches, self.local_eval, self.global_eval = \
                    stage_eval_operands(cfg, tr, self.dataset["test"],
                                        data_split["test"], lm)
        else:
            tr = self.dataset["train"]
            with spans.span("setup/stage/train", self.phase_timer):
                rows = stack_client_token_rows(tr.token, data_split["train"], list(range(U)))
                lm = label_split_masks(label_split, U, cfg["num_tokens"])
                self.train_data = self._place((rows, lm))
            te = self.dataset["test"]
            with spans.span("setup/stage/eval", self.phase_timer):
                xs, ws = stack_windows(bptt_windows(te.token, cfg["bptt"]), cfg["bptt"])
                self.global_eval = (xs, ws)

    def _ensure_eval_staged(self):
        """Streaming mode's lazy eval staging (see :meth:`stage`)."""
        if not self.streaming or self._eval_staged:
            return
        cfg = self.cfg
        U = cfg["num_users"]
        test_split, label_split = self._eval_split
        if self.kind == "vision":
            if self.eval_cohort is not None:
                # sampled/rolling eval cohort (ISSUE 9 satellite): Local
                # eval stages O(eval_cohort) per window instead of O(U) --
                # the one population-scaling surface the streaming store
                # left (and the reason the O(U) warning below is retired
                # on this path).  sBN and Global keep their full sets.
                self.sbn_batches = _batch_array(self.dataset["train"].data,
                                                cfg["batch_size"]["train"])
                b = cfg["batch_size"]["test"]
                te = self.dataset["test"]
                xg, wg = _batch_array(te.data, b)
                yg, _ = _batch_array(te.target, b)
                self.global_eval = (xg, yg, wg)
                self.local_eval = None  # staged per rolling window
                self._eval_staged = True
                return
            if U > 100_000:
                warnings.warn(
                    f"local eval stages every user's test shard (O(U) at "
                    f"num_users={U}); set eval_cohort for a rolling "
                    f"O(cohort) Local eval, cap eval_interval past "
                    f"num_epochs, or stick to population benches if this "
                    f"OOMs")
            lm = label_split_masks(label_split, U, cfg["classes_size"])
            self.sbn_batches, self.local_eval, self.global_eval = \
                stage_eval_operands(cfg, self.dataset["train"],
                                    self.dataset["test"], test_split, lm)
        else:
            te = self.dataset["test"]
            xs, ws = stack_windows(bptt_windows(te.token, cfg["bptt"]), cfg["bptt"])
            self.global_eval = (xs, ws)
        self._eval_staged = True

    # -- one round -----------------------------------------------------

    def sample_users(self, epoch: int) -> np.ndarray:
        """The K=1 host draw.  Uniform under ``sampler='perm'`` keeps the
        drivers' legacy numpy permutation stream (reference parity,
        bit-identical trajectories); everything else -- the 'prp' sampler
        and every availability schedule -- draws through THE shared
        sampling stream (:func:`~..fed.core.round_users` at the round key)
        so the K=1 and superstep paths replay the same trace: unavailable
        slots come back -1 and flow through the engines as padding."""
        if self.sched_spec.kind == "uniform" \
                and self.sampler_spec.kind == "perm":
            return self.rng.permutation(self.cfg["num_users"])[: self.num_active].astype(np.int32)
        key = jax.random.fold_in(self.host_key, epoch)
        with self.phase_timer.phase("sample"):
            return np.asarray(round_users(key, self.cfg["num_users"],
                                          self.num_active,
                                          avail=self.sched_spec.avail_row(epoch),
                                          sampler=self.sampler_spec.kind))

    def _chaos(self, point: str) -> None:
        """Chaos kill check (ISSUE 15, heterofl_tpu/chaos/): raises
        ChaosKill when an attached drill plan schedules a death at this
        boundary; no-op (one attribute test) outside drills."""
        if self.chaos is not None:
            self.chaos.check(point)

    @_first_round_is_setup
    def train_round(self, params, epoch: int, lr: float, logger: Logger):
        self._chaos("superstep")  # the K=1 dispatch boundary
        user_idx = self.sample_users(epoch)
        key = jax.random.fold_in(self.host_key, epoch)
        t0 = time.time()
        phases0 = self.phase_timer.snapshot()
        # first steady-state round actually executed (works under resume too)
        profiling = (self.cfg.get("profile_dir") and self._first_round_done
                     and not getattr(self, "_profiled", False))
        if profiling:
            self._profiled = True
            jax.profiler.start_trace(self.cfg["profile_dir"])
        if self.alt_engine is not None:
            rates = np.asarray(round_rates(key, self.cfg, jnp.asarray(user_idx)))
            if self.cfg.get("strategy") == "grouped":
                # mesh-native: params stay on device end to end; the metric
                # sums stay there too until the pipeline drains them
                params, pending = self.alt_engine.train_round(
                    params, user_idx, rates, self.train_data, lr, key,
                    timer=self.phase_timer, async_metrics=True)
            else:
                new_np, ms = self.alt_engine.train_round(
                    {k: np.asarray(v) for k, v in params.items()}, user_idx, rates,
                    self.train_data, lr, key)
                params = {k: jnp.asarray(v) for k, v in new_np.items()}
                pending = PendingMetrics(ms)
        else:
            params, ms = self.engine.train_round(params, key, lr, user_idx,
                                                 self.train_data,
                                                 timer=self.phase_timer,
                                                 epoch=epoch)
            pending = PendingMetrics(ms)
        if profiling:
            jax.block_until_ready(params)
            jax.profiler.stop_trace()
        # uids ride the tag (ISSUE 12): the K=1 ledger fold needs the drawn
        # cohort, and the legacy perm+uniform numpy stream is stateful --
        # it cannot be re-drawn at fetch time like the superstep streams
        tag = {"epoch": epoch, "lr": lr, "dt": 0.0, "phases": {},
               "uids": user_idx}
        self._chaos("fetch")
        with self.phase_timer.phase("fetch"):
            due = self.metrics_pipe.push(tag, pending)
        # dt and the phase breakdown are filled in AFTER the push (the tag is
        # the same dict object the pipeline holds, so deferred entries carry
        # their own round's values): at the parity default
        # (metrics_fetch_every=1) the push fetches synchronously, so dt spans
        # dispatch + device compute exactly like the pre-staging driver and
        # the round's own fetch shows up in ITS phases line; with K>1 the
        # non-fetching rounds record their (tiny) dispatch wall and the
        # batch-fetching round absorbs the whole batch's compute + drain, so
        # the ETA mean over rounds stays the true cadence.  First processed
        # round (compile) is excluded, parity with the reference's telemetry
        # (train_classifier_fed.py:105-119).
        tag["dt"] = dt = time.time() - t0
        tag["phases"] = self.phase_timer.delta(phases0)
        if self._first_round_done:
            self._round_times.append(dt)
        else:
            self._first_round_done = True  # exclude the compile round
            self._first_round_time = dt
        for tag0, ms_host in due:
            self._log_train_round(logger, tag0["epoch"], tag0["lr"], tag0["dt"],
                                  tag0["phases"], ms_host,
                                  uids=tag0.get("uids"))
        return params

    def _superstep_schedule(self, epoch0: int, k: int) -> np.ndarray:
        """Host-side [k, A] active-user draw from the superstep sampling
        stream (fed.core.superstep_user_schedule): what the masked engine
        samples in-jit, evaluated on the host where slot packing needs the
        ids (sharded placement, grouped level grouping, cohort staging).
        The availability schedule (ISSUE 9) threads through the shared
        stream, so host- and in-jit-sampled paths replay the same trace;
        the sampler kind (ISSUE 11) threads the same way -- host schedules
        and the in-jit draw must name the same sampler.  The draw is its
        own ``sample`` phase (PhaseTimer) so the O(U) -> O(active) win is
        visible per round instead of hiding inside ``stage``."""
        with self.phase_timer.phase("sample"):
            return superstep_user_schedule(self.host_key, epoch0, k,
                                           self.cfg["num_users"],
                                           self.num_active,
                                           schedule=self.sched_spec,
                                           sampler=self.sampler_spec.kind)

    # -- streaming cohort pipeline (ISSUE 6) ---------------------------

    def _stage_cohort(self, epoch0: int, k: int):
        """Materialise + commit the cohort for rounds ``epoch0..epoch0+k-1``
        through the engine's store-backed staging."""
        users = self._superstep_schedule(epoch0, k)
        if self.cfg.get("strategy") == "grouped":
            rates = superstep_rate_schedule(self.host_key, epoch0, k,
                                            self.cfg, users)
            return self.alt_engine.stage_cohort(self.store, users, rates,
                                                timer=self.phase_timer)
        return self.engine.stage_cohort(self.store, users,
                                        timer=self.phase_timer)

    def _take_cohort(self, epoch0: int, k: int):
        """The prefetched cohort for this superstep, or a synchronous stage
        (first superstep of a run; ``stream_prefetch`` off -- warned once:
        a sampler that depends on round-N outputs cannot prefetch, and the
        staging then serialises with compute)."""
        if self._next_cohorts and self._next_cohorts[0][:2] == (epoch0, k):
            return self._next_cohorts.pop(0)[2]
        self._next_cohorts = []  # a schedule jump invalidates the queue
        if self._commitment is not None \
                and not self._commitment.may_draw(self._ss_dispatched + 1):
            # every legal knob combination fetches (and commits) at least
            # once per superstep push, so the state THIS dispatch's draw
            # consumes is always on the host by now; reaching here means a
            # metrics fetch was deferred past the commitment horizon, and
            # drawing anyway would consume uncommitted state silently --
            # the exact hole sample_horizon exists to close.  Fail loudly.
            raise RuntimeError(
                f"schedule commitment: the superstep at epoch {epoch0} "
                f"draws from superstep "
                f"{self._ss_dispatched - self.sampler_spec.horizon}'s "
                f"state but only {self._ss_fetched} superstep(s) have "
                f"fetched -- a deferred metrics fetch crossed "
                f"sample_horizon={self.sampler_spec.horizon}")
        if self._commitment is not None and self.sampler_spec.horizon == 0 \
                and self._ss_dispatched > 0 and self.stream_prefetch \
                and not self._stream_sync_warned:
            self._stream_sync_warned = True
            warnings.warn(
                "sample_horizon=0 (strictly output-dependent sampler) is "
                "staging SYNCHRONOUSLY: each cohort draws from the "
                "previous superstep's just-fetched state, so staging "
                "cannot overlap compute -- sample_horizon=1 commits one "
                "state further back and keeps the overlap")
        if not self.stream_prefetch and not self._stream_sync_warned:
            self._stream_sync_warned = True
            warnings.warn(
                "client_store='stream' is staging SYNCHRONOUSLY "
                "(stream_prefetch=False): cohort materialisation serialises "
                "with the round compute instead of overlapping it -- an "
                "output-dependent sampler can keep the overlap by "
                "committing its schedule instead (cfg['sample_horizon'], "
                "ISSUE 11)")
        return self._stage_cohort(epoch0, k)

    def _prefetch_cohort(self, epoch0: int):
        """Stage UPCOMING supersteps' cohorts right after this superstep
        dispatched: the device_put pipeline overlaps with the in-flight
        scanned program.  ``stream_prefetch_depth`` (ISSUE 8 satellite)
        bounds how many supersteps ahead the queue runs; the stager's ring
        holds depth+1 slots and fences each slot on its previous private
        copy, so staging ahead can never corrupt an in-flight superstep."""
        if not self.stream_prefetch:
            return
        self._chaos("prefetch")
        n_rounds = self.cfg["num_epochs"]["global"]
        e = (self._next_cohorts[-1][0] + self._next_cohorts[-1][1]
             if self._next_cohorts else epoch0)
        while len(self._next_cohorts) < self._prefetch_depth \
                and e <= n_rounds:
            if self._commitment is not None and not self._commitment.may_draw(
                    self._ss_dispatched + len(self._next_cohorts) + 1):
                # schedule commitment (ISSUE 11): this superstep's cohort
                # would consume state not yet fetched -- stop here; the
                # queue refills after the next fetch commits it.  At the
                # sync default (fetch_every=1) horizon 1 always admits the
                # next superstep, so the PR 6 overlap survives.
                break
            k = min(self.superstep_rounds, n_rounds - e + 1)
            self._next_cohorts.append((e, k, self._stage_cohort(e, k)))
            e += k

    def _codec_engine(self):
        """The engine holding the wire-codec error-feedback carry and the
        buffered-async staleness buffer (the one that dispatches the
        carry-bearing programs)."""
        return self.alt_engine if self.cfg.get("strategy") == "grouped" \
            else self.engine

    def _eval_cohort_users(self, widx: int) -> list:
        """The rolling Local-eval window: ``eval_cohort`` consecutive users
        starting at ``widx * eval_cohort`` (mod the population) -- each eval
        window advances the cohort, so repeated evals sweep the population.
        Deterministic in ``widx`` (itself derived from the eval epoch), so
        checkpoint resume stages the identical window."""
        n, u = self.eval_cohort, self.cfg["num_users"]
        return [int(x) for x in (widx * n + np.arange(n)) % u]

    def _local_cohort_operands(self, widx: int):
        """Stage the rolling window's Local-eval operands (O(cohort) host
        gather + device commit; same batched layout as the population
        path's ``stage_local_eval``).  Shards pad to the POPULATION-wide
        max test-shard size so every window shares one operand shape -- the
        cached superstep program then takes each window as plain arguments
        instead of recompiling per window."""
        users = self._eval_cohort_users(widx)
        test_split, label_split = self._eval_split
        if not hasattr(self, "_eval_shard_max"):
            self._eval_shard_max = max(
                len(test_split[u]) for u in range(self.cfg["num_users"]))
        te = self.dataset["test"]
        xu, yu, mu = stack_client_shards(te.data, te.target, test_split,
                                         users)
        n = self._eval_shard_max
        if xu.shape[1] < n:
            pad = n - xu.shape[1]
            xu = np.concatenate(
                [xu, np.zeros((len(users), pad) + xu.shape[2:], xu.dtype)], 1)
            yu = np.concatenate(
                [yu, np.zeros((len(users), pad), yu.dtype)], 1)
            mu = np.concatenate(
                [mu, np.zeros((len(users), pad), np.float32)], 1)
        lm = label_split_masks({i: label_split[u] for i, u in enumerate(users)},
                               len(users), self.cfg["classes_size"])
        b = min(self.cfg["batch_size"]["test"], n)
        return stage_local_eval(xu, yu, mu, b) + (lm,)

    def _fused_eval(self, widx: Optional[int] = None):
        """The experiment's :class:`~..parallel.evaluation.FusedEval`: eval
        operands committed once (shared with the host-path memos), built
        lazily on the first eval-bearing superstep.

        ``widx`` (rolling eval cohort, ISSUE 9 satellite): the Local-eval
        window to stage.  A window change re-stages ONLY the cohort's local
        operands and rebuilds the FusedEval wrapper around them -- the sBN/
        Global commits are identity memo hits and the engines' cached
        superstep programs take the new operands as plain arguments (same
        avals, no recompile)."""
        if self.eval_cohort is not None and widx != self._eval_widx:
            self._ensure_eval_staged()
            local = self._local_cohort_operands(widx)
            self._fused = self.evaluator.fused(
                sbn_batches=self.sbn_batches, local_eval=local,
                global_eval=self.global_eval)
            self._eval_widx = widx
        if self._fused is None:
            self._ensure_eval_staged()
            if self.kind == "vision":
                self._fused = self.evaluator.fused(
                    sbn_batches=self.sbn_batches, local_eval=self.local_eval,
                    global_eval=self.global_eval)
            else:
                self._fused = self.evaluator.fused(global_eval=self.global_eval)
        return self._fused

    @_first_round_is_setup
    def train_superstep(self, params, epoch0: int, k: int, logger: Logger):
        """Run rounds ``epoch0 .. epoch0+k-1`` as ONE compiled program
        (``superstep_rounds``): the round boundary leaves the host -- one
        stage+dispatch cycle and one metric fetch serve all k rounds, and the
        per-round phase breakdown is the amortized cost (PhaseTimer).

        Rounds where the eval cadence fires (``epoch % eval_interval == 0``
        or the final round) run the fused sBN+eval phase INSIDE the program
        (ISSUE 4): the static eval mask keys the compiled superstep, the
        eval results come back in the same per-superstep fetch, and the last
        per-eval-window host round-trip is gone -- ``eval_interval`` no
        longer clamps K."""
        self._chaos("superstep")
        cfg = self.cfg
        n_rounds = cfg["num_epochs"]["global"]
        mask = tuple((epoch0 + r) % self.eval_interval == 0
                     or (epoch0 + r) == n_rounds for r in range(k))
        widx = None
        if any(mask) and self.eval_cohort is not None:
            # rolling Local-eval window (ISSUE 9 satellite): derived from
            # this superstep's FIRST eval epoch, so the sweep is
            # deterministic in the cadence and stable across resume
            first_eval = min(epoch0 + r for r in range(k) if mask[r])
            widx = first_eval // self.eval_interval
        fused = self._fused_eval(widx) if any(mask) else None
        plateau = isinstance(self.scheduler, PlateauScheduler)
        # Plateau holds the LR constant between metric steps, and steps only
        # at superstep boundaries (validated in __init__): the superstep
        # takes it as a staged scalar instead of the traced schedule
        lr_const = self.scheduler(epoch0) if plateau else None
        t0 = time.time()
        phases0 = self.phase_timer.snapshot()
        if self.streaming:
            # the cohort was (normally) prefetched while the PREVIOUS
            # superstep computed; dispatch it, then immediately stage the
            # next one so its device_put pipeline overlaps with this
            # superstep's in-flight scan
            cohort = self._take_cohort(epoch0, k)
            eng = self.alt_engine if cfg.get("strategy") == "grouped" \
                else self.engine
            params, pending = eng.train_superstep(
                params, self.host_key, epoch0, k, timer=self.phase_timer,
                eval_mask=mask if fused else None, fused_eval=fused,
                lr=lr_const, cohort=cohort)
            self._ss_dispatched += 1
            with self._trace_span("prefetch", {"epoch0": int(epoch0 + k)}):
                self._prefetch_cohort(epoch0 + k)
        elif cfg.get("strategy") == "grouped":
            users = self._superstep_schedule(epoch0, k)
            rates = superstep_rate_schedule(self.host_key, epoch0, k, cfg,
                                            users)
            params, pending = self.alt_engine.train_superstep(
                params, self.host_key, epoch0, k, users, rates,
                self.train_data, timer=self.phase_timer,
                eval_mask=mask if fused else None, fused_eval=fused,
                lr=lr_const)
        else:
            sched = None
            if cfg.get("data_placement") == "sharded":
                sched = self._superstep_schedule(epoch0, k)
            params, pending = self.engine.train_superstep(
                params, self.host_key, epoch0, k, self.train_data,
                user_schedule=sched, num_active=self.num_active,
                timer=self.phase_timer, eval_mask=mask if fused else None,
                fused_eval=fused, lr=lr_const)
        tag = {"kind": "superstep", "epoch0": epoch0, "k": k, "dt": 0.0,
               "phases": {},
               "lrs": [self.scheduler(epoch0 + r) for r in range(k)]}
        self._chaos("fetch")
        with self.phase_timer.phase("fetch"):
            due = self.metrics_pipe.push(tag, pending)
        # dt/phases fill in AFTER the push (the tag object rides the
        # pipeline, so deferred entries carry their own superstep's values);
        # at the sync default every superstep drains immediately
        dt = time.time() - t0
        tag["dt"] = dt
        tag["phases"] = self.phase_timer.amortized(phases0, k)
        if self._first_round_done:
            self._round_times.extend([dt / k] * k)
        else:
            self._first_round_done = True  # exclude the compile superstep
            self._first_round_time = dt
        for tag0, out in due:
            self._log_superstep(logger, tag0, out)
        return params

    def _trace_span(self, name: str, args: Optional[Dict[str, Any]] = None):
        """A run-trace span (ISSUE 10) -- nullcontext when tracing is off,
        so the driver's event sites cost nothing un-traced."""
        if self.tracer is not None:
            return self.tracer.span(name, cat="driver", args=args)
        return nullcontext()

    def _observe(self, logger: Logger, epoch: int, probes: Dict[str, Any],
                 ms) -> None:
        """Surface one fetched round's health probes (ISSUE 10): a
        structured obs event on the run's JSONL, a trace instant, and the
        watchdog check (loud warning or configurable abort).  This runs at
        the FETCH boundary -- the first host code that sees the round."""
        loss = None
        n = float(np.sum(ms["n"]))
        if n > 0:
            loss = float(np.sum(ms["loss_sum"])) / n
        logger.emit({"event": "probes", "epoch": int(epoch), "loss": loss,
                     **probes})
        if self.tracer is not None:
            self.tracer.instant("probes", cat="obs",
                                args={"epoch": int(epoch), "loss": loss,
                                      **probes})
        if self.watchdog is not None:
            def emit_trip(ev):
                # a watchdog trip is abort evidence: it lands on BOTH the
                # run log and the trace timeline (ISSUE 12 satellite) --
                # the last event of an aborted run is the watchdog instant
                logger.emit(ev)
                if self.tracer is not None:
                    self.tracer.instant("watchdog", cat="obs", args=ev)

            try:
                self.watchdog.check(epoch, probes=probes, loss=loss,
                                    emit=emit_trip)
            except WatchdogRollback:
                # rollback durability (ISSUE 15 satellite): the SAME
                # artifacts as the abort path, per recovery attempt -- the
                # trip instant is the last event on disk before the
                # rollback unwinds -- but via sync(), not close(): the run
                # continues tracing through the recovery
                if self.tracer is not None:
                    self.tracer.sync()
                logger.flush()
                if self.ledger is not None and jax.process_index() == 0:
                    self.ledger.save(self._ledger_path())
                raise
            except WatchdogError:
                # durability (ISSUE 12 satellite): the evidence must be ON
                # DISK before the abort unwinds -- close() fsyncs
                # events.jsonl and writes + fsyncs the Chrome trace, so a
                # crash right after loses nothing (the outer finally's
                # close is then an idempotent no-op)
                if self.tracer is not None:
                    self.tracer.close()
                logger.flush()
                if self.ledger is not None and jax.process_index() == 0:
                    # process 0 only, like the normal exit path: concurrent
                    # saves through the shared tmp name would corrupt the
                    # very snapshot the abort is trying to preserve
                    self.ledger.save(self._ledger_path())
                raise

    def _fold_ledger(self, logger: Logger, epoch0: int, k: int, rounds,
                     uid_rows: Optional[np.ndarray] = None) -> None:
        """Fold one fetch's rounds into the :class:`ClientLedger` (ISSUE
        12) and emit the ``{"tag": "ledger"}`` summary -- O(active) per
        fetch.  ``uid_rows=None`` re-draws the cohort ids from THE one
        sampling stream (:func:`~..fed.core.superstep_user_schedule`, the
        host twin of the in-jit draw -- bit-identical by contract), which
        is exactly the ``ScheduleCommitment.state_for`` alignment: fetch
        order is dispatch order, so round ``epoch0 + r``'s metric row r
        IS that draw's cohort in schedule order."""
        if uid_rows is None:
            uid_rows = superstep_user_schedule(
                self.host_key, epoch0, k, self.cfg["num_users"],
                self.num_active, schedule=self.sched_spec,
                sampler=self.sampler_spec.kind)
        tot_active = tot_new = 0
        last = None
        for r in range(k):
            u = uid_rows[r]
            a = len(u)
            ms = rounds[r]
            last = self.ledger.update(epoch0 + r, u,
                                      np.asarray(ms["rate"])[:a],
                                      np.asarray(ms["loss_sum"])[:a],
                                      np.asarray(ms["n"])[:a])
            tot_active += last["active"]
            tot_new += last["new_users"]
        rec = {"event": "ledger", "epoch0": int(epoch0), "k": int(k),
               "active": tot_active, "new_users": tot_new,
               "coverage": last["coverage"],
               "loss_ema_mean": last["loss_ema_mean"],
               "bytes": self.ledger.nbytes}
        logger.emit(rec, tag="ledger")
        if self.tracer is not None:
            self.tracer.instant("ledger", cat="obs", args=rec)

    def _ledger_path(self) -> str:
        """Where this run's ``ledger.npz`` snapshot lands: next to the
        trace artifacts when tracing (the report surface reads them
        together), else under the run's output dir."""
        base = os.path.join(self.obs_spec.trace_dir, self.tag) \
            if self.obs_spec.trace_dir \
            else os.path.join(self.cfg["output_dir"], "obs", self.tag)
        return os.path.join(base, "ledger.npz")

    def _log_superstep(self, logger: Logger, tag: Dict[str, Any], out):
        """Log one (possibly deferred) superstep's rounds: train metrics per
        round, with each fused eval's Local/Global metrics logged right
        after the round it evaluated -- the K=1 host-loop ordering."""
        if self._commitment is not None:
            # schedule commitment (ISSUE 11): this superstep's state is on
            # the host NOW -- cohorts that draw from it become stageable.
            # Fetch order == dispatch order (the metrics pipeline is FIFO),
            # so the counter pair stays consistent.
            self._ss_fetched += 1
            self._commitment.commit(self._ss_fetched, state=out)
        rounds = out["train"] if isinstance(out, dict) else out
        evals = {e["epoch"]: e for e in (out.get("eval") or [])} \
            if isinstance(out, dict) else {}
        probes = out.get("obs") if isinstance(out, dict) else None
        if self.ledger is not None:
            self._fold_ledger(logger, tag["epoch0"], tag["k"], rounds)
        per_round = tag["dt"] / tag["k"]
        for r in range(tag["k"]):
            epoch = tag["epoch0"] + r
            self._log_train_round(logger, epoch, tag["lrs"][r], per_round,
                                  tag["phases"], rounds[r],
                                  probes=probes[r] if probes else None)
            ev = evals.get(epoch)
            if ev is not None:
                self._log_fused_eval(logger, epoch, ev)
                if isinstance(self.scheduler, PlateauScheduler):
                    # same feed as the K=1 path: min-mode plateau on the
                    # test Global loss of rounds that evaluated
                    self.scheduler.step_metric(
                        logger.mean.get("test/Global-Loss", 0.0))

    def _log_fused_eval(self, logger: Logger, epoch: int, ev: Dict[str, Any]):
        """Mirror :meth:`evaluate`'s logging for one fused eval result."""
        cfg = self.cfg
        # each fused eval's test means stand alone (ISSUE 6 satellite): the
        # K=1 host loop resets the logger every round, so without this a
        # superstep's later evals BLEND with its earlier ones and the
        # best-checkpoint pivot / Plateau feed compare a blended mean
        # instead of the boundary round's own eval
        logger.reset_tag("test")
        if self.kind == "vision" and ev["local"]:
            local = ev["local"]
            named_local = summarize_sums(local, cfg["model_name"])
            logger.append(named_local, "test", n=float(np.sum(local["n"])))
        named_global = summarize_sums({k: np.asarray(v) for k, v in ev["global"].items()},
                                      cfg["model_name"], prefix="Global-")
        logger.append(named_global, "test", n=ev["global"]["n"])
        info = {"info": [f"Model: {self.tag}", f"Test Epoch: {epoch}"]}
        logger.append(info, "test", mean=False)
        test_names = [n.split("/", 1)[1] for n in logger.mean if n.startswith("test/")]
        logger.write("test", test_names)
        self.bn_state = ev["bn"]
        return named_global

    def _log_train_round(self, logger: Logger, epoch: int, lr: float, dt: float,
                         phases: Dict[str, float], ms: Dict[str, np.ndarray],
                         probes: Optional[Dict[str, Any]] = None,
                         uids: Optional[np.ndarray] = None):
        """Log one (possibly deferred) round's train metrics + info lines.

        ``probes``: this round's assembled health-probe record (superstep
        fetches carry it pre-split); the K=1 ``train_round`` path still has
        the raw ``obs_*`` leaves riding the metrics dict and splits them
        here, at the fetch boundary.  ``uids``: the K=1 path's drawn cohort
        (rides the tag) -- its ledger fold happens here, at the same fetch
        boundary the superstep path folds at."""
        if probes is None and (self.obs_spec.probes
                               or self.quarantine.enabled):
            # the quarantine counter rides as an obs_ probe even with
            # telemetry off (ISSUE 15) -- split either way
            ms, plist = split_probes(ms, self.mesh.shape["clients"],
                                     counters=self.model.meta.get("counters"))
            if plist:
                probes = plist[0]
        if uids is not None and self.ledger is not None:
            self._fold_ledger(logger, epoch, 1, [ms],
                              uid_rows=np.asarray(uids)[None])
        named = summarize_sums(ms, self.cfg["model_name"])
        logger.append(named, "train", n=float(ms["n"].sum()))
        mean_dt = float(np.mean(self._round_times)) if self._round_times else dt
        remain = self.cfg["num_epochs"]["global"] - epoch
        eta = datetime.timedelta(seconds=round(mean_dt * remain))
        breakdown = " ".join(f"{k} {v:.3f}s" for k, v in sorted(phases.items()))
        info = {"info": [f"Model: {self.tag}",
                         f"Train Epoch: {epoch}",
                         f"Learning rate: {lr:g}",
                         f"Rates: {sorted(set(ms['rate'][ms['n'] > 0].tolist()))}",
                         f"Round time: {dt:.2f}s",
                         f"Round phases: {breakdown}" if breakdown else "Round phases: n/a",
                         f"Experiment Finished Time: {eta}"]}
        logger.append(info, "train", mean=False)
        logger.write("train", list(named))
        if probes is not None:
            self._observe(logger, epoch, probes, ms)

    def _drain_metrics(self, logger: Logger):
        """Flush the async metric pipeline (checkpoint/eval boundaries)."""
        with self.phase_timer.phase("fetch"):
            due = self.metrics_pipe.flush()
        for tag, ms_host in due:
            if tag.get("kind") == "superstep":
                self._log_superstep(logger, tag, ms_host)
            else:
                self._log_train_round(logger, tag["epoch"], tag["lr"], tag["dt"],
                                      tag["phases"], ms_host,
                                      uids=tag.get("uids"))

    def evaluate(self, params, epoch: int, logger: Logger, label_split) -> Dict[str, float]:
        """Host-loop sBN + Local/Global eval -- the ``superstep_rounds=1``
        reference path (supersteps run the same phases in-program via
        :meth:`_fused_eval`; the staticcheck lint keeps host eval dispatch
        out of the steady-state superstep stride)."""
        self._drain_metrics(logger)  # eval boundary: fetch any deferred rounds
        self._ensure_eval_staged()
        cfg = self.cfg
        bn = {}
        if self.kind == "vision":
            # staticcheck: allow(no-host-eval-in-driver): the K=1 host-loop
            # eval path; supersteps fuse these phases in-program
            bn = self.evaluator.sbn_stats(params, *self.sbn_batches)
            xu, yu, mu, lm = self.local_eval
            # staticcheck: allow(no-host-eval-in-driver): K=1 host-loop path
            local = self.evaluator.eval_users(params, bn, xu, yu, mu, lm, epoch=epoch)
            named_local = summarize_sums(local, cfg["model_name"])
            logger.append(named_local, "test", n=float(np.sum(local["n"])))
            # staticcheck: allow(no-host-eval-in-driver): K=1 host-loop path
            g = self.evaluator.eval_global(params, bn, *self.global_eval, epoch=epoch)
        else:
            # staticcheck: allow(no-host-eval-in-driver): K=1 host-loop path
            g = self.evaluator.eval_global(params, {}, *self.global_eval, epoch=epoch)
        named_global = summarize_sums({k: np.asarray(v) for k, v in g.items()},
                                      cfg["model_name"], prefix="Global-")
        logger.append(named_global, "test", n=g["n"])
        info = {"info": [f"Model: {self.tag}", f"Test Epoch: {epoch}"]}
        logger.append(info, "test", mean=False)
        test_names = [n.split("/", 1)[1] for n in logger.mean if n.startswith("test/")]
        logger.write("test", test_names)
        self.bn_state = bn
        return named_global

    # -- full loop -----------------------------------------------------

    def run(self, pivot_metric: str, pivot_mode: str = "max") -> Dict[str, Any]:
        if self.obs_spec.trace_dir and self.tracer is None \
                and jax.process_index() == 0:
            # run tracing (ISSUE 10): one Chrome-trace + events-JSONL
            # recorder per run; PhaseTimer phases and the set-up and
            # compile spans file onto the same timeline, driver events land
            # via _trace_span below.  Attached when the run begins (an
            # experiment that is built and never run writes nothing) and
            # handed construction's spans, so trace.json begins with set-up
            self.tracer = recorder_from(
                os.path.join(self.obs_spec.trace_dir, self.tag),
                min([s.t0 for s in self._built_spans] or [time.perf_counter()]))
            for s in self._built_spans:
                spans.file_to(self.tracer, s)
            self.phase_timer.trace = self.tracer
        try:
            return self._run(pivot_metric, pivot_mode)
        finally:
            if self.tracer is not None:
                # the trace must survive aborts (the watchdog's whole
                # point): close on every exit path, a failed resume, split
                # or staging included
                self.tracer.close()
                self.phase_timer.trace = None

    def _run(self, pivot_metric: str, pivot_mode: str) -> Dict[str, Any]:
        cfg = self.cfg
        blob = resume(cfg["output_dir"], self.tag, cfg["resume_mode"])
        check_multihost_resume(blob)
        if blob and "data_split" in blob and blob["data_split"] is not None:
            data_split, label_split = blob["data_split"], blob["label_split"]
        else:
            data_split, label_split = self.make_splits()
        self.stage(data_split, label_split)
        with spans.span("setup/init", self.phase_timer):
            params = self.model.init(jax.random.fold_in(self.host_key, 0))
        last_epoch = 1
        logger = Logger(os.path.join(cfg["output_dir"], "runs", f"train_{self.tag}"),
                        use_tensorboard=bool(cfg.get("use_tensorboard")))
        pivot = -float("inf") if pivot_mode == "max" else float("inf")
        if blob:
            params = _restore_params(blob["params"])
            if blob.get("wire_resid") is not None:
                # resume the wire codec's error-feedback carry (ISSUE 8):
                # without it the first resumed round re-loses the residual a
                # checkpointed run already accounted for (weights-only
                # resume_mode=2 intentionally resets it to zeros)
                self._codec_engine().set_wire_resid(blob["wire_resid"])
            if blob.get("sched_buf") is not None:
                # resume the buffered-async staleness carry (ISSUE 9):
                # cohort k's in-flight update survives the checkpoint
                # boundary, so a resumed run replays the exact trajectory
                self._codec_engine().set_sched_buf(blob["sched_buf"])
            if blob.get("ledger") is not None and self.ledger is not None:
                # resume the population ledger (ISSUE 12): counts, EMAs
                # and level history CONTINUE instead of resetting --
                # bit-identical to an uninterrupted run (tested)
                self.ledger.load_state_dict(blob["ledger"])
            if "epoch" in blob:
                last_epoch = blob["epoch"]
                pivot = blob.get("pivot", pivot)
                if blob.get("logger_state"):
                    # full fidelity: running means/counters + TB step counters
                    logger.load_state_dict(blob["logger_state"])
                else:  # older blobs carried history only
                    logger.history = blob.get("logger_history", logger.history)
                if blob.get("scheduler_state") and hasattr(self.scheduler, "load_state_dict"):
                    self.scheduler.load_state_dict(blob["scheduler_state"])
        n_rounds = cfg["num_epochs"]["global"]
        eval_interval = self.eval_interval
        epoch = last_epoch
        if self.tracer is not None:
            # with the folds of the model's own counters (name -> fold):
            # obs.report sums or averages them over the rounds by it
            self.tracer.instant("run-start",
                                args={"tag": self.tag, "epoch0": int(epoch),
                                      "rounds": int(n_rounds),
                                      "counters": {k: fold for k, (_, fold) in
                                                   self.model.meta.get("counters", {}).items()}})
        try:
            return self._run_loop(logger, pivot_metric, pivot_mode, pivot,
                                  epoch, n_rounds, eval_interval, data_split,
                                  label_split, params)
        finally:
            if self.ledger is not None and jax.process_index() == 0:
                # the ledger.npz snapshot the report surface reads (ISSUE
                # 12): written on every exit path, aborts included
                self.ledger.save(self._ledger_path())

    @staticmethod
    def _tree_finite(tree) -> bool:
        """True iff every float array leaf of a nested dict/list tree is
        all-finite (non-array / non-float leaves pass)."""
        if isinstance(tree, dict):
            return all(FedExperiment._tree_finite(v) for v in tree.values())
        if isinstance(tree, (list, tuple)):
            return all(FedExperiment._tree_finite(v) for v in tree)
        try:
            arr = np.asarray(tree)
        except Exception:
            return True
        if not np.issubdtype(arr.dtype, np.floating):
            return True
        return bool(np.all(np.isfinite(arr)))

    def _load_rollback_blob(self) -> Optional[Dict[str, Any]]:
        """The newest checkpoint generation that BOTH verifies (checksum)
        and holds all-finite restorable state (ISSUE 15): under a deferred
        metrics fetch the newest generation can checksum clean yet carry
        the very NaN the watchdog tripped on -- in the params, OR in a
        restored carry (the EF residual, the buffered staleness buffer,
        the sBN state).  Restoring such a blob would trip again
        immediately and burn the whole retry budget on one poisoned blob.
        Returns None when no usable generation exists (fresh restart)."""
        from ..utils.checkpoint import iter_verified_generations

        path = checkpoint_path(self.cfg["output_dir"], self.tag)
        for p, blob in iter_verified_generations(path):
            finite = all(
                self._tree_finite(blob.get(k))
                for k in ("params", "bn_state", "wire_resid", "sched_buf"))
            if finite:
                return blob
            warnings.warn(f"rollback: checkpoint generation {p} verifies "
                          f"but holds non-finite params or carries; "
                          f"falling back a generation")
        return None

    def _recover_rollback(self, logger: Logger, trip: WatchdogRollback,
                          pivot_mode: str):
        """One watchdog-rollback recovery attempt (ISSUE 15): emit the
        recovery evidence, drop every piece of in-flight state, salt the
        round key stream (the replayed superstep draws a FRESH cohort),
        restore the newest usable checkpoint generation (or restart fresh
        when none exists), back off, and hand (params, epoch, pivot) back
        to the run loop.  Escalates to :class:`WatchdogError` -- with the
        abort path's durability -- once ``max_retries`` is spent."""
        spec = self.obs_spec.watchdog
        self._rollback_attempts += 1
        attempt = self._rollback_attempts
        if attempt > spec.max_retries:
            if self.tracer is not None:
                self.tracer.close()
            logger.flush()
            if self.ledger is not None and jax.process_index() == 0:
                self.ledger.save(self._ledger_path())
            raise WatchdogError(
                f"watchdog rollback budget spent ({spec.max_retries} "
                f"attempt(s)): escalating to abort; last trip "
                f"{trip.events[0] if trip.events else trip!r}") from trip
        # the retry salt: every replayed round re-derives its keys from the
        # salted stream, so the re-drawn cohort excludes the poisoned draw
        # deterministically (chaos.drill predicts these draws)
        self.host_key = jax.random.fold_in(self.host_key,
                                           RETRY_SALT + attempt)
        blob = self._load_rollback_blob()
        rec = {"event": "rollback", "attempt": attempt,
               "max_retries": spec.max_retries,
               "kind": trip.events[0].get("kind") if trip.events else None,
               "trip_epoch": trip.events[0].get("epoch")
               if trip.events else None,
               "restored_epoch": (blob or {}).get("epoch"),
               "fresh_restart": blob is None}
        logger.emit(rec, tag="recovery")
        if self.tracer is not None:
            self.tracer.instant("recovery", cat="obs", args=rec)
        warnings.warn(f"watchdog rollback attempt {attempt}/"
                      f"{spec.max_retries}: restoring "
                      f"{'a fresh init' if blob is None else 'epoch %s' % rec['restored_epoch']} "
                      f"with a salted cohort stream")
        logger.safe(False)  # close the aborted iteration's writer
        # drop EVERY piece of in-flight state the unwound iteration left:
        # pending metric fetches (discarded -- their rounds replay),
        # prefetched cohorts (drawn pre-salt), commitment counters, the
        # spike window, and the engines' device scan carries
        try:
            self.metrics_pipe.flush()
        except Exception:
            pass  # a poisoned pending fetch must not block recovery
        self._next_cohorts = []
        self._ss_dispatched = self._ss_fetched = 0
        if self._commitment is not None:
            self._commitment = ScheduleCommitment(self.sampler_spec.horizon)
        if self.watchdog is not None:
            self.watchdog.reset_window()
        self._codec_engine().reset_carries()
        pivot0 = -float("inf") if pivot_mode == "max" else float("inf")
        if blob is None:
            params = self.model.init(jax.random.fold_in(self.host_key, 0))
            logger.load_state_dict({})
            logger.reset()
            self.scheduler = make_scheduler(self.cfg)
            if self.ledger is not None:
                self.ledger = ClientLedger(
                    self.cfg["num_users"],
                    sorted({float(r) for r in self.cfg["model_rate"]},
                           reverse=True))
            self.bn_state = {}
            epoch, pivot = 1, pivot0
        else:
            params = {k: jnp.asarray(v) for k, v in blob["params"].items()}
            if blob.get("wire_resid") is not None:
                self._codec_engine().set_wire_resid(blob["wire_resid"])
            if blob.get("sched_buf") is not None:
                self._codec_engine().set_sched_buf(blob["sched_buf"])
            if blob.get("ledger") is not None and self.ledger is not None:
                self.ledger.load_state_dict(blob["ledger"])
            logger.load_state_dict(blob.get("logger_state") or {})
            if blob.get("scheduler_state") \
                    and hasattr(self.scheduler, "load_state_dict"):
                self.scheduler.load_state_dict(blob["scheduler_state"])
            self.bn_state = blob.get("bn_state", {})
            epoch = blob.get("epoch", 1)
            pivot = blob.get("pivot", pivot0)
        if spec.backoff > 0:
            time.sleep(min(spec.backoff * (2 ** (attempt - 1)), 30.0))
        return params, epoch, pivot

    def _run_loop(self, logger, pivot_metric, pivot_mode, pivot, epoch,
                  n_rounds, eval_interval, data_split, label_split, params):
        cfg = self.cfg
        while True:
            try:
                if epoch > n_rounds:
                    # the final drain sits INSIDE the recovery loop: under
                    # a deferred fetch the last superstep's trip surfaces
                    # here, and a rollback must restore + re-enter the
                    # round loop instead of degrading to an abort
                    self._drain_metrics(logger)  # nothing stays on device
                    break
                params, epoch, pivot = self._run_iteration(
                    logger, pivot_metric, pivot_mode, pivot, epoch, n_rounds,
                    eval_interval, data_split, label_split, params)
            except WatchdogRollback as trip:
                # watchdog auto-rollback (ISSUE 15): restore, salt, retry
                params, epoch, pivot = self._recover_rollback(
                    logger, trip, pivot_mode)
        return {"params": params, "bn_state": getattr(self, "bn_state", {}),
                "logger": logger, "data_split": data_split, "label_split": label_split,
                "first_round_time": self._first_round_time,
                "round_times": list(self._round_times)}

    def _run_iteration(self, logger, pivot_metric, pivot_mode, pivot, epoch,
                       n_rounds, eval_interval, data_split, label_split,
                       params):
        """One run-loop iteration: a dispatch window (superstep or K=1
        round + eval), the best-pivot decision, and the durable checkpoint
        write.  Returns ``(params, next_epoch, pivot)``; raises
        :class:`WatchdogRollback` through to :meth:`_run_loop` when the
        watchdog trips under ``action='rollback'``."""
        cfg = self.cfg
        logger.safe(True)
        # superstep length: the end of the run is the ONLY clamp left --
        # eval windows run inside the scan (ISSUE 4), so K no longer
        # shortens to the next eval boundary.  Checkpoints land on
        # superstep boundaries; evals inside a superstep are logged (and
        # feed Plateau) when its metrics are fetched.
        k_eff = 1
        if self.superstep_rounds > 1 or self.streaming:
            # streaming always takes the superstep path (k_eff=1 at
            # superstep_rounds=1): cohorts ride the scanned program's
            # xs, so there is exactly one store-backed dispatch shape
            k_eff = min(self.superstep_rounds, n_rounds - epoch + 1)
            # a clamped end-of-run tail still goes through the superstep
            # path (smaller k) so ONE sampling stream covers the run
            with self._trace_span("superstep",
                                  {"epoch0": int(epoch), "k": int(k_eff)}):
                params = self.train_superstep(params, epoch, k_eff, logger)
            epoch = epoch + k_eff - 1  # last round this iteration covered
            # pivot integrity: the checkpoint below holds END-OF-SUPERSTEP
            # params, so only an eval on the boundary round -- fetched
            # synchronously, i.e. logged THIS iteration -- may update the
            # best-copy pivot; mid-superstep evals log and feed Plateau
            # but their params were consumed inside the scan
            pivot_fresh = (self.metrics_pipe.fetch_every == 1
                           and (epoch % eval_interval == 0
                                or epoch == n_rounds))
        else:
            pivot_fresh = True
            lr = self.scheduler(epoch)
            with self._trace_span("round", {"epoch": int(epoch)}):
                params = self.train_round(params, epoch, lr, logger)
            evaluated = epoch % eval_interval == 0 or epoch == n_rounds
            if evaluated:
                with self._trace_span("eval", {"epoch": int(epoch)}):
                    self.evaluate(params, epoch, logger, label_split)
                if isinstance(self.scheduler, PlateauScheduler):
                    # min-mode plateau fed the test Global loss, only on
                    # rounds that actually evaluated.  (The reference
                    # feeds logger.mean['train/Global-Accuracy'], a key
                    # its train loop never writes, i.e. a constant 0 --
                    # an upstream bug we do not reproduce.)
                    self.scheduler.step_metric(
                        logger.mean.get("test/Global-Loss", 0.0))
        logger.safe(False)
        cur = logger.history.get(f"test/{pivot_metric}", [None])[-1]
        is_best = pivot_fresh and cur is not None \
            and (cur > pivot if pivot_mode == "max" else cur < pivot)
        if is_best:
            pivot = cur  # update BEFORE saving so a resumed run keeps it
        blob_out = {
            "cfg": {k: v for k, v in cfg.items() if k != "vocab"},
            "epoch": epoch + 1,
            "data_split": data_split,
            "label_split": label_split,
            "params": params,
            "bn_state": getattr(self, "bn_state", {}),
            # the error-feedback residual carry at this superstep
            # boundary (ISSUE 8; None under the dense codec)
            "wire_resid": (self._codec_engine().wire_resid_host()
                           if self.wire_codec != "dense" else None),
            # the buffered-async staleness carry at this superstep
            # boundary (ISSUE 9; None under sync aggregation)
            "sched_buf": (self._codec_engine().sched_buf_host()
                          if self.sched_spec.buffered else None),
            # the population ledger at this superstep boundary (ISSUE
            # 12; None when ledger='off')
            "ledger": (self.ledger.state_dict()
                       if self.ledger is not None else None),
            "pivot": pivot,
            "logger_history": dict(logger.history),
            "logger_state": logger.state_dict(),
            "scheduler_state": self.scheduler.state_dict()
            if hasattr(self.scheduler, "state_dict") else None,
        }
        # multi-host: the sharded writer is COLLECTIVE -- every process
        # calls it; replicated-only blobs degenerate to the process-0
        # plain write, process-local leaves (the slices EF carry) land in
        # per-process shard files named by the header (ISSUE 17)
        if jax.process_index() == 0:
            self._chaos("checkpoint")
        with self._trace_span("checkpoint", {"epoch": int(epoch)}):
            save_checkpoint_sharded(
                checkpoint_path(cfg["output_dir"], self.tag),
                blob_out, keep=self.checkpoint_keep)
            if is_best and jax.process_index() == 0:
                copy_best(cfg["output_dir"], self.tag)
        logger.reset()
        # a clean iteration ending in a durable checkpoint proves recovery:
        # the rollback budget re-arms for the next (independent) incident
        self._rollback_attempts = 0
        return params, epoch + 1, pivot


class ArmsExperiment(FedExperiment):
    """The multiplexed driver loop (ISSUE 14, heterofl_tpu/multi/): E
    trace-compatible experiment arms in ONE fused superstep program per
    dispatch.

    Reuses the base experiment's staging, engines, evaluator and schedule
    helpers; the loop differs where the arms axis surfaces on the host --
    per-arm init trees (each arm's stream root seeds its own
    ``model.init``), per-arm ``{"tag": "arms"}`` JSONL log lines carrying
    an ``arm`` field, per-arm ReduceLROnPlateau state (one scheduler per
    arm, stepped on that arm's own fused-eval Global loss, staged into the
    program as the ``[E]`` LR vector), per-arm best-pivot tracking, and
    per-arm checkpoints (one exportable blob per arm next to the
    multiplexed resume blob).  Fetches are synchronous (one fetch per
    superstep serves all E arms -- the arms win is batching compute, not
    deferring metrics)."""

    _arms_capable = True

    def __init__(self, cfg: Dict[str, Any], seed: int):
        super().__init__(cfg, seed)
        if self.arms_spec is None:
            raise ValueError("ArmsExperiment needs cfg['arms'] (an int "
                             "count or a {count, seeds, lr_scales} dict)")
        self._plateau = isinstance(self.scheduler, PlateauScheduler)
        # per-arm Plateau state: each arm owns a scheduler instance stepped
        # on its OWN eval metrics (the solo loop's semantics, per arm); the
        # arm's lr_scale multiplies the scheduler's output either way, so a
        # Plateau LR sweep still trains each arm at ITS grid value
        self._arm_scheds = [make_scheduler(self.cfg)
                            for _ in range(self.arms_spec.count)] \
            if self._plateau else None
        # per-arm watchdogs: the spike detector's rolling loss window is
        # per trajectory -- one shared Watchdog would mix E loss streams
        self._arm_watchdogs = ([Watchdog(self.obs_spec.watchdog)
                                for _ in range(self.arms_spec.count)]
                               if self.watchdog is not None else None)
        if self.obs_spec.watchdog is not None \
                and self.obs_spec.watchdog.action == "rollback":
            raise ValueError(
                "watchdog action='rollback' cannot combine with arms yet: "
                "one arm's trip would roll every arm back, and the "
                "multiplexed loop has no per-arm recovery (a ROADMAP "
                "follow-on); use 'warn'/'abort' for arms runs")
        self._staged_lr_vec = None  # the [E] LR vector of the live dispatch

    def _arms_tag(self) -> str:
        return f"{self.tag}_arms{self.arms_spec.count}"

    def _arm_tag(self, e: int) -> str:
        return f"{self._arms_tag()}_a{e}"

    def _arm_lr(self, e: int, epoch: int) -> float:
        sched = self._arm_scheds[e] if self._plateau else self.scheduler
        return float(sched(epoch)) * self.arms_spec.lr_scales[e]

    def _observe_arm(self, logger: Logger, e: int, epoch: int,
                     probes: Dict[str, Any], ms) -> None:
        """The solo loop's :meth:`_observe` with the arms axis: the probes
        event carries the ``arm`` field and each arm feeds ITS OWN
        watchdog (the spike window is per trajectory)."""
        loss = None
        n = float(np.sum(ms["n"]))
        if n > 0:
            loss = float(np.sum(ms["loss_sum"])) / n
        logger.emit({"event": "probes", "arm": e, "epoch": int(epoch),
                     "loss": loss, **probes})
        if self._arm_watchdogs is not None:
            try:
                self._arm_watchdogs[e].check(
                    epoch, probes=probes, loss=loss,
                    emit=lambda ev: logger.emit({**ev, "arm": e}))
            except WatchdogError:
                # abort evidence must be ON DISK before the unwind (the
                # solo loop's durability contract; arms runs have no
                # tracer/ledger -- both are refused at construction)
                logger.flush()
                raise

    def _init_params(self):
        """Stacked per-arm init trees: arm e's params come from ITS stream
        root (``fold_in(arm_root, 0)``, the solo loop's derivation), so the
        identity arm inits exactly like a solo run."""
        roots = arm_stream_keys(self.host_key, self.arms_spec.seeds)
        with spans.span("setup/init", self.phase_timer):
            trees = [self.model.init(jax.random.fold_in(roots[e], 0))
                     for e in range(self.arms_spec.count)]
            return jax.tree_util.tree_map(lambda *xs: jnp.stack(xs), *trees)

    def _dispatch(self, params, epoch0: int, k: int, mask):
        """One multiplexed superstep: the engines batch the arms axis; the
        driver supplies shared schedules (grouped/sharded) and the per-arm
        LR vector under Plateau."""
        fused = self._fused_eval(None) if any(mask) else None
        lr_vec = np.asarray(
            [s(epoch0) * sc for s, sc in zip(self._arm_scheds,
                                             self.arms_spec.lr_scales)],
            np.float32) if self._plateau else None
        # the fetch loop steps the Plateau schedulers mid-superstep; the
        # logged LR must be what THIS dispatch actually staged, not the
        # scheduler's post-step value (the solo loop pins lrs pre-fetch)
        self._staged_lr_vec = lr_vec
        if self.cfg.get("strategy") == "grouped":
            users = self._superstep_schedule(epoch0, k)
            rates = superstep_rate_schedule(self.host_key, epoch0, k,
                                            self.cfg, users)
            return self.alt_engine.train_superstep(
                params, self.host_key, epoch0, k, users, rates,
                self.train_data, timer=self.phase_timer,
                eval_mask=mask if fused else None, fused_eval=fused,
                lr=lr_vec)
        sched = None
        if self.cfg.get("data_placement") == "sharded":
            sched = self._superstep_schedule(epoch0, k)
        return self.engine.train_superstep(
            params, self.host_key, epoch0, k, self.train_data,
            user_schedule=sched, num_active=self.num_active,
            timer=self.phase_timer, eval_mask=mask if fused else None,
            fused_eval=fused, lr=lr_vec)

    def run(self, pivot_metric: str, pivot_mode: str = "max") -> Dict[str, Any]:
        cfg = self.cfg
        E = self.arms_spec.count
        tag = self._arms_tag()
        blob = resume(cfg["output_dir"], tag, cfg["resume_mode"])
        check_multihost_resume(blob)
        if blob and blob.get("data_split") is not None:
            data_split, label_split = blob["data_split"], blob["label_split"]
        else:
            data_split, label_split = self.make_splits()
        self.stage(data_split, label_split)
        logger = Logger(os.path.join(cfg["output_dir"], "runs",
                                     f"train_{tag}"),
                        use_tensorboard=bool(cfg.get("use_tensorboard")))
        params = self._init_params()
        epoch = 1
        pivots = [(-float("inf") if pivot_mode == "max" else float("inf"))
                  for _ in range(E)]
        if blob:
            params = _restore_params(blob["params"])
            epoch = blob.get("epoch", 1)
            pivots = blob.get("arm_pivots", pivots)
            if blob.get("wire_resid") is not None:
                # the stacked [E, ...] EF carry resumes like a solo run's
                self._codec_engine().set_wire_resid(blob["wire_resid"])
            if blob.get("arm_scheds") and self._arm_scheds:
                for s, st in zip(self._arm_scheds, blob["arm_scheds"]):
                    s.load_state_dict(st)
        n_rounds = cfg["num_epochs"]["global"]
        K = self.superstep_rounds
        while epoch <= n_rounds:
            k = min(K, n_rounds - epoch + 1)
            mask = tuple((epoch + r) % self.eval_interval == 0
                         or (epoch + r) == n_rounds for r in range(k))
            t0 = time.time()
            params, pending = self._dispatch(params, epoch, k, mask)
            with self.phase_timer.phase("fetch"):
                out = pending.fetch()
            dt = time.time() - t0
            logger.safe(True)
            evaluated: List[Optional[Dict[str, float]]] = [None] * E
            for e, arm_out in enumerate(out["arms"]):
                rounds = arm_out["train"] if isinstance(arm_out, dict) \
                    else arm_out
                evals = {ev["epoch"]: ev
                         for ev in (arm_out.get("eval") or [])} \
                    if isinstance(arm_out, dict) else {}
                probes = arm_out.get("obs") \
                    if isinstance(arm_out, dict) else None
                for r in range(k):
                    ms = rounds[r]
                    if probes:
                        self._observe_arm(logger, e, epoch + r,
                                          probes[r], ms)
                    n = float(np.sum(ms["n"]))
                    logger.emit(
                        {"event": "train", "arm": e, "epoch": epoch + r,
                         "lr": (float(self._staged_lr_vec[e])
                                if self._plateau
                                else self._arm_lr(e, epoch + r)),
                         "loss": (float(np.sum(ms["loss_sum"])) / n
                                  if n > 0 else None),
                         "n": n, "dt": dt / (k * E)}, tag="arms")
                    ev = evals.get(epoch + r)
                    if ev is not None:
                        g = summarize_sums(
                            {kk: np.asarray(v)
                             for kk, v in ev["global"].items()},
                            cfg["model_name"], prefix="Global-")
                        logger.emit({"event": "eval", "arm": e,
                                     "epoch": epoch + r,
                                     **{kk: float(vv)
                                        for kk, vv in g.items()}},
                                    tag="arms")
                        evaluated[e] = g
                        if self._plateau:
                            # per-arm Plateau: min-mode on this ARM's own
                            # test Global loss (the solo loop's feed)
                            self._arm_scheds[e].step_metric(
                                g.get("Global-Loss", 0.0))
            epoch_end = epoch + k - 1
            # per-arm exportable blobs need HOST arm slices; on an arms-
            # sharded multi-process mesh that is a collective gather (every
            # process executes it in lockstep -- checkpoint boundary only,
            # never round-path wire), on a single process a plain D2H
            from ..parallel.staging import host_fetch
            host_params = {kk: host_fetch(v) for kk, v in params.items()}
            for e in range(E):
                g = evaluated[e]
                cur = g.get(pivot_metric) if g else None
                is_best = cur is not None and \
                    (cur > pivots[e] if pivot_mode == "max"
                     else cur < pivots[e])
                if is_best:
                    pivots[e] = cur
                # per-arm exportable checkpoint: arm e's params slice +
                # stream identity, loadable by any solo consumer
                arm_blob = {
                    "cfg": {kk: v for kk, v in cfg.items() if kk != "vocab"},
                    "arm": e, "arm_seed": self.arms_spec.seeds[e],
                    "lr_scale": self.arms_spec.lr_scales[e],
                    "epoch": epoch_end + 1,
                    "params": {kk: np.asarray(v[e])
                               for kk, v in host_params.items()},
                    "pivot": pivots[e],
                }
                if jax.process_index() == 0:
                    save_checkpoint(
                        checkpoint_path(cfg["output_dir"], self._arm_tag(e)),
                        arm_blob, keep=self.checkpoint_keep)
                    if is_best:
                        copy_best(cfg["output_dir"], self._arm_tag(e))
            # the multiplexed resume blob: stacked params + per-arm state
            blob_out = {
                "cfg": {kk: v for kk, v in cfg.items() if kk != "vocab"},
                "epoch": epoch_end + 1,
                "data_split": data_split, "label_split": label_split,
                "params": params, "arm_pivots": pivots,
                "wire_resid": (self._codec_engine().wire_resid_host()
                               if self.wire_codec != "dense" else None),
                "arm_scheds": ([s.state_dict() for s in self._arm_scheds]
                               if self._arm_scheds else None),
            }
            # collective: arms-sharded params land in per-process shard
            # files; replicated blobs degenerate to the process-0 write
            save_checkpoint_sharded(checkpoint_path(cfg["output_dir"], tag),
                                    blob_out, keep=self.checkpoint_keep)
            logger.safe(False)
            epoch = epoch_end + 1
        return {"params": params, "arms": self.arms_spec, "pivots": pivots,
                "data_split": data_split, "label_split": label_split}


def run_main(description: str, model_default: str, data_default: str,
             pivot_metric: str, pivot_mode: str, argv: Optional[List[str]] = None):
    """Shared ``main()``: parse flags, loop seeds (ref
    train_classifier_fed.py:37-45), run experiments."""
    from ..parallel.mesh import initialize_distributed

    initialize_distributed()  # no-op single-host; joins the pod otherwise
    # persistent XLA compilation cache: repeated experiments skip the ~40s
    # flagship-round compile; operator env wins
    enable_persistent_cache()
    parser = build_cli(description)
    args = parser.parse_args(argv)
    cfg = cfg_from_args(args)
    if args.model_name is None:
        cfg["model_name"] = model_default
    if args.data_name is None:
        cfg["data_name"] = data_default
    cfg = C.process_control(cfg)
    results = []
    for i in range(cfg["num_experiments"]):
        seed = cfg["init_seed"] + i
        exp = FedExperiment(cfg, seed)
        print(f"Experiment: {exp.tag}")
        results.append(exp.run(pivot_metric, pivot_mode))
    return results
