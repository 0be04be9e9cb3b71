"""Rate-grouped sliced execution ON the mesh: dense per-level programs,
device-resident aggregation, no host round-trips.

The masked engine (round_engine.py) runs every client at full width with
channel masks -- uniform shapes, but a ~3.9x FLOP overhead at the canonical
a1-e1 mix (MEASUREMENTS.md roofline): a rate-1/16 client's conv FLOPs are
(1/16)^2 of full width, yet the masked program spends full-width FLOPs on it.
This engine realises the roofline's "group clients by rate level" design:

  * active clients are grouped by rate level on the host (level membership is
    data, not shape -- grouping is O(A) bookkeeping);
  * each level runs ONE jitted ``shard_map`` program: extract the level's
    dense sub-model from the global params (static prefix slices,
    ``fed.core.extract_sliced_jnp``), vmap the level's clients through dense
    local SGD at the level's own small shapes -- client slots sharded over
    the ``clients`` mesh axis -- then ``psum`` the level's counted sums and
    zero-pad them back to global shape (``embed_sliced_jnp``);
  * a final jitted combine merges the level partials into the new globals
    (counted average + stale rule, semantics = ref fed.py:180-298).

All intermediates are device arrays: the host only *dispatches* the L+1
programs per round; no parameter or data bytes move through it.  The
staging layer (staging.py) makes that literal in steady state -- data
stacks are committed to each level's (sub-)mesh once, slot packing reuses
cached host buffers, and metric sums can stay on device until the caller
fetches them (``async_metrics``).  Programs
are cached per (rate, slot-count) with slot counts bucketed to powers of
two, so the compile space is O(levels x log A) -- NOT the cross-product of
per-level counts (a per-round-pattern mega-program would recompile
combinatorially as the sampled mix varies round to round).

Two level placements (``cfg['level_placement']``): ``span`` (default) runs
every level across the whole clients axis back-to-back; ``slices``
partitions the clients-axis device rows among the levels in proportion to
their EXPECTED FLOP share (static per experiment: fix-mode per-level user
counts, dynamic-mode proportions) and dispatches each level's program to
its own disjoint sub-mesh -- the programs then overlap in time (async
dispatch), which is the pod-regime layout the MEASUREMENTS.md roofline
prescribes (params are ICI-broadcast to each slice and the level partials
brought back to the full mesh for the combine).  Static allocation keeps
the compile space at O(levels x log A) and the cache keys bound to fixed
device ranges; per-round count fluctuation is absorbed by slot bucketing
inside each slice.  Multi-process meshes fall back to ``span`` (slice
boundaries are not yet host-aligned).

Client PRNG keys are ``fold_in(fold_in(key, CLIENT_STREAM_SALT),
global_uid)`` (:func:`~..fed.core.client_stream_keys`, the masked
engine's convention) -- so with the same inputs both engines produce the same
new global parameters (tests/test_grouped.py) up to float association.

Trade-off vs masked: dense per-level compute wins when active-clients /
devices >> number of levels (the pod regime); at tiny occupancy the
per-level padding to the axis size erodes the win.  Both engines share the
aggregation algebra, so the choice is per-experiment (``cfg['strategy']``).
"""

from __future__ import annotations

from typing import Any, Dict, List, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import PartitionSpec as P

from ..compress import make_codec, resid_slots, resolve_codec_cfg
from ..config import resolve_prefetch_depth
from ..fed.core import (arm_stream_keys, client_stream_keys, combine_counted,
                        embed_sliced_jnp, extract_sliced_jnp,
                        failure_stream_key, level_flop_table, snap_to_levels)
from ..fed.sampling import resolve_sampler_cfg
from ..models import make_model
from ..multi import resolve_arms_cfg
from ..models.spec import count_masks as make_count_masks
from ..chaos import resolve_poison_cfg
from ..obs import resolve_quarantine_cfg, resolve_telemetry_cfg, split_probes
from ..obs.hist import round_hists
from ..obs.probes import round_probes
from ..obs.trace import scope
from ..ops.flatspec import FlatSpec
from ..sched import resolve_schedule_cfg
from ..sched.buffer import _SchedBufCarry, buffered_combine
from ..sched.deadline import deadline_steps
from ..utils.optim import make_traced_lr_fn
from .round_engine import (RoundEngine, _bucket_pow2, _ceil_div,
                           _shard_map, _WireCodecCarry)
from .staging import (ClientStore, CohortStager, PendingMetrics, PhaseTimer,
                      PlacementCache, SlotPacker, StagedCohort)


class GroupedRoundEngine(_WireCodecCarry, _SchedBufCarry):
    """Mesh-native sliced strategy: same public round signature as
    ``fed.sliced.SlicedFederation`` (host-side rates in, per-slot metrics
    out), but every program runs on the mesh and aggregation state never
    leaves the devices."""

    def __init__(self, cfg: Dict[str, Any], mesh):
        if cfg.get("data_placement", "replicated") == "sharded":
            raise ValueError("grouped strategy needs replicated data placement "
                             "(a level's clients span the whole clients axis); "
                             "use the masked engine for sharded placement")
        self.cfg = cfg
        self.mesh = mesh
        # 'span' (default): every level's slots spread over the whole
        # clients axis, levels run back-to-back.  'slices': the clients-axis
        # device rows are partitioned among the levels in proportion to
        # FLOP share, each level's dense program runs on its own sub-mesh
        # and the programs execute CONCURRENTLY (async dispatch to disjoint
        # devices) -- the pod-regime layout of the MEASUREMENTS.md roofline.
        # Falls back to 'span' when there are fewer device rows than levels.
        self.level_placement = cfg.get("level_placement", "span")
        if self.level_placement not in ("span", "slices"):
            raise ValueError(f"Not valid level_placement: {self.level_placement!r}")
        self.global_rate = cfg["global_model_rate"]
        self.global_model = make_model(cfg)
        self.is_lm = self.global_model.is_lm
        self.failure_rate = float(cfg.get("client_failure_rate", 0.0) or 0.0)  # staticcheck: allow(no-float-coercion): constructor-time config scalar
        self.levels: Dict[float, Tuple[Any, RoundEngine]] = {}
        for rate in sorted({float(r) for r in cfg["model_rate"]}, reverse=True):  # staticcheck: allow(no-float-coercion): constructor-time config parse
            model = make_model(cfg, model_rate=rate)
            self.levels[rate] = (model, RoundEngine(model, cfg, mesh=None))
        self._level_progs: Dict[Tuple, Any] = {}
        self._combine_progs: Dict[int, Any] = {}
        self._superstep_progs: Dict[Tuple, Any] = {}
        self._lr_fn = None  # built on first superstep (plateau raises there)
        self._slices: Dict[float, Tuple[int, int]] = {}
        # staged placement (ISSUE 1 tentpole): data stacks (and in slices
        # mode the per-level operands) are committed to their sub-meshes
        # ONCE, keyed by the static (lo, hi) ranges -- steady-state rounds
        # dispatch device-resident buffers with zero implicit resharding
        self._staging = PlacementCache(mesh)
        self._packer = SlotPacker()
        # streaming cohort pipeline (ISSUE 6): built on first stage_cohort;
        # ring depth = cfg['stream_prefetch_depth'] (ISSUE 8 satellite)
        self._cohort_stager = None
        self._prefetch_depth = resolve_prefetch_depth(cfg)
        # wire codec (ISSUE 8): compression lives in the fused superstep
        # (where the ONE global psum is); the K=1 host-orchestrated
        # per-level path stays dense and train_round refuses lossy codecs
        self._codec_name, self._error_feedback = resolve_codec_cfg(
            cfg, engine_strategy="grouped")
        self._codec_obj = None
        self._resid = None
        # per-level codec selection (ISSUE 9 satellite): a {rate: codec}
        # map compresses each level's SLICED partial under its own codec in
        # the one fused-superstep psum bind -- level-a int8 / level-e dense
        # and friends.  Works on BOTH level placements (ISSUE 14 satellite
        # retired the PR 9 slices refusal): under 'slices' every switch
        # branch emits every level's payload structure -- its own encoded
        # partial plus the other levels' identity payloads
        # (codec.zero_payload), with each level's codec counting its own
        # slice rows as participants.
        self._codec_map = None
        if isinstance(self._codec_name, dict):
            level_set = {float(r) for r in self.levels}  # staticcheck: allow(no-float-coercion): constructor-time config parse
            map_set = set(self._codec_name)
            if map_set != level_set:
                raise ValueError(
                    f"per-level wire_codec map keys {sorted(map_set)} do "
                    f"not match the engine's level table "
                    f"{sorted(level_set)}: every level needs exactly one "
                    f"codec")
            self._codec_map = self._codec_name
            self._codec_name = "per-level"  # truthy sentinel; never a codec
        self._map_lay = None  # cached per-level FlatSpec layout
        self._map_codec_objs: Dict[Tuple, Any] = {}
        # scheduler (ISSUE 9): deadline + buffered-async ride the fused
        # superstep; availability schedules reach this engine through the
        # host-packed user/rate schedules (superstep_user_schedule)
        self._sched_spec = resolve_schedule_cfg(cfg)
        # population sampler (ISSUE 11): this engine never draws in-jit
        # (level grouping needs the ids host-side, so cohorts arrive as
        # host-packed schedules drawn from THE one stream), but the kind is
        # resolved here so a typo'd sampler fails at construction and the
        # engine's stream identity is inspectable like the masked one's
        self._sampler = resolve_sampler_cfg(cfg).kind
        self._sched_buf = None
        if self._sched_spec.buffered and self._codec_name != "dense":
            raise ValueError(
                "schedule aggregation='buffered' cannot combine with a "
                "lossy wire_codec yet: both add a scan carry with its own "
                "donation/checkpoint contract -- pick one per experiment")
        # runtime telemetry (ISSUE 10): probes live in the fused superstep
        # (where the round's single psum and the combined globals are);
        # the K=1 host-orchestrated path refuses loudly in train_round
        self._obs_spec = resolve_telemetry_cfg(cfg)
        self._obs_on = self._obs_spec.probes
        # cohort histograms (ISSUE 12): telemetry='hist' folds the fixed-
        # bucket hist rows (obs/hist.py) in next to the scalar probes
        self._obs_hist = self._obs_spec.hist
        # staticcheck: allow(no-float-coercion): constructor-time config
        # parse (the probe level table, a trace-time constant)
        self._obs_levels = sorted({float(r) for r in cfg["model_rate"]},
                                  reverse=True)
        # client-update quarantine (ISSUE 15): the gate folds into each
        # level core's counted sums BEFORE the level embed and the single
        # global psum -- same zero-count-participant semantics as the
        # masked engine, identical programs when 'off'
        self._quarantine = resolve_quarantine_cfg(cfg)
        # chaos NaN poison (ISSUE 15): trace-time (round, uid) table; the
        # fused superstep threads the scan epoch into every level core
        self._poison = resolve_poison_cfg(cfg)
        # experiment arms multiplexer (ISSUE 14, heterofl_tpu/multi/): the
        # grouped engine batches arms over its SPAN fused superstep --
        # shared host user/rate schedules (level membership is slot
        # bookkeeping, one layout for all arms), per-arm streams for the
        # client/slot keys, deadline budgets and failure draws.  Carries
        # and layouts that do not batch yet refuse loudly here.
        self._arms_spec = resolve_arms_cfg(cfg)
        if "arms" in getattr(mesh, "axis_names", ()):
            raise ValueError(
                "the grouped engine does not take an 'arms' mesh axis "
                "yet: its level slot layouts assume the whole clients "
                "axis (a ROADMAP follow-on) -- use the masked engine for "
                "mesh-placed arms, or grouped arms under the vmap "
                "placement")
        if self._arms_spec is not None:
            if self._codec_name != "dense":
                raise ValueError(
                    "arms with the grouped strategy need the dense wire "
                    "codec: the grouped EF-residual carry (single-codec "
                    "and per-level maps alike) does not batch over the "
                    "arms axis yet (a ROADMAP follow-on); batch dense "
                    "grouped arms or use the masked engine for codec arms")
            if self._sched_spec.buffered:
                raise ValueError(
                    "arms cannot combine with schedule aggregation="
                    "'buffered' yet: the staleness buffer is a replicated "
                    "carry with its own donation/checkpoint contract -- "
                    "batch dense-sync arms or run buffered solo")
            if self._obs_on:
                raise ValueError(
                    "arms with the grouped strategy need telemetry='off': "
                    "the span probe rows do not carry the arms axis yet "
                    "(a ROADMAP follow-on); the masked engine supports "
                    "telemetry x arms")
            if self._quarantine.enabled:
                raise ValueError(
                    "arms with the grouped strategy need quarantine='off': "
                    "the quarantine counter rides the probe rows, which do "
                    "not carry the arms axis yet (a ROADMAP follow-on); "
                    "the masked engine supports quarantine x arms")
            if self.level_placement == "slices":
                raise ValueError(
                    "arms need level_placement='span': the slices layout "
                    "dispatches each level to its own device rows, and "
                    "the arms axis would have to batch across disjoint "
                    "sub-meshes (a ROADMAP follow-on)")
            if cfg.get("client_store", "eager") == "stream":
                raise ValueError(
                    "arms need client_store='eager': the streaming cohort "
                    "pipeline stages ONE schedule's shards per superstep "
                    "(a ROADMAP follow-on)")
        if self.level_placement == "slices":
            # multi-process meshes take the host-aligned partition (ISSUE
            # 17): level boundaries snap to process boundaries, so every
            # level's rows land on disjoint hosts and the fused switch
            # branches stay uniform per device row
            self._slices, refusal = self._static_mesh_slices()
            if not self._slices:
                self._refuse_slices(refusal)

    def _refuse_slices(self, reason: str) -> None:
        """Loud span fallback (ISSUE 17 satellite): a configured slices
        placement that cannot be honoured names WHY -- a structured
        warning by default, a :class:`ValueError` under
        ``cfg['strict_placement']`` (operators pinning the pod layout want
        the dispatch refused, not silently reshaped)."""
        import json as _json
        import warnings

        detail = _json.dumps({"event": "slices-fallback", "reason": reason,
                              "clients_rows": int(self.mesh.shape["clients"]),
                              "processes": int(jax.process_count())},
                             sort_keys=True)
        if self.cfg.get("strict_placement"):
            raise ValueError(
                f"level_placement='slices' cannot be honoured and "
                f"strict_placement is set: {reason} ({detail})")
        warnings.warn(f"level_placement='slices' falling back to 'span': "
                      f"{reason} ({detail})")
        self.level_placement = "span"

    def _clients_row_chunks(self) -> Optional[List[Tuple[int, int]]]:
        """The contiguous clients-row chunks level boundaries may land on:
        single rows on a single-process mesh, whole per-process row blocks
        on a multi-process mesh (derived from the MESH devices'
        ``process_index`` -- the same signal
        ``staticcheck.wire.dcn_axes_of`` classifies DCN-eligible axes from,
        so AOT topology meshes get host-aligned chunks too).  ``None`` when
        no host-aligned partition exists: a clients row straddling
        processes, or a process owning non-contiguous row ranges.

        ``cfg['slice_align']`` (int n > 0) forces allocation units of
        ``C/n`` contiguous rows instead -- the single-process reference run
        emulating a pod's per-process blocks (the bitwise probe,
        :mod:`~.pod`).  The forced boundaries must contain every process
        boundary, so a forced unit never straddles hosts."""
        # staticcheck: allow(no-asarray): constructor-time mesh introspection
        devs = np.asarray(self.mesh.devices)
        C = devs.shape[0]
        row_proc = []
        for i in range(C):
            procs = {getattr(d, "process_index", 0)
                     for d in np.ravel(devs[i])}
            if len(procs) > 1:
                return None
            row_proc.append(next(iter(procs)))
        if len(set(row_proc)) <= 1:
            chunks = [(i, i + 1) for i in range(C)]
            proc_bounds = {C}  # one process: no internal boundaries
        else:
            chunks, lo = [], 0
            for i in range(1, C):
                if row_proc[i] != row_proc[i - 1]:
                    chunks.append((lo, i))
                    lo = i
            chunks.append((lo, C))
            if len({row_proc[c_lo] for c_lo, _ in chunks}) != len(chunks):
                return None  # a process owns non-contiguous row ranges
            proc_bounds = {hi for _, hi in chunks}
        align = int(self.cfg.get("slice_align") or 0)
        if align > 0:
            if C % align:
                return None
            unit = C // align
            forced = [(i * unit, (i + 1) * unit) for i in range(align)]
            if not proc_bounds <= {hi for _, hi in forced}:
                return None  # a forced unit would straddle a process block
            chunks = forced
        return chunks

    def _static_mesh_slices(self
                            ) -> Tuple[Dict[float, Tuple[int, int]], str]:
        """Allocate clients-axis device rows to levels once per experiment,
        in proportion to EXPECTED FLOP share: fix mode weights each level by
        its user count, dynamic mode by its sampling proportion, both times
        the level's analytic per-step training cost from
        :func:`~..fed.core.level_flop_table` (the one source of truth the
        staticcheck FLOP audit also checks ``cost_analysis()`` against --
        unlike the bare ``rate^2`` heuristic it keeps the non-quadratic
        terms: input-channel convs, norms, the width-independent data prep).
        Static allocation keeps program cache keys bound to fixed (lo, hi)
        device ranges -- per-round count fluctuation is absorbed by slot
        bucketing inside each slice.

        Allocation happens in units of :meth:`_clients_row_chunks` -- rows
        on one process, whole per-process row blocks on a pod (ISSUE 17)
        -- so every level boundary is host-aligned by construction.
        Returns ``(slices, refusal_reason)``: an empty dict plus the reason
        when no partition exists (the caller falls back to span LOUDLY)."""
        cfg = self.cfg
        level_rates = sorted(self.levels, reverse=True)
        if len(level_rates) <= 1:
            return {}, "a single level leaves nothing to slice"
        chunks = self._clients_row_chunks()
        if chunks is None:
            return {}, ("no host-aligned partition exists: a clients row "
                        "straddles process boundaries (or a process owns "
                        "non-contiguous rows) on this mesh")
        if len(chunks) < len(level_rates):
            unit = ("process-aligned row chunks" if jax.process_count() > 1
                    else "clients rows")
            return {}, (f"{len(chunks)} {unit} cannot host "
                        f"{len(level_rates)} levels (each level needs at "
                        f"least one)")
        if cfg["model_split_mode"] == "fix":
            vec = np.asarray(cfg["model_rate"], np.float64)  # staticcheck: allow(no-asarray): constructor-time config parse
            weights = [float((vec == r).sum()) for r in level_rates]  # staticcheck: allow(no-float-coercion): host config parse
        else:
            weights = [float(p) for p in cfg["proportion"]]  # staticcheck: allow(no-float-coercion): host config parse
            # cfg['model_rate'] lists the level table in dynamic mode, in
            # the same order as cfg['proportion']
            order = {float(r): i for i, r in enumerate(cfg["model_rate"])}  # staticcheck: allow(no-float-coercion): host config parse
            weights = [weights[order[r]] for r in level_rates]
        table = level_flop_table(cfg, level_rates)
        shares = np.array([w * table[r] for w, r in zip(weights, level_rates)],
                          np.float64)
        shares = np.maximum(shares, 1e-9)
        n_units = len(chunks)
        rows = np.maximum(1, np.floor(shares / shares.sum()
                                      * n_units)).astype(int)
        while rows.sum() > n_units:  # the >=1 floor can overshoot
            cand = int(np.argmax(np.where(rows > 1, rows, -1)))
            rows[cand] -= 1
        while rows.sum() < n_units:  # leftovers go to the most loaded level
            rows[int(np.argmax(shares / rows))] += 1
        out, ulo = {}, 0
        for r, n in zip(level_rates, rows):
            out[r] = (chunks[ulo][0], chunks[ulo + int(n) - 1][1])
            ulo += int(n)
        return out, ""

    # -- per-level codec layout (ISSUE 9 satellite) --------------------

    def _map_layout(self, params) -> Dict[str, Any]:
        """Per-level flat layout of the per-level codec map: each level's
        sliced :class:`~..ops.flatspec.FlatSpec` plus the LOSSY levels'
        offsets into one concatenated ``[2, total_lossy]`` error-feedback
        carry (row 1 is only written by ``topk``; the quantising codecs use
        row 0).  Cached by the global param shapes -- a trace-time
        constant, like the codec objects themselves."""
        shapes_key = tuple((k, tuple(v.shape))
                           for k, v in sorted(params.items()))
        if self._map_lay is not None and self._map_lay[0] == shapes_key:
            return self._map_lay[1]
        gm = self.global_model
        sds = {k: jax.ShapeDtypeStruct(tuple(v.shape), jnp.float32)
               for k, v in params.items()}
        specs, offsets, off = {}, {}, 0
        for rate in sorted(self.levels, reverse=True):
            wr = rate / self.global_rate
            sub = jax.eval_shape(
                lambda p, w=wr: extract_sliced_jnp(p, gm.specs, gm.groups, w),
                sds)
            spec_l = FlatSpec({k: tuple(v.shape) for k, v in sub.items()})
            specs[rate] = spec_l
            if self._codec_map[rate] != "dense":
                offsets[rate] = off
                off += spec_l.total
        lay = {"specs": specs, "offsets": offsets, "total_lossy": off}
        self._map_lay = (shapes_key, lay)
        return lay

    def _map_codec(self, rate: float, spec_l: FlatSpec,
                   participants: Optional[int] = None):
        """The (cached) codec object of one lossy level in the per-level
        map, over that level's sliced flat layout.  ``participants``: how
        many devices ENCODE this level's payload -- the whole clients axis
        under 'span' (default), the level's own slice rows under 'slices'
        (every other row ships the codec's identity payload, and the
        decode must attribute lane offsets/scales to the encoders only)."""
        if participants is None:
            participants = self.mesh.shape["clients"]
        key = (float(rate), spec_l.total, int(participants))  # staticcheck: allow(no-float-coercion): host cache key (rate is a python level)
        obj = self._map_codec_objs.get(key)
        if obj is None:
            obj = make_codec(self._codec_map[rate], spec_l,
                             participants, self._error_feedback)
            self._map_codec_objs[key] = obj
        return obj

    def _resid_shape(self, params):
        """Per-level codec maps carry ONE concatenated EF residual
        ``[n_dev, 2, total_lossy]`` (sharded over clients rows like the
        single-codec carry); everything else defers to
        :class:`~.round_engine._WireCodecCarry`."""
        if self._codec_map is None:
            return super()._resid_shape(params)
        return (self.mesh.shape["clients"], 2,
                self._map_layout(params)["total_lossy"])

    # -- per-level program ---------------------------------------------

    def _level_core(self, rate: float, params, key, lr, uarr, data,
                    n_data: int = 1, data_axis=None, local_data: bool = False,
                    epoch=None):
        """One level's per-device in-jit core (inside ``shard_map``): dense
        local training of this device's ``uarr`` slots at ``rate`` and the
        level's counted sums in SLICED shape.  NO collectives -- the callers
        reduce: the per-level program psums sliced then embeds once, the
        fused superstep embeds per device and joins a single global psum
        (zero-pad embedding commutes with the sum exactly, so both
        associations add the same addends elementwise).

        ``local_data=True`` (ISSUE 6 streaming): ``data`` is already in
        slot order -- row j IS slot j's shard -- so no gather; ``uarr``
        still carries the GLOBAL user ids for the PRNG streams and slot
        validity."""
        gm = self.global_model
        model_l, eng_l = self.levels[rate]
        wr = rate / self.global_rate  # static for this core
        lm_all = data[-1]
        valid = (uarr >= 0).astype(jnp.float32)
        ugid = jnp.maximum(uarr, 0)
        if self.failure_rate > 0.0:
            # same crash model + PRNG stream as the masked engine
            fkey = failure_stream_key(key)
            alive = 1.0 - jax.vmap(
                lambda u: jax.random.bernoulli(
                    jax.random.fold_in(fkey, u), self.failure_rate)
            )(ugid).astype(jnp.float32)
            valid = valid * alive
        with scope("round/gather"):
            sub = extract_sliced_jnp(params, gm.specs, gm.groups, wr)
            slot_keys = client_stream_keys(key, ugid)
            lm = lm_all if local_data else lm_all[ugid]
            if self.is_lm:
                rows = data[0] if local_data else data[0][ugid]
            else:
                xs, ys, sms = (data[0], data[1], data[2]) if local_data \
                    else (data[0][ugid], data[1][ugid], data[2][ugid])
        if self.is_lm:
            if self._sched_spec.has_deadline:
                # deadline stragglers (ISSUE 9): the masked engine's exact
                # per-client budget draw (same round key + global uid, same
                # static E x S total) -- per-level masks, engine-invariant
                total_steps = eng_l.local_epochs * _ceil_div(
                    int(rows.shape[-1]), eng_l.bptt)
                limits = deadline_steps(key, ugid, total_steps,
                                        self._sched_spec.deadline_min_frac)
                with scope("round/local_train"):
                    trained, ms = jax.vmap(
                        lambda r_, l_, k_, lim_: eng_l._local_train_lm(
                            sub, 1.0, r_, l_, k_, lr, scaler_rate=wr,
                            data_axis=data_axis, n_data=n_data, step_limit=lim_)
                    )(rows, lm, slot_keys, limits)
            else:
                with scope("round/local_train"):
                    trained, ms = jax.vmap(
                        lambda r_, l_, k_: eng_l._local_train_lm(
                            sub, 1.0, r_, l_, k_, lr, scaler_rate=wr,
                            data_axis=data_axis, n_data=n_data)
                    )(rows, lm, slot_keys)
        else:
            if self._sched_spec.has_deadline:
                total_steps = eng_l.local_epochs * _ceil_div(
                    int(xs.shape[1]), eng_l.batch_size)
                limits = deadline_steps(key, ugid, total_steps,
                                        self._sched_spec.deadline_min_frac)
                with scope("round/local_train"):
                    trained, ms = jax.vmap(
                        lambda x_, y_, m_, l_, k_, lim_: eng_l._local_train_vision(
                            sub, 1.0, x_, y_, m_, l_, k_, lr, scaler_rate=wr,
                            data_axis=data_axis, n_data=n_data, step_limit=lim_)
                    )(xs, ys, sms, lm, slot_keys, limits)
            else:
                with scope("round/local_train"):
                    trained, ms = jax.vmap(
                        lambda x_, y_, m_, l_, k_: eng_l._local_train_vision(
                            sub, 1.0, x_, y_, m_, l_, k_, lr, scaler_rate=wr,
                            data_axis=data_axis, n_data=n_data)
                    )(xs, ys, sms, lm, slot_keys)
        if self._poison is not None:
            # chaos NaN poison (ISSUE 15): same (round, uid) table and
            # injection point as the masked engine -- the update goes
            # non-finite after local training, before aggregation
            if epoch is None:
                raise ValueError(
                    "chaos_poison with the grouped strategy needs the "
                    "fused superstep (superstep_rounds > 1 or client_store"
                    "='stream'): the K=1 host-orchestrated path does not "
                    "thread the round epoch into its level programs")
            from ..chaos.inject import poison_updates

            trained = poison_updates(trained, self._poison, epoch, uarr)
        # counted sums in SLICED shape (within the slice the width mask is
        # all-ones by construction; only the label-split restriction remains)
        sub_shapes = {k: v.shape for k, v in sub.items()}
        with scope("round/aggregate"):
            cms = jax.vmap(lambda l_, v_: jax.tree_util.tree_map(
                lambda m: m * v_,
                make_count_masks(sub_shapes, model_l.specs, model_l.groups, 1.0, l_)))(
                lm, valid)
        ok = None
        if self._quarantine.enabled:
            # client-update quarantine (ISSUE 15): gate this level's slots
            # on finiteness (+ optional masked update norm vs the sliced
            # sub-model) and fold into sums AND counts before the embed /
            # single global psum -- zero-count participants, exactly the
            # masked engine's semantics at sliced shape
            from ..obs.probes import quarantine_gate

            ok = quarantine_gate(trained, sub, cms,
                                 self._quarantine.max_norm)
            okf = ok.astype(jnp.float32)
            cms = {k: cms[k] * okf.reshape((-1,) + (1,) * (cms[k].ndim - 1))
                   for k in cms}
            trained = {k: jnp.where(ok.reshape((-1,) + (1,) * (v.ndim - 1)),
                                    v, jnp.zeros((), v.dtype))
                       for k, v in trained.items()}
        with scope("round/aggregate"):
            sum_l = {k: jnp.sum(trained[k] * cms[k], axis=0) for k in sub}
            cnt_l = {k: jnp.sum(cms[k], axis=0) for k in sub}
        if ok is not None:
            okf = ok.astype(jnp.float32)
            ms = {k: jnp.where(ok, v, jnp.zeros((), v.dtype)) * valid
                  for k, v in ms.items()}
            ms["rate"] = jnp.full(uarr.shape, rate, jnp.float32) * valid * okf
            ms["obs_quarantine"] = jnp.reshape(
                jnp.sum(valid * (1.0 - okf)), (1,))
        else:
            ms = {k: v * valid for k, v in ms.items()}
            ms["rate"] = jnp.full(uarr.shape, rate, jnp.float32) * valid
        return sum_l, cnt_l, ms

    def _level_prog(self, rate: float, slots: int, sub_mesh=None,
                    slice_range=None):
        """Jitted shard_map for one (rate level, slot count): dense local
        training of ``slots`` clients (sharded over the clients axis) and the
        level's counted-sum partial, embedded to global shape.  With
        ``sub_mesh`` the program spans only that fixed device slice
        (level_placement='slices'; ``slice_range`` is its (lo, hi) row range
        and keys the cache so a program can never run on a stale slice)."""
        mesh = sub_mesh if sub_mesh is not None else self.mesh
        key_ = (rate, slots, slice_range)
        if key_ in self._level_progs:
            return self._level_progs[key_]
        gm = self.global_model
        wr = rate / self.global_rate  # static for this program
        n_data = mesh.shape["data"]
        data_axis = "data" if n_data > 1 else None

        def body(params, key, lr, uarr, *data):
            sum_l, cnt_l, ms = self._level_core(rate, params, key, lr, uarr,
                                                data, n_data, data_axis)
            # ONE psum bind for the level's sums+counts (bit-compatible with
            # two binds; staticcheck audits the one-collective budget)
            with scope("round/aggregate"):
                with scope("psum"):
                    sum_l, cnt_l = jax.lax.psum((sum_l, cnt_l), "clients")
                sum_l = embed_sliced_jnp(sum_l, gm.specs, gm.groups, wr)
                cnt_l = embed_sliced_jnp(cnt_l, gm.specs, gm.groups, wr)
            return sum_l, cnt_l, ms

        data_specs = (P(), P()) if self.is_lm else (P(), P(), P(), P())
        fn = _shard_map(
            body, mesh,
            in_specs=(P(), P(), P(), P("clients")) + data_specs,
            out_specs=(P(), P(), P("clients")),
        )
        # Donation: in slices mode the params arg is this level's PRIVATE
        # broadcast copy (device_put per round in train_round), so donating
        # it releases the buffers the moment the level program consumes them.
        # In span mode the SAME global params feed every level program and
        # the combine -- donation there would invalidate shared buffers.
        prog = jax.jit(fn, donate_argnums=(0,) if sub_mesh is not None else ())
        self._level_progs[key_] = prog
        return prog

    def _combine_prog(self, n_levels: int):
        """Jitted merge of ``n_levels`` level partials into the new globals.

        Donates ONLY the old globals (arg 0): the outputs are exactly one
        params-tree, so every donated leaf is consumed by aliasing.  Donating
        the sums/cnts lists too left 2x``n_levels`` param-trees of donors
        with nothing to alias -- the "donated buffers were not usable"
        warning the test gate now promotes to an error; those intermediates
        are released by normal refcounting the moment the merge consumes
        them."""
        if n_levels in self._combine_progs:
            return self._combine_progs[n_levels]

        def merge(params, sums, cnts):
            with scope("round/aggregate"):
                summed = jax.tree_util.tree_map(lambda *xs: sum(xs), *sums)
                counts = jax.tree_util.tree_map(lambda *xs: sum(xs), *cnts)
                return combine_counted(params, summed, counts)

        prog = jax.jit(merge, donate_argnums=(0,))
        self._combine_progs[n_levels] = prog
        return prog

    def program_cache_size(self) -> int:
        """Total compiled specializations across this engine's programs
        (per-level + combine + fused superstep); see
        :meth:`~.round_engine.RoundEngine.program_cache_size`."""
        progs = list(self._level_progs.values()) \
            + list(self._combine_progs.values()) \
            + list(self._superstep_progs.values())
        return sum(p._cache_size() for p in progs)

    # -- host wrapper ---------------------------------------------------

    def train_round(self, global_params: Dict[str, Any], user_idx: np.ndarray,
                    rates: np.ndarray, data: Tuple, lr: float, key,
                    timer: PhaseTimer = None, async_metrics: bool = False):
        """One round.  ``data`` is the replicated stacked tuple the masked
        engine takes; ``rates`` are the active users' absolute rates (host
        side, same PRNG stream as the masked engine's in-jit draw).

        Steady state moves zero host data: the data stacks (and in slices
        mode every per-level operand) are committed to their (sub-)meshes
        once by the :class:`~.staging.PlacementCache`; per-round values --
        slot ids, the params broadcast -- use explicit ``device_put`` only.
        ``timer`` accounts the stage/dispatch/fetch phases.  With
        ``async_metrics=True`` the per-slot metric sums stay on device and a
        :class:`~.staging.PendingMetrics` is returned in their place, so the
        caller can overlap the D2H fetch with the next round's dispatch."""
        if self._arms_spec is not None:
            raise ValueError(
                "arms need the fused grouped superstep (train_superstep): "
                "the K=1 host-orchestrated path dispatches L+1 programs "
                "per round, which the arms axis would fork per arm")
        if self._codec_name != "dense":
            raise ValueError(
                f"wire_codec={self._codec_name!r} needs the fused grouped "
                f"superstep (set superstep_rounds > 1 or client_store="
                f"'stream'): the K=1 host-orchestrated path reduces per "
                f"level and has no single global psum to compress")
        if self._sched_spec.buffered:
            raise ValueError(
                "schedule aggregation='buffered' needs the fused grouped "
                "superstep (set superstep_rounds > 1 or client_store="
                "'stream'): the K=1 host-orchestrated path combines in its "
                "own program and has no scan carry to buffer")
        if self._obs_on:
            raise ValueError(
                "telemetry='on' with the grouped strategy needs the fused "
                "superstep (set superstep_rounds > 1 or client_store="
                "'stream'): the K=1 path splits the round across L+1 "
                "host-orchestrated programs with no shared round core to "
                "probe")
        if self.level_placement == "slices" and jax.process_count() > 1:
            raise ValueError(
                "level_placement='slices' on a multi-process mesh needs "
                "the fused superstep (set superstep_rounds > 1 or "
                "client_store='stream'): the K=1 host-orchestrated path "
                "dispatches each level onto its own sub-mesh, and a "
                "process with no devices in a level's slice cannot join "
                "that dispatch -- the fused program runs every level on "
                "the FULL mesh behind one lax.switch")
        timer = timer if timer is not None else PhaseTimer()
        n_dev = self.mesh.shape["clients"]
        with timer.phase("stage"):
            # staticcheck: allow(no-asarray): host slot-id normalization; the
            # ids reach the mesh via explicit staging.put only
            user_idx = np.asarray(user_idx, np.int32)
            # snap to the level table: float32-round-tripped or non-dyadic
            # rates either match a level or raise here, at staging -- never
            # a KeyError mid-round (ADVICE r5 item 2)
            rates = snap_to_levels(rates, self.levels)
            by_level: Dict[float, List[int]] = {}
            for pos, r in enumerate(rates):
                by_level.setdefault(float(r), []).append(pos)  # staticcheck: allow(no-float-coercion): host np scalar -> dict key
            level_order = sorted(by_level, reverse=True)
            sliced_mode = self.level_placement == "slices"
            lr_full = self._staging.scalar(lr)
            # commit the globals once: an uncommitted init tree would give
            # every level program AND the combine a second specialization on
            # round 2, when the combined outputs come back mesh-committed
            # (staticcheck recompile audit)
            global_params = self._staging.commit(global_params)

        sums, cnts, ms_levels, positions = [], [], [], []
        for rate in level_order:
            pos = by_level[rate]
            with timer.phase("stage"):
                if sliced_mode:
                    srange = self._slices[rate]
                    sub = self._staging.submesh(*srange)
                    n_dev_l = srange[1] - srange[0]
                    lr_l = self._staging.scalar(lr, srange)
                    key_l = self._staging.put(key, srange)
                else:
                    sub, n_dev_l, srange = None, n_dev, None
                    lr_l, key_l = lr_full, key
                # the level's data stacks: committed to its (sub-)mesh once,
                # keyed by the static (lo, hi) range; per-round lookups are
                # identity hits returning device-resident buffers
                args = self._staging.replicated("train_data", data, srange=srange)
                slots = _bucket_pow2(_ceil_div(len(pos), n_dev_l)) * n_dev_l
                u = self._packer.buffer((rate, slots), (slots,))
                u[: len(pos)] = user_idx[pos]
                uarr = self._staging.put(u, srange, P("clients"))
            with timer.phase("dispatch"):
                if sliced_mode:
                    # params broadcast onto this level's fixed slice (jitted
                    # ICI replicate-copy with PRIVATE buffers -- see
                    # PlacementCache.broadcast); the level program donates the
                    # copy, releasing it the moment it is consumed.
                    # Dispatches to disjoint devices overlap in time.
                    p_in = self._staging.broadcast(global_params, srange)
                else:
                    p_in = global_params
                sum_l, cnt_l, ms = self._level_prog(rate, slots, sub, srange)(
                    p_in, key_l, lr_l, uarr, *args)
                if sliced_mode:
                    # bring the level partials back onto the full mesh so the
                    # combine program sees co-located inputs
                    sum_l = self._staging.put(sum_l)
                    cnt_l = self._staging.put(cnt_l)
            sums.append(sum_l)
            cnts.append(cnt_l)
            ms_levels.append(ms)
            positions.append(pos)
        with timer.phase("dispatch"):
            if sliced_mode:
                global_params = self._staging.put(global_params)
            new_params = self._combine_prog(len(sums))(global_params, sums, cnts)

        n_slots = len(user_idx)

        def _assemble(host_levels):
            metrics = {k: np.zeros(n_slots, np.float32)
                       for k in ("loss_sum", "score_sum", "n", "rate")}
            for pos, ms in zip(positions, host_levels):
                for k in metrics:
                    metrics[k][pos] = ms[k][: len(pos)]
            if host_levels and "obs_quarantine" in host_levels[0]:
                # quarantine counter (ISSUE 15): per-device partials of
                # every level concatenate; the driver's split_probes sums
                # them into the round's quarantined-client count
                metrics["obs_quarantine"] = np.concatenate(
                    [ms["obs_quarantine"] for ms in host_levels])
            return metrics

        pending = PendingMetrics(ms_levels, assemble=_assemble)
        if async_metrics:
            return new_params, pending
        with timer.phase("fetch"):
            return new_params, pending.fetch()

    # -- fused superstep ------------------------------------------------

    def _hist_total_steps(self, x) -> int:
        """Static per-client local-step total from a data-stack aval (the
        deadline-budget denominator of the step-fraction histogram, ISSUE
        12).  Shard shapes are level-invariant, so one number serves every
        level: vision stacks end ``[..., n, H, W, C]``, LM rows ``[...,
        T]`` -- eager population stacks and streaming cohort xs alike."""
        eng0 = next(iter(self.levels.values()))[1]
        if self.is_lm:
            return eng0.local_epochs * _ceil_div(int(x.shape[-1]), eng0.bptt)
        return eng0.local_epochs * _ceil_div(int(x.shape[-4]),
                                             eng0.batch_size)

    def _fused_layout(self):
        """(mode, level boundary table) of the fused round: 'slices'
        whenever the static row partition exists, else 'span'.

        A data axis no longer refuses slices mode (ISSUE 17): the branch
        index is a function of ``axis_index("clients")`` alone, so every
        device sharing a clients row -- the participant set of every
        data-axis collective inside a branch -- takes the SAME branch.
        Each collective's replica groups are therefore uniform (a group
        either enters its level's branch together or skips it together),
        which is the only uniformity XLA's grouped collectives need."""
        if self.level_placement == "slices" and self._slices:
            return "slices", [self._slices[r][0] for r in sorted(self._slices, reverse=True)]
        return "span", None

    def _superstep_prog(self, k: int, per_dev: int, mode: str, eval_mask=None,
                        fused_eval=None, lr_arg: bool = False,
                        streaming: bool = False, arms: int = 0):
        """ONE jitted+donated ``shard_map`` program for ``k`` grouped rounds:
        the five per-level programs AND the combine fused into a single XLA
        program, wrapped in a ``lax.scan`` over the rounds (ISSUE 2).

        ``mode='span'``: every device runs every level back-to-back (a
        static python loop over the level table inside the scan body).
        ``mode='slices'``: each device row runs ONLY its level's branch
        (``lax.switch`` on the row's static slice assignment) -- the levels
        execute concurrently because XLA schedules disjoint device groups,
        not because the host dispatched them asynchronously.  Either way the
        level partials are embedded to global shape per device, ONE global
        psum joins them, and the counted-average combine runs in-program --
        aggregation state never exists outside the program.

        ``per_dev`` is the UNIFORM per-device-per-level slot count (one
        count for all levels, bucketed by the caller), so the compile space
        stays O(k-shapes x log A) -- a per-level-count key would recompile
        combinatorially as the sampled mix varies.

        ``eval_mask`` + ``fused_eval`` (ISSUE 4): on scan steps where the
        static mask fires, the :class:`~.evaluation.FusedEval` core runs the
        sBN+Local/Global eval phase on the freshly-combined globals INSIDE
        this program (outside the slices-mode ``lax.switch``, so the eval
        collectives stay uniform across devices); the per-training-round
        single-psum invariant is untouched and the eval phase's reductions
        are audited separately.  ``lr_arg``: LR as a staged scalar instead
        of the traced schedule (ReduceLROnPlateau superstep mode).

        ``streaming=True`` (ISSUE 6): the replicated population stacks are
        replaced by the sampled cohort's shards riding the scan xs in the
        SAME slot layout as the schedule (span: ``[k, L, slots, ...]``,
        slices: ``[k, slots, ...]``, slot axis sharded over ``clients``);
        each level's core then indexes identity -- program memory is
        O(k x levels x slots), independent of the population."""
        from .round_engine import (_ArmsFusedEval, eval_fused_scan,
                                   superstep_eval_groups)

        key_ = (k, per_dev, mode, eval_mask, lr_arg, streaming, arms)
        if key_ in self._superstep_progs:
            return self._superstep_progs[key_]
        gm = self.global_model
        mesh = self.mesh
        n_data = mesh.shape["data"]
        data_axis = "data" if n_data > 1 else None
        level_rates = sorted(self.levels, reverse=True)
        lr_fn = self._lr_fn
        groups = superstep_eval_groups(eval_mask) if eval_mask else None
        if groups is not None and not any(ev for _, ev, _ in groups):
            groups = None
        if groups is not None and arms:
            # arms multiplexer (ISSUE 14): the fused eval phase runs vmapped
            # over the arms axis against the shared committed operands
            fused_eval = _ArmsFusedEval(fused_eval, arms)

        def embed(tree, rate):
            return embed_sliced_jnp(tree, gm.specs, gm.groups, rate / self.global_rate)

        if mode == "slices":
            # np (not jnp): an eager jnp array here would be an implicit H2D
            # whenever a fresh slot bucket triggers a rebuild inside a
            # transfer-guarded steady state; as an np closure constant it
            # enters the program at trace time instead
            # staticcheck: allow(no-asarray): trace-time closure constant
            level_los = np.asarray([self._slices[r][0] for r in level_rates],
                                   np.int32)

        n_data_args = 2 if self.is_lm else 4
        codec = self._codec_name != "dense"
        per_level = self._codec_map is not None
        buffered = self._sched_spec.buffered
        # per-device max contributing clients: the span layout runs every
        # level's slots on every device, the slices layout one level's --
        # this bounds the partial-sum magnitude the codec's grid must cover
        cmax = (len(level_rates) if mode == "span" else 1) * per_dev

        def sbody(params, *all_rest):
            if codec:
                resid0, base_key, epoch0, *rest = all_rest
            elif buffered:
                buf0, base_key, epoch0, *rest = all_rest
            else:
                base_key, epoch0, *rest = all_rest
            idx = 0
            ascales = None
            if lr_arg:
                # under arms this is the staged PER-ARM LR vector [E]
                lr_const = rest[0]
                idx = 1
            elif arms:
                # per-arm multiplicative scales over the shared schedule
                ascales = rest[0]
                idx = 1
            sched = rest[idx]
            if streaming:
                sdata = rest[idx + 1:idx + 1 + n_data_args]
                eval_ops = rest[idx + 1 + n_data_args:]
                data = None
            else:
                data = rest[idx + 1:idx + 1 + n_data_args]
                eval_ops = rest[idx + 1 + n_data_args:]

            def attach_probes(ms_, p_old, new_p_, tot_s_, tot_c_, nr_=None,
                              nb_=None, uids_=None, key_=None, ts_=None):
                """Fold the in-program health probes into the metrics tree
                (ISSUE 10): post-psum aggregates + the combined globals,
                zero new collectives.  Identity under telemetry='off'.
                ``uids_``/``key_``/``ts_`` (ISSUE 12): the slot-uid rows,
                round key and static step total the cohort histograms
                re-derive the deadline budgets from (telemetry='hist')."""
                if not self._obs_on:
                    return ms_
                pr = round_probes(self._obs_levels, p_old, new_p_, tot_s_,
                                  tot_c_, ms_["rate"], resid=nr_,
                                  sched_buf=nb_)
                if self._obs_hist:
                    # cohort histograms (ISSUE 12): fixed-bucket rows over
                    # this device's slots of every level it runs -- same
                    # zero-collective contract as the scalar probes
                    pr = {**pr, **round_hists(
                        self._obs_levels, ms_["rate"], ms_["loss_sum"],
                        ms_["n"], key=key_, uids=uids_, total_steps=ts_,
                        min_frac=(self._sched_spec.deadline_min_frac
                                  if self._sched_spec.has_deadline
                                  else None), sched_buf=nb_)}
                if mode == "span":
                    # span metric leaves are [L, slots]: rank-pad the probe
                    # rows so the one broadcast out-spec covers the tree
                    pr = {n: v[:, None] for n, v in pr.items()}
                return {**ms_, **pr}

            def step(carry, xs):
                if codec:
                    p, rs, sb = carry[0], carry[1], None
                elif buffered:
                    p, rs, sb = carry[0], None, carry[1]
                else:
                    p, rs, sb = carry, None, None
                if streaming:
                    t, srow, *d = xs
                else:
                    t, srow = xs
                if arms:
                    # arms multiplexer (ISSUE 14): the whole span round --
                    # every level core, the embeds, the SINGLE global psum
                    # and the counted-average combine -- vmapped over the
                    # leading arms axis of the params carry.  The host
                    # schedule (level-grouped slots) is SHARED across arms
                    # (level membership is slot bookkeeping, one layout for
                    # all); per-arm streams drive the client/slot keys,
                    # deadline budgets and failure draws, so arm e is a
                    # solo grouped run with seed e on the same schedule,
                    # bitwise.  The batched psum stays ONE bind; wire
                    # bytes and FLOPs scale linearly in E (staticcheck
                    # arms variants audit both by equality).
                    scales = lr_const if lr_arg else ascales

                    def arm_core(p_e, akey, sc_e):
                        key_e = jax.random.fold_in(akey, t)
                        lr_e = sc_e if lr_arg else lr_fn(t) * sc_e
                        tot_se = tot_ce = None
                        ms_lv = []
                        for li, rate in enumerate(level_rates):
                            s_l, c_l, ms_l = self._level_core(
                                rate, p_e, key_e, lr_e, srow[li], data,
                                n_data, data_axis, epoch=t)
                            s_l, c_l = embed(s_l, rate), embed(c_l, rate)
                            tot_se = s_l if tot_se is None else \
                                {n: tot_se[n] + s_l[n] for n in tot_se}
                            tot_ce = c_l if tot_ce is None else \
                                {n: tot_ce[n] + c_l[n] for n in tot_ce}
                            ms_lv.append(ms_l)
                        ms_e = {n: jnp.stack([m[n] for m in ms_lv])
                                for n in ms_lv[0]}
                        tot_se, tot_ce = jax.lax.psum((tot_se, tot_ce),
                                                      "clients")
                        return combine_counted(p_e, tot_se, tot_ce), ms_e

                    return jax.vmap(arm_core)(p, base_key, scales)
                key = jax.random.fold_in(base_key, t)
                lr = lr_const if lr_arg else lr_fn(t)
                hist_ts = None
                if self._obs_hist and self._sched_spec.has_deadline:
                    # the step-fraction histogram's static denominator
                    # (ISSUE 12) -- from the data aval, level-invariant
                    hist_ts = self._hist_total_steps(d[0] if streaming
                                                     else data[0])
                if per_level and mode == "slices":
                    # per-level codec map x slices layout (ISSUE 14
                    # satellite, retiring the PR 9 refusal): each device
                    # row runs ONLY its level's switch branch, yet every
                    # branch emits EVERY level's payload structure -- its
                    # own level's encoded partial plus the other levels'
                    # IDENTITY payloads (codec.zero_payload, all-zero
                    # lanes).  Each level's codec counts its slice's rows
                    # as participants, so the shared decode attributes
                    # lane bias/scale sums to exactly the rows that
                    # encoded.  Still ONE global psum bind carrying the
                    # per-level payload tree -- the same wire budget as
                    # the span map (fed.core.level_codec_map_byte_table,
                    # priced by equality in staticcheck).
                    lay = self._map_layout(p)
                    row = jax.lax.axis_index("clients")
                    branch = jnp.sum(row >= level_los) - 1
                    rows_of = {r_: self._slices[r_][1] - self._slices[r_][0]
                               for r_ in level_rates}

                    def zero_tree(rate_z):
                        spec_z = lay["specs"][rate_z]
                        if self._codec_map[rate_z] == "dense":
                            return (jnp.zeros(spec_z.total, jnp.float32),
                                    jnp.zeros(spec_z.total, jnp.float32))
                        return self._map_codec(
                            rate_z, spec_z, rows_of[rate_z]).zero_payload()

                    def mk_pl(rate_own):
                        def f(p_, key_l, lr_l, u_, rs_):
                            s_l, c_l, ms_l = self._level_core(
                                rate_own, p_, key_l, lr_l, u_,
                                tuple(d) if streaming else data, n_data,
                                data_axis, local_data=streaming, epoch=t)
                            spec_o = lay["specs"][rate_own]
                            sf, cf = spec_o.flatten(s_l), spec_o.flatten(c_l)
                            payload = {f"L{lz}": zero_tree(rz)
                                       for lz, rz in enumerate(level_rates)
                                       if rz != rate_own}
                            li_own = level_rates.index(rate_own)
                            if self._codec_map[rate_own] == "dense":
                                payload[f"L{li_own}"] = (sf, cf)
                                nr_own = rs_
                            else:
                                cobj = self._map_codec(rate_own, spec_o,
                                                       rows_of[rate_own])
                                off = lay["offsets"][rate_own]
                                rs_l = jax.lax.dynamic_slice(
                                    rs_, (0, off),
                                    (2, spec_o.total))[:cobj.resid_slots]
                                sub_o = extract_sliced_jnp(
                                    p_, gm.specs, gm.groups,
                                    rate_own / self.global_rate)
                                pl, nr_l = cobj.encode(sf, cf, rs_l, sub_o,
                                                       key_l, per_dev)
                                payload[f"L{li_own}"] = pl
                                nr_own = jax.lax.dynamic_update_slice(
                                    rs_, nr_l, (0, off))
                            return payload, nr_own, ms_l
                        return f

                    payload, nr, ms = jax.lax.switch(
                        branch, [mk_pl(r_) for r_ in level_rates], p, key,
                        lr, srow, rs)
                    # THE single global psum: one bind joins every level's
                    # payload across the whole clients axis
                    agg = jax.lax.psum(payload, "clients")
                    tot_s = tot_c = None
                    for li, rate in enumerate(level_rates):
                        spec_l = lay["specs"][rate]
                        if self._codec_map[rate] == "dense":
                            sf, cf = agg[f"L{li}"]
                        else:
                            cobj = self._map_codec(rate, spec_l,
                                                   rows_of[rate])
                            sub_l = extract_sliced_jnp(
                                p, gm.specs, gm.groups,
                                rate / self.global_rate)
                            sf, cf = cobj.decode(agg[f"L{li}"], sub_l, key,
                                                 per_dev)
                        s_e = embed(spec_l.unflatten(sf), rate)
                        c_e = embed(spec_l.unflatten(cf), rate)
                        tot_s = s_e if tot_s is None else \
                            {n: tot_s[n] + s_e[n] for n in tot_s}
                        tot_c = c_e if tot_c is None else \
                            {n: tot_c[n] + c_e[n] for n in tot_c}
                    new_p = combine_counted(p, tot_s, tot_c)
                    ms = attach_probes(ms, p, new_p, tot_s, tot_c, nr_=nr,
                                       uids_=srow, key_=key, ts_=hist_ts)
                    return (new_p, nr), ms
                if per_level:
                    # per-level codec selection (ISSUE 9 satellite): each
                    # level's SLICED counted sums join the round's ONE psum
                    # bind under that level's own codec -- dense levels ship
                    # raw f32 at sliced shape, lossy levels their packed
                    # lanes, and the EF residuals of the lossy levels
                    # concatenate into one [2, total_lossy] carry (span
                    # layout; the slices layout branches above).
                    lay = self._map_layout(p)
                    payload, ms_levels, dec = {}, [], {}
                    for li, rate in enumerate(level_rates):
                        d_li = tuple(x[li] for x in d) if streaming else data
                        s_l, c_l, ms_l = self._level_core(
                            rate, p, key, lr, srow[li], d_li, n_data,
                            data_axis, local_data=streaming, epoch=t)
                        ms_levels.append(ms_l)
                        spec_l = lay["specs"][rate]
                        sf, cf = spec_l.flatten(s_l), spec_l.flatten(c_l)
                        if self._codec_map[rate] == "dense":
                            payload[f"L{li}"] = (sf, cf)
                            continue
                        cobj = self._map_codec(rate, spec_l)
                        off = lay["offsets"][rate]
                        rs_l = jax.lax.dynamic_slice(
                            rs, (0, off),
                            (2, spec_l.total))[:cobj.resid_slots]
                        sub_l = extract_sliced_jnp(
                            p, gm.specs, gm.groups, rate / self.global_rate)
                        pl, nr_l = cobj.encode(sf, cf, rs_l, sub_l, key,
                                               per_dev)
                        payload[f"L{li}"] = pl
                        dec[li] = (cobj, sub_l, nr_l, off)
                    ms = {n: jnp.stack([m[n] for m in ms_levels])
                          for n in ms_levels[0]}
                    # THE single global psum: one bind joins every level's
                    # payload (a pytree psum is one bind; staticcheck holds
                    # the summed operand bytes to the per-level-map budget)
                    agg = jax.lax.psum(payload, "clients")
                    tot_s = tot_c = None
                    nr = jnp.zeros_like(rs)
                    for li, rate in enumerate(level_rates):
                        spec_l = lay["specs"][rate]
                        if li in dec:
                            cobj, sub_l, nr_l, off = dec[li]
                            sf, cf = cobj.decode(agg[f"L{li}"], sub_l, key,
                                                 per_dev)
                            nr = jax.lax.dynamic_update_slice(nr, nr_l,
                                                              (0, off))
                        else:
                            sf, cf = agg[f"L{li}"]
                        s_e = embed(spec_l.unflatten(sf), rate)
                        c_e = embed(spec_l.unflatten(cf), rate)
                        tot_s = s_e if tot_s is None else \
                            {n: tot_s[n] + s_e[n] for n in tot_s}
                        tot_c = c_e if tot_c is None else \
                            {n: tot_c[n] + c_e[n] for n in tot_c}
                    new_p = combine_counted(p, tot_s, tot_c)
                    ms = attach_probes(ms, p, new_p, tot_s, tot_c, nr_=nr,
                                       uids_=srow, key_=key, ts_=hist_ts)
                    return (new_p, nr), ms
                if mode == "span":
                    # srow: [L, per_dev] -- this device's slots of EVERY level
                    tot_s = tot_c = None
                    ms_levels = []
                    for li, rate in enumerate(level_rates):
                        d_li = tuple(x[li] for x in d) if streaming else data
                        s_l, c_l, ms_l = self._level_core(
                            rate, p, key, lr, srow[li], d_li, n_data,
                            data_axis, local_data=streaming, epoch=t)
                        s_l, c_l = embed(s_l, rate), embed(c_l, rate)
                        tot_s = s_l if tot_s is None else \
                            {n: tot_s[n] + s_l[n] for n in tot_s}
                        tot_c = c_l if tot_c is None else \
                            {n: tot_c[n] + c_l[n] for n in tot_c}
                        ms_levels.append(ms_l)
                    ms = {n: jnp.stack([m[n] for m in ms_levels])
                          for n in ms_levels[0]}
                else:
                    # srow: [per_dev] -- this device's slots of ITS OWN level
                    row = jax.lax.axis_index("clients")
                    branch = jnp.sum(row >= level_los) - 1

                    def mk(rate):
                        def f(p_, key_l, lr_l, u_):
                            # n_data/data_axis pass through (ISSUE 17): the
                            # data-axis collectives inside this branch are
                            # uniform per clients row -- every participant
                            # of a row's "data" group takes the same branch
                            s, c, m = self._level_core(
                                rate, p_, key_l, lr_l, u_,
                                tuple(d) if streaming else data, n_data,
                                data_axis, local_data=streaming, epoch=t)
                            return embed(s, rate), embed(c, rate), m
                        return f

                    tot_s, tot_c, ms = jax.lax.switch(
                        branch, [mk(r) for r in level_rates], p, key, lr, srow)
                if codec:
                    # wire codec (ISSUE 8): the SAME single bind carries the
                    # packed compressed payload of the embedded level
                    # partials; EF residual re-injected next round
                    from ..compress.codecs import compressed_psum

                    with scope("round/aggregate"), scope("psum"):
                        tot_s, tot_c, nr = compressed_psum(
                            self._codec(p), "clients", p, tot_s, tot_c, rs,
                            key, cmax)
                else:
                    # THE single global psum of the fused round (the PR 2
                    # invariant, audited by staticcheck): one bind joins the
                    # level sums AND counts across the whole clients axis
                    with scope("round/aggregate"), scope("psum"):
                        tot_s, tot_c = jax.lax.psum((tot_s, tot_c), "clients")
                if buffered:
                    # buffered-async aggregation (ISSUE 9): this round's
                    # reduction lands NEXT round, staleness-weighted; the
                    # previous round's buffered update applies now
                    with scope("round/aggregate"):
                        new_p, nb = buffered_combine(
                            p, sb, tot_s, tot_c, FlatSpec.of(p),
                            self._sched_spec.staleness)
                    ms = attach_probes(ms, p, new_p, tot_s, tot_c, nb_=nb,
                                       uids_=srow, key_=key, ts_=hist_ts)
                    return (new_p, nb), ms
                with scope("round/aggregate"):
                    new_p = combine_counted(p, tot_s, tot_c)
                ms = attach_probes(ms, p, new_p, tot_s, tot_c,
                                   nr_=nr if codec else None,
                                   uids_=srow, key_=key, ts_=hist_ts)
                return ((new_p, nr) if codec else new_p), ms

            epochs = epoch0 + jnp.arange(k, dtype=jnp.int32)
            xs = (epochs, sched) + (tuple(sdata) if streaming else ())
            if codec:
                carry0 = (params, resid0[0])
            elif buffered:
                carry0 = (params, buf0)
            else:
                carry0 = params

            def unpack(carry):
                if codec:
                    return carry[0], (carry[1][None],)
                if buffered:
                    return carry[0], (carry[1],)
                return carry, ()

            if groups is None:
                carry, ms = jax.lax.scan(step, carry0, xs)
                p_out, extra = unpack(carry)
                return (p_out,) + extra + (ms,)
            # eval runs on the combined globals AFTER the round(s) it
            # follows, outside the slices-mode switch; the shared walk keeps
            # it at the program's top level (bit-identical-to-host contract)
            carry, ms, ev = eval_fused_scan(
                step, carry0, xs, epochs, groups, fused_eval, eval_ops,
                params_of=(lambda c: c[0]) if (codec or buffered) else None)
            p_out, extra = unpack(carry)
            return (p_out,) + extra + (ms, ev)

        lr_specs = (P(),) if (lr_arg or arms) else ()
        eval_specs = tuple(fused_eval.specs) if groups else ()
        resid_specs = (P("clients"),) if codec else ()
        buf_specs = (P(),) if buffered else ()
        carry_specs = resid_specs + buf_specs  # mutually exclusive
        sched_spec = P(None, None, "clients") if mode == "span" else P(None, "clients")
        if streaming:
            # cohort stacks ride the xs in the schedule's own slot layout
            data_specs = (sched_spec,) * n_data_args
        else:
            data_specs = (P(), P()) if self.is_lm else (P(), P(), P(), P())
        if arms:
            # [k, E, L, slots]: the arms axis rides behind the round axis
            ms_spec = P(None, None, None, "clients")
        else:
            ms_spec = P(None, None, "clients") if mode == "span" \
                else P(None, "clients")
        out_specs = (P(),) + carry_specs + (ms_spec,)
        if groups is not None:
            out_specs = out_specs + (fused_eval.out_specs,)
        fn = _shard_map(
            sbody, mesh,
            in_specs=(P(),) + carry_specs + (P(), P()) + lr_specs
            + (sched_spec,) + data_specs + eval_specs,
            out_specs=out_specs,
        )
        # Codec/buffered programs donate ONLY their extra carry, not the
        # params carry: donating the replicated params here trips an
        # XLA:CPU executable-serialization bug (jaxlib 0.4.36) where the
        # program reloaded from the persistent compile cache mis-assigns
        # the params-sized extra OUTPUT buffer and returns nondeterministic
        # garbage on a stable subset of its elements (fresh compiles are
        # correct; caught by test_resid_checkpoint_roundtrip_grouped on a
        # warm cache).  Cost: one extra params-size buffer per dispatch,
        # priced into the staticcheck HBM budgets.  Arms programs (ISSUE
        # 14) donate NOTHING: the same bug class intermittently corrupts
        # the E-stacked params carry on deserialized executables (see
        # round_engine._build_superstep).
        if arms:
            donate = ()
        else:
            donate = (1,) if (codec or buffered) else (0,)
        prog = jax.jit(fn, donate_argnums=donate)
        self._superstep_progs[key_] = prog
        return prog

    def _cohort_layout(self, user_schedule: np.ndarray,
                       rate_schedule: np.ndarray):
        """Shared slot-layout math of the eager schedule packing and the
        streaming cohort staging: snap rates, group positions per level,
        and bucket the per-device slot count.  Returns ``(sched_shape,
        per_dev, mode, positions, level_rates)`` -- the schedule buffer of
        ``sched_shape`` (span: ``[k, L, n_dev*per_dev]``, slices:
        ``[k, n_dev*per_dev]`` with each level at its slice rows) is
        allocated by the caller and written by ``_fill_schedule``."""
        k, a = user_schedule.shape
        n_dev = self.mesh.shape["clients"]
        snapped = snap_to_levels(rate_schedule.reshape(-1), self.levels)
        rate_schedule = snapped.reshape(k, a)
        level_rates = sorted(self.levels, reverse=True)
        mode, _ = self._fused_layout()
        positions = [[np.flatnonzero(rate_schedule[r] == lr_)
                      for lr_ in level_rates] for r in range(k)]
        if mode == "slices":
            rows = {r: self._slices[r][1] - self._slices[r][0]
                    for r in level_rates}
            need = max(_ceil_div(len(pos), rows[lr_]) if len(pos) else 1
                       for per_round in positions
                       for lr_, pos in zip(level_rates, per_round))
            per_dev = _bucket_pow2(need)
            shape = (k, n_dev * per_dev)
        else:
            need = max(_ceil_div(len(pos), n_dev) if len(pos) else 1
                       for per_round in positions for pos in per_round)
            per_dev = _bucket_pow2(need)
            shape = (k, len(level_rates), n_dev * per_dev)
        return shape, per_dev, mode, positions, level_rates

    @staticmethod
    def _fill_schedule(sched: np.ndarray, user_schedule: np.ndarray,
                       positions, level_rates, mode, per_dev, slices):
        """Write the packed slot ids into a (pre-filled -1) schedule buffer
        -- one code path for the eager and streaming stagings."""
        k = user_schedule.shape[0]
        if mode == "slices":
            for r in range(k):
                for lr_, pos in zip(level_rates, positions[r]):
                    lo = slices[lr_][0]
                    sched[r, lo * per_dev: lo * per_dev + len(pos)] = \
                        user_schedule[r][pos]
        else:
            for r in range(k):
                for li, pos in enumerate(positions[r]):
                    sched[r, li, : len(pos)] = user_schedule[r][pos]

    def stage_cohort(self, store: ClientStore, user_schedule,
                     rate_schedule, timer: PhaseTimer = None) -> StagedCohort:
        """Materialise + commit ONE superstep's cohort from a
        :class:`~.staging.ClientStore` (ISSUE 6): the cohort's shards pack
        into the stager's ring buffers in the SAME per-level slot layout as
        the schedule (level grouping is slot bookkeeping, done here once
        per superstep) and commit via explicit ``device_put`` + private
        copy.  O(k x levels x slots x shard) memory, population-free.
        Call for superstep N+1 right after dispatching superstep N."""
        timer = timer if timer is not None else PhaseTimer()
        with timer.phase("stage"):
            # staticcheck: allow(no-asarray): host schedule normalization;
            # the cohort reaches the mesh via the stager's explicit puts only
            user_schedule = np.asarray(user_schedule, np.int32)
            rate_schedule = np.asarray(rate_schedule)  # staticcheck: allow(no-asarray): host schedule normalization
            if user_schedule.shape != rate_schedule.shape \
                    or user_schedule.ndim != 2:
                raise ValueError(
                    f"user/rate schedules must both be [k, A], got "
                    f"{user_schedule.shape} / {rate_schedule.shape}")
            k, a = user_schedule.shape
            shape, per_dev, mode, positions, level_rates = \
                self._cohort_layout(user_schedule, rate_schedule)
            if self._cohort_stager is None:
                self._cohort_stager = CohortStager(self.mesh,
                                                   depth=self._prefetch_depth)
            st = self._cohort_stager
            n = store.shard_max
            if self.is_lm:
                dshapes = [shape + store.row_shape,
                           shape + (store.classes_size,)]
                dtypes = [store.data.dtype, np.float32]
            else:
                dshapes = [shape + (n,) + store.data.shape[1:],
                           shape + (n,), shape + (n,),
                           shape + (store.classes_size,)]
                dtypes = [store.data.dtype, store.target.dtype, np.float32,
                          np.float32]
            layouts = [(shape, np.int32, -1)] + \
                [(s, d, None) for s, d in zip(dshapes, dtypes)]
            key = ("grouped", mode, shape)
            slot_i, bufs = st.buffers(key, layouts)
            sched = bufs[0]
            self._fill_schedule(sched, user_schedule, positions, level_rates,
                                mode, per_dev, self._slices)
            flat = sched.reshape(-1)
            if self.is_lm:
                store.fill_lm(flat, bufs[1].reshape((-1,) + store.row_shape))
                store.fill_labels(flat, bufs[2].reshape(-1, store.classes_size))
            else:
                store.fill_vision(flat,
                                  bufs[1].reshape((-1, n) + store.data.shape[1:]),
                                  bufs[2].reshape(-1, n),
                                  bufs[3].reshape(-1, n))
                store.fill_labels(flat, bufs[4].reshape(-1, store.classes_size))
            spec = P(None, None, "clients") if mode == "span" \
                else P(None, "clients")
            dev = st.commit(key, slot_i, bufs, (spec,) * len(bufs))
        return StagedCohort(engine="grouped", k=k, a=a, per_dev=per_dev,
                            sched=dev[0], data=tuple(dev[1:]), mode=mode,
                            positions=positions)

    def train_superstep(self, global_params: Dict[str, Any], base_key,
                        epoch0: int, k: int,
                        user_schedule: Optional[np.ndarray] = None,
                        rate_schedule: Optional[np.ndarray] = None,
                        data: Optional[Tuple] = None,
                        timer: PhaseTimer = None, eval_mask=None,
                        fused_eval=None, lr=None,
                        cohort: Optional[StagedCohort] = None):
        """Run ``k`` grouped rounds as ONE compiled program.

        ``user_schedule``: int32 ``[k, A]`` active user ids per round (the
        superstep sampling stream, :func:`~..fed.core.round_users`);
        ``rate_schedule``: ``[k, A]`` absolute model rates drawn host-side
        from the same per-round keys as the sequential wrapper
        (:func:`~..fed.core.round_rates`) -- level membership is slot
        bookkeeping, so the grouping happens here, once per superstep, and
        the rounds themselves never touch the host.  Per-round keys are
        ``fold_in(base_key, epoch0 + r)``; the LR schedule is evaluated
        in-jit from the round index.  Returns ``(new_params,
        PendingMetrics)`` whose ``fetch()`` yields a list of k per-round
        metric dicts in active-client order.

        ``eval_mask`` + ``fused_eval`` (ISSUE 4): fuse the sBN+eval phase
        into the scan on the masked rounds; the fetch then yields
        ``{"train": [...], "eval": [...]}`` (see
        :meth:`~.round_engine.RoundEngine.train_superstep`).  ``lr``: stage
        a constant LR scalar (ReduceLROnPlateau superstep mode).

        ``cohort`` (ISSUE 6): a :class:`~.staging.StagedCohort` from
        :meth:`stage_cohort` replaces ``user_schedule``/``rate_schedule``/
        ``data`` -- the level-grouped cohort rides the scan xs and the
        program never sees the population stacks; results are bit-identical
        to the eager path at matched schedules."""
        from .round_engine import normalize_eval_mask

        eval_mask = normalize_eval_mask(eval_mask, k, fused_eval)
        lr_arg = lr is not None
        if not lr_arg and self._lr_fn is None:
            self._lr_fn = make_traced_lr_fn(self.cfg)
        timer = timer if timer is not None else PhaseTimer()
        aspec = self._arms_spec
        arms = aspec.count if aspec is not None else 0
        if cohort is not None:
            if aspec is not None:
                raise ValueError(
                    "arms need the eager data path: a staged cohort holds "
                    "ONE schedule's shards, and per-arm cohorts would "
                    "multiply the staged bytes by E (a ROADMAP follow-on)")
            if cohort.engine != "grouped" or cohort.k != k:
                raise ValueError(
                    f"cohort mismatch: staged for engine={cohort.engine!r} "
                    f"k={cohort.k}, dispatching grouped k={k}")
            with timer.phase("stage"):
                a, per_dev, mode = cohort.a, cohort.per_dev, cohort.mode
                positions = cohort.positions
                level_rates = sorted(self.levels, reverse=True)
                sched_dev, args = cohort.sched, tuple(cohort.data)
                lr_args = (self._staging.scalar(lr),) if lr_arg else ()
                eval_args = tuple(fused_eval.ops) if eval_mask is not None else ()
                epoch0_dev = self._staging.scalar(epoch0, dtype=np.int32)
                global_params = self._staging.commit(global_params)
                carry_args = self._carry_args(global_params)
                prog = self._superstep_prog(k, per_dev, mode,
                                            eval_mask=eval_mask,
                                            fused_eval=fused_eval,
                                            lr_arg=lr_arg, streaming=True)
        else:
            if user_schedule is None or rate_schedule is None or data is None:
                raise ValueError("train_superstep needs user/rate schedules "
                                 "+ data stacks, or a staged cohort")
            with timer.phase("stage"):
                n_dev = self.mesh.shape["clients"]
                # staticcheck: allow(no-asarray): host schedule normalization;
                # the packed slots reach the mesh via explicit staging.put only
                user_schedule = np.asarray(user_schedule, np.int32)
                rate_schedule = np.asarray(rate_schedule)  # staticcheck: allow(no-asarray): host schedule normalization
                if user_schedule.shape != rate_schedule.shape \
                        or user_schedule.ndim != 2 or user_schedule.shape[0] != k:
                    raise ValueError(
                        f"user/rate schedules must both be [k={k}, A], got "
                        f"{user_schedule.shape} / {rate_schedule.shape}")
                a = user_schedule.shape[1]
                # slot layout shared with the streaming staging (positions
                # drive metric reassembly + slot packing in both paths)
                shape, per_dev, mode, positions, level_rates = \
                    self._cohort_layout(user_schedule, rate_schedule)
                sched = self._packer.buffer(("gss", mode, shape), shape)
                self._fill_schedule(sched, user_schedule, positions,
                                    level_rates, mode, per_dev, self._slices)
                args = self._staging.replicated("train_data", data)
                spec = P(None, None, "clients") if mode == "span" \
                    else P(None, "clients")
                sched_dev = self._staging.put(sched, spec=spec)
                if lr_arg:
                    # arms: the per-arm LR vector [E] (Plateau steps each
                    # arm's own state); solo: a scalar
                    lr_args = ((self._staging.put(
                        np.asarray(lr, np.float32).reshape(arms)),) if arms  # staticcheck: allow(no-asarray): host LR-vector normalization; reaches the mesh via the explicit staging.put
                        else (self._staging.scalar(lr),))
                elif arms:
                    # per-arm multiplicative scales over the shared schedule
                    lr_args = (self._staging.put(
                        np.asarray(aspec.lr_scales, np.float32)),)  # staticcheck: allow(no-asarray): host scale-vector normalization; reaches the mesh via the explicit staging.put
                else:
                    lr_args = ()
                eval_args = tuple(fused_eval.ops) if eval_mask is not None else ()
                epoch0_dev = self._staging.scalar(epoch0, dtype=np.int32)
                # commit the params carry (see train_round)
                global_params = self._staging.commit(global_params)
                carry_args = self._carry_args(global_params)
                if arms and mode != "span":  # pragma: no cover - slices
                    raise ValueError(  # refused at construction already
                        "arms need level_placement='span'")
                prog = self._superstep_prog(k, per_dev, mode,
                                            eval_mask=eval_mask,
                                            fused_eval=fused_eval,
                                            lr_arg=lr_arg, arms=arms)
        # arms (ISSUE 14): the program takes the stacked [E] per-arm key
        # roots in the base-key slot (fed.core.arm_stream_keys)
        dispatch_key = arm_stream_keys(base_key, aspec.seeds) \
            if aspec is not None else base_key
        with timer.phase("dispatch"):
            out = prog(global_params, *carry_args, dispatch_key, epoch0_dev,
                       *lr_args, sched_dev, *args, *eval_args)
        if self._codec_name != "dense":
            # stash the new error-feedback carry (checkpointed via
            # wire_resid_host / set_wire_resid at superstep boundaries)
            self._resid = out[1]
            out = (out[0],) + out[2:]
        elif self._sched_spec.buffered:
            # stash the new staleness buffer (checkpointed via
            # sched_buf_host / set_sched_buf at superstep boundaries)
            self._sched_buf = out[1]
            out = (out[0],) + out[2:]

        def _split(host):
            """Probe leaves out of a fetched metrics tree (ISSUE 10):
            telemetry-off trees pass through untouched (None probes).  The
            quarantine counter (ISSUE 15) rides as an obs_ probe even
            under telemetry='off'."""
            if self._obs_on or self._quarantine.enabled:
                return split_probes(host, self.mesh.shape["clients"],
                                    layout="span" if mode == "span"
                                    else "flat",
                                    counters=self.global_model.meta.get("counters"))
            return host, None

        def _assemble_train(host):
            rounds = []
            for r in range(k):
                mr = {n: np.zeros(a, np.float32) for n in host}
                for li, (lr_, pos) in enumerate(zip(level_rates, positions[r])):
                    if not len(pos):
                        continue
                    for n in mr:
                        if mode == "span":
                            mr[n][pos] = host[n][r, li, : len(pos)]
                        else:
                            lo = self._slices[lr_][0]
                            mr[n][pos] = host[n][r, lo * per_dev:
                                                 lo * per_dev + len(pos)]
                rounds.append(mr)
            return rounds

        if eval_mask is None:
            new_params, ms = out

            def _assemble(host):
                if arms:
                    # [k, E, L, slots] -> per-arm [k, L, slots], then the
                    # solo reassembly (ISSUE 14; probes refused with arms)
                    return {"arms": [
                        _assemble_train({n: v[:, e] for n, v in host.items()})
                        for e in range(arms)]}
                host, probes = _split(host)
                rounds = _assemble_train(host)
                if probes is not None:
                    return {"train": rounds, "obs": probes}
                return rounds

            return new_params, PendingMetrics(ms, assemble=_assemble)

        new_params, ms, ev = out
        eval_epochs = [epoch0 + r for r, m in enumerate(eval_mask) if m]

        def _assemble_eval(host):
            ms_h, ev_h = host
            if arms:
                return {"arms": [
                    {"train": _assemble_train({n: v[:, e]
                                               for n, v in ms_h.items()}),
                     "eval": fused_eval.assemble(
                         jax.tree_util.tree_map(lambda v: v[:, e], ev_h),
                         eval_epochs)}
                    for e in range(arms)]}
            ms_h, probes = _split(ms_h)
            out_d = {"train": _assemble_train(ms_h),
                     "eval": fused_eval.assemble(ev_h, eval_epochs)}
            if probes is not None:
                out_d["obs"] = probes
            return out_d

        return new_params, PendingMetrics((ms, ev), assemble=_assemble_eval)
