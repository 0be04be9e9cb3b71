"""The federated round engine: one XLA program per communication round.

Replaces the reference's host-side round (sequential per-client training with
deepcopy'd state_dicts, ref train_classifier_fed.py:99-124) with a single
jitted ``shard_map`` over a ``clients`` mesh axis:

  gather client shards -> vmap(local SGD over epochs x batches via lax.scan)
  -> per-client count masks -> ``psum`` counted-average over ICI -> new global

Width heterogeneity (5 rate levels) is runtime data (masks), so one compiled
program serves every rate mix, including dynamic re-rolls (ref fed.py:15-19).
All client datasets stay resident on device; a round moves no host data.
"""

from __future__ import annotations

import math
from functools import partial
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, PartitionSpec as P

from ..chaos import resolve_poison_cfg
from ..compress import make_codec, resid_slots, resolve_codec_cfg
from ..config import resolve_chunk_cfg, resolve_prefetch_depth
from ..multi import resolve_arms_cfg
from ..obs import resolve_quarantine_cfg, resolve_telemetry_cfg, split_probes
from ..obs.hist import round_hists
from ..obs.probes import round_probes
from ..obs.trace import scope, scoped
from ..data.datasets import DATASET_STATS
from ..fed.core import (arm_stream_keys, client_stream_keys, combine_counted,
                        failure_stream_key, round_rates, round_users)
from ..fed.sampling import resolve_sampler_cfg
from ..sched import resolve_schedule_cfg
from ..sched.buffer import _SchedBufCarry, buffered_combine
from ..sched.deadline import deadline_steps
from .ring_attention import ring_attention
from .staging import (ClientStore, CohortStager, PendingMetrics, PhaseTimer,
                      PlacementCache, SlotPacker, StagedCohort)
from ..models.base import ModelDef
from ..models.spec import count_masks as make_count_masks, mask_params, param_mask
from ..ops.augment import augment_cifar, normalize_image
from ..ops.flatspec import FlatSpec
from ..utils.optim import clip_by_global_norm, make_optimizer, make_traced_lr_fn


def _shard_map(f, mesh, in_specs, out_specs):
    return jax.shard_map(f, mesh=mesh, in_specs=in_specs, out_specs=out_specs,
                         check_vma=False)


def _ceil_div(a: int, b: int) -> int:
    return -(-a // b)


def _bucket_pow2(n: int) -> int:
    """Smallest power of two >= n (n >= 1): slot-count bucketing keeps the
    program cache O(log A) instead of one entry per observed count."""
    p = 1
    while p < n:
        p *= 2
    return p


def eval_fused_scan(step, params, xs, epochs, groups, fused_eval, eval_ops,
                    params_of=None):
    """THE eval-fused scan-group walk, shared by both engines' superstep
    programs (parity-critical: the bit-identical-to-host-loop contract
    lives here, so there is exactly one copy).

    Walks the static ``groups`` from :func:`superstep_eval_groups`,
    threading the params carry through per-segment ``lax.scan``s of
    ``step`` and running ``fused_eval.core`` on the rounds where the mask
    fired.  The eval core always runs at the PROGRAM'S top level, never
    inside an outer scan body: XLA compiles a while-loop body differently
    from straight-line code (measured ~1e-7 relative drift on the local
    eval loss reduction), so a repeated group's scan emits a params
    SNAPSHOT per segment end (ys) and the eval phases run unrolled on the
    stacked snapshots -- one train-body trace, one eval trace per eval
    point, n_evals x params of transient snapshot memory.  Returns
    ``(new_carry, train_ms [k, ...], eval_ms [n_evals, ...])``.

    ``params_of`` extracts the params tree from a compound scan carry (the
    wire-codec supersteps carry ``(params, error-feedback residual)``,
    ISSUE 8); None = the carry IS the params tree."""
    if params_of is None:
        params_of = lambda c: c  # noqa: E731
    tree_map = jax.tree_util.tree_map
    p, train_ms, eval_ms, off = params, [], [], 0
    for n, do_eval, c in groups:
        xs_g = tree_map(
            lambda x, o=off, cc=c, nn=n:
                x[o:o + cc * nn].reshape((cc, nn) + x.shape[1:]), xs)
        if c == 1:
            p, ms = jax.lax.scan(step, p, tree_map(lambda x: x[0], xs_g))
            if do_eval:
                ev = fused_eval.core(params_of(p), epochs[off + n - 1],
                                     eval_ops)
                eval_ms.append(tree_map(lambda x: x[None], ev))
        else:
            # c repeats of (n train rounds + eval): only eval-bearing
            # segments group (the trailing train-only run is always a single
            # segment), so every outer step ends on an eval point and
            # snapshots its params
            def seg_body(p, xs_one):
                p, ms = jax.lax.scan(step, p, xs_one)
                return p, (ms, params_of(p))

            p, (ms, snaps) = jax.lax.scan(seg_body, p, xs_g)
            ms = tree_map(lambda x: x.reshape((c * n,) + x.shape[2:]), ms)
            for j in range(c):
                ev = fused_eval.core(
                    tree_map(lambda x, jj=j: x[jj], snaps),
                    epochs[off + (j + 1) * n - 1], eval_ops)
                eval_ms.append(tree_map(lambda x: x[None], ev))
        train_ms.append(ms)
        off += c * n
    ms = train_ms[0] if len(train_ms) == 1 else tree_map(
        lambda *xs_: jnp.concatenate(xs_, 0), *train_ms)
    ev = eval_ms[0] if len(eval_ms) == 1 else tree_map(
        lambda *xs_: jnp.concatenate(xs_, 0), *eval_ms)
    return p, ms, ev


def normalize_eval_mask(eval_mask, k: int, fused_eval):
    """Shared eval-mask validation for both engines' ``train_superstep``:
    returns the static bool tuple, or None when no round evaluates."""
    if eval_mask is None:
        return None
    eval_mask = tuple(bool(m) for m in eval_mask)
    if len(eval_mask) != k:
        raise ValueError(f"eval_mask must have k={k} entries, got "
                         f"{len(eval_mask)}")
    if not any(eval_mask):
        return None
    if fused_eval is None:
        raise ValueError("eval_mask needs a FusedEval (Evaluator.fused) "
                         "carrying the staged eval operands")
    return eval_mask


class _ArmsFusedEval:
    """:class:`~.evaluation.FusedEval` adapter for arms-batched supersteps
    (ISSUE 14): ``core`` runs the inner eval phase vmapped over the leading
    arms axis of the params stack against the SHARED once-committed eval
    operands, so each arm's sBN recalibration + Local/Global eval is the
    solo core's computation on that arm's params; ``out_specs`` grow the
    arms axis behind the eval-stack axis.  Host-side assembly stays the
    inner object's (the engines slice each arm out before assembling)."""

    def __init__(self, inner, count: int, axis=None):
        self._inner = inner
        self.count = count
        self.axis = axis  # 'arms' under the mesh placement, else None
        self.ops = inner.ops
        self.specs = inner.specs

    @property
    def out_specs(self):
        # [n_evals, E, ...]: bn moments and Global sums replicated within
        # an arm (sharded over the arms axis under the mesh placement),
        # the per-user Local sums sharded over clients behind (evals, arms)
        return {"bn": P(None, self.axis),
                "local": P(None, self.axis, "clients"),
                "global": P(None, self.axis)}

    def core(self, params, epoch, ops):
        # the fence sits OUTSIDE the vmap (optimization_barrier has no
        # batching rule): same fusion isolation as the solo core, one
        # fence per eval point
        params, epoch, ops = jax.lax.optimization_barrier(
            (params, epoch, ops))
        out = jax.vmap(
            lambda p: self._inner.core_unfenced(p, epoch, ops))(params)
        return jax.lax.optimization_barrier(out)


def superstep_eval_groups(mask):
    """Compress a static per-round eval mask into ``[(n, do_eval, repeat)]``
    scan groups: ``n`` training rounds followed (``do_eval``) by one fused
    eval phase, the segment repeated ``repeat`` times as an outer scan.

    The mask is STATIC (it keys the compiled superstep program), so the
    program unrolls O(groups) scan segments instead of K round bodies; any
    uniform cadence -- ``eval_interval`` dividing K, equal to K, or a
    multiple of K -- compresses to at most one eval group plus one trailing
    train-only group, and the steady-state mask repeats superstep to
    superstep (no recompiles).  ``sum(n * repeat) == len(mask)``."""
    segs, run = [], 0
    for m in mask:
        run += 1
        if m:
            segs.append((run, True))
            run = 0
    if run:
        segs.append((run, False))
    groups = []
    for seg in segs:
        if groups and groups[-1][0] == seg:
            groups[-1][1] += 1
        else:
            groups.append([seg, 1])
    return [(n, ev, c) for (n, ev), c in groups]


def shard_client_data(mesh: Mesh, data: Tuple[Any, ...]) -> Tuple[jnp.ndarray, ...]:
    """Place per-user data stacks with the user axis sharded over ``clients``.

    Pads the user dimension to a multiple of the ``clients`` axis size (the
    padded users own empty shards and are never sampled), then ``device_put``s
    each array with ``P('clients')`` so every device holds only ``U/n_dev``
    client shards -- device memory scales down with the mesh instead of
    replicating the whole federation's data everywhere (VERDICT r1 item 6).
    Use together with ``cfg['data_placement'] = 'sharded'``.
    """
    from jax.sharding import NamedSharding

    n_dev = mesh.shape["clients"]
    u = int(data[0].shape[0])
    pad = (-u) % n_dev
    out = []
    for arr in data:
        # staticcheck: allow(no-asarray): once-per-experiment staging helper;
        # the commit below is an explicit device_put, not an implicit wrap
        a = np.asarray(arr)
        if pad:
            a = np.concatenate([a, np.zeros((pad,) + a.shape[1:], a.dtype)])
        out.append(jax.device_put(a, NamedSharding(mesh, P("clients"))))
    return tuple(out)


class _WireCodecCarry:
    """Shared wire-codec scaffolding of both round engines (ISSUE 8): the
    lazily-built codec object over the engine's param shapes and the
    device-resident error-feedback residual carry, with its checkpoint
    read/restore pair.  ONE copy on purpose -- the donation policy below is
    a correctness pin, and a fix that lands in only one engine rots.

    Donation policy: codec programs donate ONLY the resid carry.  Donating
    the replicated params carry alongside a params-sized resid output trips
    an XLA:CPU executable-serialization bug (jaxlib 0.4.36): the program
    RELOADED from the persistent compile cache mis-assigns the resid output
    buffer and returns nondeterministic garbage on a stable subset of its
    elements, while fresh compiles are correct (caught by the checkpoint
    round-trip tests on a warm cache -- grouped int8 and masked signsgd).
    Cost: one extra params-size buffer per lossy-codec dispatch, priced
    into the staticcheck HBM budgets and donation-savings accounting.

    Expects on ``self``: ``mesh``, ``_codec_name``, ``_error_feedback``,
    ``_codec_obj``, ``_resid`` (the latter two initialised to None)."""

    def _codec(self, params):
        """The engine's wire codec over these param shapes (None = dense);
        built once over the params' FlatSpec (ops/flatspec.py) --
        for the grouped engine these are the GLOBAL shapes (its fused
        superstep's single psum joins the embedded level partials at global
        shape, the same layout the masked engine compresses)."""
        if self._codec_name == "dense":
            return None
        shapes = {k: tuple(v.shape) for k, v in params.items()}
        if self._codec_obj is None or self._codec_obj.spec.shapes != shapes:
            self._codec_obj = make_codec(self._codec_name, FlatSpec(shapes),
                                         self.mesh.shape["clients"],
                                         self._error_feedback)
        return self._codec_obj

    def _arms_count(self) -> int:
        """E when this engine multiplexes experiment arms (ISSUE 14), else
        0: the EF residual grows a leading arms axis (even at E=1 -- the
        arms programs always carry it) -- each arm owns its own
        compression-error stream, exactly like a solo run's."""
        spec = getattr(self, "_arms_spec", None)
        return spec.count if spec is not None else 0

    def _resid_pspec(self):
        """The residual carry's PartitionSpec: per-device rows over the
        clients axis, behind the arms axis when arms are on (the arms
        axis itself is sharded under the mesh placement)."""
        if not self._arms_count():
            return P("clients")
        return P("arms", "clients") if getattr(self, "_arms_mesh", False) \
            else P(None, "clients")

    def _resid_shape(self, params) -> Tuple[int, ...]:
        e = self._arms_count()
        # under arms the params tree arrives STACKED [E, ...]: the flat
        # layout (and so the residual's trailing dim) is per arm
        shapes = {k: (tuple(v.shape[1:]) if e else tuple(v.shape))
                  for k, v in params.items()}
        base = (self.mesh.shape["clients"], resid_slots(self._codec_name),
                FlatSpec(shapes).total)
        return ((e,) + base) if e else base

    def _ensure_resid(self, params):
        """The committed error-feedback carry (zeros on first use): built by
        a jitted program so the buffer is PRIVATE and donation-safe, sharded
        one row per device over the clients axis."""
        from jax.sharding import NamedSharding

        shape = self._resid_shape(params)
        if self._resid is None or tuple(self._resid.shape) != shape:
            sh = NamedSharding(self.mesh, self._resid_pspec())
            # staticcheck: allow(jit-needs-donation): one-time zeros init
            # (nothing to donate); steady-state rounds donate the carry
            self._resid = jax.jit(
                lambda: jnp.zeros(shape, jnp.float32), out_shardings=sh)()
        return self._resid

    def reset_carries(self) -> None:
        """Drop the device scan carries (EF residual / staleness buffer):
        the rollback path (ISSUE 15) re-seeds them from the restored
        checkpoint blob -- a NaN that reached a carry must not survive the
        recovery.  The next dispatch rebuilds zeros via the lazy
        ``_ensure_*`` paths unless a checkpointed carry is restored
        first."""
        self._resid = None
        self._sched_buf = None

    def wire_resid_host(self):
        """Host copy of the error-feedback residual carry (checkpointing);
        None for the dense codec or before the first compressed round.  On
        a multi-process mesh the carry is sharded over rows other hosts
        own, so this returns THIS process's local blocks as a
        :func:`~..utils.checkpoint.host_shard_blocks` marker -- the sharded
        checkpoint writer persists exactly those rows (ISSUE 17)."""
        if self._resid is None:
            return None
        if not self._resid.is_fully_addressable:
            from ..utils.checkpoint import host_shard_blocks
            return host_shard_blocks(self._resid)
        # staticcheck: allow(no-asarray): checkpoint-boundary D2H fetch
        # (superstep boundaries only), not steady-state round code
        return np.asarray(self._resid)

    def set_wire_resid(self, arr) -> None:
        """Restore the residual carry from a checkpoint (resume): committed
        through a jitted copy so the restored buffer is donation-safe.  A
        shard-blocks marker (multi-process checkpoint) recommits straight
        onto the carry sharding from the merged block set."""
        from jax.sharding import NamedSharding

        from ..utils.checkpoint import dense_from_blocks, is_shard_marker

        sh = NamedSharding(self.mesh, self._resid_pspec())
        if is_shard_marker(arr):
            # merged multi-process blocks -> dense host array: topology-
            # independent (a 2-process checkpoint resumes on 1, and back)
            arr = dense_from_blocks(arr)
        # staticcheck: allow(no-asarray): checkpoint-restore host
        # normalization; the carry reaches the mesh via the explicit
        # commit + jitted private copy below
        host = np.asarray(arr, np.float32)
        from .staging import commit_global
        # staticcheck: allow(jit-needs-donation): one-time restore copy
        # severing host-buffer aliasing; donating its input would free the
        # caller's checkpoint array
        self._resid = jax.jit(lambda t: t + 0, out_shardings=sh)(
            commit_global(host, sh))

    def _carry_args(self, params) -> Tuple:
        """The round/superstep programs' extra donated carry argument: the
        wire-codec EF residual (ISSUE 8) or the buffered-async staleness
        buffer (ISSUE 9, :class:`~..sched.buffer._SchedBufCarry` -- both
        engines mix the two carries in together); empty under dense sync
        lockstep, the zero-new-args contract.  The two carries are mutually
        exclusive (validated at engine construction)."""
        if self._codec_name != "dense":
            return (self._ensure_resid(params),)
        if self._sched_spec.buffered:
            return (self._ensure_sched_buf(params),)
        return ()


class RoundEngine(_WireCodecCarry, _SchedBufCarry):
    """Jitted train/eval/sBN programs for one (model, cfg, mesh) triple.

    Shapes are taken from the arrays passed in; jit re-specialises on new
    shapes automatically (in practice: one compile per experiment).
    """

    def __init__(self, model: ModelDef, cfg: Dict[str, Any], mesh: Optional[Mesh] = None):
        self.model = model
        self.cfg = cfg
        self.mesh = mesh
        self.global_rate = cfg["global_model_rate"]
        ne = cfg["num_epochs"]
        self.local_epochs = ne["local"] if isinstance(ne, dict) else 1
        self.batch_size = cfg["batch_size"]["train"]
        self.is_lm = model.is_lm
        self.bptt = cfg.get("bptt", 64)
        self.norm_stats = cfg.get("norm_stats") or DATASET_STATS.get(cfg["data_name"])
        self.augment = cfg["data_name"].startswith("CIFAR")
        # staticcheck: allow(no-asarray): constructor-time config parse
        self.fix_rates = np.asarray(cfg["model_rate"], np.float32) \
            if cfg["model_split_mode"] == "fix" else None
        self.placement = cfg.get("data_placement", "replicated")
        if self.placement not in ("replicated", "sharded"):
            raise ValueError(f"Not valid data_placement: {self.placement!r}")
        # lax.scan unroll factor for the local-step loop: the round is
        # latency-bound at HeteroFL's shapes (MEASUREMENTS.md), so fewer
        # while-loop trips with more fusion scope per trip can shave per-step
        # overhead; 1 = no unrolling (identical program)
        self.scan_unroll = int(cfg.get("scan_unroll", 1) or 1)
        # cohort chunking (ISSUE 28): slots of a device trained at once;
        # None = all, the one-vmap round every other program is
        self._chunk = resolve_chunk_cfg(cfg)
        self._opt_init, self._opt_update = make_optimizer(cfg)
        # wire codec (ISSUE 8): compress the aggregation payload inside the
        # round program -- quantise -> ONE global psum -> dequantise, with
        # the error-feedback residual as an extra donated carry.  'dense'
        # keeps today's program bit for bit (no new args, no residual).
        self._codec_name, self._error_feedback = resolve_codec_cfg(
            cfg, engine_strategy="masked")
        if isinstance(self._codec_name, dict):
            # per-level maps belong to the grouped engine's fused superstep;
            # this engine may still be CONSTRUCTED (the driver always builds
            # its default-engine slot), so the refusal fires at dispatch
            self._codec_name = "__per-level-map__"
        self._codec_obj = None  # built lazily (needs the param shapes)
        self._resid = None      # device [n_dev, slots, total] EF carry
        # scheduler (ISSUE 9, heterofl_tpu/sched/): availability schedule +
        # deadline stragglers + buffered-async aggregation.  The lockstep
        # default builds byte-identical programs (zero new carry args).
        self._sched_spec = resolve_schedule_cfg(cfg)
        # population sampler (ISSUE 11, heterofl_tpu/fed/sampling.py): the
        # in-jit cohort draw's kind -- 'prp' (O(active) index map, default)
        # or 'perm' (legacy full permutation).  Resolved at construction so
        # a typo'd sampler fails here, and captured by _build_superstep so
        # the compiled draw matches the host schedule stream.
        self._sampler = resolve_sampler_cfg(cfg).kind
        self._sched_buf = None  # device [2, total] staleness carry
        # runtime telemetry (ISSUE 10, heterofl_tpu/obs/): telemetry='on'
        # folds the in-program health probes into the metrics pytree of
        # every round core -- zero new collectives, zero new arguments;
        # 'off' (default) leaves every program bit-identical to pre-obs.
        self._obs_spec = resolve_telemetry_cfg(cfg)
        self._obs_on = self._obs_spec.probes
        # cohort histograms (ISSUE 12): telemetry='hist' folds the fixed-
        # bucket hist rows (obs/hist.py) in next to the scalar probes
        self._obs_hist = self._obs_spec.hist
        # staticcheck: allow(no-float-coercion): constructor-time config
        # parse (the probe level table, a trace-time constant)
        self._obs_levels = sorted({float(r) for r in cfg["model_rate"]},
                                  reverse=True)
        # client-update quarantine (ISSUE 15): a per-slot finiteness (+
        # optional update-norm) gate folded into the sums AND counts
        # before the single psum -- a poisoned client becomes a zero-count
        # participant.  'off' (default) builds bit-identical programs;
        # 'on' is bit-identical whenever every update is clean (the gate
        # multiplies by 1.0 / selects the unchanged value).
        self._quarantine = resolve_quarantine_cfg(cfg)
        # chaos NaN poison (ISSUE 15, heterofl_tpu/chaos/): a trace-time
        # (round, uid) table; None (default) leaves programs untouched
        self._poison = resolve_poison_cfg(cfg)
        if self._sched_spec.buffered and self._codec_name != "dense":
            raise ValueError(
                "schedule aggregation='buffered' cannot combine with a "
                "lossy wire_codec yet: both add a scan carry with its own "
                "donation/checkpoint contract -- pick one per experiment")
        # experiment arms multiplexer (ISSUE 14, heterofl_tpu/multi/): E
        # trace-compatible sweep arms vmapped over a leading axis of the
        # fused superstep -- structural for THIS engine instance (the arms
        # count keys every program), resolved once here.  None = single
        # trajectory, every program byte-identical to pre-arms.
        self._arms_spec = resolve_arms_cfg(cfg)
        if self._arms_spec is not None:
            if self._sched_spec.buffered:
                raise ValueError(
                    "arms cannot combine with schedule aggregation="
                    "'buffered' yet: the staleness buffer is a replicated "
                    "carry with its own donation/checkpoint contract -- "
                    "batch dense-sync arms or run buffered solo")
            if cfg.get("client_store", "eager") == "stream":
                raise ValueError(
                    "arms need client_store='eager': the streaming cohort "
                    "pipeline stages ONE schedule's shards per superstep, "
                    "and per-arm cohorts would multiply the staged bytes "
                    "by E (a ROADMAP follow-on)")
        # arms placement (ISSUE 14): the stacked arms axis is either
        # vmap-batched on every device (the default -- E x per-device
        # work, one dispatch) or laid over a dedicated 'arms' MESH axis
        # (make_mesh(n_arms=E)): each arm's whole federation lives on its
        # own device rows, the per-arm psum reduces within them, and E
        # arms execute CONCURRENTLY -- the mesh-filling layout for a pod
        # (or CPU core pool) a single arm cannot fill.
        self._arms_mesh = mesh is not None and "arms" in mesh.axis_names
        if self._arms_mesh:
            if self._arms_spec is None:
                raise ValueError(
                    "mesh has an 'arms' axis but cfg['arms'] is off: a "
                    "solo program on an arms mesh would silently train an "
                    "independent replica per arm row -- drop the axis or "
                    "set cfg['arms']")
            if mesh.shape["arms"] != self._arms_spec.count:
                raise ValueError(
                    f"mesh arms axis size ({mesh.shape['arms']}) must "
                    f"equal the arms count ({self._arms_spec.count}): one "
                    f"device row group per arm")
        self._train = None
        self._superstep_progs: Dict[Tuple, Any] = {}
        self._lr_fn = None  # built on first superstep (plateau raises there)
        self._sbn = None
        self._eval_users = None
        self._eval_global = None
        # staged placement + cached slot packing (ISSUE 1): the data stacks
        # are committed to the mesh once, the per-round slot arrays reuse
        # preallocated host buffers, and every transfer on the round path is
        # an explicit device_put.  mesh=None engines (the grouped engine's
        # per-level sub-engines) never run train_round and skip staging.
        self._staging = PlacementCache(mesh) if mesh is not None else None
        self._packer = SlotPacker()
        # streaming cohort pipeline (ISSUE 6): built on first stage_cohort;
        # ring depth = cfg['stream_prefetch_depth'] (ISSUE 8 satellite:
        # deeper pipelines once per-superstep compute shrinks on real TPUs)
        self._cohort_stager = None
        self._prefetch_depth = resolve_prefetch_depth(cfg)

    def _reject_per_level_map(self):
        """A per-level wire_codec map (ISSUE 9 satellite) only exists on
        the grouped engine's fused superstep; dispatching the masked engine
        under one is a config error, refused loudly here."""
        if self._codec_name == "__per-level-map__":
            raise ValueError(
                "a per-level wire_codec map needs the grouped strategy "
                "(its fused superstep owns per-level payloads); the masked "
                "engine has no levels to assign codecs to")

    # ------------------------------------------------------------------
    # per-client local training (pure; vmapped across clients)
    # ------------------------------------------------------------------

    @scoped("augment")
    def _prep_vision_batch(self, x_u8, w, key, train=True):
        if self.augment and train:
            x_u8 = augment_cifar(key, x_u8)
        if self.norm_stats is not None:
            img = normalize_image(x_u8, *self.norm_stats)
        else:
            img = x_u8.astype(jnp.float32)
        return img

    def _grad_masks(self, shapes, wr):
        """Per-param width-activity masks for the gradient epilogue.

        Loop-INVARIANT: they depend only on (shape, spec, wr), all fixed for
        one client's whole local run, so :meth:`_local_setup` builds them
        OUT of the ``lax.scan`` step body -- the seed program re-materialised
        every mask (iota + compare + broadcast per sliced axis per leaf) 250
        times per round, and left it to each compiler to move them out."""
        model = self.model
        return {k: param_mask(shape, model.specs[k], model.groups, wr)
                for k, shape in shapes.items()}

    def _local_setup(self, p, wr):
        """(scan-carry params, optimizer state, hoisted grad masks) for one
        client's local run.

        The scan carries the parameter and momentum TREES: each leaf in the
        layout the model reads it in and its gradient arrives in, so the
        step's update runs per leaf in place (on the v5e a flat carry's
        flatten / pack / unpack / unflatten were 60.0 of 78.6 ms a ResNet-18
        step and 107.9 of 129.4 ms an LM step: ledger, PR 26; gone on the
        chip with PR 27, everywhere with PR 30)."""
        masks = self._grad_masks({k: v.shape for k, v in p.items()}, wr)
        return p, self._opt_init(p), masks

    @scoped("step/update")
    def _apply_update(self, p, grads, opt, masks, n_glob, lr, has=None):
        """The per-step optimizer epilogue, per leaf: mean-normalise +
        width-mask + global-norm clip + optimizer update (+ ``has`` gating
        for all-padding batches and steps past a deadline)."""
        grads = {k: g / jnp.maximum(n_glob, 1e-6) for k, g in grads.items()}
        grads = {k: g * masks[k] for k, g in grads.items()}
        grads, _ = clip_by_global_norm(grads, 1.0)
        p_new, opt_new = self._opt_update(p, grads, opt, lr)
        if has is not None:
            # all-padding batch: skip the step entirely (no wd/momentum drift)
            p_new = jax.tree_util.tree_map(
                lambda a, b: jnp.where(has, a, b), p_new, p)
            opt_new = jax.tree_util.tree_map(
                lambda a, b: jnp.where(has, a, b), opt_new, opt)
        return p_new, opt_new

    def _local_train_vision(self, params, wr, x, y, sm, lm, key, lr, scaler_rate=None,
                            data_axis=None, n_data: int = 1, step_limit=None):
        """Local SGD for one client.

        ``data_axis``/``n_data``: intra-client batch data-parallelism -- each
        device on that mesh axis processes ``B/n_data`` of every batch,
        gradients/metrics are ``psum``-ed and BN runs synchronised, so the
        result is numerically identical to single-device execution (modulo
        augmentation RNG).  Callers outside ``shard_map`` pass ``None``.

        ``step_limit`` (ISSUE 9 deadline): this client's local-step budget
        (traced int32); steps at index >= the budget gate off the optimizer
        update AND their metric contributions -- truncated training, pure
        in-scan arithmetic.  ``None`` (the lockstep default) leaves the
        step body byte-identical to the pre-scheduler program.
        """
        model, B, E = self.model, self.batch_size, self.local_epochs
        N = x.shape[0]
        S = _ceil_div(N, B)
        SB = S * B
        sr = wr if scaler_rate is None else scaler_rate
        p = mask_params(params, model.specs, model.groups, wr)
        p, opt, emasks = self._local_setup(p, wr)
        ekeys = jax.random.split(jax.random.fold_in(key, 1), E)
        # Shuffle, then stable-sort the *real* samples (sm==1) to the front:
        # batches are dense like the reference's DataLoader over the true
        # shard, trailing all-padding batches carry zero weight and their
        # optimizer step is skipped below -- exact ceil(sz/B) step parity
        # for shards smaller than the stacked maximum.
        def epoch_perm(k):
            perm = jax.random.permutation(k, N)
            order = jnp.argsort(-sm[perm], stable=True)
            return perm[order]

        perms = jax.vmap(epoch_perm)(ekeys)  # [E, N]
        if SB > N:
            reps = _ceil_div(SB, N)
            perms = jnp.tile(perms, (1, reps))[:, :SB]
            wpad = jnp.concatenate([jnp.ones(N, jnp.float32), jnp.zeros(SB - N, jnp.float32)])
        else:
            wpad = jnp.ones(SB, jnp.float32)

        b_loc = _ceil_div(B, n_data)
        bp = b_loc * n_data

        def step(carry, t):
            p, opt, acc = carry
            with scope("step/batch"):
                e, s = t // S, t % S
                ids = jax.lax.dynamic_slice(perms, (e, s * B), (1, B))[0]
                w = jax.lax.dynamic_slice(wpad, (s * B,), (B,)) * sm[ids]
                has = (jnp.sum(w) > 0)  # global batch weight BEFORE any sharding
                n_glob = jnp.sum(w)
                live = None
                if step_limit is not None:
                    # deadline straggler (ISSUE 9): steps past this client's
                    # budget are no-ops -- update skipped, metrics zeroed
                    live = t < step_limit
                    has = jnp.logical_and(has, live)
                aug_key = jax.random.fold_in(key, 2 + t)
                if data_axis is not None and n_data > 1:
                    # this device's slice of the client's batch, with the
                    # augmentation key decorrelated across slices
                    d = jax.lax.axis_index(data_axis)
                    ids = jnp.concatenate([ids, ids[: bp - B]]) if bp > B else ids
                    w = jnp.concatenate([w, jnp.zeros(bp - B, jnp.float32)]) if bp > B else w
                    ids = jax.lax.dynamic_slice(ids, (d * b_loc,), (b_loc,))
                    w = jax.lax.dynamic_slice(w, (d * b_loc,), (b_loc,))
                    aug_key = jax.random.fold_in(aug_key, d)
                img = self._prep_vision_batch(x[ids], w, aug_key)
                batch = {"img": img, "label": y[ids]}

            def loss_fn(pt):
                # entered INSIDE the differentiated function: the forward
                # is jvp(step/model), the backward transpose(jvp(step/model))
                with scope("step/model"):
                    out, _ = model.apply(pt, batch, train=True, width_rate=wr, scaler_rate=sr,
                                         label_mask=lm, sample_weight=w,
                                         rng=jax.random.fold_in(key, 5000 + t),
                                         bn_axis=data_axis if n_data > 1 else None)
                n_loc = jnp.sum(w)
                # weighted-SUM form so cross-device reduction recovers the
                # exact full-batch mean gradient
                return out["loss"] * n_loc, out["score"]

            (lsum, score), grads = jax.value_and_grad(loss_fn, has_aux=True)(p)
            correct = jnp.sum((jnp.argmax(score, -1) == y[ids]) * w)
            if data_axis is not None and n_data > 1:
                grads, lsum, correct = jax.lax.psum((grads, lsum, correct), data_axis)
            p, opt = self._apply_update(p, grads, opt, emasks, n_glob, lr,
                                        has=has)
            if live is not None:
                g = live.astype(jnp.float32)
                lsum, correct, n_glob = lsum * g, correct * g, n_glob * g
            acc = (acc[0] + lsum, acc[1] + correct, acc[2] + n_glob)
            return (p, opt, acc), None

        acc0 = (jnp.zeros(()), jnp.zeros(()), jnp.zeros(()))
        (p, _, acc), _ = jax.lax.scan(step, (p, opt, acc0), jnp.arange(E * S),
                                      unroll=self.scan_unroll)
        return p, {"loss_sum": acc[0], "score_sum": acc[1], "n": acc[2]}

    def _local_train_lm(self, params, wr, rows, lm, key, lr, scaler_rate=None,
                        data_axis=None, n_data: int = 1, step_limit=None):
        """Local SGD on one client's token rows.

        ``step_limit`` (ISSUE 9 deadline): per-client local-step budget --
        same truncation semantics as :meth:`_local_train_vision` (None =
        byte-identical lockstep body).

        ``data_axis``/``n_data``: sequence parallelism -- each device on that
        mesh axis holds ``bptt/n_data`` positions of every window, attention
        runs as exact ring attention over the axis (ppermute neighbour
        exchanges), and gradients are ``psum``-ed, so the result matches
        single-device execution up to float association (token corruption is
        drawn shard-invariantly; dropout shards are decorrelated by design).
        """
        model, E, bptt = self.model, self.local_epochs, self.bptt
        R, T = rows.shape
        S = _ceil_div(T, bptt)
        pad = S * bptt - T
        sr = wr if scaler_rate is None else scaler_rate
        rows_p = jnp.pad(rows, ((0, 0), (0, pad)))
        wpos = jnp.pad(jnp.ones((R, T), jnp.float32), ((0, 0), (0, pad)))
        p = mask_params(params, model.specs, model.groups, wr)
        p, opt, emasks = self._local_setup(p, wr)

        seq_sharded = data_axis is not None and n_data > 1
        if seq_sharded:
            if bptt % n_data:
                raise ValueError(f"data axis size ({n_data}) must divide bptt={bptt} "
                                 f"for sequence-parallel LM rounds")
            s_loc = bptt // n_data
            attn = partial(ring_attention, axis_name=data_axis, axis_size=n_data)

        def step(carry, t):
            p, opt, acc = carry
            with scope("step/batch"):
                s = t % S
                lab = jax.lax.dynamic_slice(rows_p, (0, s * bptt), (R, bptt))
                w = jax.lax.dynamic_slice(wpos, (0, s * bptt), (R, bptt))
                batch = {"label": lab}
                extra = {}
                if seq_sharded:
                    d = jax.lax.axis_index(data_axis)
                    off = d * s_loc
                    lab = jax.lax.dynamic_slice(lab, (0, off), (R, s_loc))
                    w = jax.lax.dynamic_slice(w, (0, off), (R, s_loc))
                    batch = {"label": lab, "pos_offset": off, "seq_full": bptt}
                    extra = {"attn_override": lambda q, k, v, temp: attn(q, k, v, temperature=temp)}

            def loss_fn(pt):
                # inside the differentiated function (see _local_train_vision)
                with scope("step/model"):
                    out, _ = model.apply(pt, batch, train=True, width_rate=wr,
                                         scaler_rate=sr, label_mask=lm, sample_weight=w,
                                         rng=jax.random.fold_in(key, 5000 + t), **extra)
                # weighted-SUM form so the cross-shard reduction recovers the
                # exact full-window mean gradient
                n_loc = jnp.sum(w)
                return out["loss"] * n_loc, \
                    (n_loc, out.get("counters") if counted else None)

            (lsum, (n_loc, ctr)), grads = jax.value_and_grad(loss_fn, has_aux=True)(p)
            if seq_sharded:
                grads, lsum, n_glob = jax.lax.psum((grads, lsum, n_loc), data_axis)
            else:
                n_glob = n_loc
            loss = lsum / jnp.maximum(n_glob, 1e-6)
            live = None if step_limit is None else (t < step_limit)
            p, opt = self._apply_update(p, grads, opt, emasks, n_glob, lr,
                                        has=live)
            # Logger weight: rows per window (ref train_transformer_fed.py
            # appends with input['label'].size(0)); Perplexity = exp(window CE).
            n = np.float32(R)  # static trace-time constant, not a device wrap
            if live is not None:
                n = n * live.astype(jnp.float32)  # deadline: truncated steps
            acc = (acc[0] + loss * n, acc[1] + jnp.exp(loss) * n, acc[2] + n) + acc[3:]
            if counted:
                # the model's own counters (an expert layer's tokens per held
                # expert, ...): summed over the steps that ran
                g = 1.0 if live is None else live.astype(jnp.float32)
                acc = acc[:3] + ({k: acc[3][k] + ctr[k] * g for k in ctr},)
            return (p, opt, acc), None

        # counters a model declares (meta['counters']: name -> (shape, fold)) ride the
        # metrics as obs_ probes when telemetry is on; otherwise the program
        # is the one without them
        counted = self._obs_on and bool(model.meta.get("counters"))
        acc0 = (jnp.zeros(()), jnp.zeros(()), jnp.zeros(()))
        if counted:
            acc0 += ({k: jnp.zeros(shape, jnp.float32)
                      for k, (shape, _) in model.meta["counters"].items()},)
        (p, _, acc), _ = jax.lax.scan(step, (p, opt, acc0), jnp.arange(E * S),
                                      unroll=self.scan_unroll)
        ms = {"loss_sum": acc[0], "score_sum": acc[1], "n": acc[2]}
        if counted:
            ms.update({"obs_" + k: v for k, v in acc[3].items()})
        return p, ms

    # ------------------------------------------------------------------
    # the round program
    # ------------------------------------------------------------------

    def _round_core(self, params, key, lr, user_loc, user_glob, data,
                    resid=None, sched_buf=None, epoch=None):
        """One round's in-jit core, per device (runs inside ``shard_map``):
        slot training + counted-average ``psum``.  Shared by the one-round
        program (:meth:`_build_train`) and the K-round superstep scan
        (:meth:`_build_superstep`), so the two paths are the same
        computation by construction.

        ``user_loc``: this device's slot of active users as indices into its
        local view of the per-user data stacks (== ``user_glob`` under
        replicated placement), or ``None`` when the data stacks are already
        in slot order (the streaming cohort path: slot j's data IS row j, no
        gather); ``user_glob``: the users' global ids, used for all
        per-client randomness so results are placement- and
        mesh-shape-invariant.  -1 = padding slot.  ``data`` carries the
        fix-rates table as its last element in fix mode.  ``resid``: this
        device's ``[slots, total]`` error-feedback carry (lossy wire codecs
        only; None under dense).  ``sched_buf``: the replicated ``[2,
        total]`` staleness carry (buffered-async aggregation only, ISSUE 9;
        the previous round's reduced sums/counts apply here one round late
        while this cohort's reduction is buffered for the next).  Returns
        ``(new_params, metric sums, new_resid-or-None,
        new_sched_buf-or-None)``."""
        model, cfg, mesh = self.model, self.cfg, self.mesh
        dynamic = cfg["model_split_mode"] == "dynamic"
        # staticcheck: allow(no-float-coercion): trace-time config scalar
        failure_rate = float(cfg.get("client_failure_rate", 0.0) or 0.0)
        valid = (user_glob >= 0).astype(jnp.float32)
        ugid = jnp.maximum(user_glob, 0)
        if failure_rate > 0.0:
            # net-new fault injection (the reference only models dropout
            # implicitly via frac-sampling): a failed client trains but
            # its update never reaches aggregation -- like a crash after
            # local work. All-failed rounds degrade to the stale rule.
            fkey = failure_stream_key(key)
            alive = 1.0 - jax.vmap(
                lambda u: jax.random.bernoulli(jax.random.fold_in(fkey, u), failure_rate)
            )(ugid).astype(jnp.float32)
            valid = valid * alive
        with scope("round/gather"):
            uidx = None if user_loc is None else jnp.maximum(user_loc, 0)
            if dynamic:
                # the shared per-round rate stream (fed.core.round_rates):
                # re-roll ALL users, index the active ones (ref fed.py:15-24)
                rates_abs = round_rates(key, cfg, ugid)
            else:
                rates_abs = data[-1][ugid]  # fix_rates passed as last data arg
            wr = rates_abs / self.global_rate
            slot_keys = client_stream_keys(key, ugid)

        n_data = mesh.shape["data"]
        limits = None
        if self.is_lm:
            all_rows, all_lm = data[0], data[1]
            with scope("round/gather"):
                rows = all_rows if uidx is None else all_rows[uidx]
                lm = all_lm if uidx is None else all_lm[uidx]
            sdata = (rows, lm)
            total_steps = self.local_epochs * _ceil_div(
                int(rows.shape[-1]), self.bptt)
        else:
            all_x, all_y, all_m, all_lm = data[0], data[1], data[2], data[3]
            if uidx is None:
                xs, ys, sms, lm = all_x, all_y, all_m, all_lm
            else:
                with scope("round/gather"):
                    xs, ys, sms, lm = all_x[uidx], all_y[uidx], all_m[uidx], all_lm[uidx]
            sdata = (xs, ys, sms, lm)
            total_steps = self.local_epochs * _ceil_div(
                int(xs.shape[1]), self.batch_size)
        if self._sched_spec.has_deadline:
            # deadline stragglers (ISSUE 9): per-client step budgets
            # from the shared (round key, uid) stream -- the grouped
            # engine draws the identical budgets in _level_core
            limits = deadline_steps(key, ugid, total_steps,
                                    self._sched_spec.deadline_min_frac)
        if self._poison is not None and epoch is None:
            raise ValueError(
                "chaos_poison needs the round epoch threaded into the "
                "round core (pass epoch= to train_round)")
        slots = int(user_glob.shape[0])
        chunk = self._chunk_of(slots)
        per_slot = (wr, valid, user_glob, slot_keys, limits) + sdata
        if chunk == slots:
            summed, counts, ms, ok = self._train_slots(
                params, lr, epoch, n_data, *per_slot)
        else:
            # chunked cohort (ISSUE 28): `chunk` slots train at a time; the
            # scan carries the aggregate's sums and counts, so the round
            # holds `chunk` (not `slots`) copies of model, momentum and
            # gradients.  The per-slot streams and the one psum below are
            # the unchunked round's.
            def chunk_body(summed, sl):
                with scope("round/chunk"):
                    s_, _, ms_, ok_ = self._train_slots(
                        params, lr, epoch, n_data, *sl, one=(chunk == 1),
                        want_counts=False)
                return jax.tree_util.tree_map(jnp.add, summed, s_), (ms_, ok_)

            zeros = {k: jnp.zeros(v.shape, v.dtype) for k, v in params.items()}
            summed, (ms, ok) = jax.lax.scan(
                chunk_body, zeros, jax.tree_util.tree_map(
                    lambda x: x.reshape((slots // chunk, chunk) + x.shape[1:]),
                    per_slot))
            ms, ok = jax.tree_util.tree_map(
                lambda x: x.reshape((slots,) + x.shape[2:]), (ms, ok))
            # the counts need no training result (a slot's level, labels and
            # gates give them), so they are not carried beside the sums
            # through the training loop: a loop of their own, slot by slot,
            # after it, when no client's model, momentum or gradients live
            gate = valid if ok is None else valid * ok.astype(jnp.float32)
            shapes = {k: v.shape for k, v in params.items()}

            def count_body(counts, sl):
                w_, l_, g_ = sl
                with scope("round/aggregate"):
                    cm = make_count_masks(shapes, model.specs, model.groups, w_, l_)
                    return {k: counts[k] + cm[k] * g_ for k in counts}, None

            counts, _ = jax.lax.scan(count_body, zeros, (wr, sdata[-1], gate))
        # a model's own counters (obs_ leaves of the per-slot metrics): this
        # device's sum over its valid slots, finished on the host like every
        # per-device partial (obs.split_probes)
        counters = {k: ms.pop(k) for k in [k for k in ms if k.startswith("obs_")]}
        with scope("round/aggregate"):
            codec = self._codec(params)
            if codec is None:
                # ONE psum bind for sums+counts: the round's single global
                # collective (per-leaf addends are identical to two separate
                # psums, so this is bit-compatible; staticcheck audits the
                # exactly-one-psum budget)
                with scope("psum"):
                    summed, counts = jax.lax.psum((summed, counts), "clients")
                new_resid = None
            else:
                # wire codec (ISSUE 8): quantise this device's partial -> the
                # SAME single psum bind carries the packed payload -> dequantise;
                # the error-feedback residual re-injects the compression error
                # next round.  cmax = this device's slot count (it bounds the
                # partial-sum magnitude, sizing the shared quantisation grid).
                from ..compress.codecs import compressed_psum

                with scope("psum"):
                    summed, counts, new_resid = compressed_psum(
                        codec, "clients", params, summed, counts, resid, key,
                        int(user_glob.shape[0]))
            if self._sched_spec.buffered:
                # buffered-async aggregation (ISSUE 9): this cohort's reduction
                # lands NEXT round (staleness-weighted); the previous round's
                # buffered update applies now.  The single-psum wire contract
                # is untouched -- buffering happens after the reduction.
                new_params, new_buf = buffered_combine(
                    params, sched_buf, summed, counts, FlatSpec.of(params),
                    self._sched_spec.staleness)
            else:
                new_params = combine_counted(params, summed, counts)
                new_buf = None
        if ok is not None:
            # a quarantined client's metric sums may themselves be NaN
            # (its training diverged): select-sanitise, then mask like a
            # failed client -- its rate zeroes too, so the participation
            # probe and the ledger see a zero-count participant.  The
            # clean path (ok all-True) selects the unchanged values.
            okf = ok.astype(jnp.float32)
            ms = {k: jnp.where(ok, v, jnp.zeros((), v.dtype)) * valid
                  for k, v in ms.items()}
            ms["rate"] = rates_abs * valid * okf
            ms["obs_quarantine"] = jnp.reshape(
                jnp.sum(valid * (1.0 - okf)), (1,))
        else:
            ms = {k: v * valid for k, v in ms.items()}
            ms["rate"] = rates_abs * valid
        for k, v in counters.items():
            gate = valid if ok is None else valid * ok.astype(jnp.float32)
            ms[k] = jnp.sum(v * gate.reshape((-1,) + (1,) * (v.ndim - 1)), axis=0)
        if self._obs_on:
            # in-program health probes (ISSUE 10): derived from the
            # already-reduced aggregates and the replicated carries --
            # ZERO new collectives (the staticcheck telemetry variants pin
            # the same one-psum budget and the same wire bytes); per-device
            # partials ride the metrics out-spec and finish on the host
            ms = {**ms, **round_probes(self._obs_levels, params, new_params,
                                       summed, counts, ms["rate"],
                                       resid=new_resid, sched_buf=new_buf)}
            if self._obs_hist:
                # cohort histograms (ISSUE 12): fixed-bucket rows over the
                # per-slot metric sums this device already holds -- same
                # zero-collective contract as the scalar probes, same
                # metrics out-spec ride to the host.  total_steps is THE
                # denominator the deadline branches above budgeted against
                # (defined exactly when has_deadline).
                ms = {**ms, **round_hists(
                    self._obs_levels, ms["rate"], ms["loss_sum"], ms["n"],
                    key=key, uids=ugid,
                    total_steps=(total_steps
                                 if self._sched_spec.has_deadline else None),
                    min_frac=(self._sched_spec.deadline_min_frac
                              if self._sched_spec.has_deadline else None),
                    sched_buf=new_buf)}
        return new_params, ms, new_resid, new_buf

    def _chunk_of(self, slots: int) -> int:
        """Slots of a device that train at once: ``cfg['round_chunk']`` (None
        = all of them).  It must divide the slots a device holds."""
        chunk = self._chunk
        if chunk is None or chunk >= slots:
            return slots
        if slots % chunk:
            raise ValueError(
                f"round_chunk={chunk} does not divide the {slots} slots a "
                f"device holds this round")
        return chunk

    def _train_slots(self, params, lr, epoch, n_data, wr, valid, user_glob,
                     slot_keys, limits, *sdata, one: bool = False,
                     want_counts: bool = True):
        """Local training and the aggregate's partial sums over a set of
        slots (all of a device's, or one chunk of them): ``(summed, counts,
        per-slot metric sums, quarantine gate or None)``.  ``sdata``: the
        slots' gathered data, ``(rows, lm)`` or ``(x, y, sample mask, lm)``;
        ``limits``: their deadline step budgets or None.  ``one``: a chunk
        of ONE slot runs without the client vmap (under which a
        ``lax.cond`` computes both branches)."""
        model = self.model
        lm = sdata[-1]
        data_axis = "data" if n_data > 1 else None
        if one:
            def vmap(f):
                take = lambda t: jax.tree_util.tree_map(lambda x: x[0], t)  # noqa: E731
                return lambda *a: jax.tree_util.tree_map(
                    lambda x: x[None], f(*take(a)))
        else:
            vmap = jax.vmap
        # limits None (no deadline) maps to step_limit=None: the lockstep body
        train = self._local_train_lm if self.is_lm else self._local_train_vision
        with scope("round/local_train"):
            trained, ms = vmap(
                lambda w_, k_, lim_, *d: train(
                    params, w_, *d, k_, lr, data_axis=data_axis, n_data=n_data,
                    step_limit=lim_)
            )(wr, slot_keys, limits, *sdata)

        if self._poison is not None:
            # chaos NaN poison (ISSUE 15): the matched (round, uid) slots'
            # updates go non-finite BEFORE aggregation -- the adversarial-
            # client model the quarantine gate / watchdog rollback recover
            # from.  Padding slots (uid -1) never match.
            from ..chaos.inject import poison_updates

            trained = poison_updates(trained, self._poison, epoch, user_glob)
        shapes = {k: v.shape for k, v in params.items()}
        with scope("round/aggregate"):
            cms = jax.vmap(lambda w_, l_, v_: jax.tree_util.tree_map(
                lambda m: m * v_, make_count_masks(shapes, model.specs, model.groups, w_, l_)))(
                wr, lm, valid)
        ok = None
        if self._quarantine.enabled:
            # client-update quarantine (ISSUE 15 tentpole): the gate folds
            # into BOTH the sums and the counts BEFORE the single global
            # psum -- a quarantined client is a zero-count
            # participant, and the where-select sanitises its (possibly
            # NaN) trained values so NaN * 0-count cannot poison the sum.
            # All-clean rounds are bit-identical: the gate multiplies by
            # 1.0 and the select returns the unchanged value.
            from ..obs.probes import quarantine_gate

            ok = quarantine_gate(trained, params, cms,
                                 self._quarantine.max_norm)
            okf = ok.astype(jnp.float32)
            cms = {k: cms[k] * okf.reshape((-1,) + (1,) * (cms[k].ndim - 1))
                   for k in cms}
            trained = {k: jnp.where(ok.reshape((-1,) + (1,) * (v.ndim - 1)),
                                    v, jnp.zeros((), v.dtype))
                       for k, v in trained.items()}
        with scope("round/aggregate"):
            summed = {k: jnp.sum(trained[k] * cms[k], axis=0) for k in params}
            counts = {k: jnp.sum(cms[k], axis=0) for k in params} \
                if want_counts else None
        return summed, counts, ms, ok

    def _data_specs(self) -> Tuple[P, ...]:
        """shard_map in_specs of the ``data`` tuple (incl. the fix-rates
        tail): per-user stacks are device-sharded under ``sharded``
        placement, replicated otherwise."""
        per_user = P("clients") if self.placement == "sharded" else P()
        if self.is_lm:
            data_specs = (per_user, per_user)
        else:
            data_specs = (per_user, per_user, per_user, per_user)
        if self.fix_rates is not None:
            data_specs = data_specs + (P(),)
        return data_specs

    def _ep_kw(self, ep) -> dict:
        """The round core's ``epoch=`` kwarg, present ONLY when a chaos
        poison table is configured: unpoisoned calls keep the exact
        pre-ISSUE-15 signature (the staticcheck/wirecheck seeded-detector
        tests monkeypatch ``_round_core`` with epoch-free stubs)."""
        return {} if self._poison is None else {"epoch": ep}

    def _build_train(self):
        # chaos poison (ISSUE 15): the K=1 program takes the round epoch
        # as one extra replicated scalar ONLY when a poison table is
        # configured -- unpoisoned programs keep their exact argument list
        poisoned = self._poison is not None
        ep_args = (P(),) if poisoned else ()

        def _split_ep(extra):
            return (extra[0] if poisoned else None), \
                (extra[1:] if poisoned else extra)

        if self._codec_name != "dense":
            # compressed round (ISSUE 8): the EF residual is an extra
            # donated carry -- [1, slots, total] per device in, same out
            def body(params, resid, key, lr, *rest):
                ep, rest = _split_ep(rest)
                user_loc, user_glob, *data = rest
                p, ms, r, _ = self._round_core(params, key, lr, user_loc,
                                               user_glob, data,
                                               resid=resid[0],
                                               **self._ep_kw(ep))
                return p, r[None], ms

            fn = _shard_map(
                body, self.mesh,
                in_specs=(P(), P("clients"), P(), P()) + ep_args
                + (P("clients"), P("clients")) + self._data_specs(),
                out_specs=(P(), P("clients"), P("clients")),
            )
            # resid-only donation: donating the params carry alongside the
            # params-sized resid trips the XLA:CPU executable-serialization
            # bug (see _WireCodecCarry) -- both engines pin the same policy
            return jax.jit(fn, donate_argnums=(1,))

        if self._sched_spec.buffered:
            # buffered-async round (ISSUE 9): the staleness buffer is an
            # extra donated carry -- replicated [2, total] in, same out
            def body(params, buf, key, lr, *rest):
                ep, rest = _split_ep(rest)
                user_loc, user_glob, *data = rest
                p, ms, _, nb = self._round_core(params, key, lr, user_loc,
                                                user_glob, data,
                                                sched_buf=buf,
                                                **self._ep_kw(ep))
                return p, nb, ms

            fn = _shard_map(
                body, self.mesh,
                in_specs=(P(), P(), P(), P()) + ep_args
                + (P("clients"), P("clients")) + self._data_specs(),
                out_specs=(P(), P(), P("clients")),
            )
            # buf-only donation: donating the params carry alongside a
            # params-sized buffer output is the trigger pattern of the
            # XLA:CPU executable-serialization bug (see _WireCodecCarry /
            # _SchedBufCarry) -- same policy as the codec programs
            return jax.jit(fn, donate_argnums=(1,))

        def body(params, key, lr, *rest):
            ep, rest = _split_ep(rest)
            user_loc, user_glob, *data = rest
            p, ms, _, _ = self._round_core(params, key, lr, user_loc,
                                           user_glob, data,
                                           **self._ep_kw(ep))
            return p, ms

        fn = _shard_map(
            body, self.mesh,
            in_specs=(P(), P(), P()) + ep_args
            + (P("clients"), P("clients")) + self._data_specs(),
            out_specs=(P(), P("clients")),
        )
        if self._chunk is not None:
            # chunked cohort: the global parameters are read by every chunk
            # and once more by the counted average, so an output that must
            # live in their (donated) buffer cannot also be the buffer the
            # sums grow in, and the chip's compiler keeps one more
            # parameter-sized temporary: 14.9 GB of temporaries donated
            # against 11.8 + 1.7 of output for the 425 M-parameter cell
            # (compiled for a described v5e, PR 28).
            # staticcheck: allow(jit-needs-donation): measured, see above
            return jax.jit(fn)
        return jax.jit(fn, donate_argnums=(0,))

    def _build_superstep(self, k: int, per_dev: int, in_jit: bool,
                         num_active: int = 0, eval_mask=None, fused_eval=None,
                         lr_arg: bool = False, streaming: bool = False,
                         arms: int = 0):
        """One jitted+donated program for ``k`` federated rounds: the round
        boundary leaves the host (ISSUE 2 tentpole).

        A ``lax.scan`` INSIDE the ``shard_map`` carries ``(params)`` across
        rounds; per-round keys are ``fold_in(base_key, epoch)``, the LR
        schedule is evaluated in-jit from the round index
        (:func:`~..utils.optim.make_traced_lr_fn`), and with ``in_jit``
        sampling (replicated placement) the active clients are drawn from
        :func:`~..fed.core.round_users` inside the scan -- a steady-state
        superstep moves no slot ids at all.  ``in_jit=False`` takes a
        host-packed ``[k, slots]`` schedule as scan xs (sharded placement:
        slot->owner packing is placement bookkeeping).  Per-round per-slot
        metric sums come back stacked ``[k, slots]`` -- one fetch per
        superstep.

        ``eval_mask`` (ISSUE 4 tentpole): a static k-tuple of bools; on
        rounds where it fires, the :class:`~.evaluation.FusedEval` core --
        sBN recalibration + Local/Global eval -- runs INSIDE this program on
        the pre-staged eval operands (appended to the argument list with
        ``fused_eval.specs``), and the eval results come back stacked over
        the superstep's eval points.  The mask compresses to scan groups
        (:func:`superstep_eval_groups`), so ``eval_interval=1`` is one
        (round + eval) scan of length k, not k unrolled blocks.
        ``lr_arg=True`` takes the LR as a staged scalar argument instead of
        the traced schedule (ReduceLROnPlateau: LR is constant within a
        superstep, stepped on eval metrics at superstep boundaries).

        ``streaming=True`` (ISSUE 6): the per-user data stacks are NOT a
        program invariant -- the sampled cohort's shards ride the scan xs as
        ``[k, slots, ...]`` stacks sharded over the slot axis (one slot = one
        device-local cohort row, so the round core indexes identity), and
        only the tiny fix-rates table stays invariant.  Program memory is
        O(k x active_clients), independent of the population."""
        mesh = self.mesh
        n_dev = mesh.shape["clients"]
        slots_total = per_dev * n_dev
        num_users = self.cfg["num_users"]
        lr_fn = self._lr_fn
        sampler = self._sampler  # the in-jit draw's kind (ISSUE 11)
        if streaming:
            n_stream = 2 if self.is_lm else 4
            n_fix = 1 if self.fix_rates is not None else 0
            data_specs = (P(None, "clients"),) * n_stream + (P(),) * n_fix
            sched_specs = (P(None, "clients"),)
        else:
            data_specs = self._data_specs()
            n_data_args = len(data_specs)
            sched_specs = () if in_jit else (P(None, "clients"), P(None, "clients"))
        groups = superstep_eval_groups(eval_mask) if eval_mask else None
        if groups is not None and not any(ev for _, ev, _ in groups):
            groups = None  # an all-False mask is the plain train superstep
        codec = self._codec_name != "dense"
        buffered = self._sched_spec.buffered
        arms_axis = "arms" if (arms and self._arms_mesh) else None
        if groups is not None and arms:
            # arms multiplexer (ISSUE 14): the fused eval phase runs vmapped
            # over the (local) arms axis against the shared committed
            # operands -- one arm per device row under the mesh placement
            fused_eval = _ArmsFusedEval(fused_eval, arms, axis=arms_axis)
        # in-jit availability sampling (ISSUE 9): only the eager replicated
        # path samples inside the scan -- a non-uniform schedule threads its
        # [T, U] trace in as a replicated program argument there; every
        # host-schedule path (sharded/streaming/grouped) consumes the trace
        # through fed.core.superstep_user_schedule instead
        trace_arg = bool(in_jit and not streaming
                         and self._sched_spec.kind != "uniform")

        def sbody(params, *all_rest):
            if codec:
                # wire codec (ISSUE 8): the EF residual joins the scan carry
                resid0, base_key, epoch0, *rest = all_rest
            elif buffered:
                # buffered-async aggregation (ISSUE 9): the staleness buffer
                # joins the scan carry
                buf0, base_key, epoch0, *rest = all_rest
            else:
                base_key, epoch0, *rest = all_rest
            idx = 0
            if trace_arg:
                trace = rest[0]
                idx = 1
            ascales = None
            if lr_arg:
                # under arms this is the staged PER-ARM LR vector [E]
                lr_const = rest[idx]
                idx += 1
            elif arms:
                # per-arm multiplicative scales over the shared schedule
                ascales = rest[idx]
                idx += 1
            if streaming:
                sched_ug = rest[idx]
                idx += 1
                sdata = rest[idx:idx + n_stream]
                idx += n_stream
                fix = rest[idx:idx + n_fix]
                idx += n_fix
                eval_ops = rest[idx:]
            else:
                if not in_jit:
                    sched_ul, sched_ug = rest[idx], rest[idx + 1]
                    idx += 2
                data = rest[idx:idx + n_data_args]
                eval_ops = rest[idx + n_data_args:]

            def step(carry, xs):
                if codec:
                    p, rs, sb = carry[0], carry[1], None
                elif buffered:
                    p, rs, sb = carry[0], None, carry[1]
                else:
                    p, rs, sb = carry, None, None

                def pack(new_p, nr, nb):
                    if codec:
                        return (new_p, nr)
                    if buffered:
                        return (new_p, nb)
                    return new_p

                if arms:
                    # arms multiplexer (ISSUE 14): one round of E arms --
                    # the round core vmapped over the leading arms axis of
                    # the params carry (and EF residual), each arm keyed by
                    # its own stream root (base_key here is the stacked
                    # [E] arm keys) with the population stacks SHARED.
                    # The vmapped psum inside _round_core stays EXACTLY
                    # one bind per fused round (a batched pytree psum);
                    # wire bytes scale linearly in E (staticcheck arms
                    # variants audit both by equality).  In-jit sampling
                    # draws each arm its OWN cohort from its stream -- a
                    # solo run with the same seed replays it bitwise;
                    # host-schedule paths share the packed slots.
                    if in_jit:
                        (t,) = xs
                        ul_s = ug_s = None
                    else:
                        t, ul_s, ug_s = xs
                    scales = lr_const if lr_arg else ascales

                    def arm_core(p_e, akey, sc_e, rs_e):
                        key = jax.random.fold_in(akey, t)
                        lr = sc_e if lr_arg else lr_fn(t) * sc_e
                        if in_jit:
                            if trace_arg:
                                row = jnp.take(trace,
                                               (t - 1) % trace.shape[0],
                                               axis=0)
                                active = round_users(key, num_users,
                                                     num_active, avail=row,
                                                     sampler=sampler)
                            else:
                                active = round_users(key, num_users,
                                                     num_active,
                                                     sampler=sampler)
                            padv = jnp.full((slots_total - num_active,),
                                            -1, jnp.int32)
                            padded = jnp.concatenate([active, padv])
                            d = jax.lax.axis_index("clients")
                            ug_e = jax.lax.dynamic_slice(
                                padded, (d * per_dev,), (per_dev,))
                            ul_e = ug_e
                        else:
                            ul_e, ug_e = ul_s, ug_s
                        new_p, ms, nr, _ = self._round_core(
                            p_e, key, lr, ul_e, ug_e, data, resid=rs_e,
                            **self._ep_kw(t))
                        return new_p, ms, nr

                    if codec:
                        new_p, ms, nr = jax.vmap(arm_core)(
                            p, base_key, scales, rs)
                    else:
                        new_p, ms, nr = jax.vmap(
                            arm_core, in_axes=(0, 0, 0, None))(
                            p, base_key, scales, None)
                    return pack(new_p, nr, None), ms

                if streaming:
                    t, ug, *d = xs
                    key = jax.random.fold_in(base_key, t)
                    lr = lr_const if lr_arg else lr_fn(t)
                    # slot-local cohort rows: user_loc=None = identity gather
                    new_p, ms, nr, nb = self._round_core(
                        p, key, lr, None, ug, tuple(d) + tuple(fix),
                        resid=rs, sched_buf=sb, **self._ep_kw(t))
                    return pack(new_p, nr, nb), ms
                if in_jit:
                    (t,) = xs
                    key = jax.random.fold_in(base_key, t)
                    if trace_arg:
                        # availability-trace sampling (ISSUE 9): round t's
                        # 0/1 row gates the shared sampling stream; slots
                        # the availability cannot fill come back -1
                        # (padding).  (t - 1) % T is the host twin's index
                        # (ScheduleSpec.avail_row), shared by construction.
                        row = jnp.take(trace, (t - 1) % trace.shape[0],
                                       axis=0)
                        active = round_users(key, num_users, num_active,
                                             avail=row, sampler=sampler)
                    else:
                        active = round_users(key, num_users, num_active,
                                             sampler=sampler)
                    pad = jnp.full((slots_total - num_active,), -1, jnp.int32)
                    padded = jnp.concatenate([active, pad])
                    d = jax.lax.axis_index("clients")
                    ug = jax.lax.dynamic_slice(padded, (d * per_dev,), (per_dev,))
                    ul = ug
                else:
                    t, ul, ug = xs
                    key = jax.random.fold_in(base_key, t)
                lr = lr_const if lr_arg else lr_fn(t)
                new_p, ms, nr, nb = self._round_core(p, key, lr, ul, ug,
                                                     data, resid=rs,
                                                     sched_buf=sb,
                                                     **self._ep_kw(t))
                return pack(new_p, nr, nb), ms

            epochs = epoch0 + jnp.arange(k, dtype=jnp.int32)
            if streaming:
                xs = (epochs, sched_ug) + tuple(sdata)
            else:
                xs = (epochs,) if in_jit else (epochs, sched_ul, sched_ug)
            if codec:
                # arms: the per-device residual arrives [E, 1, slots,
                # total] -- drop the device axis behind the arms axis
                carry0 = (params, resid0[:, 0] if arms else resid0[0])
            elif buffered:
                carry0 = (params, buf0)
            else:
                carry0 = params

            def unpack(carry):
                if codec:
                    return carry[0], ((carry[1][:, None] if arms
                                       else carry[1][None]),)
                if buffered:
                    return carry[0], (carry[1],)
                return carry, ()

            if groups is None:
                carry, ms = jax.lax.scan(step, carry0, xs)
                p_out, extra = unpack(carry)
                return (p_out,) + extra + (ms,)
            carry, ms, ev = eval_fused_scan(
                step, carry0, xs, epochs, groups, fused_eval, eval_ops,
                params_of=(lambda c: c[0]) if (codec or buffered) else None)
            p_out, extra = unpack(carry)
            return (p_out,) + extra + (ms, ev)

        # under the mesh placement the stacked [E] leaves -- params carry,
        # arm keys, LR scales, metrics -- shard over the 'arms' axis (one
        # arm per device row group); under vmap they replicate
        arm_lead = P(arms_axis)
        lr_specs = (arm_lead if arms else P(),) if (lr_arg or arms) else ()
        trace_specs = (P(),) if trace_arg else ()
        eval_specs = tuple(fused_eval.specs) if groups else ()
        resid_specs = (self._resid_pspec(),) if codec else ()
        buf_specs = (P(),) if buffered else ()
        carry_specs = resid_specs + buf_specs  # mutually exclusive
        ms_spec = P(None, arms_axis, "clients") if arms \
            else P(None, "clients")
        params_spec = arm_lead if arms else P()
        key_spec = arm_lead if arms else P()
        out_specs = (params_spec,) + carry_specs + (ms_spec,)
        if groups is not None:
            out_specs = out_specs + (fused_eval.out_specs,)
        fn = _shard_map(
            sbody, mesh,
            in_specs=(params_spec,) + carry_specs + (key_spec, P())
            + trace_specs + lr_specs + sched_specs + data_specs
            + eval_specs,
            out_specs=out_specs,
        )
        # codec/buffered programs donate ONLY their extra carry (see
        # _WireCodecCarry: params donation + a params-sized extra output
        # trips an XLA:CPU executable-serialization bug when reloaded from
        # the persistent compile cache; caught by the masked signsgd
        # checkpoint round-trip on a warm cache).  Arms programs (ISSUE
        # 14) donate NOTHING when dense: donating the E-stacked params
        # carry intermittently corrupts single leaves (1e24-magnitude
        # garbage) when the program is DESERIALIZED from the persistent
        # cache -- the same upstream XLA:CPU bug class, reproduced on the
        # multiplexed driver's resume path.  Cost: one extra E x params
        # buffer per dispatch, priced into the staticcheck arms budgets.
        if arms:
            donate = (1,) if (codec or buffered) else ()
        else:
            donate = (1,) if (codec or buffered) else (0,)
        return jax.jit(fn, donate_argnums=donate)

    def stage_cohort(self, store: ClientStore, user_schedule,
                     timer: PhaseTimer = None) -> StagedCohort:
        """Materialise + commit ONE superstep's cohort from a
        :class:`~.staging.ClientStore` (ISSUE 6 tentpole).

        ``user_schedule``: int32 ``[k, A]`` active user ids per round (the
        superstep sampling stream, :func:`~..fed.core.round_users`).  The
        cohort's shards pack into the stager's ring buffers in the masked
        engine's slot layout -- schedule order, ``ceil(A / n_dev)`` slots
        per device, padding slots materialising user 0 exactly like the
        eager gather -- and commit via explicit ``device_put`` + private
        copy, sharded over the slot axis.  Host/device cost is
        O(k x A x shard), independent of the population.  Call it for
        superstep N+1 right after dispatching superstep N: the device_put
        pipeline overlaps with N's compute (prefetch depth 1)."""
        if self._staging is None:
            raise ValueError("stage_cohort needs a mesh-attached engine")
        timer = timer if timer is not None else PhaseTimer()
        with timer.phase("stage"):
            # staticcheck: allow(no-asarray): host schedule normalization;
            # the cohort reaches the mesh via the stager's explicit puts only
            user_schedule = np.asarray(user_schedule, np.int32)
            if user_schedule.ndim != 2:
                raise ValueError(
                    f"user_schedule must be [k, A], got {user_schedule.shape}")
            k, a = user_schedule.shape
            n_dev = self.mesh.shape["clients"]
            per_dev = _ceil_div(a, n_dev)
            slots = per_dev * n_dev
            if self._cohort_stager is None:
                self._cohort_stager = CohortStager(self.mesh,
                                                   depth=self._prefetch_depth)
            st = self._cohort_stager
            n = store.shard_max
            if self.is_lm:
                layouts = [((k, slots), np.int32, -1),
                           ((k, slots) + store.row_shape, store.data.dtype, None),
                           ((k, slots, store.classes_size), np.float32, None)]
            else:
                layouts = [((k, slots), np.int32, -1),
                           ((k, slots, n) + store.data.shape[1:],
                            store.data.dtype, None),
                           ((k, slots, n), store.target.dtype, None),
                           ((k, slots, n), np.float32, None),
                           ((k, slots, store.classes_size), np.float32, None)]
            key = ("masked", k, slots)
            slot_i, bufs = st.buffers(key, layouts)
            sched = bufs[0]
            sched[:, :a] = user_schedule  # trailing slots stay -1 (padding)
            flat = sched.reshape(-1)
            if self.is_lm:
                store.fill_lm(flat, bufs[1].reshape((-1,) + bufs[1].shape[2:]))
                store.fill_labels(flat, bufs[2].reshape(-1, store.classes_size))
            else:
                store.fill_vision(flat,
                                  bufs[1].reshape((-1,) + bufs[1].shape[2:]),
                                  bufs[2].reshape((-1,) + bufs[2].shape[2:]),
                                  bufs[3].reshape(-1, n))
                store.fill_labels(flat, bufs[4].reshape(-1, store.classes_size))
            dev = st.commit(key, slot_i, bufs,
                            (P(None, "clients"),) * len(bufs))
        return StagedCohort(engine="masked", k=k, a=a, per_dev=per_dev,
                            sched=dev[0], data=tuple(dev[1:]))

    def train_superstep(self, params, base_key, epoch0: int, k: int,
                        data: Optional[Tuple[jnp.ndarray, ...]] = None,
                        user_schedule=None,
                        num_active: Optional[int] = None,
                        timer: PhaseTimer = None, eval_mask=None,
                        fused_eval=None, lr: Optional[float] = None,
                        cohort: Optional[StagedCohort] = None):
        """Run ``k`` rounds as ONE compiled program (``superstep_rounds``).

        Per-round keys are ``fold_in(base_key, epoch0 + r)`` -- the driver's
        stream with ``base_key = host_key``.  Under replicated placement
        with ``user_schedule=None`` the per-round active set is sampled
        in-jit from :func:`~..fed.core.round_users` (``num_active`` defaults
        to ``ceil(frac * num_users)``); under sharded placement a host
        ``user_schedule`` int32 ``[k, A]`` drawn from the same stream is
        required, packed here into owner-aligned slot arrays (scan xs).
        Returns ``(new_params, PendingMetrics)`` whose ``fetch()`` yields a
        LIST of k per-round metric dicts -- metrics accumulate on device and
        cross to the host once per superstep.

        ``eval_mask`` + ``fused_eval`` (ISSUE 4): run the fused sBN+eval
        phase in-program on the rounds where the static mask fires; the
        fetch then yields ``{"train": [k dicts], "eval": [per-eval dicts]}``
        with each eval dict carrying ``epoch``/``bn``/``local``/``global``.
        ``lr``: stage a constant LR scalar instead of the traced schedule
        (the ReduceLROnPlateau superstep mode).

        ``cohort`` (ISSUE 6): a :class:`~.staging.StagedCohort` from
        :meth:`stage_cohort` replaces ``data`` entirely -- the cohort's
        shards ride the scan xs and the program never sees the population.
        The slot layout and sampling stream match the in-jit draw, so a
        streamed superstep is bit-identical to the eager one."""
        self._reject_per_level_map()
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
            if cohort.engine != "masked" or cohort.k != k:
                raise ValueError(
                    f"cohort mismatch: staged for engine={cohort.engine!r} "
                    f"k={cohort.k}, dispatching masked k={k}")
            with timer.phase("stage"):
                a, per_dev = cohort.a, cohort.per_dev
                sched_args = (cohort.sched,)
                args = tuple(cohort.data)
                if self.fix_rates is not None:
                    args = args + self._staging.replicated(
                        "fix_rates", (self.fix_rates,))
                lr_args = (self._staging.scalar(lr),) if lr_arg else ()
                eval_args = tuple(fused_eval.ops) if eval_mask is not None else ()
                epoch0_dev = self._staging.scalar(epoch0, dtype=np.int32)
                params = self._staging.commit(params)
                carry_args = self._carry_args(params)
                pkey = (k, per_dev, "stream", a, eval_mask, lr_arg)
                prog = self._superstep_progs.get(pkey)
                if prog is None:
                    prog = self._build_superstep(k, per_dev, False,
                                                 num_active=a,
                                                 eval_mask=eval_mask,
                                                 fused_eval=fused_eval,
                                                 lr_arg=lr_arg, streaming=True)
                    self._superstep_progs[pkey] = prog
            with timer.phase("dispatch"):
                out = prog(params, *carry_args, base_key, epoch0_dev,
                           *lr_args, *sched_args, *args, *eval_args)
            return self._assemble_superstep(out, epoch0, k, eval_mask,
                                            fused_eval)
        if data is None:
            raise ValueError("train_superstep needs data stacks or a cohort")
        with timer.phase("stage"):
            n_dev = self.mesh.shape["clients"]
            sched_args = ()
            if user_schedule is not None:
                # staticcheck: allow(no-asarray): host slot-id normalization;
                # the ids reach the mesh via explicit staging.put only
                user_schedule = np.asarray(user_schedule, np.int32)
                if user_schedule.ndim != 2 or user_schedule.shape[0] != k:
                    raise ValueError(
                        f"user_schedule must be [k={k}, A], got {user_schedule.shape}")
            if self.placement == "sharded":
                if user_schedule is None:
                    raise ValueError(
                        "sharded placement needs a host user_schedule [k, A]: "
                        "slot->owner packing is placement bookkeeping (draw it "
                        "from fed.core.round_users to keep the superstep stream)")
                u_pad = int(data[0].shape[0])
                if u_pad % n_dev:
                    raise ValueError(
                        f"sharded placement needs the user axis ({u_pad}) padded to a "
                        f"multiple of the clients axis ({n_dev}); use shard_client_data")
                per = u_pad // n_dev
                rows = [[user_schedule[r][user_schedule[r] // per == d]
                         for d in range(n_dev)] for r in range(k)]
                # bucket the per-device slot count: the raw max ownership
                # density fluctuates draw to draw, and it keys the K-round
                # program -- unbucketed it recompiles the superstep (K x the
                # flagship compile) whenever the density changes
                per_dev = _bucket_pow2(max(1, max(len(b) for row in rows
                                                  for b in row)))
                ug_buf = self._packer.buffer(("ss_glob", k, n_dev, per_dev),
                                             (k, n_dev, per_dev))
                ul_buf = self._packer.buffer(("ss_loc", k, n_dev, per_dev),
                                             (k, n_dev, per_dev))
                for r in range(k):
                    for d, b in enumerate(rows[r]):
                        ug_buf[r, d, : len(b)] = b
                        ul_buf[r, d, : len(b)] = b - d * per
                ug = self._staging.put(ug_buf.reshape(k, -1), spec=P(None, "clients"))
                ul = self._staging.put(ul_buf.reshape(k, -1), spec=P(None, "clients"))
                sched_args, in_jit, a = (ul, ug), False, 0
                args = tuple(data)
            else:
                if user_schedule is not None:
                    a = user_schedule.shape[1]
                    per_dev = _ceil_div(a, n_dev)
                    buf = self._packer.buffer(("ss_rep", k, per_dev * n_dev),
                                              (k, per_dev * n_dev))
                    buf[:, :a] = user_schedule
                    ug = self._staging.put(buf, spec=P(None, "clients"))
                    sched_args, in_jit = (ug, ug), False
                else:
                    a = int(num_active if num_active is not None
                            else math.ceil(self.cfg["frac"] * self.cfg["num_users"]))
                    per_dev = _ceil_div(a, n_dev)
                    in_jit = True
                args = self._staging.replicated("train_data", data)
            if self.fix_rates is not None:
                args = args + self._staging.replicated("fix_rates", (self.fix_rates,))
            arm_vec_spec = P("arms") if self._arms_mesh else P()
            if lr_arg:
                # arms: the per-arm LR vector [E] (Plateau steps each arm's
                # own state at superstep boundaries); solo: a scalar
                lr_args = ((self._staging.put(
                    np.asarray(lr, np.float32).reshape(arms),  # staticcheck: allow(no-asarray): host LR-vector normalization; reaches the mesh via the explicit staging.put
                    spec=arm_vec_spec),) if arms
                    else (self._staging.scalar(lr),))
            elif arms:
                # per-arm multiplicative LR scales over the shared schedule
                lr_args = (self._staging.put(
                    np.asarray(aspec.lr_scales, np.float32),  # staticcheck: allow(no-asarray): host scale-vector normalization; reaches the mesh via the explicit staging.put
                    spec=arm_vec_spec),)
            else:
                lr_args = ()
            eval_args = tuple(fused_eval.ops) if eval_mask is not None else ()
            epoch0_dev = self._staging.scalar(epoch0, dtype=np.int32)
            # commit the params carry: an uncommitted init tree would
            # specialise this program once and recompile on round 2 when the
            # outputs come back mesh-committed (staticcheck recompile audit).
            # Under the mesh arms placement the stacked axis commits sharded
            # over the 'arms' rows (each arm's params live on its own rows)
            params = self._staging.commit(
                params,
                spec=P("arms") if (arms and self._arms_mesh) else P())
            carry_args = self._carry_args(params)
            trace_args = ()
            if in_jit and self._sched_spec.kind != "uniform":
                # the availability trace enters the in-jit sampling program
                # as a committed replicated argument (ISSUE 9); the cached
                # property returns one host array, so this commit is a
                # steady-state identity hit
                trace_args = self._staging.replicated(
                    "sched_trace", (self._sched_spec.trace,))
            # arms (ISSUE 14): the program takes the stacked [E] per-arm
            # key roots in the base-key slot -- THE one stream derivation
            # (fed.core.arm_stream_keys), shared with solo runs; the mesh
            # placement commits them one per arm row group
            if aspec is not None:
                dispatch_key = arm_stream_keys(base_key, aspec.seeds)
                if self._arms_mesh:
                    dispatch_key = self._staging.put(dispatch_key,
                                                     spec=P("arms"))
            else:
                dispatch_key = base_key
            pkey = (k, per_dev, in_jit, a, eval_mask, lr_arg, arms,
                    self._arms_mesh)
            prog = self._superstep_progs.get(pkey)
            if prog is None:
                prog = self._build_superstep(k, per_dev, in_jit, num_active=a,
                                             eval_mask=eval_mask,
                                             fused_eval=fused_eval,
                                             lr_arg=lr_arg, arms=arms)
                self._superstep_progs[pkey] = prog
        with timer.phase("dispatch"):
            out = prog(params, *carry_args, dispatch_key, epoch0_dev,
                       *trace_args, *lr_args, *sched_args, *args, *eval_args)
        return self._assemble_superstep(out, epoch0, k, eval_mask, fused_eval,
                                        arms=arms)

    def _assemble_superstep(self, out, epoch0: int, k: int, eval_mask,
                            fused_eval, arms: int = 0):
        """Package one superstep dispatch's outputs: ``(new_params,
        PendingMetrics)``; shared by the eager and streaming paths.  Under a
        lossy wire codec the second output is the new error-feedback carry;
        under buffered-async aggregation it is the new staleness buffer --
        either way stashed on the engine (read/restored via
        :meth:`wire_resid_host`/:meth:`set_wire_resid` or
        :meth:`~..sched.buffer._SchedBufCarry.sched_buf_host`/
        :meth:`set_sched_buf` at checkpoint boundaries).

        ``arms`` (ISSUE 14): every fetched leaf carries the arms axis right
        behind the round/eval-stack axis; the assemble slices each arm out
        and runs the solo assembly on it, returning ``{"arms": [per-arm
        results]}`` -- each entry exactly what a solo run's fetch yields
        (probe records included), so downstream consumers are per-arm
        unchanged."""
        if self._codec_name != "dense":
            self._resid = out[1]
            out = (out[0],) + out[2:]
        elif self._sched_spec.buffered:
            self._sched_buf = out[1]
            out = (out[0],) + out[2:]
        n_dev = self.mesh.shape["clients"]
        # the quarantine counter rides the metrics pytree as an obs_ probe
        # even under telemetry='off' (ISSUE 15): split whenever either is on
        obs_on = self._obs_on or self._quarantine.enabled

        def _split(host):
            """Probe leaves out of a fetched metrics tree (ISSUE 10):
            telemetry-off trees pass through untouched (None probes)."""
            if obs_on:
                return split_probes(host, n_dev, counters=self.model.meta.get("counters"))
            return host, None

        if eval_mask is None:
            new_params, ms = out

            def _assemble_one(host):
                host, probes = _split(host)
                rounds = [{name: v[r] for name, v in host.items()}
                          for r in range(k)]
                if probes is not None:
                    return {"train": rounds, "obs": probes}
                return rounds

            if arms:
                def _assemble(host):
                    return {"arms": [
                        _assemble_one({name: v[:, e]
                                       for name, v in host.items()})
                        for e in range(arms)]}

                return new_params, PendingMetrics(ms, assemble=_assemble)
            return new_params, PendingMetrics(ms, assemble=_assemble_one)

        new_params, ms, ev = out
        eval_epochs = [epoch0 + r for r, m in enumerate(eval_mask) if m]

        def _assemble_eval_one(host):
            ms_h, ev_h = host
            ms_h, probes = _split(ms_h)
            out_d = {"train": [{name: v[r] for name, v in ms_h.items()}
                               for r in range(k)],
                     "eval": fused_eval.assemble(ev_h, eval_epochs)}
            if probes is not None:
                out_d["obs"] = probes
            return out_d

        if arms:
            def _assemble_eval(host):
                ms_h, ev_h = host
                return {"arms": [
                    _assemble_eval_one((
                        {name: v[:, e] for name, v in ms_h.items()},
                        jax.tree_util.tree_map(lambda v: v[:, e], ev_h)))
                    for e in range(arms)]}

            return new_params, PendingMetrics((ms, ev),
                                              assemble=_assemble_eval)
        return new_params, PendingMetrics((ms, ev),
                                          assemble=_assemble_eval_one)

    def program_cache_size(self) -> int:
        """Total compiled specializations across this engine's train
        programs (round + superstep): the staticcheck recompile audit and
        the streaming tests hold it flat across repeated dispatches."""
        progs = ([self._train] if self._train is not None else []) \
            + list(self._superstep_progs.values())
        return sum(p._cache_size() for p in progs)

    def train_round(self, params, key, lr, user_idx, data: Tuple[jnp.ndarray, ...],
                    timer: PhaseTimer = None, epoch: Optional[int] = None):
        """Run one communication round.

        ``user_idx``: int32 [A] active user ids.  ``data``: for vision
        ``(all_x[U,N,H,W,C] uint8, all_y[U,N], all_m[U,N], all_lm[U,classes])``;
        for LM ``(all_rows[U,R,T], all_lm[U,vocab])``.  Under ``sharded``
        placement the per-user arrays must come from :func:`shard_client_data`
        (user axis padded to the clients-axis size and device-sharded); each
        client then trains on the device owning its shard -- no round moves
        any client data.  Under ``replicated`` placement the stacks are
        committed to the mesh once by the placement cache, so steady-state
        rounds move only the slot ids (explicit device_put).  ``timer``
        accounts the stage/dispatch phases.  Returns ``(new_params,
        per-client metric sums)`` with the metric sums still on device.
        """
        self._reject_per_level_map()
        if self._arms_spec is not None:
            raise ValueError(
                "arms need the fused superstep (train_superstep): the K=1 "
                "train_round path is the host-loop reference twin, which "
                "the arms axis would fork per arm -- set superstep_rounds "
                ">= 1 through the superstep API")
        if self._train is None:
            self._train = self._build_train()
        timer = timer if timer is not None else PhaseTimer()
        with timer.phase("stage"):
            n_dev = self.mesh.shape["clients"]
            # staticcheck: allow(no-asarray): host slot-id normalization;
            # the ids reach the mesh via explicit staging.put only
            user_idx = np.asarray(user_idx, np.int32)
            if self.placement == "sharded":
                u_pad = int(data[0].shape[0])
                if u_pad % n_dev:
                    raise ValueError(
                        f"sharded placement needs the user axis ({u_pad}) padded to a "
                        f"multiple of the clients axis ({n_dev}); use shard_client_data")
                per = u_pad // n_dev
                owners = user_idx // per
                by_dev = [user_idx[owners == d] for d in range(n_dev)]
                slots = max(1, max(len(b) for b in by_dev))
                user_glob = self._packer.buffer(("glob", n_dev, slots), (n_dev, slots))
                user_loc = self._packer.buffer(("loc", n_dev, slots), (n_dev, slots))
                for d, b in enumerate(by_dev):
                    user_glob[d, : len(b)] = b
                    user_loc[d, : len(b)] = b - d * per
                user_glob = user_glob.reshape(-1)
                user_loc = user_loc.reshape(-1)
                args = tuple(data)  # committed P('clients') by shard_client_data
            else:
                a = len(user_idx)
                pad = (-a) % n_dev
                user_glob = self._packer.buffer(("rep", a + pad), (a + pad,))
                user_glob[:a] = user_idx
                user_loc = user_glob
                args = self._staging.replicated("train_data", data)
            if self.fix_rates is not None:
                args = args + self._staging.replicated("fix_rates", (self.fix_rates,))
            lr = self._staging.scalar(lr)
            ug = self._staging.put(user_glob, spec=P("clients"))
            ul = ug if user_loc is user_glob else self._staging.put(user_loc, spec=P("clients"))
            # commit params so dispatch 1 and the steady state share ONE
            # program specialization (see train_superstep)
            params = self._staging.commit(params)
            carry_args = self._carry_args(params)
            ep_args = ()
            if self._poison is not None:
                # chaos poison (ISSUE 15): the (round, uid) match needs the
                # round's epoch; the superstep paths thread it from the
                # scan, the K=1 program takes it as a staged scalar
                if epoch is None:
                    raise ValueError(
                        "chaos_poison needs epoch= on train_round (the "
                        "K=1 program matches poisons by (round, uid))")
                ep_args = (self._staging.scalar(epoch, dtype=np.int32),)
        with timer.phase("dispatch"):
            if self._codec_name != "dense":
                new_p, self._resid, ms = self._train(
                    params, *carry_args, key, lr, *ep_args, ul, ug, *args)
                return new_p, ms
            if self._sched_spec.buffered:
                new_p, self._sched_buf, ms = self._train(
                    params, *carry_args, key, lr, *ep_args, ul, ug, *args)
                return new_p, ms
            return self._train(params, key, lr, *ep_args, ul, ug, *args)
