"""Federation core: sub-model extraction and counted-average aggregation.

This replaces the reference ``Federation`` class (``src/fed.py``) with pure
functions over param pytrees.  Two execution strategies share one algebra:

* **masked** (default, TPU-native): ``distribute`` multiplies the global
  params by the client's width mask (suffix -> 0); ``combine`` is
  ``sum(P_c * M_c) / sum(M_c)`` with the stale-value fallback where no client
  contributed (ref fed.py:217-218).  Everything is static-shape and jittable;
  under ``shard_map`` the two sums become ``psum`` over the clients axis.
* **sliced**: true small tensors via host-side gather (``extract_sliced``) and
  scatter-back (``embed_sliced``), matching the reference's deepcopy
  simulation; used for debugging and the equivalence tests.

Label-split restriction of output layers (ref fed.py:193-198,228-233,263-274)
enters through the ``label_mask`` axis of the count masks -- clients train
their full output rows but only their label rows are aggregated.
"""

from __future__ import annotations

import math
from typing import Any, Dict, Optional

import jax
import jax.numpy as jnp
import numpy as np

from ..models.base import ModelDef
from ..models.spec import Group, ParamSpec, count_masks as _count_masks, mask_params


def sample_model_rates(key: jax.Array, cfg: Dict[str, Any],
                       user_idx: Optional[jnp.ndarray] = None) -> jnp.ndarray:
    """Absolute model rates of the given users for one round.

    ``fix``: the static per-user vector computed by ``process_control`` (ref
    utils.py:134-144), indexed by the *selected* user ids (ref fed.py
    ``self.model_rate[user_idx[m]]``).  ``dynamic``: i.i.d. multinomial
    re-roll over ``cfg['proportion']`` every round (ref fed.py:15-19) -- a
    traced sample, so dynamic mode stays inside the jitted round.

    NOTE: these are *absolute* rates; convert with :func:`to_width_rates`
    before driving masks/Scaler (the reference likewise slices by
    ``model_rate / global_model_rate``, fed.py:46).
    """
    if user_idx is None:
        user_idx = jnp.arange(cfg["num_users"])
    user_idx = jnp.asarray(user_idx)
    if cfg["model_split_mode"] == "fix":
        return jnp.take(jnp.asarray(cfg["model_rate"], jnp.float32), user_idx)
    if cfg["model_split_mode"] == "dynamic":
        # re-roll ALL users then index the selected ones (ref fed.py:15-24 +
        # distribute) -- also keeps the PRNG stream identical to the masked
        # round engine's in-jit draw for any selection.
        rates = jnp.asarray(cfg["model_rate"], jnp.float32)
        idx = jax.random.choice(key, len(rates), shape=(cfg["num_users"],),
                                p=jnp.asarray(cfg["proportion"]))
        return rates[idx][user_idx]
    raise ValueError("Not valid model split mode")


def validate_width_geometry(model: ModelDef, cfg: Dict[str, Any]) -> None:
    """Reject width configs where the per-head q/k/v slice outruns the
    prefix width slice (ref fed.py:115-131 couples the two; when
    ``heads * ceil(head_dim * r) != ceil(size * r)`` at some level the
    sub-model rows reference zeroed embedding dims -- the reference
    silently degrades, here it would NaN).  Raises with the minimal fix."""
    rates = {float(r) / cfg["global_model_rate"] for r in cfg["model_rate"]}
    for name, g in model.groups.items():
        for wr in sorted(rates):
            widths = g.rule.coupled_width(g, wr)
            if widths is not None and widths[0] != widths[1]:
                raise ValueError(
                    f"width geometry: group {name!r} (size {g.size}, "
                    f"{g.num_heads} heads) is inconsistent at rate {wr:g}: "
                    f"per-head slice keeps {widths[0]} "
                    f"dims but the width slice keeps {widths[1]}; "
                    f"pick embedding_size so embedding*rate is a multiple-safe "
                    f"size (e.g. embedding_size*min_rate >= num_heads and "
                    f"head_dim divisible by 1/min_rate)")
    # grouped-query slicing: the query heads, the key/value heads and the
    # head norms' gains keep the same dims of a head, in whole rotary pairs
    families: Dict[str, list] = {}
    for name, g in model.groups.items():
        if g.family:
            families.setdefault(g.family, []).append((name, g))
    for family, members in families.items():
        for wr in sorted(rates):
            kept = {name: g.rule.head_width(g, wr) for name, g in members}
            odd = [name for name, g in members if kept[name] is None or kept[name] % g.multiple]
            if odd or len(set(kept.values())) != 1:
                raise ValueError(
                    f"width geometry: head family {family!r} is inconsistent at "
                    f"rate {wr:g}: its groups keep {kept} dims a head (they must "
                    f"agree, in whole multiples of each group's `multiple`); give "
                    f"them one head size, one rule and one `multiple`")


ROUND_RATE_SALT = 7
USER_SAMPLE_SALT = 11
#: PRNG salt of the per-arm stream derivation (ISSUE 14), folded into
#: the HOST key.  Must stay outside the host key's other fold families
#: (the per-round epoch keys [1, NUM_ROUNDS_BOUND] and the watchdog's
#: RETRY_SALT window): the old value 17 sat inside the epoch family, so
#: round 17's key WAS the arms salt root and arm seed 7's stream
#: collided with round 17's rate stream (staticcheck's key-stream audit
#: now proves the intervals disjoint).
ARM_STREAM_SALT = 0x4152  # 16722, past any epoch index
#: PRNG sub-root salts of the engines' in-round streams (ISSUE 18).  The
#: per-client slot keys descend from ``fold_in(round_key,
#: CLIENT_STREAM_SALT)`` and the failure draws from ``fold_in(round_key,
#: FAILURE_STREAM_SALT)``, so the unbounded uid family lives in its own
#: subtree: the old flat ``fold_in(round_key, 13 + uid)`` derivation
#: collided with the failure root at uid 85 (13 + 85 == 98) and with the
#: deadline salt at uid 118 (13 + 118 == 131) -- at flagship scale
#: (num_users=100) client 85's stream WAS the failure stream.
CLIENT_STREAM_SALT = 13
FAILURE_STREAM_SALT = 98


def arm_stream_keys(base_key: jax.Array, seeds) -> jax.Array:
    """Stacked ``[E]`` per-arm base keys: THE one definition of the arms
    stream derivation (ISSUE 14, :mod:`~..multi`).

    Arm ``e`` with seed ``s`` owns the stream ``fold_in(fold_in(base_key,
    ARM_STREAM_SALT), s)``; a ``None`` seed is the IDENTITY arm -- it
    consumes ``base_key`` itself, which is what makes an ``arms=1`` run
    bit-identical to the unbatched program (the equivalence contract in
    tests/test_arms.py).  Engines consume these as the per-round key roots
    of each arm's round cores (cohort draw, dynamic rates, client/slot
    keys, deadline budgets, failure draws); the batched program and a solo
    run with the same seed therefore replay the identical streams."""
    salted = jax.random.fold_in(base_key, ARM_STREAM_SALT)
    return jnp.stack([base_key if s is None
                      else jax.random.fold_in(salted, s) for s in seeds])


def client_stream_keys(round_key: jax.Array, uids: jnp.ndarray) -> jax.Array:
    """Stacked per-client slot keys ``fold_in(fold_in(round_key,
    CLIENT_STREAM_SALT), uid)``: THE one definition of the client stream.

    The masked, grouped and sliced engines all consume this derivation
    for their local-training keys, which is what keeps the engine
    equivalence contracts bitwise.  The two-level fold keeps the
    unbounded uid family in its own subtree (see CLIENT_STREAM_SALT
    above); staticcheck's key-stream audit pins this shape."""
    root = jax.random.fold_in(round_key, CLIENT_STREAM_SALT)
    return jax.vmap(lambda u: jax.random.fold_in(root, u))(jnp.asarray(uids))


def failure_stream_key(round_key: jax.Array) -> jax.Array:
    """The failure-draw root ``fold_in(round_key, FAILURE_STREAM_SALT)``:
    per-client crash draws fold the uid into THIS key, never into the
    round key directly (uid subtrees stay disjoint from sibling salts)."""
    return jax.random.fold_in(round_key, FAILURE_STREAM_SALT)


def round_rates(round_key: jax.Array, cfg: Dict[str, Any],
                user_idx: Optional[jnp.ndarray] = None) -> jnp.ndarray:
    """The per-round rate draw, salt included: THE one definition of the
    rate stream.  Used in-jit by the masked engine's dynamic branch and on
    the host by ``entry/common.py`` and the parity harness for the grouped/
    sliced engines -- all three must consume the identical stream or
    round-level engine equivalence silently becomes a PRNG artifact."""
    return sample_model_rates(jax.random.fold_in(round_key, ROUND_RATE_SALT), cfg, user_idx)


def round_users(round_key: jax.Array, num_users: int, num_active: int,
                avail=None, sampler: str = "prp") -> jnp.ndarray:
    """The per-round active-client draw, salt included: THE one definition
    of the superstep sampling stream (the jax twin of the drivers'
    ``rng.permutation(num_users)[:num_active]``).  Consumed in-jit by the
    masked superstep (replicated placement) and on the host when packing
    slot schedules (sharded placement, grouped engine) -- every consumer
    must use this function or superstep-vs-sequential equivalence silently
    becomes a PRNG artifact.  Traceable (``round_key`` may be a traced
    key).

    ``sampler`` (ISSUE 11, :mod:`.sampling`): ``'prp'`` (default) draws
    the cohort as the image of ``[0, num_active)`` under a keyed
    pseudorandom-permutation index map -- O(num_active) work, no ``[U]``
    buffer; ``'perm'`` is the legacy full ``permutation(num_users)`` draw,
    preserved bit for bit for parity tests and old trajectories.  The two
    are DIFFERENT streams: switching re-baselines every seeded trajectory
    (deliberately; the bench refuses cross-stream comparisons).

    ``avail`` (ISSUE 9, :mod:`~..sched`): this round's ``[num_users]`` 0/1
    availability row.  ``None`` (uniform) keeps the sampler's plain draw
    bit for bit.  With a row, available users are drawn FIRST in
    permutation order and slots the availability cannot fill come back as
    ``-1`` -- the engines' padding-slot convention, so a thin round
    degrades to partial participation instead of resampling unavailable
    users.  Under ``perm`` the filter is the legacy ``[U]`` gather +
    stable argsort; under ``prp`` it is an O(num_active x overdraw)
    draw-then-filter walk along the PRP with bounded spill
    (:func:`~.sampling.prp_round_users`).  Either way an all-ones row
    selects exactly that sampler's uniform cohort, which is what makes
    trace replay a strict generalisation of the uniform stream."""
    if not 0 <= num_active <= num_users:
        raise ValueError(
            f"round_users: num_active={num_active} must be in [0, "
            f"num_users={num_users}] -- the legacy permutation draw would "
            f"silently short the cohort (and a negative count silently "
            f"wrap); fix cfg['frac']/num_active")
    if sampler not in ("perm", "prp"):
        raise ValueError(f"Not valid sampler: {sampler!r} (one of "
                         f"('perm', 'prp'))")
    skey = jax.random.fold_in(round_key, USER_SAMPLE_SALT)
    if sampler == "prp":
        from .sampling import prp_round_users

        return prp_round_users(skey, num_users, num_active, avail=avail)
    perm = jax.random.permutation(skey, num_users)
    if avail is None:
        return perm[:num_active].astype(jnp.int32)
    a = jnp.asarray(avail, jnp.float32)[perm]
    order = jnp.argsort(-a, stable=True)[:num_active]
    sel = perm[order]
    ok = a[order] > 0
    return jnp.where(ok, sel, -1).astype(jnp.int32)


def superstep_user_schedule(host_key: jax.Array, epoch0: int, k: int,
                            num_users: int, num_active: int,
                            schedule=None, sampler: str = "prp") -> np.ndarray:
    """Host-side ``[k, A]`` active-user draw from THE superstep sampling
    stream (:func:`round_users` at per-round keys ``fold_in(host_key,
    epoch0 + r)``): the one host twin of the masked engine's in-jit draw.
    Shared by the fed drivers, the streaming cohort staging
    and the equivalence tests -- a private copy of this loop is how the
    superstep stream silently forks.

    ``schedule`` (ISSUE 9): a :class:`~..sched.ScheduleSpec`; its per-round
    availability rows thread into :func:`round_users` (``None`` or the
    uniform kind leaves the stream untouched).  ``-1`` entries mark slots
    the availability could not fill -- padding slots to every consumer.
    ``sampler`` (ISSUE 11) threads straight through -- the host schedule
    and the in-jit draw must name the same sampler or the stream forks."""
    if epoch0 < 0:
        raise ValueError(f"superstep_user_schedule: epoch0={epoch0} must "
                         f"be non-negative (per-round keys are fold_in("
                         f"host_key, epoch0 + r); a negative epoch silently "
                         f"replays another round's stream)")
    if k < 0:
        raise ValueError(f"superstep_user_schedule: k={k} must be "
                         f"non-negative")
    return np.stack([
        np.asarray(round_users(
            jax.random.fold_in(host_key, epoch0 + r), num_users, num_active,
            avail=None if schedule is None else schedule.avail_row(epoch0 + r),
            sampler=sampler))
        for r in range(k)]) if k else np.zeros((0, num_active), np.int32)


def superstep_rate_schedule(host_key: jax.Array, epoch0: int, k: int,
                            cfg: Dict[str, Any], user_schedule) -> np.ndarray:
    """Host-side ``[k, A]`` absolute-rate draw matching
    :func:`superstep_user_schedule`'s rounds (:func:`round_rates` at the
    same per-round keys) -- what the grouped engine's slot grouping and the
    masked engine's in-jit draw both consume."""
    return np.stack([
        np.asarray(round_rates(jax.random.fold_in(host_key, epoch0 + r), cfg,
                               jnp.asarray(user_schedule[r])))
        for r in range(k)])


def snap_to_levels(rates, levels, rtol: float = 1e-5, atol: float = 1e-8) -> np.ndarray:
    """Snap sampled absolute model rates onto an engine's level table.

    Incoming rates round-trip through float32 (:func:`round_rates`) while
    level tables are host floats; exact-equality lookups only work because
    the stock ``MODEL_SPLIT_RATE`` table is dyadic.  Nearest-level matching
    with an ``isclose`` guard makes any rate table either snap cleanly or
    fail loudly AT STAGING -- a ``ValueError`` naming the offending rates --
    instead of a ``KeyError`` mid-round (ADVICE r5 item 2)."""
    table = np.asarray(sorted({float(r) for r in levels}, reverse=True), np.float64)
    r = np.asarray(rates, np.float64).reshape(-1)
    if r.size == 0:
        return r
    snapped = table[np.argmin(np.abs(r[:, None] - table[None, :]), axis=1)]
    ok = np.isclose(r, snapped, rtol=rtol, atol=atol)
    if not ok.all():
        bad = sorted(set(np.round(r[~ok], 6).tolist()))
        raise ValueError(
            f"model rates {bad} are not in the engine's level table "
            f"{table.tolist()}: every sampled rate must match a level built "
            f"at engine construction (fix cfg['model_rate'] or the incoming "
            f"rate stream)")
    return snapped


#: module_table rows whose backward pass re-runs the contraction twice
#: (grad wrt inputs + grad wrt weights); everything else (norms, relu,
#: pools) back-propagates at ~1x its forward cost.
_MATMUL_LIKE = ("conv", "linear", "shortcut", "mha", "ff.l", "dec.l",
                "embedding", "qk", "av", "attn.", "mlp.", "moe.", "head")

#: optimizer + width/label masking + clipping cost per parameter per step
#: (SGD momentum update, weight decay, mask multiply, global-norm terms)
_OPT_FLOPS_PER_PARAM = 10.0


def level_flop_table(cfg: Dict[str, Any], rates: Optional[list] = None
                     ) -> Dict[float, float]:
    """Analytic per-client per-local-step training FLOPs at each level of the
    rate table: THE one source of truth for level FLOP budgets.

    Derived from the profiler's per-module MAC table
    (:func:`~..analysis.summary.module_table`) rather than the bare
    ``rate^2`` heuristic: forward = 2x MACs, backward = 2x forward for
    matmul-like modules (input grad + weight grad) and ~1x for elementwise
    ones, plus an optimizer/masking term per parameter and the
    width-INDEPENDENT per-batch data-prep cost (normalize/augment) that
    dominates tiny levels.  Consumers: the grouped engine's ``slices`` row
    allocation (:meth:`~..parallel.grouped.GroupedRoundEngine._static_mesh_slices`),
    the staticcheck FLOP-budget audit, and ``scripts/grouped_flops.py``.
    Absolute values are a model, not a measurement -- compare *shares*
    (:func:`level_flop_shares`) against ``cost_analysis()`` numbers."""
    from ..analysis.summary import module_table

    grate = cfg["global_model_rate"]
    if rates is None:
        rates = sorted({float(r) for r in cfg["model_rate"]}, reverse=True)
    bs = cfg["batch_size"]["train"] if isinstance(cfg["batch_size"], dict) \
        else cfg["batch_size"]
    prep = 0.0
    if cfg.get("data_shape"):
        h, w, c = cfg["data_shape"]
        # normalize: sub+div per pixel; CIFAR adds crop/flip augmentation
        prep = 2.0 * bs * h * w * c
        if str(cfg.get("data_name", "")).startswith("CIFAR"):
            prep *= 3.0
    out: Dict[float, float] = {}
    for r in rates:
        wr = float(r) / grate
        fwd = bwd = 0.0
        nparam = 0
        for name, _insz, _outsz, p, macs in module_table(cfg, wr, bs):
            fl = 2.0 * macs
            fwd += fl
            bwd += fl * (2.0 if any(t in name for t in _MATMUL_LIKE) else 1.0)
            nparam += p
        out[float(r)] = fwd + bwd + _OPT_FLOPS_PER_PARAM * nparam + prep
    return out


#: bytes per parameter element on the wire and in HBM: params, update sums
#: and count masks are all float32 (compute_dtype only narrows activations)
PARAM_ITEMSIZE = 4


def level_param_table(cfg: Dict[str, Any], rates: Optional[list] = None
                      ) -> Dict[float, int]:
    """Analytic per-level parameter COUNTS of the sliced sub-model at each
    rate of the level table (a count view over :func:`level_byte_table`,
    which owns the per-module accounting; the counts match ``model.init``
    trees exactly, which the staticcheck wire audit relies on)."""
    return {r: v["param_bytes"] // PARAM_ITEMSIZE
            for r, v in level_byte_table(cfg, rates).items()}


def level_byte_table(cfg: Dict[str, Any], rates: Optional[list] = None,
                     itemsize: int = PARAM_ITEMSIZE) -> Dict[float, Dict[str, int]]:
    """Analytic per-level byte/shape table (ISSUE 7): for each rate level,

    * ``param_bytes`` -- the sliced sub-model's parameter footprint;
    * ``wire_bytes`` -- the dense per-round reduction payload of that
      level's round program: ``sum(param_bytes) + count_bytes`` (the
      counted-average aggregation psums the update sums AND the
      element-count masks, both param-shaped f32, in ONE bind);
    * ``activation_bytes`` -- per-local-step forward activation output
      bytes at the training batch size (``module_table`` output sizes x
      f32), the per-client working-set term of the HBM budget.

    The wire numbers are exact for the audited programs (verified against
    traced psum operand avals), which is what lets staticcheck enforce the
    wire budget by equality rather than tolerance."""
    from ..analysis.summary import module_table

    grate = cfg["global_model_rate"]
    if rates is None:
        rates = sorted({float(r) for r in cfg["model_rate"]}, reverse=True)
    out: Dict[float, Dict[str, int]] = {}
    for r in rates:
        rows = module_table(cfg, float(r) / grate)
        nparam = int(sum(row[3] for row in rows))
        act = int(sum(int(np.prod(row[2])) for row in rows))
        out[float(r)] = {
            "param_bytes": nparam * itemsize,
            "wire_bytes": 2 * nparam * itemsize,
            "activation_bytes": act * itemsize,
        }
    return out


def level_codec_byte_table(cfg: Dict[str, Any], codec: str,
                           rates: Optional[list] = None,
                           n_leaves: int = 0) -> Dict[float, int]:
    """Analytic per-level COMPRESSED wire bytes of one fused training round
    under ``codec`` (ISSUE 8): the per-participant psum payload of that
    level's flat element count, priced by the one formula in
    :func:`~..compress.codec_payload_bytes`.  THE single source the
    staticcheck wire budget enforces by equality against the traced psum
    operand avals -- there is no second bytes formula.  ``n_leaves`` (the param-tree leaf count) only
    affects the ``signsgd`` scale vector; the fused rounds of both engines
    reduce at the level-a (global) footprint, so their budget is this
    table's top-rate entry."""
    from ..compress import codec_payload_bytes

    return {r: codec_payload_bytes(codec, n, n_leaves)
            for r, n in level_param_table(cfg, rates).items()}


def level_codec_map_byte_table(cfg: Dict[str, Any],
                               codec_map: Dict[float, str],
                               rates: Optional[list] = None,
                               n_leaves: int = 0) -> Dict[float, int]:
    """Analytic per-level wire bytes of one fused GROUPED round under a
    per-level codec map (ISSUE 9 satellite): level ``r``'s payload is its
    SLICED flat element count priced by its own codec -- dense levels move
    ``2 x 4 x n_r`` (f32 sums + counts at sliced shape), lossy levels their
    packed-lane footprint -- and the round's single psum carries the sum
    over levels.  Same single bytes formula
    (:func:`~..compress.codec_payload_bytes`) as every other wire budget,
    so staticcheck still enforces the per-level-codec programs by equality
    against the traced psum operand avals."""
    from ..compress import codec_payload_bytes

    table = level_param_table(cfg, rates)
    missing = set(table) - {float(r) for r in codec_map}
    if missing:
        raise ValueError(f"codec map misses levels {sorted(missing)}: every "
                         f"level in the rate table needs a codec")
    return {r: codec_payload_bytes(codec_map[float(r)], n, n_leaves)
            for r, n in table.items()}


def level_flop_shares(cfg: Dict[str, Any],
                      weights: Optional[Dict[float, float]] = None,
                      rates: Optional[list] = None) -> Dict[float, float]:
    """Normalized expected FLOP share of each rate level: ``weight x
    per-step analytic cost`` (:func:`level_flop_table`), summing to 1.
    ``weights`` defaults to uniform (equal client counts per level)."""
    table = level_flop_table(cfg, rates)
    w = {r: 1.0 for r in table} if weights is None \
        else {float(r): float(v) for r, v in weights.items()}
    raw = {r: w.get(r, 0.0) * f for r, f in table.items()}
    tot = sum(raw.values())
    if tot <= 0.0:
        raise ValueError(f"level FLOP shares degenerate: weights {w}")
    return {r: v / tot for r, v in raw.items()}


def to_width_rates(model_rates: jnp.ndarray, cfg: Dict[str, Any]) -> jnp.ndarray:
    """Absolute model rate -> width/scaler rate relative to the global model
    (``scaler_rate = model_rate / global_model_rate``, ref fed.py:46,
    models/conv.py:79).  Group sizes are already scaled by the global rate, so
    masks must use this relative rate or non-'a' global modes double-shrink."""
    return jnp.asarray(model_rates, jnp.float32) / cfg["global_model_rate"]


def distribute_masked(global_params: Dict[str, jnp.ndarray], model: ModelDef,
                      width_rate) -> Dict[str, jnp.ndarray]:
    """Masked-strategy ``Federation.distribute`` for one client
    (ref fed.py:161-178): active prefix keeps global values, suffix is zero."""
    return mask_params(global_params, model.specs, model.groups, width_rate)


def client_count_masks(global_params: Dict[str, jnp.ndarray], model: ModelDef,
                       width_rate, label_mask) -> Dict[str, jnp.ndarray]:
    """Aggregation contribution masks for one client (width x label split)."""
    shapes = {k: v.shape for k, v in global_params.items()}
    return _count_masks(shapes, model.specs, model.groups, width_rate, label_mask)


def combine_counted(global_params: Dict[str, jnp.ndarray],
                    summed: Dict[str, jnp.ndarray],
                    counts: Dict[str, jnp.ndarray]) -> Dict[str, jnp.ndarray]:
    """Counted average with stale fallback: ``v[count>0] = (sum/count)``,
    elements no client held keep the previous global value (ref fed.py:217-218)."""
    out = {}
    for k, v in global_params.items():
        c = counts[k]
        out[k] = jnp.where(c > 0, summed[k] / jnp.maximum(c, 1.0), v)
    return out


# ---------------------------------------------------------------------------
# Sliced strategy, in-jit half (static prefix slices / zero-pad embeds)
#
# HeteroFL's index sets are always nested prefixes (ref fed.py:46-48) or
# per-head prefixes (ref fed.py:124-131), so at a *static* width rate the
# reference's gather ``v[meshgrid(idx)]`` is a static XLA slice and the
# scatter-back is a zero pad -- no gather/scatter ops, fully fusible.  These
# power the mesh-native rate-grouped engine (parallel/grouped.py).
# ---------------------------------------------------------------------------

def slice_axis(v: jnp.ndarray, group: Group, width_rate: float, axis: int) -> jnp.ndarray:
    """Slice one tensor axis to its active entries at a static ``width_rate``
    (the group's own rule, ``models.spec.GROUP_RULES``)."""
    return group.rule.slice(v, group, width_rate, axis)


def pad_axis(v: jnp.ndarray, group: Group, width_rate: float, axis: int) -> jnp.ndarray:
    """Zero-pad one sliced axis back to full size (inverse of :func:`slice_axis`)."""
    return group.rule.pad(v, group, width_rate, axis)


def extract_sliced_jnp(params: Dict[str, jnp.ndarray], specs: Dict[str, ParamSpec],
                       groups: Dict[str, Group], width_rate: float) -> Dict[str, jnp.ndarray]:
    """In-jit sub-model extraction at a static rate (the traced twin of
    :func:`extract_sliced`; ref fed.py:165-178)."""
    out = {}
    for k, v in params.items():
        for axis, gname in sorted(specs[k].axis_groups.items()):
            v = slice_axis(v, groups[gname], width_rate, axis)
        out[k] = v
    return out


def embed_sliced_jnp(sliced: Dict[str, jnp.ndarray], specs: Dict[str, ParamSpec],
                     groups: Dict[str, Group], width_rate: float) -> Dict[str, jnp.ndarray]:
    """In-jit zero-pad of sliced tensors back to global shapes (the traced
    twin of :func:`embed_sliced`)."""
    out = {}
    for k, v in sliced.items():
        for axis, gname in sorted(specs[k].axis_groups.items()):
            v = pad_axis(v, groups[gname], width_rate, axis)
        out[k] = v
    return out


# ---------------------------------------------------------------------------
# Sliced strategy (host-side gather/scatter, reference-shaped sub-models)
# ---------------------------------------------------------------------------

def active_indices(group: Group, width_rate: float) -> np.ndarray:
    """Concrete active index set of a group at a given rate (host-side)."""
    return group.rule.indices(group, width_rate)


def extract_sliced(params: Dict[str, np.ndarray], specs: Dict[str, ParamSpec],
                   groups: Dict[str, Group], width_rate: float) -> Dict[str, np.ndarray]:
    """Gather a true sub-model's params from the global params
    (the reference's ``v[torch.meshgrid(param_idx)]`` deepcopy, fed.py:165-178)."""
    out = {}
    for k, v in params.items():
        v = np.asarray(v)
        for axis, gname in sorted(specs[k].axis_groups.items()):
            v = np.take(v, active_indices(groups[gname], width_rate), axis=axis)
        out[k] = v.copy()
    return out


def embed_sliced(sliced: Dict[str, np.ndarray], specs: Dict[str, ParamSpec],
                 groups: Dict[str, Group], width_rate: float,
                 full_shapes: Dict[str, tuple]) -> Dict[str, np.ndarray]:
    """Scatter a sub-model's params back into zero full-width tensors
    (inverse of :func:`extract_sliced`; the sliced-strategy half of combine)."""
    out = {}
    for k, small in sliced.items():
        idx_arrays = {axis: active_indices(groups[gname], width_rate)
                      for axis, gname in specs[k].axis_groups.items()}
        if not idx_arrays:
            out[k] = np.asarray(small).copy()
            continue
        full = np.zeros(full_shapes[k], dtype=np.asarray(small).dtype)
        out[k] = _scatter_axes(full, np.asarray(small), idx_arrays)
    return out


def _scatter_axes(full: np.ndarray, small: np.ndarray, idx_arrays: Dict[int, np.ndarray]) -> np.ndarray:
    """full[axes-product of idx] = small, returning full."""
    axes = sorted(idx_arrays)
    perm = axes + [a for a in range(full.ndim) if a not in axes]
    inv = np.argsort(perm)
    fullp = np.transpose(full, perm)
    smallp = np.transpose(small, perm)
    fullp[np.ix_(*[idx_arrays[a] for a in axes])] = smallp
    return np.transpose(fullp, inv)
