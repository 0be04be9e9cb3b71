"""Front 1: the compiled-program auditor.

Lowers every flagship round-program variant -- masked + grouped engines x
replicated/sharded/streaming-cohort (masked) and span/slices/streaming
(grouped) placements x ``superstep_rounds`` in {1, 8} -- on a CPU mesh and
statically enforces:

(a) **no host callbacks** (``pure_callback``/``io_callback``/
    ``debug_callback``) and **no f64** anywhere in a round program;
(b) **donation coverage** -- every donated leaf is consumed by input-output
    aliasing in the optimized HLO, and JAX "donated buffers were not
    usable" warnings are promoted to audit failures (silent memory
    doubling);
(c) **collectives budget** -- psum binds are counted per program and the
    fused grouped round must perform EXACTLY ONE global psum (the PR 2
    invariant), with every collective axis resolvable in the mesh;
(d) **recompile hazard** -- two dispatches with fresh-but-identical host
    inputs leave ``engine.program_cache_size()`` unchanged (weak-type /
    python-scalar cache-key leaks recompile the ~40s flagship program);
(e) **FLOP budget** -- ``cost_analysis()`` FLOPs per level program are
    checked against the analytic shares from
    :func:`~..fed.core.level_flop_shares`;
(f) **wire budget** (ISSUE 7, :mod:`.wire`) -- every collective bind is
    priced from its operand avals and each fused training round must move
    EXACTLY one dense global reduction of the level-a footprint
    (``sum(param_bytes) + count_bytes``, per-level slices for the grouped
    K=1 programs), matched by equality against
    :func:`~..fed.core.level_byte_table`;
(g) **HBM budget** (ISSUE 7, :mod:`.memory`) -- ``memory_analysis()``
    temp/argument/output bytes are required fields held to analytic
    ceilings, with donation-savings accounting;
(h) **reshard detector** (ISSUE 7) -- zero data-movement collectives, in
    the jaxpr (``all_to_all``/``ppermute``) and in the optimized HLO
    (GSPMD-introduced ``all-to-all``/``collective-permute``);
(i) **wire codecs** (ISSUE 8, :mod:`..compress`) -- every lossy codec's
    fused superstep still binds EXACTLY one global psum, its compressed
    payload matches :func:`~..fed.core.level_codec_byte_table` by equality
    (the packed psum operand avals ARE the wire format), the error-feedback
    residual carry is the ONLY donated input (both engines pin resid-only
    donation around an XLA:CPU executable-serialization bug; see
    parallel.round_engine._WireCodecCarry), and the analytic flagship int8
    payload stays <= 25% of the dense baseline (``wire-frontier``);
(j) **telemetry** (ISSUE 10, :mod:`..obs`) -- the ``telemetry='on'``
    program variants carry the in-program health probes at ZERO wire cost:
    same single global psum, same wire bytes by equality, full donation,
    and the k1 step body inside the unchanged kernel budget;
(l) **cohort histograms** (ISSUE 12, :mod:`..obs.hist`) -- the
    ``telemetry='hist'`` variants carry the fixed-bucket cohort
    histograms next to the scalar probes at the SAME budgets: one global
    psum, wire bytes by equality (dense AND int8-codec), full/resid-only
    donation, unchanged k1 step body;
(k) **sampler** (ISSUE 11, :mod:`..fed.sampling`) -- both sampler kinds'
    in-jit draws audited as programs (the legacy ``perm`` superstep stays
    a pinned variant next to the default ``prp`` one, same psum/wire/
    donation/HBM budgets), plus the stream-consistency check
    (:func:`sampler_stream_check`: in-jit == host bitwise, all-ones
    availability == uniform cohort, exact PRP bijection) and sampler
    entries in the recompile-hazard matrix.

Widths: the default audit config keeps the flagship *structure* (5-level
a1-e1 fix mix, both engines, both placements, K in {1, 8}) at test-scale
widths so the whole matrix lowers+compiles in tens of seconds on a CPU --
every property above except the FLOP-share tolerance is width-independent.
``flagship=True`` swaps in the full CIFAR-10 ResNet-18 widths, where the
conv terms dominate and the share tolerance tightens to 2%
(``FLAGSHIP_FLOP_TOL``); at tiny widths the width-independent per-step
costs (RNG, data prep, slicing) are a large fraction of the smallest
levels, so the default tolerance is ``SMALL_FLOP_TOL`` and a strict
monotonicity check carries the regression-catching weight instead.
"""

from __future__ import annotations

import fnmatch
import math
import os
import warnings
from typing import Any, Dict, List, Optional, Set, Tuple

import numpy as np

from .jaxpr_walk import (aliased_outputs, count_collectives, count_psum_joint,
                         count_psum_over, donation_marks, find_callbacks,
                         find_f64, find_reshards, random_bind_files,
                         reshard_ops, scan_body_kernel_count)
from .memory import (analytic_budget, check_memory, collect_memory,
                     donation_accounting)
from .report import AuditReport, Finding, ProgramReport
from .wire import check_wire, program_wire

#: FLOP-share tolerance (max relative error of measured vs analytic level
#: shares).  2% holds where conv/matmul FLOPs dominate (flagship widths);
#: the tiny-width gate config runs the same check at a documented looser
#: bound plus strict share monotonicity.
FLAGSHIP_FLOP_TOL = 0.02
SMALL_FLOP_TOL = 0.45

#: the PR 2 invariant: one global psum per (fused) TRAINING round program
PSUM_BUDGET = 1

#: the ISSUE 4 eval-phase budget: the fused sBN moment reduction + the
#: Global metric reduction, each ONE joint (clients, data) psum bind per
#: eval point's trace (the per-user Local sums stay sharded -- no
#: collective)
EVAL_PSUM_BUDGET = 2

#: the hot-step budget: max INSTRUCTIONS per iteration of the LOCAL-STEP
#: scan body (optimized HLO, CPU-mesh lowering) for the two programs on the
#: level-a critical path.  Sized from the bodies of the one local step there
#: is -- the parameter and momentum leaves carried as the model reads them,
#: the width masks hoisted out of the scan, the update per leaf (masked 176,
#: grouped level-a 171 at the audit widths, XLA:CPU of jaxlib 0.9.0) -- with
#: +5 headroom.  What it guards is GROWTH of the per-step body by a new
#: per-leaf chain: one more reduce per leaf (the gradients' norm taken a
#: second time) gives 188 and fails the audit the same way a second psum
#: would.  What it does NOT see (tests/test_staticcheck.py pins both): masks
#: re-materialised in the step -- loop-invariant ones XLA:CPU moves out
#: itself (176), step-dependent ones fuse into the update's fusions (173) --
#: and the chip's time: the count was green while the v5e's step went from
#: ~20 to 78.6 ms under the flat carry (ROADMAP.md).  Fusions and
#: instructions stay recorded per program and ratcheted by the baseline.
STEP_BODY_BUDGET = {
    "masked/replicated/k1": 181,
    "grouped/span/level-1/k1": 176,
    # ISSUE 10: the health probes live at ROUND level (post-psum), never
    # inside the local-step scan body -- the telemetry-on k1 program is
    # held to the SAME step-body budget as its dense twin
    "masked/replicated/k1-telemetry": 181,
    # ISSUE 12: the cohort histograms are round-level bucketing over the
    # already-emitted per-slot metric sums -- same unchanged step body
    "masked/replicated/k1-hist": 181,
    # ISSUE 15: the quarantine gate lives at ROUND level (after local
    # training, folded into the counted sums before the psum), never
    # inside the local-step scan body -- same unchanged step body
    "masked/replicated/k1-quarantine": 181,
}


def default_audit_cfg(flagship: bool = False) -> Dict[str, Any]:
    """The audit config: flagship federation structure (5-level a1-e1 fix
    mix over 10 users, iid, BN) at test widths (``flagship=True``: full
    CIFAR-10 ResNet-18 widths)."""
    from .. import config as C

    cfg = C.default_cfg()
    cfg["control"] = C.parse_control_name("1_10_0.5_iid_fix_a1-b1-c1-d1-e1_bn_1_1")
    cfg["data_name"] = "CIFAR10" if flagship else "MNIST"
    cfg["model_name"] = "resnet18" if flagship else "conv"
    cfg["synthetic"] = True
    cfg = C.process_control(cfg)
    if not flagship:
        cfg["conv"] = {"hidden_size": [8, 16]}
    cfg["classes_size"] = 10
    return cfg


def build_setup(flagship: bool = False, seed: int = 0) -> Dict[str, Any]:
    """cfg + synthetic client-stacked data + model/params + 8-row CPU mesh.

    Needs >= 5 mesh rows so the slices placement exists (tests/CLI force an
    8-device host platform before jax initialises)."""
    import jax

    from ..data import (fetch_dataset, label_split_masks, split_dataset,
                        stack_client_shards)
    from ..models import make_model
    from ..parallel import make_mesh

    cfg = default_audit_cfg(flagship)
    users = cfg["num_users"]
    n_train = 2000 if flagship else 400
    ds = fetch_dataset(cfg["data_name"], synthetic=True, seed=seed,
                       synthetic_sizes={"train": n_train, "test": 100})
    rng = np.random.default_rng(seed)
    split, lsplit = split_dataset(ds, users, "iid", rng, classes_size=10)
    x, y, m = stack_client_shards(ds["train"].data, ds["train"].target,
                                  split["train"], list(range(users)))
    lm = label_split_masks(lsplit, users, 10)
    data = (x, y, m, lm)
    model = make_model(cfg)
    params = model.init(jax.random.key(seed))
    n_dev = min(8, len(jax.devices()))
    if n_dev < 5:
        raise RuntimeError(
            f"staticcheck audit needs >= 5 devices for the slices placement "
            f"(have {n_dev}); set "
            f"XLA_FLAGS=--xla_force_host_platform_device_count=8 before jax "
            f"initialises (the CLI and tests/conftest.py both do)")
    mesh = make_mesh(n_dev, 1)

    # eval operands for the eval-fused superstep variants (ISSUE 4), staged
    # through the DRIVER'S OWN assembly so the audited operand layout is
    # exactly the one the driver commits
    from ..entry.common import stage_eval_operands

    sbn, local, glob = stage_eval_operands(cfg, ds["train"], ds["test"],
                                           split["test"], lm)
    eval_data = {"sbn": sbn, "local": local, "global": glob}

    # streaming population store (ISSUE 6): the same split as the eager
    # stacks, so the streamed audit variants stage bit-identical cohorts
    from ..parallel import ClientStore

    store = ClientStore.from_split(ds["train"].data, ds["train"].target,
                                   split["train"], lsplit, 10)

    # analytic per-level byte/shape table (ISSUE 7): the wire and HBM
    # budgets' source of truth
    from ..fed.core import level_byte_table

    return {"cfg": cfg, "data": data, "model": model, "params": params,
            "mesh": mesh, "flagship": flagship, "key": jax.random.key(seed),
            "lr": np.float32(0.05), "users": users, "eval_data": eval_data,
            "store": store, "byte_table": level_byte_table(cfg)}


def fused_eval_for(setup):
    """One :class:`~..parallel.evaluation.FusedEval` per setup (memoised):
    the eval-fused audit targets and the recompile check share its committed
    operands, exactly like the driver does."""
    if "fused_eval" not in setup:
        from ..parallel.evaluation import Evaluator

        ev = Evaluator(setup["model"], setup["cfg"], setup["mesh"], seed=0)
        ed = setup["eval_data"]
        setup["fused_eval"] = ev.fused(sbn_batches=ed["sbn"],
                                       local_eval=ed["local"],
                                       global_eval=ed["global"])
    return setup["fused_eval"]


def _sds(shape: Tuple[int, ...], dtype=np.int32):
    import jax

    return jax.ShapeDtypeStruct(shape, dtype)


def _args_bytes(args) -> int:
    """Total byte footprint of a program's example arguments (arrays and
    ShapeDtypeStructs alike) -- the staged-operand term of the analytic HBM
    bound.  PRNG-key leaves have an extended dtype without an itemsize; a
    key is one (2,)-uint32 cell per element."""
    import jax

    total = 0
    for leaf in jax.tree_util.tree_leaves(args):
        shape = getattr(leaf, "shape", None)
        dt = getattr(leaf, "dtype", None)
        if shape is None or dt is None:
            continue
        try:
            total += int(np.prod(shape)) * np.dtype(dt).itemsize
        except TypeError:
            total += int(np.prod(shape)) * 8
    return total


def _mem_expect(byte_table: Dict[float, Dict[str, int]], rate: float,
                clients_per_device: int) -> Dict[str, int]:
    """The per-program analytic-HBM-bound inputs the target builders embed
    in ``expect['mem']``: the GLOBAL parameter footprint (the carry every
    program holds, donated or not), the program's own level activation
    bytes, and its per-device client concurrency."""
    top = max(byte_table)
    return {"param_bytes": byte_table[top]["param_bytes"],
            "activation_bytes": byte_table[rate]["activation_bytes"],
            "clients_per_device": int(clients_per_device)}


def _ceil_div(a: int, b: int) -> int:
    return -(-a // b)


# ---------------------------------------------------------------------------
# the program matrix
# ---------------------------------------------------------------------------

def _masked_targets(setup) -> List[Tuple[str, Any, Tuple, Dict[str, Any]]]:
    """(name, jitted program, example args, expectations) for the masked
    engine: replicated + sharded placements x K in {1, 8}.  Arg shapes
    mirror the engines' own staging math (slot padding/bucketing)."""
    import jax

    from ..parallel import RoundEngine, shard_client_data
    from ..utils.optim import make_traced_lr_fn

    cfg, model, mesh = setup["cfg"], setup["model"], setup["mesh"]
    params, key, lr = setup["params"], setup["key"], setup["lr"]
    users = setup["users"]
    n_dev = mesh.shape["clients"]
    n_leaves = len(jax.tree_util.tree_leaves(params))
    k = 8
    targets = []

    # the masked engine trains the full global model under masks, so every
    # program's single reduction moves the LEVEL-A (global) footprint:
    # sums + count masks, both param-shaped f32 (ISSUE 7 wire budget)
    bt = setup["byte_table"]
    top = max(bt)
    wire = bt[top]["wire_bytes"]

    def mem(cpd: int) -> Dict[str, int]:
        return _mem_expect(bt, top, cpd)

    # replicated
    eng = RoundEngine(model, cfg, mesh)
    eng._lr_fn = make_traced_lr_fn(cfg)
    fix = (eng.fix_rates,) if eng.fix_rates is not None else ()
    data = tuple(setup["data"]) + fix
    slots = users + ((-users) % n_dev)
    targets.append((
        "masked/replicated/k1", eng._build_train(),
        (params, key, lr, _sds((slots,)), _sds((slots,))) + data,
        {"donated": n_leaves, "psum": PSUM_BUDGET, "wire_bytes": wire,
         "mem": mem(_ceil_div(slots, n_dev))}))
    a = int(math.ceil(cfg["frac"] * users))
    targets.append((
        "masked/replicated/k8",
        eng._build_superstep(k, _ceil_div(a, n_dev), True, num_active=a),
        (params, key, np.int32(1)) + data,
        {"donated": n_leaves, "psum": PSUM_BUDGET, "wire_bytes": wire,
         "mem": mem(_ceil_div(a, n_dev))}))
    # sampler variants (ISSUE 11): the default engine above draws its
    # cohort in-jit from the PRP index map (cfg default sampler='prp'); the
    # legacy full-permutation stream stays an audited program too -- same
    # psum/wire/donation/HBM budgets, because the draw is round-level
    # integer work that must never touch a collective or the step body
    eng_perm = RoundEngine(model, dict(cfg, sampler="perm"), mesh)
    eng_perm._lr_fn = make_traced_lr_fn(cfg)
    targets.append((
        "masked/replicated/k8-perm",
        eng_perm._build_superstep(k, _ceil_div(a, n_dev), True, num_active=a),
        (params, key, np.int32(1)) + data,
        {"donated": n_leaves, "psum": PSUM_BUDGET, "wire_bytes": wire,
         "mem": mem(_ceil_div(a, n_dev))}))
    # eval-fused variants (ISSUE 4): the ACCEPTANCE cadence eval_interval=1
    # (every round evaluates; the eval core is traced once per eval point,
    # so the joint-psum budget scales with k) and the boundary cadence
    # eval_interval=K (one eval point)
    fe = fused_eval_for(setup)
    targets.append((
        "masked/replicated/k8-eval1",
        eng._build_superstep(k, _ceil_div(a, n_dev), True, num_active=a,
                             eval_mask=(True,) * k, fused_eval=fe),
        (params, key, np.int32(1)) + data + tuple(fe.ops),
        {"donated": n_leaves, "psum": PSUM_BUDGET, "wire_bytes": wire,
         "psum_eval": EVAL_PSUM_BUDGET * k, "mem": mem(_ceil_div(a, n_dev))}))
    targets.append((
        "masked/replicated/k8-eval8",
        eng._build_superstep(k, _ceil_div(a, n_dev), True, num_active=a,
                             eval_mask=(False,) * (k - 1) + (True,),
                             fused_eval=fe),
        (params, key, np.int32(1)) + data + tuple(fe.ops),
        {"donated": n_leaves, "psum": PSUM_BUDGET, "wire_bytes": wire,
         "psum_eval": EVAL_PSUM_BUDGET, "mem": mem(_ceil_div(a, n_dev))}))

    # streaming cohort superstep (ISSUE 6): the cohort's data stacks ride
    # the scan xs; the program never sees the population.  The staged
    # cohort's REAL committed arrays are the example args (audit only
    # traces/lowers), so the audited layout is the engine's own staging.
    from ..fed.core import superstep_user_schedule

    sched = superstep_user_schedule(key, 1, k, users, a)
    coh = eng.stage_cohort(setup["store"], sched)
    targets.append((
        "masked/stream/k8",
        eng._build_superstep(k, coh.per_dev, False, num_active=coh.a,
                             streaming=True),
        (params, key, np.int32(1), coh.sched) + tuple(coh.data) + fix,
        {"donated": n_leaves, "psum": PSUM_BUDGET, "wire_bytes": wire,
         "mem": mem(coh.per_dev)}))
    targets.append((
        "masked/stream/k8-eval1",
        eng._build_superstep(k, coh.per_dev, False, num_active=coh.a,
                             eval_mask=(True,) * k, fused_eval=fe,
                             streaming=True),
        (params, key, np.int32(1), coh.sched) + tuple(coh.data) + fix
        + tuple(fe.ops),
        {"donated": n_leaves, "psum": PSUM_BUDGET, "wire_bytes": wire,
         "psum_eval": EVAL_PSUM_BUDGET * k, "mem": mem(coh.per_dev)}))

    # sharded: per-user stacks device-sharded over the clients axis
    eng_sh = RoundEngine(model, dict(cfg, data_placement="sharded"), mesh)
    eng_sh._lr_fn = make_traced_lr_fn(cfg)
    data_sh = shard_client_data(mesh, setup["data"]) + fix
    per = _ceil_div(users, n_dev)
    slots_sh = per * n_dev  # every device owns at most `per` active users
    targets.append((
        "masked/sharded/k1", eng_sh._build_train(),
        (params, key, lr, _sds((slots_sh,)), _sds((slots_sh,))) + data_sh,
        {"donated": n_leaves, "psum": PSUM_BUDGET, "wire_bytes": wire,
         "mem": mem(per)}))
    targets.append((
        "masked/sharded/k8", eng_sh._build_superstep(k, per, False),
        (params, key, np.int32(1), _sds((k, slots_sh)), _sds((k, slots_sh)))
        + data_sh,
        {"donated": n_leaves, "psum": PSUM_BUDGET, "wire_bytes": wire,
         "mem": mem(per)}))
    targets.append((
        "masked/sharded/k8-eval1",
        eng_sh._build_superstep(k, per, False, eval_mask=(True,) * k,
                                fused_eval=fe),
        (params, key, np.int32(1), _sds((k, slots_sh)), _sds((k, slots_sh)))
        + data_sh + tuple(fe.ops),
        {"donated": n_leaves, "psum": PSUM_BUDGET, "wire_bytes": wire,
         "psum_eval": EVAL_PSUM_BUDGET * k, "mem": mem(per)}))
    return targets


def _grouped_targets(setup) -> Tuple[List, Dict[str, float], Any]:
    """Targets for the grouped engine (span + slices x K in {1, 8} plus the
    combine), the span per-level program names by rate (the FLOP-budget
    check reads their measured flops), and the slices engine."""
    import jax

    from ..parallel import GroupedRoundEngine
    from ..parallel.grouped import _bucket_pow2
    from ..utils.optim import make_traced_lr_fn

    cfg, mesh = setup["cfg"], setup["mesh"]
    params, key, lr = setup["params"], setup["key"], setup["lr"]
    n_dev = mesh.shape["clients"]
    n_leaves = len(jax.tree_util.tree_leaves(params))
    data = tuple(setup["data"])
    k = 8
    per_level = 2  # 10 users over 5 levels, all active: 2 clients per level

    grp = GroupedRoundEngine(cfg, mesh)
    grp._lr_fn = make_traced_lr_fn(cfg)
    level_rates = sorted(grp.levels, reverse=True)
    targets, level_prog_names = [], {}

    # wire budgets (ISSUE 7): a per-level program psums its SLICED sums +
    # counts (the embed to global shape happens after the reduction), so its
    # payload is that level's 2 x param_bytes; the fused superstep joins the
    # embedded level partials in one GLOBAL (level-a footprint) reduction,
    # exactly like the masked engine
    bt = setup["byte_table"]
    top = max(bt)
    wire_top = bt[top]["wire_bytes"]

    slots = _bucket_pow2(_ceil_div(per_level, n_dev)) * n_dev
    for rate in level_rates:
        name = f"grouped/span/level-{rate:g}/k1"
        level_prog_names[rate] = name
        targets.append((
            name, grp._level_prog(rate, slots),
            (params, key, lr, _sds((slots,))) + data,
            {"donated": 0, "psum": PSUM_BUDGET,
             "wire_bytes": bt[rate]["wire_bytes"],
             "mem": _mem_expect(bt, rate, _ceil_div(slots, n_dev))}))
    psds = jax.tree_util.tree_map(
        lambda v: _sds(v.shape, v.dtype), dict(params))
    targets.append((
        "grouped/span/combine", grp._combine_prog(len(level_rates)),
        (params, [psds] * len(level_rates), [psds] * len(level_rates)),
        {"donated": n_leaves, "psum": 0, "wire_bytes": 0,
         "mem": _mem_expect(bt, top, 0)}))
    per_dev = _bucket_pow2(_ceil_div(per_level, n_dev))
    targets.append((
        "grouped/span/k8-fused", grp._superstep_prog(k, per_dev, "span"),
        (params, key, np.int32(1),
         _sds((k, len(level_rates), per_dev * n_dev))) + data,
        {"donated": n_leaves, "psum": PSUM_BUDGET, "wire_bytes": wire_top,
         "mem": _mem_expect(bt, top, per_dev)}))
    fe = fused_eval_for(setup)
    targets.append((
        "grouped/span/k8-eval1-fused",
        grp._superstep_prog(k, per_dev, "span", eval_mask=(True,) * k,
                            fused_eval=fe),
        (params, key, np.int32(1),
         _sds((k, len(level_rates), per_dev * n_dev))) + data + tuple(fe.ops),
        {"donated": n_leaves, "psum": PSUM_BUDGET, "wire_bytes": wire_top,
         "psum_eval": EVAL_PSUM_BUDGET * k,
         "mem": _mem_expect(bt, top, per_dev)}))

    # streaming cohort superstep (ISSUE 6): level-grouped cohort stacks as
    # scan xs, staged through the engine's own cohort pipeline
    from ..fed.core import superstep_rate_schedule, superstep_user_schedule

    a_stream = cfg["num_users"]  # every user active: all levels populated
    sched_st = superstep_user_schedule(key, 1, k, cfg["num_users"], a_stream)
    rates_st = superstep_rate_schedule(key, 1, k, cfg, sched_st)
    coh = grp.stage_cohort(setup["store"], sched_st, rates_st)
    targets.append((
        "grouped/stream/span/k8",
        grp._superstep_prog(k, coh.per_dev, "span", streaming=True),
        (params, key, np.int32(1), coh.sched) + tuple(coh.data),
        {"donated": n_leaves, "psum": PSUM_BUDGET, "wire_bytes": wire_top,
         "mem": _mem_expect(bt, top, coh.per_dev)}))

    grp_sl = GroupedRoundEngine(dict(cfg, level_placement="slices"), mesh)
    grp_sl._lr_fn = make_traced_lr_fn(cfg)
    if grp_sl.level_placement == "slices":
        for rate in level_rates:
            srange = grp_sl._slices[rate]
            rows = srange[1] - srange[0]
            slots_l = _bucket_pow2(_ceil_div(per_level, rows)) * rows
            targets.append((
                f"grouped/slices/level-{rate:g}/k1",
                grp_sl._level_prog(rate, slots_l,
                                   grp_sl._staging.submesh(*srange), srange),
                (params, key, lr, _sds((slots_l,))) + data,
                {"donated": n_leaves, "psum": PSUM_BUDGET,
                 "wire_bytes": bt[rate]["wire_bytes"],
                 "mem": _mem_expect(bt, rate, _ceil_div(slots_l, rows))}))
        mode, _ = grp_sl._fused_layout()
        if mode == "slices":
            need = max(_ceil_div(per_level, grp_sl._slices[r][1] - grp_sl._slices[r][0])
                       for r in level_rates)
            per_dev_sl = _bucket_pow2(need)
            targets.append((
                "grouped/slices/k8-fused",
                grp_sl._superstep_prog(k, per_dev_sl, "slices"),
                (params, key, np.int32(1), _sds((k, per_dev_sl * n_dev))) + data,
                {"donated": n_leaves, "psum": PSUM_BUDGET,
                 "wire_bytes": wire_top,
                 "mem": _mem_expect(bt, top, per_dev_sl)}))
            targets.append((
                "grouped/slices/k8-eval1-fused",
                grp_sl._superstep_prog(k, per_dev_sl, "slices",
                                       eval_mask=(True,) * k, fused_eval=fe),
                (params, key, np.int32(1), _sds((k, per_dev_sl * n_dev)))
                + data + tuple(fe.ops),
                {"donated": n_leaves, "psum": PSUM_BUDGET,
                 "wire_bytes": wire_top,
                 "psum_eval": EVAL_PSUM_BUDGET * k,
                 "mem": _mem_expect(bt, top, per_dev_sl)}))
            coh_sl = grp_sl.stage_cohort(setup["store"], sched_st, rates_st)
            targets.append((
                "grouped/stream/slices/k8",
                grp_sl._superstep_prog(k, coh_sl.per_dev, "slices",
                                       streaming=True),
                (params, key, np.int32(1), coh_sl.sched) + tuple(coh_sl.data),
                {"donated": n_leaves, "psum": PSUM_BUDGET,
                 "wire_bytes": wire_top,
                 "mem": _mem_expect(bt, top, coh_sl.per_dev)}))

            # multi-host fake-mesh variants (ISSUE 17): the same fused
            # slices programs re-audited with the clients axis classified
            # as crossing process boundaries -- the host-aligned placement
            # puts levels on disjoint hosts, so every byte the training
            # round moves cross-host is the ONE dense level-a reduction
            # (DCN budget enforced by EQUALITY), with zero reshards.
            # wire_only: the compile-side checks already ran on the
            # single-process entries above (same program objects).
            mh = {"dcn_axes": ("clients",), "dcn_budget_bytes": wire_top,
                  "dcn_exact": True, "wire_only": True}
            targets.append((
                "grouped/slices/k8-fused/mh",
                grp_sl._superstep_prog(k, per_dev_sl, "slices"),
                (params, key, np.int32(1), _sds((k, per_dev_sl * n_dev))) + data,
                {"donated": n_leaves, "psum": PSUM_BUDGET,
                 "wire_bytes": wire_top, **mh,
                 "mem": _mem_expect(bt, top, per_dev_sl)}))
            targets.append((
                "grouped/stream/slices/k8/mh",
                grp_sl._superstep_prog(k, coh_sl.per_dev, "slices",
                                       streaming=True),
                (params, key, np.int32(1), coh_sl.sched) + tuple(coh_sl.data),
                {"donated": n_leaves, "psum": PSUM_BUDGET,
                 "wire_bytes": wire_top, **mh,
                 "mem": _mem_expect(bt, top, coh_sl.per_dev)}))
    return targets, level_prog_names, grp_sl


def _codec_targets(setup) -> List[Tuple[str, Any, Tuple, Dict[str, Any]]]:
    """Wire-codec variants (ISSUE 8): every lossy codec's fused superstep
    for both engines, plus the int8 placement/eval spread.

    The compressed payload rides the SAME single psum bind the dense
    programs are budgeted on, so ``psum`` stays at :data:`PSUM_BUDGET`; the
    wire budget switches to :func:`~..fed.core.level_codec_byte_table` --
    still enforced by EQUALITY, because the packed int32/f32 psum operand
    avals ARE the wire format.  Donation: every codec program donates ONLY
    the error-feedback residual -- donating the params carry alongside a
    params-sized resid output trips an XLA:CPU serialized-executable
    aliasing bug in BOTH engines (see parallel.round_engine._WireCodecCarry),
    so the audit pins codec programs at exactly 1 donated leaf with the
    residual's bytes in the savings accounting (a budgeted cost, not a
    silent shortfall)."""
    import jax

    from ..compress import LOSSY_CODECS, resid_slots
    from ..fed.core import level_codec_byte_table
    from ..ops.flatspec import FlatSpec
    from ..parallel import GroupedRoundEngine, RoundEngine, shard_client_data
    from ..utils.optim import make_traced_lr_fn

    cfg, model, mesh = setup["cfg"], setup["model"], setup["mesh"]
    params, key = setup["params"], setup["key"]
    users = setup["users"]
    n_dev = mesh.shape["clients"]
    n_leaves = len(jax.tree_util.tree_leaves(params))
    total = FlatSpec.of(params).total
    bt = setup["byte_table"]
    top = max(bt)
    k = 8
    a = int(math.ceil(cfg["frac"] * users))
    per_level = 2
    targets = []

    def mem(cpd: int) -> Dict[str, int]:
        return _mem_expect(bt, top, cpd)

    def resid_sds(codec: str):
        return _sds((n_dev, resid_slots(codec), total), np.float32)

    fe = fused_eval_for(setup)
    from ..parallel.grouped import _bucket_pow2

    per_dev_g = _bucket_pow2(_ceil_div(per_level, n_dev))
    for codec in LOSSY_CODECS:
        wire = level_codec_byte_table(cfg, codec, n_leaves=n_leaves)[top]
        # resid-only donation (see the docstring); the residual's global
        # footprint is what aliasing can save
        resid_bytes = n_dev * resid_slots(codec) * total * 4
        expect = {"donated": 1, "psum": PSUM_BUDGET, "wire_bytes": wire,
                  "donated_bytes": resid_bytes}
        ceng = RoundEngine(model, dict(cfg, wire_codec=codec), mesh)
        ceng._lr_fn = make_traced_lr_fn(cfg)
        fix = (ceng.fix_rates,) if ceng.fix_rates is not None else ()
        data = tuple(setup["data"]) + fix
        targets.append((
            f"masked/replicated/k8-{codec}",
            ceng._build_superstep(k, _ceil_div(a, n_dev), True, num_active=a),
            (params, resid_sds(codec), key, np.int32(1)) + data,
            {**expect, "mem": mem(_ceil_div(a, n_dev))}))
        cgrp = GroupedRoundEngine(dict(cfg, wire_codec=codec), mesh)
        cgrp._lr_fn = make_traced_lr_fn(cfg)
        targets.append((
            f"grouped/span/k8-fused-{codec}",
            cgrp._superstep_prog(k, per_dev_g, "span"),
            (params, resid_sds(codec), key, np.int32(1),
             _sds((k, len(cgrp.levels), per_dev_g * n_dev)))
            + tuple(setup["data"]),
            {**expect, "mem": mem(per_dev_g)}))
        if codec != "int8":
            continue
        # int8 carries the placement/eval spread: the sharded slot schedule,
        # the slices layout, and the eval-fused program whose EVAL phase
        # stays dense (only the training reduction compresses)
        eng_sh = RoundEngine(model, dict(cfg, data_placement="sharded",
                                         wire_codec=codec), mesh)
        eng_sh._lr_fn = make_traced_lr_fn(cfg)
        per = _ceil_div(users, n_dev)
        slots_sh = per * n_dev
        targets.append((
            f"masked/sharded/k8-{codec}",
            eng_sh._build_superstep(k, per, False),
            (params, resid_sds(codec), key, np.int32(1),
             _sds((k, slots_sh)), _sds((k, slots_sh)))
            + shard_client_data(mesh, setup["data"]) + fix,
            {**expect, "mem": mem(per)}))
        targets.append((
            f"masked/replicated/k8-eval8-{codec}",
            ceng._build_superstep(k, _ceil_div(a, n_dev), True, num_active=a,
                                  eval_mask=(False,) * (k - 1) + (True,),
                                  fused_eval=fe),
            (params, resid_sds(codec), key, np.int32(1)) + data
            + tuple(fe.ops),
            {**expect, "psum_eval": EVAL_PSUM_BUDGET,
             "mem": mem(_ceil_div(a, n_dev))}))
        grp_sl = GroupedRoundEngine(dict(cfg, level_placement="slices",
                                         wire_codec=codec), mesh)
        grp_sl._lr_fn = make_traced_lr_fn(cfg)
        mode, _ = grp_sl._fused_layout()
        if mode == "slices":
            need = max(_ceil_div(per_level,
                                 grp_sl._slices[r][1] - grp_sl._slices[r][0])
                       for r in grp_sl.levels)
            per_dev_sl = _bucket_pow2(need)
            targets.append((
                f"grouped/slices/k8-fused-{codec}",
                grp_sl._superstep_prog(k, per_dev_sl, "slices"),
                (params, resid_sds(codec), key, np.int32(1),
                 _sds((k, per_dev_sl * n_dev))) + tuple(setup["data"]),
                {**expect, "mem": mem(per_dev_sl)}))
    return targets


def _sched_targets(setup) -> List[Tuple[str, Any, Tuple, Dict[str, Any]]]:
    """Scheduler variants (ISSUE 9): the program matrix grows the scenario
    mechanisms so the standing gates cover them --

    * ``-trace``: the masked in-jit sampler with an availability trace
      riding as a replicated program argument (the selection arithmetic
      adds NO collective: one global psum, dense wire budget, full params
      donation, all unchanged);
    * ``-deadline``: per-client step truncation (pure in-scan arithmetic:
      same budgets as lockstep) for both engines;
    * ``-buffered``: the buffered-async staleness carry -- donation pins to
      the buffer ONLY (the codec programs' XLA:CPU serialization-bug
      policy), the wire budget stays the one dense reduction (buffering is
      post-psum), and the carry's bytes land in the donation-savings
      accounting;
    * ``-perlevel``: the grouped per-level codec map (level-a int8, rest
      dense): ONE psum bind whose payload is budgeted BY EQUALITY against
      :func:`~..fed.core.level_codec_map_byte_table`'s per-level sum.
    """
    import jax

    from ..fed.core import level_codec_map_byte_table
    from ..ops.flatspec import FlatSpec
    from ..parallel import GroupedRoundEngine, RoundEngine
    from ..parallel.grouped import _bucket_pow2
    from ..sched import markov_trace
    from ..utils.optim import make_traced_lr_fn

    cfg, model, mesh = setup["cfg"], setup["model"], setup["mesh"]
    params, key = setup["params"], setup["key"]
    users = setup["users"]
    n_dev = mesh.shape["clients"]
    n_leaves = len(jax.tree_util.tree_leaves(params))
    total = FlatSpec.of(params).total
    bt = setup["byte_table"]
    top = max(bt)
    wire = bt[top]["wire_bytes"]
    k = 8
    a = int(math.ceil(cfg["frac"] * users))
    per_dev = _ceil_div(a, n_dev)
    per_level = 2
    per_dev_g = _bucket_pow2(_ceil_div(per_level, n_dev))
    data = tuple(setup["data"])
    targets = []

    def mem(cpd: int) -> Dict[str, int]:
        return _mem_expect(bt, top, cpd)

    # availability trace, in-jit sampling (masked replicated)
    trace = markov_trace(users, k, 0.6, 0.4, seed=0)
    tcfg = dict(cfg, schedule={"kind": "trace", "trace": trace.tolist()})
    eng_tr = RoundEngine(model, tcfg, mesh)
    eng_tr._lr_fn = make_traced_lr_fn(cfg)
    fix = (eng_tr.fix_rates,) if eng_tr.fix_rates is not None else ()
    targets.append((
        "masked/replicated/k8-trace",
        eng_tr._build_superstep(k, per_dev, True, num_active=a),
        (params, key, np.int32(1), eng_tr._sched_spec.trace) + data + fix,
        {"donated": n_leaves, "psum": PSUM_BUDGET, "wire_bytes": wire,
         "mem": mem(per_dev)}))

    # deadline stragglers: both engines
    dcfg = dict(cfg, schedule={"deadline": {"min_frac": 0.5}})
    eng_dl = RoundEngine(model, dcfg, mesh)
    eng_dl._lr_fn = make_traced_lr_fn(cfg)
    targets.append((
        "masked/replicated/k8-deadline",
        eng_dl._build_superstep(k, per_dev, True, num_active=a),
        (params, key, np.int32(1)) + data + fix,
        {"donated": n_leaves, "psum": PSUM_BUDGET, "wire_bytes": wire,
         "mem": mem(per_dev)}))
    grp_dl = GroupedRoundEngine(dcfg, mesh)
    grp_dl._lr_fn = make_traced_lr_fn(cfg)
    targets.append((
        "grouped/span/k8-fused-deadline",
        grp_dl._superstep_prog(k, per_dev_g, "span"),
        (params, key, np.int32(1),
         _sds((k, len(grp_dl.levels), per_dev_g * n_dev))) + data,
        {"donated": n_leaves, "psum": PSUM_BUDGET, "wire_bytes": wire,
         "mem": mem(per_dev_g)}))

    # buffered-async aggregation: both engines, buf-only donation
    bcfg = dict(cfg, schedule={"aggregation": "buffered"})
    buf_sds = _sds((2, total), np.float32)
    buf_bytes = 2 * total * 4
    eng_bf = RoundEngine(model, bcfg, mesh)
    eng_bf._lr_fn = make_traced_lr_fn(cfg)
    targets.append((
        "masked/replicated/k8-buffered",
        eng_bf._build_superstep(k, per_dev, True, num_active=a),
        (params, buf_sds, key, np.int32(1)) + data + fix,
        {"donated": 1, "psum": PSUM_BUDGET, "wire_bytes": wire,
         "donated_bytes": buf_bytes, "mem": mem(per_dev)}))
    grp_bf = GroupedRoundEngine(bcfg, mesh)
    grp_bf._lr_fn = make_traced_lr_fn(cfg)
    targets.append((
        "grouped/span/k8-fused-buffered",
        grp_bf._superstep_prog(k, per_dev_g, "span"),
        (params, buf_sds, key, np.int32(1),
         _sds((k, len(grp_bf.levels), per_dev_g * n_dev))) + data,
        {"donated": 1, "psum": PSUM_BUDGET, "wire_bytes": wire,
         "donated_bytes": buf_bytes, "mem": mem(per_dev_g)}))

    # per-level codec map (ISSUE 9 satellite): level-a int8, rest dense --
    # the single bind's payload equals the per-level byte-table sum
    level_rates = sorted(bt, reverse=True)
    codec_map = {r: ("int8" if r == top else "dense") for r in level_rates}
    # the per-level map is a grouped-superstep-only feature, and
    # resolve_codec_cfg (which the engine ctor re-applies) refuses it
    # elsewhere -- declare the strategy/K this target actually audits
    mcfg = dict(cfg, strategy="grouped", superstep_rounds=k,
                wire_codec={f"{r:g}": c for r, c in codec_map.items()})
    grp_pl = GroupedRoundEngine(mcfg, mesh)
    grp_pl._lr_fn = make_traced_lr_fn(cfg)
    lay = grp_pl._map_layout(params)
    wire_map = sum(level_codec_map_byte_table(
        cfg, codec_map, n_leaves=n_leaves).values())
    resid_bytes = n_dev * 2 * lay["total_lossy"] * 4
    targets.append((
        "grouped/span/k8-fused-perlevel",
        grp_pl._superstep_prog(k, per_dev_g, "span"),
        (params, _sds((n_dev, 2, lay["total_lossy"]), np.float32), key,
         np.int32(1), _sds((k, len(grp_pl.levels), per_dev_g * n_dev)))
        + data,
        {"donated": 1, "psum": PSUM_BUDGET, "wire_bytes": wire_map,
         "donated_bytes": resid_bytes, "mem": mem(per_dev_g)}))
    # per-level codec map x slices layout (ISSUE 14 satellite, retiring
    # the PR 9 refusal): every switch branch emits every level's payload
    # structure (identity payloads for non-owned levels), so the single
    # bind's operand bytes equal the SAME per-level byte-table sum as the
    # span map -- enforced by equality against the traced avals
    grp_pl_sl = GroupedRoundEngine(dict(mcfg, level_placement="slices"),
                                   mesh)
    grp_pl_sl._lr_fn = make_traced_lr_fn(cfg)
    mode_sl, _ = grp_pl_sl._fused_layout()
    if mode_sl == "slices":
        need = max(_ceil_div(per_level,
                             grp_pl_sl._slices[r][1] - grp_pl_sl._slices[r][0])
                   for r in grp_pl_sl.levels)
        per_dev_sl = _bucket_pow2(need)
        targets.append((
            "grouped/slices/k8-fused-perlevel",
            grp_pl_sl._superstep_prog(k, per_dev_sl, "slices"),
            (params, _sds((n_dev, 2, lay["total_lossy"]), np.float32), key,
             np.int32(1), _sds((k, per_dev_sl * n_dev))) + data,
            {"donated": 1, "psum": PSUM_BUDGET, "wire_bytes": wire_map,
             "donated_bytes": resid_bytes, "mem": mem(per_dev_sl)}))
    return targets


def _arms_targets(setup) -> List[Tuple[str, Any, Tuple, Dict[str, Any]]]:
    """Arms-multiplexer variants (ISSUE 14): the E-arm vmapped supersteps
    of both engines at ARMS-SCALED budgets.

    The batched counted-average reduction stays EXACTLY one psum bind per
    fused training round (a vmapped pytree psum is one bind -- the
    ``psum`` budget does NOT scale with E), while the bind's operand
    bytes scale linearly: the wire budget is ``E x`` the per-arm dense
    reduction, enforced by equality against the traced avals.  The HBM
    budget scales the params carry and the per-device client concurrency
    by E (each arm's slot cohort trains concurrently).  Program FLOPs are
    held to E-linearity by :func:`arms_flop_check` against the unbatched
    twin.  Donation pins to ZERO leaves: donating the E-stacked params
    carry trips the XLA:CPU deserialized-executable aliasing bug (see
    ``round_engine._build_superstep``), so the arms programs keep the
    carry undonated -- a budgeted extra params buffer, not a silent
    coverage shortfall."""
    import jax

    from ..fed.core import arm_stream_keys
    from ..multi import default_seeds
    from ..parallel import GroupedRoundEngine, RoundEngine
    from ..parallel.grouped import _bucket_pow2
    from ..utils.optim import make_traced_lr_fn

    cfg, model, mesh = setup["cfg"], setup["model"], setup["mesh"]
    params, key = setup["params"], setup["key"]
    users = setup["users"]
    n_dev = mesh.shape["clients"]
    bt = setup["byte_table"]
    top = max(bt)
    wire = bt[top]["wire_bytes"]
    k = 8
    a = int(math.ceil(cfg["frac"] * users))
    per_dev = _ceil_div(a, n_dev)
    per_level = 2
    per_dev_g = _bucket_pow2(_ceil_div(per_level, n_dev))
    targets = []

    def amem(cpd: int, e: int) -> Dict[str, int]:
        m = _mem_expect(bt, top, cpd)
        # the params carry (and its donated/output footprint) stacks E
        # arms; per-device client concurrency multiplies the same way
        return {"param_bytes": e * m["param_bytes"],
                "activation_bytes": m["activation_bytes"],
                "clients_per_device": e * cpd}

    def stacked_params(e: int):
        return jax.tree_util.tree_map(
            lambda v: _sds((e,) + tuple(v.shape), v.dtype), dict(params))

    for e in (2, 4):
        acfg = dict(cfg, arms=e)
        eng = RoundEngine(model, acfg, mesh)
        eng._lr_fn = make_traced_lr_fn(cfg)
        fix = (eng.fix_rates,) if eng.fix_rates is not None else ()
        data = tuple(setup["data"]) + fix
        keys_e = arm_stream_keys(key, default_seeds(e))
        scales_e = np.ones(e, np.float32)
        targets.append((
            f"masked/replicated/k8-arms{e}",
            eng._build_superstep(k, per_dev, True, num_active=a, arms=e),
            (stacked_params(e), keys_e, np.int32(1), scales_e) + data,
            {"donated": 0, "psum": PSUM_BUDGET,
             "wire_bytes": e * wire, "mem": amem(per_dev, e)}))
    grp = GroupedRoundEngine(dict(cfg, arms=2), mesh)
    grp._lr_fn = make_traced_lr_fn(cfg)
    keys_2 = arm_stream_keys(key, default_seeds(2))
    # grouped arms share the host user/rate schedule, so the count masks
    # are ARM-INVARIANT and vmap leaves them unbatched: the single bind
    # carries E sum payloads + ONE counts payload -- (E+1)/2 x the dense
    # wire, tighter than the masked engine's E x (whose per-arm cohorts
    # batch the counts too).  Still enforced by equality.
    targets.append((
        "grouped/span/k8-fused-arms2",
        grp._superstep_prog(k, per_dev_g, "span", arms=2),
        (stacked_params(2), keys_2, np.int32(1), np.ones(2, np.float32),
         _sds((k, len(grp.levels), per_dev_g * n_dev)))
        + tuple(setup["data"]),
        {"donated": 0, "psum": PSUM_BUDGET,
         "wire_bytes": (2 + 1) * wire // 2,
         "mem": amem(per_dev_g, 2)}))
    return targets


def arms_flop_check(report: "AuditReport") -> Dict[str, Any]:
    """FLOP linearity of the arms axis (ISSUE 14): the MARGINAL cost of an
    arm is constant -- ``flops(E=4) == 2 x flops(E=2)`` to 0.1% (each arm
    re-runs the identical per-arm math; doubling the batch doubles it) --
    and an E-arm program stays within a few percent of ``E x`` the
    unbatched twin (the small super-E offset is the per-arm in-jit cohort
    draw and LR scaling that the solo program binds only once; a blowout
    here means the vmap fell off the batched lowering).  Read from the
    per-program ``cost_analysis`` numbers already recorded by the audit
    (nothing recompiles here)."""
    out: Dict[str, Any] = {"ok": True, "pairs": {}}

    def flops_of(name):
        return getattr(report.programs.get(name), "flops", None)

    f2 = flops_of("masked/replicated/k8-arms2")
    f4 = flops_of("masked/replicated/k8-arms4")
    if f2 and f4:
        out["pairs"]["masked-arms4-vs-arms2"] = {
            "flops": f4, "half_flops": f2, "ratio": round(f4 / f2, 6)}
        if abs(f4 / f2 - 2.0) > 2e-3:
            report.fail(out, "arms-flop-linearity",
                        f"masked k8 arms4 compiled flops {f4:.4g} are "
                        f"{f4 / f2:.6f}x arms2's ({f2:.4g}); the marginal "
                        f"arm cost must be constant (2x to 0.1%)")
    for arms_name, solo_name, e in (
            ("masked/replicated/k8-arms2", "masked/replicated/k8", 2),
            ("masked/replicated/k8-arms4", "masked/replicated/k8", 4),
            ("grouped/span/k8-fused-arms2", "grouped/span/k8-fused", 2)):
        fa, fs = flops_of(arms_name), flops_of(solo_name)
        if not fa or not fs:
            continue  # cost analysis unavailable on this backend
        ratio = fa / fs
        out["pairs"][arms_name] = {"flops": fa, "solo_flops": fs,
                                   "ratio": round(ratio, 6), "expect": e}
        if not e <= ratio <= 1.1 * e:
            report.fail(out, "arms-flop-linearity",
                        f"{arms_name}: compiled flops {fa:.4g} are "
                        f"{ratio:.6f}x the unbatched {solo_name} "
                        f"({fs:.4g}), outside [{e}, {1.1 * e:g}]: the "
                        f"arms axis must scale FLOPs ~{e}x (per-arm draw "
                        f"overhead only)")
    return out


def _obs_targets(setup) -> List[Tuple[str, Any, Tuple, Dict[str, Any]]]:
    """Telemetry variants (ISSUE 10): ``telemetry='on'`` folds the health
    probes into the metrics pytree of every round core, and these targets
    pin the zero-cost contract statically -- SAME single global psum, SAME
    dense (or codec) wire bytes by equality (the probes derive from
    already-reduced values and per-device partials, never a new
    collective), full params donation, and the k1 program held to the
    unchanged step-body kernel budget (the probes live outside the
    local-step scan).  The int8 variant proves the probe of the
    error-feedback residual rides the codec programs without touching
    their resid-only donation policy or compressed payload."""
    import jax

    from ..compress import resid_slots
    from ..fed.core import level_codec_byte_table
    from ..ops.flatspec import FlatSpec
    from ..parallel import GroupedRoundEngine, RoundEngine
    from ..parallel.grouped import _bucket_pow2
    from ..utils.optim import make_traced_lr_fn

    cfg, model, mesh = setup["cfg"], setup["model"], setup["mesh"]
    params, key, lr = setup["params"], setup["key"], setup["lr"]
    users = setup["users"]
    n_dev = mesh.shape["clients"]
    n_leaves = len(jax.tree_util.tree_leaves(params))
    bt = setup["byte_table"]
    top = max(bt)
    wire = bt[top]["wire_bytes"]
    k = 8
    a = int(math.ceil(cfg["frac"] * users))
    per_dev = _ceil_div(a, n_dev)
    per_level = 2
    per_dev_g = _bucket_pow2(_ceil_div(per_level, n_dev))
    targets = []

    def mem(cpd: int) -> Dict[str, int]:
        return _mem_expect(bt, top, cpd)

    tcfg = dict(cfg, telemetry="on")
    eng = RoundEngine(model, tcfg, mesh)
    eng._lr_fn = make_traced_lr_fn(cfg)
    fix = (eng.fix_rates,) if eng.fix_rates is not None else ()
    data = tuple(setup["data"]) + fix
    slots = users + ((-users) % n_dev)
    targets.append((
        "masked/replicated/k1-telemetry", eng._build_train(),
        (params, key, lr, _sds((slots,)), _sds((slots,))) + data,
        {"donated": n_leaves, "psum": PSUM_BUDGET, "wire_bytes": wire,
         "mem": mem(_ceil_div(slots, n_dev))}))
    targets.append((
        "masked/replicated/k8-telemetry",
        eng._build_superstep(k, per_dev, True, num_active=a),
        (params, key, np.int32(1)) + data,
        {"donated": n_leaves, "psum": PSUM_BUDGET, "wire_bytes": wire,
         "mem": mem(per_dev)}))

    grp = GroupedRoundEngine(tcfg, mesh)
    grp._lr_fn = make_traced_lr_fn(cfg)
    targets.append((
        "grouped/span/k8-fused-telemetry",
        grp._superstep_prog(k, per_dev_g, "span"),
        (params, key, np.int32(1),
         _sds((k, len(grp.levels), per_dev_g * n_dev))) + data[:4],
        {"donated": n_leaves, "psum": PSUM_BUDGET, "wire_bytes": wire,
         "mem": mem(per_dev_g)}))

    total = FlatSpec.of(params).total
    ceng = RoundEngine(model, dict(cfg, telemetry="on", wire_codec="int8"),
                       mesh)
    ceng._lr_fn = make_traced_lr_fn(cfg)
    wire_i8 = level_codec_byte_table(cfg, "int8", n_leaves=n_leaves)[top]
    resid_bytes = n_dev * resid_slots("int8") * total * 4
    targets.append((
        "masked/replicated/k8-telemetry-int8",
        ceng._build_superstep(k, per_dev, True, num_active=a),
        (params, _sds((n_dev, resid_slots("int8"), total), np.float32), key,
         np.int32(1)) + data,
        {"donated": 1, "psum": PSUM_BUDGET, "wire_bytes": wire_i8,
         "donated_bytes": resid_bytes, "mem": mem(per_dev)}))
    return targets


def _quarantine_targets(setup) -> List[Tuple[str, Any, Tuple,
                                             Dict[str, Any]]]:
    """Client-update quarantine variants (ISSUE 15 tentpole): the
    finiteness (+ norm) gate folds into the counted sums and counts BEFORE
    the single global psum, from values each device already holds -- so
    these targets pin quarantine='on' to the EXACT budgets of the dense
    twins: SAME one psum, SAME dense wire bytes by equality (the gate is
    elementwise math + the one [1]-shaped obs_quarantine metrics leaf,
    never a collective), full params donation, and the k1 program held to
    the unchanged step-body kernel budget (the gate lives at round level,
    outside the local-step scan).  The max_norm variant proves the
    masked-update-norm term also stays collective-free; telemetry stays
    OFF here, pinning the counter's ride-along contract on its own."""
    import jax

    from ..parallel import GroupedRoundEngine, RoundEngine
    from ..parallel.grouped import _bucket_pow2
    from ..utils.optim import make_traced_lr_fn

    cfg, model, mesh = setup["cfg"], setup["model"], setup["mesh"]
    params, key, lr = setup["params"], setup["key"], setup["lr"]
    users = setup["users"]
    n_dev = mesh.shape["clients"]
    n_leaves = len(jax.tree_util.tree_leaves(params))
    bt = setup["byte_table"]
    top = max(bt)
    wire = bt[top]["wire_bytes"]
    k = 8
    a = int(math.ceil(cfg["frac"] * users))
    per_dev = _ceil_div(a, n_dev)
    per_dev_g = _bucket_pow2(_ceil_div(2, n_dev))

    def mem(cpd: int) -> Dict[str, int]:
        return _mem_expect(bt, top, cpd)

    qcfg = dict(cfg, quarantine="on")
    eng = RoundEngine(model, qcfg, mesh)
    eng._lr_fn = make_traced_lr_fn(cfg)
    fix = (eng.fix_rates,) if eng.fix_rates is not None else ()
    data = tuple(setup["data"]) + fix
    slots = users + ((-users) % n_dev)
    targets = [(
        "masked/replicated/k1-quarantine", eng._build_train(),
        (params, key, lr, _sds((slots,)), _sds((slots,))) + data,
        {"donated": n_leaves, "psum": PSUM_BUDGET, "wire_bytes": wire,
         "mem": mem(_ceil_div(slots, n_dev))}), (
        "masked/replicated/k8-quarantine",
        eng._build_superstep(k, per_dev, True, num_active=a),
        (params, key, np.int32(1)) + data,
        {"donated": n_leaves, "psum": PSUM_BUDGET, "wire_bytes": wire,
         "mem": mem(per_dev)})]

    neng = RoundEngine(model, dict(cfg, quarantine={"max_norm": 100.0}),
                       mesh)
    neng._lr_fn = make_traced_lr_fn(cfg)
    targets.append((
        "masked/replicated/k8-quarantine-norm",
        neng._build_superstep(k, per_dev, True, num_active=a),
        (params, key, np.int32(1)) + data,
        {"donated": n_leaves, "psum": PSUM_BUDGET, "wire_bytes": wire,
         "mem": mem(per_dev)}))

    grp = GroupedRoundEngine(qcfg, mesh)
    grp._lr_fn = make_traced_lr_fn(cfg)
    targets.append((
        "grouped/span/k8-fused-quarantine",
        grp._superstep_prog(k, per_dev_g, "span"),
        (params, key, np.int32(1),
         _sds((k, len(grp.levels), per_dev_g * n_dev))) + data[:4],
        {"donated": n_leaves, "psum": PSUM_BUDGET, "wire_bytes": wire,
         "mem": mem(per_dev_g)}))
    return targets


def _obs_hist_targets(setup) -> List[Tuple[str, Any, Tuple, Dict[str, Any]]]:
    """Cohort-histogram telemetry variants (ISSUE 12): ``telemetry='hist'``
    folds the fixed-bucket cohort histograms (obs/hist.py: per-client
    loss, deadline step fraction, level membership, buffered staleness
    magnitude) into the metrics pytree NEXT TO the scalar probes -- and
    these targets pin the same zero-cost contract the ISSUE 10 variants
    pin: IDENTICAL single-global-psum, wire-byte (by equality), donation
    and step-body budgets as the scalar-probe/dense twins.  The bucketing
    is one searchsorted + scatter-add per histogram over per-slot values
    each device already holds -- per-device partials riding the metrics
    out-spec, never a collective.  The int8 variant proves the histograms
    ride the codec programs at the compressed wire budget and resid-only
    donation unchanged."""
    import jax

    from ..compress import resid_slots
    from ..fed.core import level_codec_byte_table
    from ..ops.flatspec import FlatSpec
    from ..parallel import GroupedRoundEngine, RoundEngine
    from ..parallel.grouped import _bucket_pow2
    from ..utils.optim import make_traced_lr_fn

    cfg, model, mesh = setup["cfg"], setup["model"], setup["mesh"]
    params, key, lr = setup["params"], setup["key"], setup["lr"]
    users = setup["users"]
    n_dev = mesh.shape["clients"]
    n_leaves = len(jax.tree_util.tree_leaves(params))
    bt = setup["byte_table"]
    top = max(bt)
    wire = bt[top]["wire_bytes"]
    k = 8
    a = int(math.ceil(cfg["frac"] * users))
    per_dev = _ceil_div(a, n_dev)
    per_level = 2
    per_dev_g = _bucket_pow2(_ceil_div(per_level, n_dev))
    targets = []

    def mem(cpd: int) -> Dict[str, int]:
        return _mem_expect(bt, top, cpd)

    hcfg = dict(cfg, telemetry="hist")
    eng = RoundEngine(model, hcfg, mesh)
    eng._lr_fn = make_traced_lr_fn(cfg)
    fix = (eng.fix_rates,) if eng.fix_rates is not None else ()
    data = tuple(setup["data"]) + fix
    slots = users + ((-users) % n_dev)
    targets.append((
        "masked/replicated/k1-hist", eng._build_train(),
        (params, key, lr, _sds((slots,)), _sds((slots,))) + data,
        {"donated": n_leaves, "psum": PSUM_BUDGET, "wire_bytes": wire,
         "mem": mem(_ceil_div(slots, n_dev))}))
    targets.append((
        "masked/replicated/k8-hist",
        eng._build_superstep(k, per_dev, True, num_active=a),
        (params, key, np.int32(1)) + data,
        {"donated": n_leaves, "psum": PSUM_BUDGET, "wire_bytes": wire,
         "mem": mem(per_dev)}))

    grp = GroupedRoundEngine(hcfg, mesh)
    grp._lr_fn = make_traced_lr_fn(cfg)
    targets.append((
        "grouped/span/k8-fused-hist",
        grp._superstep_prog(k, per_dev_g, "span"),
        (params, key, np.int32(1),
         _sds((k, len(grp.levels), per_dev_g * n_dev))) + data[:4],
        {"donated": n_leaves, "psum": PSUM_BUDGET, "wire_bytes": wire,
         "mem": mem(per_dev_g)}))

    total = FlatSpec.of(params).total
    ceng = RoundEngine(model, dict(cfg, telemetry="hist", wire_codec="int8"),
                       mesh)
    ceng._lr_fn = make_traced_lr_fn(cfg)
    wire_i8 = level_codec_byte_table(cfg, "int8", n_leaves=n_leaves)[top]
    resid_bytes = n_dev * resid_slots("int8") * total * 4
    targets.append((
        "masked/replicated/k8-hist-int8",
        ceng._build_superstep(k, per_dev, True, num_active=a),
        (params, _sds((n_dev, resid_slots("int8"), total), np.float32), key,
         np.int32(1)) + data,
        {"donated": 1, "psum": PSUM_BUDGET, "wire_bytes": wire_i8,
         "donated_bytes": resid_bytes, "mem": mem(per_dev)}))
    return targets


def codec_frontier_check(report: "AuditReport") -> Dict[str, Any]:
    """The analytic flagship compression frontier (ISSUE 8 acceptance): each
    codec's per-round payload at full CIFAR-10 ResNet-18 widths vs the
    dense 89.4 MB baseline, all numbers from the ONE byte formula
    (:func:`~..compress.codec_payload_bytes` via the fed.core tables, no
    lowering needed).  Enforced: the int8 payload is <= 25% of dense (the
    8-bit value lane + 8-bit count lane vs two f32 trees; the small slack
    absorbs the <= 1 padded lane word per packed stream).  The signsgd row
    excludes its per-leaf scale vector (a few hundred bytes against tens of
    MB -- the audited small-width programs DO price it exactly)."""
    from ..compress import LOSSY_CODECS
    from ..fed.core import level_byte_table, level_codec_byte_table

    fcfg = default_audit_cfg(flagship=True)
    bt = level_byte_table(fcfg)
    top = max(bt)
    dense = bt[top]["wire_bytes"]
    sec: Dict[str, Any] = {"ok": True, "flagship_dense_bytes": dense,
                           "source": "fed.core.level_codec_byte_table",
                           "codecs": {}}
    for name in LOSSY_CODECS:
        comp = level_codec_byte_table(fcfg, name)[top]
        sec["codecs"][name] = {
            "payload_bytes_per_round": comp,
            "ratio_vs_dense": round(comp / dense, 6),
            "reduction_x": round(dense / comp, 3),
        }
    int8 = sec["codecs"]["int8"]["payload_bytes_per_round"]
    if 4 * int8 > dense + 32:
        report.fail(sec, "wire-frontier",
                    f"flagship int8 payload {int8} B/round exceeds 25% of "
                    f"the dense baseline {dense} B/round "
                    f"({int8 / dense:.2%}): the compressed wire budget "
                    f"regressed past the ISSUE 8 acceptance line")
    return sec


# ---------------------------------------------------------------------------
# per-program checks
# ---------------------------------------------------------------------------

def audit_program(name: str, prog, args: Tuple, expect: Dict[str, Any],
                  mesh, bind_files: Optional[Set[str]] = None) -> ProgramReport:
    """Trace, lower and compile one program; run checks (a)-(c), the ISSUE 7
    wire/HBM/reshard passes, and record flops/memory for (e).  Never
    executes the program.

    ``bind_files`` (ISSUE 18): a shared set the caller passes to collect
    the package-relative source files of every PRNG bind in the traced
    jaxpr -- the key-stream audit cross-checks them against its modeled
    modules."""
    from ..analysis import cost_analysis_dict

    rep = ProgramReport(name=name, donation_expected=int(expect["donated"]))
    jaxpr = prog.trace(*args).jaxpr
    if bind_files is not None:
        bind_files.update(random_bind_files(
            jaxpr, os.path.dirname(os.path.dirname(os.path.abspath(__file__)))))
    for prim, prov in find_callbacks(jaxpr):
        rep.fail("no-host-callback",
                 f"host callback op `{prim}` inside the round program "
                 f"(bound at {prov}): one callback serialises the whole "
                 f"fused round on the host boundary")
    for what, prov in find_f64(jaxpr):
        rep.fail("no-f64", f"{what} (bound at {prov})")

    # explicit (jaxpr-level) reshards: data-movement collectives the round
    # programs never need -- the HLO half joins after compile
    jaxpr_reshards = find_reshards(jaxpr)
    for prim, prov in jaxpr_reshards:
        rep.fail("reshard",
                 f"explicit data-movement collective `{prim}` bound at "
                 f"{prov}: the round programs move bytes through the single "
                 f"reduction only")

    counts, axes = count_collectives(jaxpr)
    # the eval phase's reductions bind (clients, data) JOINTLY; every
    # training psum binds a single axis -- count them as separate budgets
    # (ISSUE 4: "one global psum per fused round" means per TRAINING round)
    rep.psum_eval = count_psum_joint(jaxpr, ("clients", "data"))
    rep.psum_clients = count_psum_over(jaxpr, "clients") - rep.psum_eval
    rep.all_gather = counts.get("all_gather", 0)
    rep.collective_axes = sorted(axes)
    mesh_axes = set(mesh.axis_names)
    bad_axes = axes - mesh_axes
    if bad_axes:
        rep.fail("collective-axis",
                 f"collective axes {sorted(bad_axes)} not resolvable in the "
                 f"mesh axes {sorted(mesh_axes)}")
    if rep.psum_clients != expect["psum"]:
        rep.fail("psum-budget",
                 f"{rep.psum_clients} global psum bind(s) over the clients "
                 f"axis, budget is exactly {expect['psum']}")
    if rep.psum_eval != expect.get("psum_eval", 0):
        rep.fail("eval-psum-budget",
                 f"{rep.psum_eval} joint (clients, data) psum bind(s), "
                 f"budget is exactly {expect.get('psum_eval', 0)} (sBN + "
                 f"Global reductions per traced eval point)")
    if rep.all_gather:
        rep.fail("collective-budget",
                 f"{rep.all_gather} all_gather bind(s); the round programs "
                 f"move aggregates through the single psum only")

    # wire model (ISSUE 7 tentpole): price every collective bind and hold
    # the training round to its dense-reduction byte budget.  Multi-host
    # variants (ISSUE 17) override the link classification with an
    # explicit dcn_axes (the fake-mesh audit: classify AS IF the clients
    # axis crossed processes) and hold DCN to EXACTLY one dense reduction
    rep.wire = program_wire(jaxpr, mesh, dcn_axes=expect.get("dcn_axes"))
    if "wire_bytes" in expect:
        check_wire(rep, rep.wire, expect["wire_bytes"],
                   n_eval_points=expect.get("psum_eval", 0) // EVAL_PSUM_BUDGET,
                   dcn_budget_bytes=expect.get("dcn_budget_bytes", 0),
                   dcn_exact=expect.get("dcn_exact", False))

    if any(f.rule == "no-host-callback" for f in rep.findings):
        # a host callback is fatal on its own AND may refuse to lower under
        # a mesh -- report what the jaxpr walk found and stop here
        rep.reshards = {"jaxpr": [list(t) for t in jaxpr_reshards],
                        "total": len(jaxpr_reshards)}
        return rep

    if expect.get("wire_only"):
        # multi-host fake-mesh variant (ISSUE 17): the SAME program object
        # as its single-process entry (lowered, compiled and budgeted
        # there); this entry re-audits the trace-level wire classification
        # under the multi-process link model -- dcn_axes forced onto the
        # clients axis, DCN held to exactly one dense train reduction --
        # so it skips the duplicate lower/compile
        rep.reshards = {"jaxpr": [list(t) for t in jaxpr_reshards],
                        "total": len(jaxpr_reshards)}
        return rep

    with warnings.catch_warnings(record=True) as caught:
        warnings.simplefilter("always")
        lowered = prog.lower(*args)
        compiled = lowered.compile()
    for w in caught:
        msg = str(w.message)
        if "donated" in msg.lower() or "donation" in msg.lower():
            rep.fail("donation-unused",
                     f"jax donation warning promoted to failure: {msg[:300]}")

    lowered_text = lowered.as_text()
    compiled_text = compiled.as_text()
    # reshard detector, HLO half (ISSUE 7): GSPMD-introduced data-movement
    # instructions the jaxpr never shows -- zero allowed, and the tripwire
    # the multi-host slices work must keep green
    hlo_reshards = reshard_ops(compiled_text)
    rep.reshards = {**hlo_reshards,
                    "jaxpr": [list(t) for t in jaxpr_reshards],
                    "total": hlo_reshards["total"] + len(jaxpr_reshards)}
    if hlo_reshards["total"]:
        rep.fail("reshard",
                 f"optimized HLO carries {hlo_reshards['total']} "
                 f"GSPMD-introduced data-movement instruction(s) "
                 f"({ {k: v for k, v in hlo_reshards.items() if k != 'total' and v} }): "
                 f"sharding propagation decided operands live on the wrong "
                 f"devices -- an implicit reshard crept into the program")
    # hot-step body size (ISSUE 5): recorded for EVERY program, budgeted
    # on the level-a critical-path bodies (STEP_BODY_BUDGET)
    rep.step_body = scan_body_kernel_count(compiled_text)
    rep.step_body_budget = expect.get("step_body_instructions",
                                      STEP_BODY_BUDGET.get(name))
    if rep.step_body_budget is not None \
            and rep.step_body["instructions"] > rep.step_body_budget:
        rep.fail("step-body-budget",
                 f"{rep.step_body['instructions']} instructions per "
                 f"scan-body iteration (body {rep.step_body['body']}, "
                 f"{rep.step_body['fusions']} fusions), budget is "
                 f"{rep.step_body_budget}: the per-step body has grown "
                 f"(a new per-leaf chain)")
    rep.donated = donation_marks(lowered_text)
    rep.aliased = aliased_outputs(compiled_text)
    if rep.donated != expect["donated"]:
        rep.fail("donation-coverage",
                 f"{rep.donated} donated input leaves at lowering, expected "
                 f"{expect['donated']} (params/opt-state coverage)")
    if rep.aliased != expect["donated"]:
        rep.fail("donation-consumed",
                 f"only {rep.aliased}/{expect['donated']} donated leaves "
                 f"were consumed by input-output aliasing in the compiled "
                 f"program -- unconsumed donation is silent memory doubling")

    try:
        rep.flops = float(cost_analysis_dict(compiled).get("flops", float("nan")))
    except Exception as e:  # cost analysis availability varies by backend
        rep.flops = None
        rep.findings.append(Finding("cost-analysis", name,
                                    f"cost_analysis unavailable: {e!r} "
                                    f"(informational)"))

    # HBM footprint (ISSUE 7): memory_analysis() fields are REQUIRED now --
    # an absent field on a compiled flagship program is a loud
    # memory-analysis-missing finding, not the old getattr-skipped empty
    # record -- and each is held to the analytic bound, with the bytes that
    # donation actually saved accounted alongside
    try:
        ma = compiled.memory_analysis()
    except Exception:
        ma = None
    rep.memory, mem_findings = collect_memory(ma, name)
    if mem_findings:
        rep.ok = False
        rep.findings.extend(mem_findings)
    if "mem" in expect:
        mi = expect["mem"]
        budget = analytic_budget(mi["param_bytes"], mi["activation_bytes"],
                                 mi["clients_per_device"], _args_bytes(args),
                                 expect.get("wire_bytes", 0))
        budget["donation"] = donation_accounting(
            rep, expect.get("donated_bytes", mi["param_bytes"]))
        rep.memory_budget = budget
        check_memory(rep, rep.memory, budget)
    return rep


# ---------------------------------------------------------------------------
# cross-program checks: (d) recompile hazard, (e) FLOP budget
# ---------------------------------------------------------------------------

def recompile_hazard_check(setup) -> Dict[str, Any]:
    """Dispatch each engine twice with FRESH but value-identical host inputs
    (new numpy buffers, new python floats) and require
    ``engine.program_cache_size()`` to stay flat after the first call --
    the classic leaks (weak-typed scalars, python floats in cache keys,
    re-bucketed slots) all show up as growth here."""
    import jax

    from ..parallel import GroupedRoundEngine, RoundEngine, shard_client_data

    cfg, model, mesh = setup["cfg"], setup["model"], setup["mesh"]
    data = tuple(setup["data"])
    out: Dict[str, Any] = {"ok": True}

    def fresh_idx():
        return np.array([0, 2, 4, 6, 8, 1], dtype=np.int64)  # re-allocated

    def fresh_lr():
        return float("0.05")  # a NEW python float each dispatch

    eng = RoundEngine(model, cfg, mesh)
    p = model.init(jax.random.key(0))
    p, _ = eng.train_round(p, jax.random.key(1), fresh_lr(), fresh_idx(), data)
    size1 = eng.program_cache_size()
    p, _ = eng.train_round(p, jax.random.key(2), fresh_lr(), fresh_idx(), data)
    out["masked_round"] = {"after_warm": size1,
                           "after_repeat": eng.program_cache_size()}

    p, pend = eng.train_superstep(p, jax.random.key(3), 1, 2, data,
                                  num_active=4)
    pend.fetch()
    size1 = eng.program_cache_size()
    p, pend = eng.train_superstep(p, jax.random.key(3), 3, 2, data,
                                  num_active=4)
    pend.fetch()
    out["masked_superstep"] = {"after_warm": size1,
                               "after_repeat": eng.program_cache_size()}

    # sampler variants (ISSUE 11): the superstep above draws in-jit from
    # the default PRP index map; the legacy permutation engine must stay
    # recompile-free too (the sampler kind is an engine-construction
    # constant, never a per-dispatch cache key)
    eng_pm = RoundEngine(model, dict(cfg, sampler="perm"), mesh)
    ppm = model.init(jax.random.key(0))
    ppm, pend = eng_pm.train_superstep(ppm, jax.random.key(3), 1, 2, data,
                                       num_active=4)
    pend.fetch()
    size1 = eng_pm.program_cache_size()
    ppm, pend = eng_pm.train_superstep(ppm, jax.random.key(3), 3, 2, data,
                                       num_active=4)
    pend.fetch()
    out["masked_superstep_perm"] = {"after_warm": size1,
                                    "after_repeat": eng_pm.program_cache_size()}

    # eval-fused superstep (ISSUE 4): a fresh-but-identical eval mask (a NEW
    # tuple of the same booleans) must hit the cached program -- the mask is
    # part of the program key, so a tuple-identity (rather than equality)
    # key would recompile the flagship program every superstep
    fe = fused_eval_for(setup)
    p, pend = eng.train_superstep(p, jax.random.key(3), 5, 2, data,
                                  num_active=4, eval_mask=(True, True),
                                  fused_eval=fe)
    pend.fetch()
    size1 = eng.program_cache_size()
    p, pend = eng.train_superstep(p, jax.random.key(3), 7, 2, data,
                                  num_active=4,
                                  eval_mask=tuple([True] * 2), fused_eval=fe)
    pend.fetch()
    out["masked_superstep_eval"] = {"after_warm": size1,
                                    "after_repeat": eng.program_cache_size()}

    # sharded placement superstep: the host-packed slot schedule's ownership
    # density keys the K-round program -- fresh-but-identical schedules must
    # not recompile (per_dev bucketing regression, found by this very check)
    from ..fed.core import round_users

    eng_sh = RoundEngine(model, dict(cfg, data_placement="sharded"), mesh)
    data_sh = shard_client_data(mesh, data)
    base = jax.random.key(5)

    def fresh_sched():
        return np.stack([np.asarray(round_users(jax.random.fold_in(base, 1 + j),
                                                setup["users"], 4))
                         for j in range(2)])

    ps = model.init(jax.random.key(0))
    ps, pend = eng_sh.train_superstep(ps, base, 1, 2, data_sh,
                                      user_schedule=fresh_sched())
    pend.fetch()
    size1 = eng_sh.program_cache_size()
    ps, pend = eng_sh.train_superstep(ps, base, 3, 2, data_sh,
                                      user_schedule=fresh_sched())
    pend.fetch()
    out["masked_sharded_superstep"] = {"after_warm": size1,
                                       "after_repeat": eng_sh.program_cache_size()}

    # streaming cohort supersteps (ISSUE 6): every superstep restages a
    # FRESH cohort (new host buffers, new device arrays) -- the program key
    # is the static layout (k, per_dev, stream), so steady-state streaming
    # must stay one compiled specialization per engine
    from ..fed.core import superstep_rate_schedule, superstep_user_schedule

    store = setup["store"]
    eng_st = RoundEngine(model, cfg, mesh)
    pst = model.init(jax.random.key(0))

    def fresh_cohort(epoch0):
        sched = superstep_user_schedule(base, epoch0, 2, setup["users"], 4)
        return eng_st.stage_cohort(store, sched)

    pst, pend = eng_st.train_superstep(pst, base, 1, 2, cohort=fresh_cohort(1))
    pend.fetch()
    size1 = eng_st.program_cache_size()
    pst, pend = eng_st.train_superstep(pst, base, 3, 2, cohort=fresh_cohort(3))
    pend.fetch()
    out["masked_stream_superstep"] = {"after_warm": size1,
                                      "after_repeat": eng_st.program_cache_size()}

    grp_st = GroupedRoundEngine(cfg, mesh)
    gst = model.init(jax.random.key(0))

    def fresh_gcohort(epoch0):
        sched = superstep_user_schedule(base, epoch0, 2, setup["users"],
                                        setup["users"])
        rates = superstep_rate_schedule(base, epoch0, 2, cfg, sched)
        return grp_st.stage_cohort(store, sched, rates)

    gst, pend = grp_st.train_superstep(gst, base, 1, 2, cohort=fresh_gcohort(1))
    pend.fetch()
    size1 = grp_st.program_cache_size()
    gst, pend = grp_st.train_superstep(gst, base, 3, 2, cohort=fresh_gcohort(3))
    pend.fetch()
    out["grouped_stream_superstep"] = {"after_warm": size1,
                                       "after_repeat": grp_st.program_cache_size()}

    grp = GroupedRoundEngine(cfg, mesh)
    rates_vec = np.asarray(cfg["model_rate"], np.float32)
    g = model.init(jax.random.key(0))
    g, _ = grp.train_round(g, fresh_idx(), rates_vec[fresh_idx()], data,
                           fresh_lr(), jax.random.key(1))
    size1 = grp.program_cache_size()
    g, _ = grp.train_round(g, fresh_idx(), rates_vec[fresh_idx()], data,
                           fresh_lr(), jax.random.key(2))
    out["grouped_round"] = {"after_warm": size1,
                            "after_repeat": grp.program_cache_size()}

    # arms superstep (ISSUE 14): the stacked per-arm key roots and LR
    # scales are per-dispatch VALUES; the arms count is an engine
    # constant.  A fresh-but-identical dispatch (new key derivation, new
    # scale buffer) must hit the cached E-arm program.
    eng_ar = RoundEngine(model, dict(cfg, arms=2), mesh)
    par = jax.tree_util.tree_map(
        lambda v: jax.numpy.stack([v, v]), model.init(jax.random.key(0)))
    par, pend = eng_ar.train_superstep(par, jax.random.key(3), 1, 2, data,
                                       num_active=4)
    pend.fetch()
    size1 = eng_ar.program_cache_size()
    par, pend = eng_ar.train_superstep(par, jax.random.key(3), 3, 2, data,
                                       num_active=4)
    pend.fetch()
    out["masked_arms_superstep"] = {"after_warm": size1,
                                    "after_repeat":
                                        eng_ar.program_cache_size()}
    return out


def sampler_stream_check(report: AuditReport, setup) -> Dict[str, Any]:
    """Sampling-stream consistency (ISSUE 11): for BOTH sampler kinds the
    in-jit draw must equal the host draw bitwise (the one-stream contract
    behind superstep == sequential), an all-ones availability row must
    select exactly that sampler's uniform cohort (trace replay stays a
    strict generalisation of the uniform stream), a uniform cohort must be
    duplicate-free, and the PRP index map must be an exact bijection on
    ``[0, num_users)``.  Executes tiny draws, like the recompile check."""
    import jax

    from ..fed.core import round_users
    from ..fed.sampling import prp_map

    users = setup["users"]
    a = max(1, users // 2)
    key = jax.random.fold_in(setup["key"], 77)
    sec: Dict[str, Any] = {"ok": True, "num_users": users, "num_active": a,
                           "kinds": {}}
    for kind in ("perm", "prp"):
        host = np.asarray(round_users(key, users, a, sampler=kind))
        jitd = np.asarray(jax.jit(
            lambda kk, _kind=kind: round_users(kk, users, a,
                                               sampler=_kind))(key))
        ones = np.asarray(round_users(key, users, a,
                                      avail=np.ones(users, np.uint8),
                                      sampler=kind))
        rec = {"in_jit_equals_host": bool((host == jitd).all()),
               "all_ones_equals_uniform": bool((host == ones).all()),
               "cohort_distinct": len(set(host.tolist())) == a}
        sec["kinds"][kind] = rec
        if not rec["in_jit_equals_host"]:
            report.fail(sec, "sampler-stream",
                        f"sampler {kind!r}: in-jit draw differs from the "
                        f"host draw -- the superstep stream has forked "
                        f"(host {host.tolist()[:8]} vs jit "
                        f"{jitd.tolist()[:8]})")
        if not rec["all_ones_equals_uniform"]:
            report.fail(sec, "sampler-stream",
                        f"sampler {kind!r}: an all-ones availability row "
                        f"selects {ones.tolist()[:8]} instead of the "
                        f"uniform cohort {host.tolist()[:8]} -- trace "
                        f"replay is no longer a generalisation of the "
                        f"uniform stream")
        if not rec["cohort_distinct"]:
            report.fail(sec, "sampler-stream",
                        f"sampler {kind!r}: uniform cohort carries "
                        f"duplicate ids ({host.tolist()})")
    image = np.sort(np.asarray(prp_map(key, np.arange(users), users)))
    sec["prp_bijection"] = bool((image == np.arange(users)).all())
    if not sec["prp_bijection"]:
        report.fail(sec, "sampler-bijection",
                    f"prp_map is not a bijection on [0, {users}): sorted "
                    f"image {image.tolist()[:12]}...")
    return sec


def flop_budget_check(report: AuditReport, setup,
                      level_prog_names: Dict[float, str],
                      tol: Optional[float] = None) -> Dict[str, Any]:
    """Measured per-level-program FLOP shares vs the analytic shares from
    :func:`~..fed.core.level_flop_shares` (equal client counts per level in
    the audit matrix -> uniform weights), plus strict monotonicity of the
    measured shares in the rate."""
    from ..fed.core import level_flop_shares

    if tol is None:
        tol = FLAGSHIP_FLOP_TOL if setup["flagship"] else SMALL_FLOP_TOL
    rates = sorted(level_prog_names, reverse=True)
    measured = {r: report.programs[level_prog_names[r]].flops for r in rates}
    sec: Dict[str, Any] = {"ok": True, "tol": tol,
                           "measured_flops": {f"{r:g}": measured[r] for r in rates}}
    if any(measured[r] is None for r in rates):
        report.fail(sec, "flop-budget", "cost_analysis unavailable for a "
                    "level program; FLOP budget cannot be audited")
        return sec
    total = sum(measured.values())
    analytic = level_flop_shares(setup["cfg"])
    sec["measured_shares"] = {f"{r:g}": measured[r] / total for r in rates}
    sec["analytic_shares"] = {f"{r:g}": analytic[r] for r in rates}
    for r in rates:
        ms, as_ = measured[r] / total, analytic[r]
        rel = abs(ms - as_) / as_
        if rel > tol:
            report.fail(sec, "flop-budget",
                        f"level {r:g}: measured FLOP share {ms:.4f} vs "
                        f"analytic {as_:.4f} (rel err {rel:.3f} > tol {tol})")
    for hi, lo in zip(rates, rates[1:]):
        if measured[hi] <= measured[lo]:
            report.fail(sec, "flop-monotonicity",
                        f"level {hi:g} program FLOPs ({measured[hi]:.3e}) not "
                        f"above level {lo:g} ({measured[lo]:.3e}): the "
                        f"dense-per-level win has regressed")
    return sec


# ---------------------------------------------------------------------------
# entry points
# ---------------------------------------------------------------------------

def _build_targets(setup):
    """Assemble the full program matrix: ``(targets, level_prog_names)``
    where each target is ``(name, prog, args, expect)``.  Shared by the
    audit proper and the CLI's ``--list``."""
    targets = list(_masked_targets(setup))
    grouped, level_prog_names, _ = _grouped_targets(setup)
    targets.extend(grouped)
    targets.extend(_codec_targets(setup))
    targets.extend(_sched_targets(setup))
    targets.extend(_obs_targets(setup))
    targets.extend(_obs_hist_targets(setup))
    targets.extend(_quarantine_targets(setup))
    targets.extend(_arms_targets(setup))
    return targets, level_prog_names


#: names of the cross-program checks, for ``--list`` (the per-program
#: checks run inside every audited program and have no standalone names)
CROSS_CHECKS = ("flop_budget", "wire_frontier", "sampler", "arms",
                "recompile", "lattice", "key_streams")


def list_targets(flagship: bool = False, seed: int = 0) -> List[str]:
    """Program names of the audit matrix, without auditing anything
    (the target builders only close over setup; nothing is traced)."""
    setup = build_setup(flagship=flagship, seed=seed)
    targets, _ = _build_targets(setup)
    return [name for name, _prog, _args, _expect in targets]


def _release_executables() -> None:
    """Drop every compiled executable jax still caches.

    Each XLA:CPU executable keeps its code in memory mappings of its own,
    and a process may hold 65,530 of them (``vm.max_map_count``).  The
    program matrix alone reached 61,364 by the time the recompile check
    ran, and the next compile died inside LLVM with "Cannot allocate
    memory" -- a segfault or an abort, wherever it happened to land
    (measured under jax 0.9.0; ``jax.clear_caches()`` brought the count
    back to 709).  Nothing audited is ever executed, so nothing needs the
    cache."""
    import gc

    import jax

    jax.clear_caches()
    gc.collect()


def run_audit(flagship: bool = False, flop_tol: Optional[float] = None,
              seed: int = 0, with_recompile_check: bool = True,
              with_aot: bool = False,
              only: Optional[str] = None) -> AuditReport:
    """The full program audit.  Returns an :class:`AuditReport` (the CLI
    adds lint findings and serialises to STATICCHECK.json).

    ``with_aot`` additionally runs the subprocess v4-128 AOT multi-host
    check (ISSUE 17) and records it under ``config["aot_v4128"]`` -- a
    config record, never a program entry, so the ratchet baseline stays
    environment-stable; a child that RAN and violated the DCN budget
    still fails the audit.

    ``only`` (ISSUE 18): an fnmatch glob over program names; audits the
    matching subset and SKIPS every cross-program check (they reason
    over the full matrix -- a partial run would fabricate findings).
    The CLI refuses ``--only`` + ``--diff-baseline`` for the same
    reason."""
    report = AuditReport()
    setup = build_setup(flagship=flagship, seed=seed)
    report.config = {
        "flagship": flagship,
        "data_name": setup["cfg"]["data_name"],
        "model_name": setup["cfg"]["model_name"],
        "num_users": setup["users"],
        "levels": sorted({float(r) for r in setup["cfg"]["model_rate"]},
                         reverse=True),
        "mesh": dict(zip(setup["mesh"].axis_names,
                         (int(s) for s in setup["mesh"].devices.shape))),
    }
    mesh = setup["mesh"]
    targets, level_prog_names = _build_targets(setup)
    if only is not None:
        report.config["only"] = only
        targets = [t for t in targets if fnmatch.fnmatch(t[0], only)]
    bind_files: Set[str] = set()
    for i, (name, prog, args, expect) in enumerate(targets):
        report.add_program(audit_program(name, prog, args, expect, mesh,
                                         bind_files=bind_files))
        if i % 16 == 15:
            _release_executables()

    if only is not None:
        skipped = {"ok": True, "skipped": f"--only {only}"}
        report.flop_budget = dict(skipped)
        report.recompile = dict(skipped)
        report.wire_frontier = dict(skipped)
        report.sampler = dict(skipped)
        report.arms = dict(skipped)
        report.lattice = dict(skipped)
        report.key_streams = dict(skipped)
        return report

    report.flop_budget = flop_budget_check(report, setup, level_prog_names,
                                           tol=flop_tol)
    report.wire_frontier = codec_frontier_check(report)
    report.sampler = sampler_stream_check(report, setup)
    report.arms = arms_flop_check(report)

    # ISSUE 18: config-lattice exhaustiveness + RNG-stream provenance.
    # The lattice's program: evidence refs must point at GREEN audited
    # programs; the key-stream pass gets the PRNG bind files collected
    # from every traced jaxpr above.
    from .keys import key_streams_check
    from .lattice import lattice_check

    report.lattice = lattice_check(
        audited={n for n, p in report.programs.items() if p.ok})
    report.ok = report.ok and report.lattice["ok"]
    report.key_streams = key_streams_check(
        os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
        bind_files=sorted(bind_files))
    report.ok = report.ok and report.key_streams["ok"]
    if with_recompile_check:
        _release_executables()
        rc = recompile_hazard_check(setup)
        for which, sizes in list(rc.items()):
            if isinstance(sizes, dict) and \
                    sizes["after_repeat"] > sizes["after_warm"]:
                report.fail(rc, "recompile-hazard",
                            f"{which}: program cache grew "
                            f"{sizes['after_warm']} -> {sizes['after_repeat']} "
                            f"on a fresh-but-identical dispatch (cache-key "
                            f"leak: weak types / python scalars / slot "
                            f"re-bucketing)")
        report.recompile = rc
    if with_aot:
        from .aot import aot_v4128_check

        res = aot_v4128_check(flagship=flagship)
        report.config["aot_v4128"] = res
        if res.get("available") and res.get("ok") is False:
            report.fail(res, "aot-dcn",
                        f"v4-128 AOT audit ({res.get('mode')}): DCN carries "
                        f"{res.get('dcn_bytes_per_round')} bytes/round "
                        f"against a budget of exactly "
                        f"{res.get('budget_bytes')} with "
                        f"{res.get('reshards_jaxpr')} reshard(s)")
    return report


def flop_account(cfg, data, mesh, user_idx, rates,
                 params=None) -> Dict[str, Any]:
    """Masked-vs-grouped compiled FLOP account at an explicit active mix:
    the one implementation behind ``scripts/grouped_flops.py`` and the
    engine-comparison numbers in MEASUREMENTS.md.  Nothing is executed --
    programs are lowered and compiled only.  Counts are per scan-body
    execution (XLA's cost model counts loop bodies once), which cancels in
    every ratio/share."""
    import jax

    from ..analysis import cost_analysis_dict
    from ..fed.core import level_flop_shares
    from ..models import make_model
    from ..parallel import GroupedRoundEngine, RoundEngine

    model = make_model(cfg)
    if params is None:
        params = model.init(jax.random.key(0))
    key, lr = jax.random.key(0), np.float32(0.1)
    data = tuple(data)

    eng = RoundEngine(model, cfg, mesh)
    fix = (eng.fix_rates,) if eng.fix_rates is not None else ()
    ug = np.asarray(user_idx, np.int32)
    masked = cost_analysis_dict(
        eng._build_train().lower(params, key, lr, ug, ug, *(data + fix))
        .compile())["flops"]

    grp = GroupedRoundEngine(cfg, mesh)
    by: Dict[float, List[int]] = {}
    for pos, r in enumerate(np.asarray(rates)):
        by.setdefault(float(r), []).append(pos)
    per_level: Dict[str, float] = {}
    sums, cnts = [], []
    for r in sorted(by, reverse=True):
        u = np.asarray(ug[by[r]], np.int32)
        prog = grp._level_prog(r, len(u))
        per_level[f"{r:g}"] = cost_analysis_dict(
            prog.lower(params, key, lr, u, *data).compile())["flops"]
        # avals only (nothing executes): the combine lowering needs the
        # level partials' shapes/dtypes, not values
        s, c, _ = jax.eval_shape(prog, params, key, lr, u, *data)
        sums.append(s)
        cnts.append(c)
    combine = cost_analysis_dict(
        grp._combine_prog(len(sums)).lower(params, sums, cnts).compile())["flops"]
    grouped_total = sum(per_level.values()) + combine
    weights = {r: float(len(p)) for r, p in by.items()}
    return {
        "masked_flops_per_round": masked,
        "grouped_flops_per_round": grouped_total,
        "grouped_per_level_flops": per_level,
        "combine_flops": combine,
        "flop_ratio_masked_over_grouped": round(masked / grouped_total, 3),
        "analytic_level_shares": {f"{r:g}": v for r, v in
                                  level_flop_shares(cfg, weights).items()},
    }
