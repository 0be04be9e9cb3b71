"""Fused masked-SGD epilogue (ISSUE 5): the ops/fused_update.py primitive
and the engine-level fused-vs-reference matrix.

The contract, in three tiers:

* PRIMITIVE: the fused update is bit-identical to the reference op chain
  on the same inputs -- XLA fallback unconditionally (including the
  global-norm clip decision: same reduces over the same per-leaf arrays in
  the same order); the Pallas kernel (interpret mode here) matches
  elementwise exactly and associates the norm per lane-block, so it is
  bit-exact whenever clipping does not engage and float-tolerant when it
  does.
* STEP RESULTS: fused-vs-reference engine programs produce BIT-IDENTICAL
  params at the step level across the whole matrix -- masked x
  {replicated, sharded}, grouped x {span, slices}, K in {1, 8}, with and
  without the eval mask (proven with one-local-step rounds, where nothing
  can amortise a mismatch away).
* TRAJECTORIES: over many multi-step rounds the two programs agree at
  float-association level, NOT bitwise -- the flat scan carry changes
  XLA's global fusion choices, which shifts some reduce emission by 1 ulp
  that SGD amplifies chaotically.  This is the same agreement class as the
  repo's standing masked-vs-sliced / grouped-vs-masked engine contracts;
  the within-engine bitwise contracts (superstep-vs-sequential,
  eval-fused-vs-host) are untouched because both sides share one body.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from heterofl_tpu.models import make_model
from heterofl_tpu.models.spec import param_mask
from heterofl_tpu.ops.fused_update import (FlatSpec, masked_sgd_step,
                                           resolve_fused_mode)
from heterofl_tpu.parallel import (GroupedRoundEngine, RoundEngine, make_mesh,
                                   shard_client_data)
from heterofl_tpu.fed.core import round_users
from heterofl_tpu.utils.optim import clip_by_global_norm

from test_round import _vision_setup, _lm_setup

HOST_KEY = jax.random.key(0)


# ---------------------------------------------------------------------------
# unit level: the primitive vs the reference op chain
# ---------------------------------------------------------------------------

def _reference_chain(p, g, bufs, m, n_glob, lr, momentum, wd, has):
    """The seed engines' epilogue, verbatim semantics."""
    g = {k: v / jnp.maximum(n_glob, 1e-6) for k, v in g.items()}
    g = {k: v * m[k] for k, v in g.items()}
    g, _ = clip_by_global_norm(g, 1.0)
    nb = jax.tree_util.tree_map(lambda pp, gg, bb: momentum * bb + gg + wd * pp,
                                p, g, bufs)
    np_ = jax.tree_util.tree_map(lambda pp, bb: pp - lr * bb, p, nb)
    if has is not None:
        np_ = jax.tree_util.tree_map(lambda a, c: jnp.where(has, a, c), np_, p)
        nb = jax.tree_util.tree_map(lambda a, c: jnp.where(has, a, c), nb, bufs)
    return np_, nb


def _rand_trees(seed=0, gscale=1.0):
    rng = np.random.default_rng(seed)
    shapes = {"blk.conv.w": (3, 3, 4, 8), "blk.norm.g": (8,),
              "blk.norm.b": (8,), "fc.w": (8, 10), "fc.b": (10,)}
    p = {k: jnp.asarray(rng.normal(size=s), jnp.float32) for k, s in shapes.items()}
    b = {k: jnp.asarray(rng.normal(size=s) * 0.1, jnp.float32) for k, s in shapes.items()}
    g = {k: jnp.asarray(rng.normal(size=s) * gscale, jnp.float32) for k, s in shapes.items()}
    m = {k: jnp.asarray(rng.random(s) > 0.3, jnp.float32) for k, s in shapes.items()}
    return p, g, b, m


def _assert_tree_equal(a, b, exact=True, err=""):
    for k in a:
        if exact:
            np.testing.assert_array_equal(np.asarray(a[k]), np.asarray(b[k]),
                                          err_msg=f"{err} leaf {k}")
        else:
            np.testing.assert_allclose(np.asarray(a[k]), np.asarray(b[k]),
                                       rtol=2e-7, atol=2e-7,
                                       err_msg=f"{err} leaf {k}")


def test_flatspec_roundtrip_and_order():
    p, *_ = _rand_trees()
    spec = FlatSpec.of(p)
    assert spec.names == sorted(p)  # jax dict-flatten order
    flat = spec.flatten(p)
    assert flat.shape == (spec.total,)
    back = spec.unflatten(flat)
    _assert_tree_equal(back, p)


@pytest.mark.parametrize("gscale", [1e-3, 1e2])  # no-clip / clip regimes
@pytest.mark.parametrize("has", [True, None])
def test_xla_fallback_bit_identical(gscale, has):
    """The XLA fallback is bit-identical to the reference chain
    UNCONDITIONALLY -- including when the global-norm clip engages."""
    p, g, b, m = _rand_trees(gscale=gscale)
    hv = None if has is None else jnp.asarray(has)
    rp, rb = jax.jit(lambda *a: _reference_chain(*a, 0.9, 5e-4, hv))(
        p, g, b, m, jnp.float32(37.0), jnp.float32(0.05))
    fp, fb = jax.jit(lambda *a: masked_sgd_step(
        *a, momentum=0.9, weight_decay=5e-4, has=hv, mode="xla"))(
        p, g, b, m, jnp.float32(37.0), jnp.float32(0.05))
    _assert_tree_equal(fp, rp)
    _assert_tree_equal(fb, rb)


def test_pallas_kernel_bit_identical_no_clip():
    """Interpret-mode kernel vs the reference chain in the no-clip regime
    (elementwise path is exactly the reference's; the clip scale is exactly
    1.0 in both), to two f32 ulps.

    This was bit-identical up to jaxlib 0.4.x.  XLA:CPU of jaxlib 0.9.0
    emits the reference chain through its new fusion emitters, which
    contract ``momentum * buf + g`` and ``+ wd * p`` into FMAs, while the
    interpreted kernel's loads and stores keep those ops apart: the
    reference equals an FMA emulation on all 288 elements, the kernel a
    non-FMA one on 286, and ``--xla_cpu_use_fusion_emitters=false`` makes
    the two bit-identical again (measured, PR 23).  The contraction is the
    compiler's choice, not the program's, so the contract is restated as
    <= 2 ulps: one per contracted multiply-add of the chain (61/288 momentum
    entries differ, by at most 1.5e-8)."""
    p, g, b, m = _rand_trees(gscale=1e-3)
    has = jnp.asarray(True)
    rp, rb = jax.jit(lambda *a: _reference_chain(*a, 0.9, 5e-4, has))(
        p, g, b, m, jnp.float32(37.0), jnp.float32(0.05))
    fp, fb = jax.jit(lambda *a: masked_sgd_step(
        *a, momentum=0.9, weight_decay=5e-4, has=has, mode="pallas",
        interpret=True))(p, g, b, m, jnp.float32(37.0), jnp.float32(0.05))
    for got, ref in ((fp, rp), (fb, rb)):
        for k in ref:
            np.testing.assert_array_max_ulp(np.asarray(got[k]),
                                            np.asarray(ref[k]), maxulp=2)


def test_pallas_kernel_clip_engaged_value_agreement():
    """When clipping engages, the kernel's two-phase block-associated norm
    may differ from the per-leaf association in the last ulp -- value
    agreement is pinned at float tolerance (the XLA fallback, which the CPU
    engines actually run, stays bit-exact -- see above)."""
    p, g, b, m = _rand_trees(gscale=1e2)
    rp, rb = _reference_chain(p, g, b, m, jnp.float32(37.0), jnp.float32(0.05),
                              0.9, 5e-4, None)
    fp, fb = masked_sgd_step(p, g, b, m, 37.0, 0.05, momentum=0.9,
                             weight_decay=5e-4, mode="pallas", interpret=True)
    _assert_tree_equal(fp, rp, exact=False)
    _assert_tree_equal(fb, rb, exact=False)


@pytest.mark.parametrize("mode", ["xla", "pallas"])
def test_all_padding_batch_has_gating(mode):
    """``has=False`` (an all-padding batch) must return params and momentum
    UNTOUCHED, bit-for-bit -- no weight-decay or momentum drift."""
    p, g, b, m = _rand_trees()
    fp, fb = masked_sgd_step(p, g, b, m, 0.0, 0.05, momentum=0.9,
                             weight_decay=5e-4, has=jnp.asarray(False),
                             mode=mode, interpret=True)
    _assert_tree_equal(fp, p)
    _assert_tree_equal(fb, b)


@pytest.mark.parametrize("mode", ["xla", "pallas"])
def test_zero_width_mask_rows_at_level_e(mode):
    """Level-e width masks on a real model spec zero whole channel rows;
    the fused update must match the reference chain there AND keep the
    masked tail of masked params identically zero (weight decay sees p=0,
    momentum starts 0 -- nothing can move the inactive region)."""
    from test_models import small_cfg

    cfg = small_cfg("conv")
    model = make_model(cfg)
    params = model.init(jax.random.key(0))
    wr = 0.0625  # level e
    masks = {k: param_mask(v.shape, model.specs[k], model.groups, wr)
             for k, v in params.items()}
    p = {k: v * masks[k] for k, v in params.items()}
    b = {k: jnp.zeros_like(v) for k, v in params.items()}
    rng = np.random.default_rng(3)
    g = {k: jnp.asarray(rng.normal(size=v.shape) * 1e-3, jnp.float32)
         for k, v in params.items()}
    # jit BOTH sides: that is how the engines run them, and eager-vs-jit
    # comparisons differ by FMA contraction in the last ulp
    rp, rb = jax.jit(lambda *a: _reference_chain(*a, 0.9, 5e-4, None))(
        p, g, b, masks, jnp.float32(10.0), jnp.float32(0.05))
    fp, fb = jax.jit(lambda *a: masked_sgd_step(
        *a, momentum=0.9, weight_decay=5e-4, mode=mode, interpret=True))(
        p, g, b, masks, jnp.float32(10.0), jnp.float32(0.05))
    _assert_tree_equal(fp, rp)
    _assert_tree_equal(fb, rb)
    for k in fp:
        inactive = np.asarray(masks[k]) == 0.0
        assert np.all(np.asarray(fp[k])[inactive] == 0.0), k


@pytest.mark.parametrize("backend, on", [
    # True resolves by backend: the flat XLA form on the CPU test mesh ...
    ("cpu", "xla"),
    # ... and the tree carry with the per-leaf chain on the chip (PR 27:
    # the flat carry's plumbing was 76 % / 83 % of the step there)
    ("tpu", None)])
def test_resolve_fused_mode(backend, on, monkeypatch):
    monkeypatch.setattr(jax, "default_backend", lambda: backend)
    assert resolve_fused_mode({"fused_update": True,
                               "optimizer_name": "SGD"}) == on
    assert resolve_fused_mode({"optimizer_name": "SGD"}) == on  # the default
    # everything else means the same on every backend
    assert resolve_fused_mode({"fused_update": False,
                               "optimizer_name": "SGD"}) is None
    assert resolve_fused_mode({"fused_update": True,
                               "optimizer_name": "Adam"}) is None
    for forced in ("xla", "pallas"):
        assert resolve_fused_mode({"fused_update": forced,
                                   "optimizer_name": "SGD"}) == forced
    with pytest.raises(ValueError, match="fused_update"):
        resolve_fused_mode({"fused_update": "turbo", "optimizer_name": "SGD"})


# ---------------------------------------------------------------------------
# engine level: the acceptance matrix
# ---------------------------------------------------------------------------

def _metrics_agree(a, b, exact=True):
    for lx, ly in zip(jax.tree_util.tree_leaves(a),
                      jax.tree_util.tree_leaves(b)):
        if exact:
            np.testing.assert_array_equal(np.asarray(lx), np.asarray(ly))
        else:
            # association-level trajectories: loss/weight sums within 2%,
            # DISCRETE correct-counts may flip by a sample or two once the
            # params drift an ulp (argmax is a step function)
            np.testing.assert_allclose(np.asarray(lx), np.asarray(ly),
                                       rtol=2e-2, atol=2.0)


def _assert_tree_close(a, b):
    """Association-level trajectory agreement (see module docstring)."""
    for k in a:
        np.testing.assert_allclose(np.asarray(a[k]), np.asarray(b[k]),
                                   rtol=5e-3, atol=1e-3,
                                   err_msg=f"leaf {k}")


@pytest.fixture(scope="module")
def tiny():
    """One-local-step rounds: 8 users x 10-sample shards (== one batch),
    local_epochs=1 -- every round is exactly ONE optimizer step per client,
    so fused-vs-reference step results must match bit-for-bit (nothing can
    amortise a mismatch away)."""
    from test_models import small_cfg
    from heterofl_tpu.data import (fetch_dataset, label_split_masks,
                                   split_dataset, stack_client_shards)
    from heterofl_tpu.parallel.evaluation import Evaluator
    from test_evalfused import _batch

    cfg = small_cfg("conv", data_name="MNIST",
                    control="1_8_0.5_iid_fix_a1-b1-c1-d1-e1_bn_1_1")
    cfg["num_epochs"] = dict(cfg["num_epochs"], local=1)
    ds = fetch_dataset("MNIST", synthetic=True, seed=0,
                       synthetic_sizes={"train": 80, "test": 40})
    rng = np.random.default_rng(0)
    split, lsplit = split_dataset(ds, 8, "iid", rng, classes_size=10)
    x, y, m = stack_client_shards(ds["train"].data, ds["train"].target,
                                  split["train"], list(range(8)))
    lm = label_split_masks(lsplit, 8, 10)
    data = (jnp.asarray(x), jnp.asarray(y), jnp.asarray(m), jnp.asarray(lm))
    model = make_model(cfg)
    mesh = make_mesh(8, 1)
    te = ds["test"]
    ev = Evaluator(model, cfg, mesh, seed=0)
    xg, wg = _batch(te.data, 20)
    yg, _ = _batch(te.target, 20)
    fe = ev.fused(
        sbn_batches=_batch(ds["train"].data, 20),
        local_eval=(te.data[:32].reshape(8, 1, 4, 28, 28, 1),
                    te.target[:32].reshape(8, 1, 4),
                    np.ones((8, 1, 4), np.float32),
                    np.ones((8, 10), np.float32)),
        global_eval=(xg, yg, wg))
    return {"cfg": cfg, "data": data, "model": model, "mesh": mesh,
            "fused_eval": fe}


@pytest.mark.parametrize("cell", ["masked-replicated", "masked-sharded",
                                  "grouped-span", "grouped-slices"])
def test_fused_step_results_bit_identical_matrix(tiny, cell):
    """THE acceptance matrix: fused-epilogue step results are BIT-IDENTICAL
    to the reference op chain for masked x {replicated, sharded} and
    grouped x {span, slices}, K in {1, 8}, with and without the eval mask
    -- params and metrics, after 17 one-step rounds spanning the one-round
    program, the train superstep and the eval-fused superstep."""
    cfg, model, mesh, data = (tiny["cfg"], tiny["model"], tiny["mesh"],
                              tiny["data"])
    fe = tiny["fused_eval"]
    rates_vec = np.asarray(cfg["model_rate"], np.float32)
    outs = {}
    for name, over in [("fused", {}), ("ref", {"fused_update": False})]:
        if cell.startswith("grouped"):
            eng = GroupedRoundEngine(
                dict(cfg, level_placement=cell.split("-")[1], **over), mesh)
            p = model.init(jax.random.key(0))
            ui = np.array([0, 2, 4, 6, 1, 3])
            p, ms1 = eng.train_round(p, ui, rates_vec[ui], data, 0.05,
                                     jax.random.key(1))
            us = _sched(cfg, 2, 8)
            p, pend = eng.train_superstep(p, HOST_KEY, 2, 8, us,
                                          rates_vec[us], data)
            ms8 = pend.fetch()
            us = _sched(cfg, 10, 8)
            p, pend = eng.train_superstep(p, HOST_KEY, 10, 8, us,
                                          rates_vec[us], data,
                                          eval_mask=(False,) * 7 + (True,),
                                          fused_eval=fe)
            mse = pend.fetch()
        else:
            d = data
            if cell == "masked-sharded":
                d = shard_client_data(mesh, data)
                eng = RoundEngine(model,
                                  dict(cfg, data_placement="sharded", **over),
                                  mesh)
            else:
                eng = RoundEngine(model, dict(cfg, **over), mesh)
            p = model.init(jax.random.key(0))
            p, ms1 = eng.train_round(p, jax.random.key(1), 0.05,
                                     np.array([0, 2, 4, 6]), d)
            kw = {"user_schedule": _sched(cfg, 2, 8)} \
                if cell == "masked-sharded" else {"num_active": 4}
            p, pend = eng.train_superstep(p, HOST_KEY, 2, 8, d, **kw)
            ms8 = pend.fetch()
            kw = {"user_schedule": _sched(cfg, 10, 8)} \
                if cell == "masked-sharded" else {"num_active": 4}
            p, pend = eng.train_superstep(p, HOST_KEY, 10, 8, d,
                                          eval_mask=(False,) * 7 + (True,),
                                          fused_eval=fe, **kw)
            mse = pend.fetch()
        outs[name] = (jax.device_get(p), jax.device_get(ms1), ms8, mse)
    _assert_tree_equal(outs["fused"][0], outs["ref"][0], err=cell)
    _metrics_agree(outs["fused"][1], outs["ref"][1])
    _metrics_agree(outs["fused"][2], outs["ref"][2])
    _metrics_agree(outs["fused"][3], outs["ref"][3])


@pytest.fixture(scope="module")
def vision():
    cfg, ds, data = _vision_setup()
    return {"cfg": cfg, "ds": ds, "data": data,
            "model": make_model(cfg), "mesh": make_mesh(8, 1)}


@pytest.fixture(scope="module")
def fused_eval(vision):
    """One FusedEval shared by the fused and reference engines (the eval
    phase is untouched by fused_update; sharing pins identical operands)."""
    from test_evalfused import _batch
    from heterofl_tpu.parallel.evaluation import Evaluator

    ds, cfg = vision["ds"], vision["cfg"]
    te = ds["test"]
    sbn_b = _batch(ds["train"].data, 20)
    xu = te.data[:96].reshape(8, 1, 12, 28, 28, 1)
    yu = te.target[:96].reshape(8, 1, 12)
    wu = np.ones((8, 1, 12), np.float32)
    lmu = np.ones((8, 10), np.float32)
    xg, wg = _batch(te.data, 20)
    yg, _ = _batch(te.target, 20)
    ev = Evaluator(vision["model"], cfg, vision["mesh"], seed=0)
    return ev.fused(sbn_batches=sbn_b, local_eval=(xu, yu, wu, lmu),
                    global_eval=(xg, yg, wg))


def _sched(cfg, epoch0, k, num_active=4):
    return np.stack([
        np.asarray(round_users(jax.random.fold_in(HOST_KEY, epoch0 + r),
                               cfg["num_users"], num_active))
        for r in range(k)])


def test_fused_masked_replicated_trajectory(vision, fused_eval):
    """masked x replicated, K in {1, 8}, with and without the eval mask:
    multi-step-round trajectories agree at float-association level (the
    bitwise step-level contract is test_fused_step_results_bit_identical_
    matrix)."""
    cfg, model, mesh, data = (vision["cfg"], vision["model"], vision["mesh"],
                              vision["data"])
    outs = {}
    for name, over in [("fused", {}), ("ref", {"fused_update": False})]:
        eng = RoundEngine(model, dict(cfg, **over), mesh)
        p = model.init(jax.random.key(0))
        # K=1: the one-round program
        p, ms1 = eng.train_round(p, jax.random.key(1), 0.05,
                                 np.array([0, 2, 4, 6]), data)
        # K=8 train-only superstep (in-jit sampling)
        p, pend = eng.train_superstep(p, HOST_KEY, 2, 8, data, num_active=4)
        ms8 = pend.fetch()
        # K=8 with the eval mask (eval inside the scanned program)
        p, pend = eng.train_superstep(p, HOST_KEY, 10, 8, data, num_active=4,
                                      eval_mask=(False,) * 7 + (True,),
                                      fused_eval=fused_eval)
        mse = pend.fetch()
        outs[name] = (jax.device_get(p), jax.device_get(ms1), ms8, mse)
    _assert_tree_close(outs["fused"][0], outs["ref"][0])
    _metrics_agree(outs["fused"][1], outs["ref"][1], exact=False)
    _metrics_agree(outs["fused"][2], outs["ref"][2], exact=False)
    _metrics_agree(outs["fused"][3], outs["ref"][3], exact=False)


def test_fused_masked_sharded_trajectory(vision, fused_eval):
    """masked x sharded placement, K in {1, 8}, with and without eval
    (association-level; see the step-level matrix test for bitwise)."""
    cfg, model, mesh = vision["cfg"], vision["model"], vision["mesh"]
    data_sh = shard_client_data(mesh, vision["data"])
    outs = {}
    for name, over in [("fused", {}), ("ref", {"fused_update": False})]:
        eng = RoundEngine(model, dict(cfg, data_placement="sharded", **over),
                          mesh)
        p = model.init(jax.random.key(0))
        p, ms1 = eng.train_round(p, jax.random.key(1), 0.05,
                                 np.array([1, 3, 5, 7]), data_sh)
        p, pend = eng.train_superstep(p, HOST_KEY, 2, 8, data_sh,
                                      user_schedule=_sched(cfg, 2, 8))
        ms8 = pend.fetch()
        p, pend = eng.train_superstep(p, HOST_KEY, 10, 8, data_sh,
                                      user_schedule=_sched(cfg, 10, 8),
                                      eval_mask=(False,) * 7 + (True,),
                                      fused_eval=fused_eval)
        mse = pend.fetch()
        outs[name] = (jax.device_get(p), jax.device_get(ms1), ms8, mse)
    _assert_tree_close(outs["fused"][0], outs["ref"][0])
    _metrics_agree(outs["fused"][1], outs["ref"][1], exact=False)
    _metrics_agree(outs["fused"][2], outs["ref"][2], exact=False)
    _metrics_agree(outs["fused"][3], outs["ref"][3], exact=False)


@pytest.mark.parametrize("placement", ["span", "slices"])
def test_fused_grouped_trajectory(vision, fused_eval, placement):
    """grouped x {span, slices}, K in {1, 8}, with and without eval
    (association-level; see the step-level matrix test for bitwise)."""
    cfg, model, mesh, data = (vision["cfg"], vision["model"], vision["mesh"],
                              vision["data"])
    rates_vec = np.asarray(cfg["model_rate"], np.float32)
    user_idx = np.array([0, 2, 4, 6, 1, 3])
    outs = {}
    for name, over in [("fused", {}), ("ref", {"fused_update": False})]:
        grp = GroupedRoundEngine(
            dict(cfg, level_placement=placement, **over), mesh)
        p = model.init(jax.random.key(0))
        p, ms1 = grp.train_round(p, user_idx, rates_vec[user_idx], data,
                                 0.05, jax.random.key(1))
        us = _sched(cfg, 2, 8)
        p, pend = grp.train_superstep(p, HOST_KEY, 2, 8, us, rates_vec[us],
                                      data)
        ms8 = pend.fetch()
        us = _sched(cfg, 10, 8)
        p, pend = grp.train_superstep(p, HOST_KEY, 10, 8, us, rates_vec[us],
                                      data, eval_mask=(False,) * 7 + (True,),
                                      fused_eval=fused_eval)
        mse = pend.fetch()
        outs[name] = (jax.device_get(p), ms1, ms8, mse)
    _assert_tree_close(outs["fused"][0], outs["ref"][0])
    _metrics_agree(outs["fused"][1], outs["ref"][1], exact=False)
    _metrics_agree(outs["fused"][2], outs["ref"][2], exact=False)
    _metrics_agree(outs["fused"][3], outs["ref"][3], exact=False)


@pytest.mark.slow
def test_fused_lm_round_bit_identical():
    """The LM local step (no has-gating, sequence-parallel axis) keeps the
    same contract."""
    cfg, data = _lm_setup()
    model = make_model(cfg)
    mesh = make_mesh(2, 2)
    outs = {}
    for name, over in [("fused", {}), ("ref", {"fused_update": False})]:
        eng = RoundEngine(model, dict(cfg, **over), mesh)
        p = model.init(jax.random.key(0))
        p, _ = eng.train_round(p, jax.random.key(1), 0.05,
                               np.array([0, 1, 2, 3]), data)
        outs[name] = jax.device_get(p)
    _assert_tree_equal(outs["fused"], outs["ref"])


def test_non_sgd_optimizer_keeps_reference_chain(vision):
    """A non-SGD optimizer silently keeps the reference chain (fused mode
    resolves to None) and the round still runs."""
    cfg, model, mesh, data = (vision["cfg"], vision["model"], vision["mesh"],
                              vision["data"])
    eng = RoundEngine(model, dict(cfg, optimizer_name="Adam"), mesh)
    assert eng._fused_mode is None
    p = model.init(jax.random.key(0))
    p, ms = eng.train_round(p, jax.random.key(1), 0.01,
                            np.array([0, 2]), data)
    assert np.isfinite(np.asarray(ms["loss_sum"])).all()


@pytest.mark.slow
def test_fused_resnet_single_step_bit_identical():
    """ResNet-18 depth: one local step is bitwise exact fused-vs-reference
    -- the per-step math is the reference chain's.  (Multi-round ResNet
    trajectories diverge at float-association level: XLA's global fusion
    choices shift one reduce emission by 1 ulp somewhere in the ~400-fusion
    loop body and SGD amplifies it chaotically -- the same class of
    agreement as the masked-vs-sliced engine contract.  The conv/LM matrix
    above is bitwise at trajectory level.)"""
    from heterofl_tpu import config as C
    from heterofl_tpu.data import (fetch_dataset, label_split_masks,
                                   split_dataset, stack_client_shards)

    users = 8
    cfg = C.default_cfg()
    cfg["control"] = C.parse_control_name(
        f"1_{users}_0.5_iid_fix_a1-b1-c1-d1-e1_bn_1_1")
    cfg["data_name"], cfg["model_name"], cfg["synthetic"] = \
        "MNIST", "resnet18", True
    cfg = C.process_control(cfg)
    cfg["resnet"] = {"hidden_size": [8, 16, 16, 16]}
    cfg["classes_size"] = 10
    cfg["num_epochs"] = dict(cfg["num_epochs"], local=1)
    ds = fetch_dataset("MNIST", synthetic=True, seed=0,
                       synthetic_sizes={"train": 80, "test": 40})
    rng = np.random.default_rng(0)
    split, lsplit = split_dataset(ds, users, "iid", rng, classes_size=10)
    x, y, m = stack_client_shards(ds["train"].data, ds["train"].target,
                                  split["train"], list(range(users)))
    lm = label_split_masks(lsplit, users, 10)
    data = (jnp.asarray(x), jnp.asarray(y), jnp.asarray(m), jnp.asarray(lm))
    model = make_model(cfg)
    mesh = make_mesh(8, 1)
    outs = {}
    for name, over in [("fused", {}), ("ref", {"fused_update": False})]:
        eng = RoundEngine(model, dict(cfg, **over), mesh)
        p = model.init(jax.random.key(0))
        p, _ = eng.train_round(p, jax.random.key(0), 0.1, np.arange(8), data)
        outs[name] = jax.device_get(p)
    _assert_tree_equal(outs["fused"], outs["ref"])
