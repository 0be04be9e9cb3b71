"""Round engine integration: multi-device federated rounds on the virtual
8-device CPU mesh (the multi-chip validation path, SURVEY §4)."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from heterofl_tpu import config as C
from heterofl_tpu.data import fetch_dataset, label_split_masks, split_dataset, stack_client_shards
from heterofl_tpu.models import make_model
from heterofl_tpu.models.spec import mask_params
from heterofl_tpu.parallel import RoundEngine, make_mesh
from heterofl_tpu.parallel.evaluation import Evaluator

from test_models import small_cfg


def _vision_setup(control="1_8_0.5_iid_fix_a1-b1-c1-d1-e1_bn_1_1", data="MNIST", users=8):
    cfg = small_cfg("conv", data_name=data, control=control)
    ds = fetch_dataset(data, synthetic=True, seed=0, synthetic_sizes={"train": 400, "test": 100})
    rng = np.random.default_rng(0)
    split, lsplit = split_dataset(ds, users, cfg["data_split_mode"], rng, classes_size=10)
    x, y, m = stack_client_shards(ds["train"].data, ds["train"].target, split["train"],
                                  list(range(users)))
    lm = label_split_masks(lsplit, users, 10)
    return cfg, ds, (jnp.asarray(x), jnp.asarray(y), jnp.asarray(m), jnp.asarray(lm))


def test_vision_round_loss_decreases_multidevice():
    cfg, ds, data = _vision_setup()
    model = make_model(cfg)
    params = model.init(jax.random.key(0))
    mesh = make_mesh(n_clients=4, n_data=2)
    eng = RoundEngine(model, cfg, mesh)
    user_idx = np.array([0, 2, 4, 6])  # rates 1, .5, .25, .0625 territory
    losses = []
    for r in range(3):
        params, ms = eng.train_round(params, jax.random.key(r), 0.05, user_idx, data)
        ms = {k: np.asarray(v) for k, v in ms.items()}
        losses.append(float(ms["loss_sum"].sum() / ms["n"].sum()))
    assert losses[-1] < losses[0], losses
    assert np.isfinite(losses).all()
    # padded slots report zero weight
    params2, ms2 = eng.train_round(params, jax.random.key(9), 0.05, np.array([1, 3, 5]), data)
    n = np.asarray(ms2["n"])
    assert n.shape[0] == 4 and n[-1] == 0.0
    # masked suffix of aggregated params stays identically zero under e-rate view
    sm = mask_params(params2, model.specs, model.groups, 0.0625)
    tail = np.asarray(params2["block1.conv.w"])[:, :, :, 1:] - np.asarray(sm["block1.conv.w"])[:, :, :, 1:]
    assert np.isfinite(np.asarray(params2["block1.conv.w"])).all()


@pytest.mark.slow
def test_tiny_shards_smaller_than_batch():
    """Shards with N < batch size (and N < B/2) must still trace and train:
    the epoch permutation is tiled, dead steps are skipped (review regression)."""
    cfg, ds, _ = _vision_setup()
    model = make_model(cfg)
    params = model.init(jax.random.key(0))
    mesh = make_mesh(n_clients=2, n_data=1)
    eng = RoundEngine(model, cfg, mesh)
    # 4 samples per client with train batch 10 -> SB-N=6 > N=4
    rng = np.random.default_rng(0)
    x = jnp.asarray(rng.integers(0, 255, (8, 4, 28, 28, 1)), jnp.uint8)
    y = jnp.asarray(rng.integers(0, 10, (8, 4)))
    m = jnp.ones((8, 4), jnp.float32)
    # client 1 has only 2 real samples
    m = m.at[1, 2:].set(0.0)
    lm = jnp.ones((8, 10), jnp.float32)
    p2, ms = eng.train_round(params, jax.random.key(0), 0.05, np.array([0, 1]), (x, y, m, lm))
    ms = {k: np.asarray(v) for k, v in ms.items()}
    assert np.isfinite(ms["loss_sum"]).all()
    E = cfg["num_epochs"]["local"]
    assert ms["n"][0] == 4.0 * E  # every real sample seen once per local epoch
    assert ms["n"][1] == 2.0 * E


def test_round_deterministic():
    cfg, ds, data = _vision_setup()
    model = make_model(cfg)
    params = model.init(jax.random.key(0))
    mesh = make_mesh(n_clients=2, n_data=1)
    eng = RoundEngine(model, cfg, mesh)
    p1, m1 = eng.train_round(params, jax.random.key(5), 0.05, np.array([0, 1]), data)
    eng2 = RoundEngine(model, cfg, mesh)
    params_b = model.init(jax.random.key(0))
    p2, m2 = eng2.train_round(params_b, jax.random.key(5), 0.05, np.array([0, 1]), data)
    for k in p1:
        np.testing.assert_allclose(np.asarray(p1[k]), np.asarray(p2[k]), rtol=1e-6, err_msg=k)


# ---------------------------------------------------------------------------
# the local step's update (RoundEngine._local_setup / _apply_update) against
# the reference's step, stated here in NumPy float64
# ---------------------------------------------------------------------------

LEVELS = {"a": 1.0, "e": 0.0625}


def _step_case(family, level, optimizer="SGD"):
    """(engine, masked params, its width rate) of a tiny model at one level."""
    cfg = small_cfg(family, data_name="WikiText2" if family == "transformer" else "MNIST")
    cfg["optimizer_name"] = optimizer
    model = make_model(cfg)
    wr = LEVELS[level]
    p = mask_params(model.init(jax.random.key(0)), model.specs, model.groups, wr)
    return RoundEngine(model, cfg, mesh=None), p, wr


def _active(eng, p, wr):
    """Where a level's sub-model lives, without the engine's own masks: what
    ``mask_params`` keeps of a tree of ones."""
    ones = {k: jnp.ones_like(v) for k, v in p.items()}
    kept = mask_params(ones, eng.model.specs, eng.model.groups, wr)
    return {k: np.asarray(v) != 0.0 for k, v in kept.items()}


def _normalised_grads(g, active, n):
    """The batch-mean gradient of the sub-model after ``clip_grad_norm_(1)``
    (ref train_classifier_fed.py:205), and its norm before the clip."""
    g = {k: np.asarray(v, np.float64) / max(n, 1e-6) * active[k] for k, v in g.items()}
    total = np.sqrt(sum((v ** 2).sum() for v in g.values()))
    scale = min(1.0, 1.0 / (total + 1e-6))
    return {k: v * scale for k, v in g.items()}, total


def _random_grads(p, seed, scale):
    rng = np.random.default_rng(seed)
    return {k: jnp.asarray(rng.normal(size=v.shape) * scale, jnp.float32)
            for k, v in p.items()}


def _update(eng, has):
    gate = {} if has is None else {"has": jnp.asarray(has)}
    return jax.jit(lambda p, g, opt, masks, n, lr: eng._apply_update(
        p, g, opt, masks, n, lr, **gate))


@pytest.mark.parametrize("level", sorted(LEVELS))
@pytest.mark.parametrize("has", [None, True, False])
@pytest.mark.parametrize("clip", [False, True], ids=["noclip", "clip"])
@pytest.mark.parametrize("family", ["conv", "transformer"])
def test_apply_update_is_the_references_step(family, clip, has, level):
    """Two consecutive steps (the second with momentum behind it): mean-
    normalise, width mask, ``clip_grad_norm_(1)``, torch SGD with momentum
    0.9 and weight decay 5e-4.  Outside the level's slice the parameters
    stay exactly zero and so does the momentum; an all-padding batch
    (``has`` False) moves nothing, not by weight decay either."""
    eng, p, wr = _step_case(family, level)
    active = _active(eng, p, wr)
    p, opt, masks = eng._local_setup(p, wr)
    n, lr, momentum, wd = 7.0, 0.05, 0.9, 5e-4
    assert (eng.cfg["momentum"], eng.cfg["weight_decay"]) == (momentum, wd)
    update = _update(eng, has)
    for step in range(2):
        g = _random_grads(p, 10 + step, 1e2 if clip else 1e-3)
        g64, total = _normalised_grads(g, active, n)
        assert (total > 1.0) == clip, total
        new_p, new_opt = update(p, g, opt, masks, jnp.float32(n), jnp.float32(lr))
        if has is False:
            for k in p:
                np.testing.assert_array_equal(np.asarray(new_p[k]), np.asarray(p[k]), err_msg=k)
                np.testing.assert_array_equal(np.asarray(new_opt.slots[k]),
                                              np.asarray(opt.slots[k]), err_msg=k)
            assert int(new_opt.step) == int(opt.step)
            has, update = True, _update(eng, True)  # the next step is a real one
            new_p, new_opt = update(p, g, opt, masks, jnp.float32(n), jnp.float32(lr))
        for k in p:
            buf = momentum * np.asarray(opt.slots[k], np.float64) + g64[k] \
                + wd * np.asarray(p[k], np.float64)
            np.testing.assert_allclose(np.asarray(new_opt.slots[k]), buf,
                                       rtol=1e-5, atol=1e-7, err_msg=k)
            np.testing.assert_allclose(np.asarray(new_p[k]),
                                       np.asarray(p[k], np.float64) - lr * buf,
                                       rtol=1e-5, atol=1e-7, err_msg=k)
            assert np.all(np.asarray(new_p[k])[~active[k]] == 0.0), k
            assert np.all(np.asarray(new_opt.slots[k])[~active[k]] == 0.0), k
            if level == "a":
                assert active[k].all(), k
        if level == "e":  # whole rows of zero width: most of the tree is inactive
            assert sum(a.sum() for a in active.values()) \
                < 0.2 * sum(a.size for a in active.values())
        p, opt = new_p, new_opt
    assert int(opt.step) == 2


@pytest.mark.parametrize("level", sorted(LEVELS))
@pytest.mark.parametrize("family", ["conv", "resnet18", "transformer"])
def test_local_setup_hoists_the_masks(family, level):
    """What the scan closes over: the masked tree as it came, a zero
    optimizer state of the same leaves, and per leaf the width mask
    ``param_mask`` gives -- built once, outside the step."""
    from heterofl_tpu.models.spec import param_mask

    eng, p, wr = _step_case(family, level)
    carry, opt, masks = eng._local_setup(p, wr)
    assert carry is p
    assert sorted(masks) == sorted(opt.slots) == sorted(p)
    active = _active(eng, p, wr)
    for k, v in p.items():
        assert masks[k].shape == v.shape and opt.slots[k].shape == v.shape
        np.testing.assert_array_equal(
            np.asarray(masks[k]),
            np.asarray(param_mask(v.shape, eng.model.specs[k], eng.model.groups, wr)),
            err_msg=k)
        np.testing.assert_array_equal(np.asarray(masks[k]) != 0.0, active[k], err_msg=k)
        assert not np.asarray(opt.slots[k]).any()
    # nothing of them is rebuilt in the step: its jaxpr holds no iota
    update = _update(eng, True)
    g = _random_grads(p, 0, 1e-3)
    text = str(jax.make_jaxpr(update)(p, g, opt, masks, jnp.float32(1.0), jnp.float32(0.1)))
    assert "iota" not in text


@pytest.mark.parametrize("optimizer", ["RMSprop", "Adam", "Adamax"])
def test_every_optimizer_takes_the_one_chain(optimizer):
    """No optimizer has a step of its own: each gets the normalised, masked,
    clipped gradient, and the ``has`` gate holds its whole state."""
    from heterofl_tpu.utils.optim import make_optimizer

    eng, p, wr = _step_case("conv", "e", optimizer)
    active = _active(eng, p, wr)
    p, opt, masks = eng._local_setup(p, wr)
    g = _random_grads(p, 3, 1e2)
    n, lr = jnp.float32(4.0), jnp.float32(0.01)
    g64, total = _normalised_grads(g, active, 4.0)
    assert total > 1.0
    want_p, want_opt = jax.jit(make_optimizer(eng.cfg)[1])(
        p, {k: jnp.asarray(v, jnp.float32) for k, v in g64.items()}, opt, lr)
    got_p, got_opt = _update(eng, True)(p, g, opt, masks, n, lr)
    for got, want in zip(jax.tree_util.tree_leaves((got_p, got_opt)),
                         jax.tree_util.tree_leaves((want_p, want_opt))):
        np.testing.assert_allclose(np.asarray(got), np.asarray(want), rtol=1e-4, atol=1e-6)
    for k in p:
        assert np.all(np.asarray(got_p[k])[~active[k]] == 0.0), k
    assert any(np.asarray(got_p[k] != p[k]).any() for k in p)
    held_p, held_opt = _update(eng, False)(p, g, opt, masks, n, lr)
    for got, want in zip(jax.tree_util.tree_leaves((held_p, held_opt)),
                         jax.tree_util.tree_leaves((p, opt))):
        np.testing.assert_array_equal(np.asarray(got), np.asarray(want))


@pytest.mark.slow
def test_dynamic_mode_round():
    cfg, ds, data = _vision_setup(control="1_8_0.5_iid_dynamic_a1-e1_bn_1_1")
    model = make_model(cfg)
    params = model.init(jax.random.key(0))
    mesh = make_mesh(n_clients=4, n_data=1)
    eng = RoundEngine(model, cfg, mesh)
    params, ms = eng.train_round(params, jax.random.key(0), 0.05, np.array([0, 1, 2, 3]), data)
    rates = np.asarray(ms["rate"])
    assert set(np.unique(rates).tolist()) <= {1.0, 0.0625}
    assert np.isfinite(float(np.asarray(ms["loss_sum"]).sum()))


@pytest.mark.slow
def test_lm_round():
    cfg = small_cfg("transformer", data_name="WikiText2")
    users = 4
    # 4 users x 2 rows x 48 tokens
    rng = np.random.default_rng(0)
    rows = rng.integers(0, 50, size=(users, 2, 48)).astype(np.int64)
    lm = np.ones((users, 50), np.float32)
    model = make_model(cfg)
    params = model.init(jax.random.key(0))
    mesh = make_mesh(n_clients=2, n_data=1)
    eng = RoundEngine(model, cfg, mesh)
    data = (jnp.asarray(rows), jnp.asarray(lm))
    losses = []
    for r in range(3):
        params, ms = eng.train_round(params, jax.random.key(r), 0.5, np.arange(users), data)
        ms = {k: np.asarray(v) for k, v in ms.items()}
        losses.append(float(ms["loss_sum"].sum() / ms["n"].sum()))
    assert losses[-1] < losses[0], losses


def _lm_setup(control="1_4_0.5_iid_fix_a1-b1_bn_1_1", users=4):
    cfg = small_cfg("transformer", data_name="WikiText2", control=control)
    rng = np.random.default_rng(0)
    rows = rng.integers(0, 50, size=(users, 2, 48)).astype(np.int64)
    lm = np.ones((users, 50), np.float32)
    return cfg, (jnp.asarray(rows), jnp.asarray(lm))


@pytest.mark.slow
def test_lm_seq_parallel_matches_single_device():
    """Sequence parallelism over the 'data' axis (ring attention + psum'd
    grads, shard-invariant token corruption) matches the clients-only mesh:
    a (2,2) mesh LM round equals a (2,1) mesh round with the same keys
    (dropout 0 -- dropout shards are decorrelated by design)."""
    cfg, data = _lm_setup()
    model = make_model(cfg)
    user_idx = np.arange(4)

    p1 = model.init(jax.random.key(0))
    eng1 = RoundEngine(model, cfg, make_mesh(2, 1))
    out1, ms1 = eng1.train_round(p1, jax.random.key(5), 0.5, user_idx, data)

    p2 = model.init(jax.random.key(0))
    eng2 = RoundEngine(model, cfg, make_mesh(2, 2))
    out2, ms2 = eng2.train_round(p2, jax.random.key(5), 0.5, user_idx, data)

    for k in out1:
        np.testing.assert_allclose(np.asarray(out1[k]), np.asarray(out2[k]),
                                   rtol=2e-3, atol=1e-5, err_msg=k)
    np.testing.assert_allclose(np.asarray(ms1["loss_sum"]), np.asarray(ms2["loss_sum"]),
                               rtol=1e-4)
    np.testing.assert_allclose(np.asarray(ms1["n"]), np.asarray(ms2["n"]))


@pytest.mark.slow
def test_lm_seq_parallel_four_way_with_dropout_runs():
    """4-way sequence sharding with dropout>0 trains and the loss falls."""
    cfg, data = _lm_setup()
    cfg["transformer"]["dropout"] = 0.1
    model = make_model(cfg)
    mesh = make_mesh(2, 4)
    eng = RoundEngine(model, cfg, mesh)
    params = model.init(jax.random.key(0))
    losses = []
    for r in range(3):
        params, ms = eng.train_round(params, jax.random.key(r), 0.5, np.arange(4), data)
        ms = {k: np.asarray(v) for k, v in ms.items()}
        losses.append(float(ms["loss_sum"].sum() / ms["n"].sum()))
    assert np.isfinite(losses).all() and losses[-1] < losses[0], losses


@pytest.mark.slow
def test_sbn_and_eval():
    cfg, ds, data = _vision_setup()
    model = make_model(cfg)
    params = model.init(jax.random.key(0))
    mesh = make_mesh(n_clients=4, n_data=2)
    ev = Evaluator(model, cfg, mesh)
    # batch the train set [S, B, ...]
    B = 20
    xtr = ds["train"].data[:400].reshape(-1, B, 28, 28, 1)
    wtr = np.ones(xtr.shape[:2], np.float32)
    bn = ev.sbn_stats(params, xtr, wtr)
    assert set(bn.keys()) == set(model.bn_sites)
    for site, (mu, var) in bn.items():
        assert np.isfinite(np.asarray(mu)).all() and (np.asarray(var) >= 0).all()
    # global eval
    xte = ds["test"].data.reshape(-1, 20, 28, 28, 1)
    yte = ds["test"].target.reshape(-1, 20)
    wte = np.ones(xte.shape[:2], np.float32)
    out = ev.eval_global(params, bn, xte, yte, wte)
    assert out["n"] == 100.0
    assert 0 <= out["score_sum"] <= 100
    # per-user local eval: 4 users, shards of 25 -> 1 batch of 25 (pad to B=25)
    xu = ds["test"].data[:100].reshape(4, 1, 25, 28, 28, 1)
    yu = ds["test"].target[:100].reshape(4, 1, 25)
    wu = np.ones((4, 1, 25), np.float32)
    lmu = np.ones((4, 10), np.float32)
    res = ev.eval_users(params, bn, xu, yu, wu, lmu)
    assert res["n"].shape == (4,) and np.all(res["n"] == 25.0)


@pytest.mark.slow
def test_eval_rng_varies_across_epochs():
    """Eval-time LM token corruption draws fresh noise per round: keys are
    fold_in(key, epoch), so a frozen model yields *different* Global metrics
    across epochs (ref draws fresh Bernoulli noise per eval pass,
    src/models/transformer.py:148-151) while the same epoch reproduces
    exactly."""
    cfg, _ = _lm_setup()
    model = make_model(cfg)
    params = model.init(jax.random.key(0))
    ev = Evaluator(model, cfg, make_mesh(2, 1))
    rng = np.random.default_rng(1)
    rows = rng.integers(0, 50, size=(2, 2, 48)).astype(np.int64)
    w = np.ones(rows.shape, np.float32)
    g0a = ev.eval_global(params, {}, rows, w, epoch=0)
    g0b = ev.eval_global(params, {}, rows, w, epoch=0)
    g1 = ev.eval_global(params, {}, rows, w, epoch=1)
    assert g0a["loss_sum"] == g0b["loss_sum"]
    assert g0a["loss_sum"] != g1["loss_sum"]


@pytest.mark.slow
def test_eval_rng_varies_across_seeds():
    """Eval RNG descends from the EXPERIMENT seed (ref: the eval pass draws
    from the seed-controlled global torch RNG, src/models/transformer.py:148-151):
    two experiments with different seeds see different LM corruption noise on
    the same frozen model, while the same seed reproduces exactly."""
    cfg, _ = _lm_setup()
    model = make_model(cfg)
    params = model.init(jax.random.key(0))
    rng = np.random.default_rng(1)
    rows = rng.integers(0, 50, size=(2, 2, 48)).astype(np.int64)
    w = np.ones(rows.shape, np.float32)
    g_s0 = Evaluator(model, cfg, make_mesh(2, 1), seed=0).eval_global(params, {}, rows, w, epoch=0)
    g_s0b = Evaluator(model, cfg, make_mesh(2, 1), seed=0).eval_global(params, {}, rows, w, epoch=0)
    g_s1 = Evaluator(model, cfg, make_mesh(2, 1), seed=1).eval_global(params, {}, rows, w, epoch=0)
    assert g_s0["loss_sum"] == g_s0b["loss_sum"]
    assert g_s0["loss_sum"] != g_s1["loss_sum"]


@pytest.mark.slow
def test_client_failure_injection():
    """Failed clients' updates never reach aggregation; an all-failed round
    leaves the global model untouched (stale rule)."""
    cfg, ds, data = _vision_setup()
    cfg["client_failure_rate"] = 1.0
    model = make_model(cfg)
    params = model.init(jax.random.key(0))
    p_np = {k: np.asarray(v) for k, v in params.items()}
    eng = RoundEngine(model, cfg, make_mesh(2, 1))
    new, ms = eng.train_round(params, jax.random.key(0), 0.05, np.array([0, 1]), data)
    for k in p_np:
        np.testing.assert_array_equal(np.asarray(new[k]), p_np[k], err_msg=k)
    assert float(np.asarray(ms["n"]).sum()) == 0.0
    # partial failure still trains
    cfg2 = dict(cfg)
    cfg2["client_failure_rate"] = 0.5
    eng2 = RoundEngine(model, cfg2, make_mesh(2, 1))
    params2 = model.init(jax.random.key(0))
    new2, ms2 = eng2.train_round(params2, jax.random.key(3), 0.05,
                                 np.arange(8, dtype=np.int32), data)
    n2 = np.asarray(ms2["n"])
    assert 0 < (n2 > 0).sum() < 8  # some failed, some trained


@pytest.mark.slow
def test_data_parallel_axis_matches_single_device():
    """Intra-client batch DP over the 'data' axis (psum'd grads + sync BN) is
    numerically identical to running each client on one device: a (2,2) mesh
    round equals a (4,1) mesh round with the same keys (MNIST: no augment)."""
    cfg, ds, data = _vision_setup()
    model = make_model(cfg)
    user_idx = np.array([0, 2, 4, 6])

    p1 = model.init(jax.random.key(0))
    eng1 = RoundEngine(model, cfg, make_mesh(4, 1))
    out1, ms1 = eng1.train_round(p1, jax.random.key(5), 0.05, user_idx, data)

    p2 = model.init(jax.random.key(0))
    eng2 = RoundEngine(model, cfg, make_mesh(2, 2))
    out2, ms2 = eng2.train_round(p2, jax.random.key(5), 0.05, user_idx, data)

    for k in out1:
        np.testing.assert_allclose(np.asarray(out1[k]), np.asarray(out2[k]),
                                   rtol=5e-3, atol=5e-5, err_msg=k)
    np.testing.assert_allclose(np.asarray(ms1["loss_sum"]), np.asarray(ms2["loss_sum"]),
                               rtol=1e-4)
    np.testing.assert_allclose(np.asarray(ms1["n"]), np.asarray(ms2["n"]))


@pytest.mark.slow
def test_sharded_placement_matches_replicated():
    """Client-sharded data placement (each client trains on the device owning
    its shard, VERDICT r1 item 6): numerically identical global params to the
    replicated layout (per-client RNG is keyed by global user id, so the
    client->device assignment cannot matter), and per-device train-stack
    buffers hold exactly U/n_dev client shards."""
    from heterofl_tpu.parallel import shard_client_data

    cfg, ds, data = _vision_setup()
    model = make_model(cfg)
    user_idx = np.array([0, 2, 5, 6])  # owners {0,1,2,3} on a 4-dev axis: 0,1,2,3
    mesh = make_mesh(n_clients=4, n_data=1)

    p1 = model.init(jax.random.key(0))
    eng1 = RoundEngine(model, cfg, mesh)
    out1, ms1 = eng1.train_round(p1, jax.random.key(5), 0.05, user_idx, data)

    cfg2 = dict(cfg)
    cfg2["data_placement"] = "sharded"
    sharded = shard_client_data(mesh, data)
    # the big per-user stacks live 1/n_dev per device
    for arr, orig in zip(sharded, data):
        shard0 = arr.addressable_shards[0].data
        assert shard0.shape[0] == arr.shape[0] // 4
        assert shard0.nbytes * 4 == arr.nbytes
    p2 = model.init(jax.random.key(0))
    eng2 = RoundEngine(model, cfg2, mesh)
    out2, ms2 = eng2.train_round(p2, jax.random.key(5), 0.05, user_idx, data=sharded)

    for k in out1:
        np.testing.assert_allclose(np.asarray(out1[k]), np.asarray(out2[k]),
                                   rtol=1e-6, atol=1e-7, err_msg=k)
    # metric sums are slot-order independent
    np.testing.assert_allclose(np.asarray(ms1["loss_sum"]).sum(),
                               np.asarray(ms2["loss_sum"]).sum(), rtol=1e-6)
    assert np.asarray(ms1["n"]).sum() == np.asarray(ms2["n"]).sum()


@pytest.mark.slow
def test_sharded_placement_lm_matches_replicated():
    """Sharded placement on the LM path: token-row stacks sharded over the
    clients axis give the same round as replicated."""
    from heterofl_tpu.parallel import shard_client_data

    cfg, data = _lm_setup()
    model = make_model(cfg)
    mesh = make_mesh(2, 1)
    user_idx = np.arange(4)

    p1 = model.init(jax.random.key(0))
    out1, ms1 = RoundEngine(model, cfg, mesh).train_round(
        p1, jax.random.key(5), 0.5, user_idx, data)

    cfg2 = dict(cfg)
    cfg2["data_placement"] = "sharded"
    sharded = shard_client_data(mesh, data)
    assert sharded[0].addressable_shards[0].data.shape[0] == 2
    p2 = model.init(jax.random.key(0))
    out2, ms2 = RoundEngine(model, cfg2, mesh).train_round(
        p2, jax.random.key(5), 0.5, user_idx, sharded)

    for k in out1:
        np.testing.assert_allclose(np.asarray(out1[k]), np.asarray(out2[k]),
                                   rtol=1e-6, atol=1e-7, err_msg=k)
    np.testing.assert_allclose(np.asarray(ms1["n"]).sum(), np.asarray(ms2["n"]).sum())


@pytest.mark.slow
def test_sharded_placement_unbalanced_and_padded():
    """Sharded placement with a non-divisible user count and an unbalanced
    active set (3 actives owned by one device) trains correctly; padded users
    are never touched."""
    from heterofl_tpu.parallel import shard_client_data

    cfg, ds, data = _vision_setup(control="1_6_0.5_iid_fix_a1-b1_bn_1_1", users=6)
    model = make_model(cfg)
    mesh = make_mesh(n_clients=4, n_data=1)  # U=6 pads to 8, 2 users per device
    sharded = shard_client_data(mesh, data)
    assert sharded[0].shape[0] == 8
    cfg = dict(cfg)
    cfg["data_placement"] = "sharded"
    eng = RoundEngine(model, cfg, mesh)
    params = model.init(jax.random.key(0))
    user_idx = np.array([0, 1, 2, 5])  # devices 0,0,1,2 -> slots=2, dev 3 idle
    out, ms = eng.train_round(params, jax.random.key(1), 0.05, user_idx, sharded)
    ms = {k: np.asarray(v) for k, v in ms.items()}
    E = cfg["num_epochs"]["local"]
    expect = float(np.asarray(data[2])[user_idx].sum()) * E
    assert ms["n"].sum() == expect  # every active shard fully visited
    assert np.isfinite(ms["loss_sum"]).all()
    for k in out:
        assert np.isfinite(np.asarray(out[k])).all(), k


@pytest.mark.slow
def test_scan_unroll_equivalent():
    """``scan_unroll`` is a pure perf knob: unrolled local-step loops (incl. a
    non-dividing factor) give the same round up to XLA fusion reassociation."""
    cfg, ds, data = _vision_setup()
    model = make_model(cfg)
    outs = []
    for unroll in (1, 3):
        cfg_u = dict(cfg)
        cfg_u["scan_unroll"] = unroll
        p = model.init(jax.random.key(0))
        eng = RoundEngine(model, cfg_u, make_mesh(1, 1))
        out, _ = eng.train_round(p, jax.random.key(3), 0.05,
                                 np.arange(2, dtype=np.int32), data)
        outs.append({k: np.asarray(v) for k, v in out.items()})
    for k in outs[0]:
        # fusion reassociation compounds over the local steps; a semantic bug
        # (skipped/duplicated step) would show as O(1e-1) differences
        np.testing.assert_allclose(outs[0][k], outs[1][k], rtol=2e-2, atol=2e-4,
                                   err_msg=k)


@pytest.mark.slow
def test_scan_unroll_single_step_exact():
    """With exactly ONE local step (E*S=1) the unrolled and non-unrolled
    programs must agree near-exactly -- a tight complement to the loose
    multi-step tolerance above that would catch an off-by-one in the unroll
    remainder handling (advisor finding, round 2)."""
    cfg, ds, _ = _vision_setup()
    cfg["num_epochs"]["local"] = 1
    model = make_model(cfg)
    rng = np.random.default_rng(0)
    # one batch per client: shard size == train batch size -> S=1
    b = cfg["batch_size"]["train"]
    x = jnp.asarray(rng.integers(0, 255, (8, b, 28, 28, 1)), jnp.uint8)
    y = jnp.asarray(rng.integers(0, 10, (8, b)))
    m = jnp.ones((8, b), jnp.float32)
    lm = jnp.ones((8, 10), jnp.float32)
    data = (x, y, m, lm)
    outs = []
    for unroll in (1, 3):
        cfg_u = dict(cfg)
        cfg_u["scan_unroll"] = unroll
        p = model.init(jax.random.key(0))
        eng = RoundEngine(model, cfg_u, make_mesh(1, 1))
        out, ms = eng.train_round(p, jax.random.key(3), 0.05,
                                  np.arange(2, dtype=np.int32), data)
        assert float(np.asarray(ms["n"]).sum()) == 2.0 * b  # exactly one pass
        outs.append({k: np.asarray(v) for k, v in out.items()})
    for k in outs[0]:
        np.testing.assert_allclose(outs[0][k], outs[1][k], rtol=1e-6, atol=1e-7,
                                   err_msg=k)


# ---------------------------------------------------------------------------
# the chunked cohort (cfg['round_chunk'], ISSUE 28) and the Kanana-2 family
# ---------------------------------------------------------------------------

def _chunk_case(family):
    """(cfg, data) of 8 users with 2 rows of 32 tokens each."""
    if family == "kanana2":
        from benchmark.tests import tiny_kanana2 as tiny

        cfg = tiny.program_cfg(control="1_8_0.5_iid_fix_a1-b1-c1-e1_bn_1_1",
                               num_hidden_layers=2)
    else:
        cfg = small_cfg("transformer", data_name="WikiText2",
                        control="1_8_0.5_iid_fix_a1-b1-c1_bn_1_1")
        cfg["transformer"]["dropout"] = 0.1  # per-slot streams must not move
    vocab = cfg["num_tokens"]
    rng = np.random.default_rng(0)
    rows = rng.integers(0, vocab, size=(8, 2, 32)).astype(np.int64)
    lm = np.ones((8, vocab), np.float32)
    lm[:, ::5] = 0.0
    return cfg, (jnp.asarray(rows), jnp.asarray(lm))


def _chunk_round(cfg, data, chunk, n_dev=1, users=(0, 1, 2, 3, 4, 5, 6, 7), **extra):
    cfg = dict(cfg, round_chunk=chunk, **extra)
    model = make_model(cfg)
    eng = RoundEngine(model, cfg, make_mesh(n_dev, 1))
    out, ms = eng.train_round(model.init(jax.random.key(0)), jax.random.key(5), 0.5,
                              np.asarray(users), data)
    return ({k: np.asarray(v) for k, v in out.items()},
            {k: np.asarray(v) for k, v in ms.items()})


@pytest.mark.parametrize("family", ["transformer", "kanana2"])
def test_chunked_round_is_the_unchunked_round(family):
    """(e) The cohort trained 1, 2 or all slots at a time, and 2 at a time on
    each of two devices, is the round of one vmap over all 8 slots: the same
    per-slot streams (dropout included), the same counts, the same single
    psum.  What differs is the order in which the slots' float32 products are
    added (all at once against chunk after chunk) and, for a chunk of one,
    the client step without its vmap: a few units in the last place of a
    parameter of order 1, so 1e-5 relative / 1e-6 absolute; a slot lost,
    doubled or trained on another slot's stream is off by 1e-2."""
    cfg, data = _chunk_case(family)
    base, base_ms = _chunk_round(cfg, data, None)
    for chunk, n_dev in [(1, 1), (2, 1), (8, 1), (2, 2)]:
        out, ms = _chunk_round(cfg, data, chunk, n_dev)
        for k in base:
            np.testing.assert_allclose(out[k], base[k], rtol=1e-5, atol=1e-6,
                                       err_msg=f"{family} chunk {chunk}: {k}")
        for k in ("loss_sum", "n", "rate"):
            np.testing.assert_allclose(ms[k], base_ms[k], rtol=1e-5)
    with pytest.raises(ValueError, match="does not divide"):
        _chunk_round(cfg, data, 3)


def test_chunked_round_keeps_padding_slots_and_the_engines_refuse():
    """A cohort with a padding slot (-1) under chunk 1 is the unchunked one;
    the engines without a chunked round core refuse the key at config
    resolution."""
    cfg, data = _chunk_case("transformer")
    users = (0, 1, 2, 3, 4, 5, 6, -1)
    base, base_ms = _chunk_round(cfg, data, None, users=users)
    out, ms = _chunk_round(cfg, data, 1, users=users)
    assert ms["n"][-1] == 0.0 and base_ms["n"][-1] == 0.0
    for k in base:
        np.testing.assert_allclose(out[k], base[k], rtol=1e-5, atol=1e-6, err_msg=k)
    for bad in (0, True, "2"):
        with pytest.raises(ValueError, match="round_chunk"):
            C.resolve_chunk_cfg({"round_chunk": bad})
    for strategy in ("grouped", "sliced"):
        with pytest.raises(ValueError, match="round_chunk"):
            C.resolve_chunk_cfg({"round_chunk": 2, "strategy": strategy})

