"""Federation algebra: distribute/combine identities, nesting, counted
averaging, label-split restriction, stale-value fallback (ref fed.py:180-298)."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from heterofl_tpu import config as C
from heterofl_tpu.fed import (
    active_indices,
    client_count_masks,
    combine_counted,
    distribute_masked,
    embed_sliced,
    extract_sliced,
    sample_model_rates,
)
from heterofl_tpu.models import make_model
from heterofl_tpu.models.spec import Group, ParamSpec, mask_params

from test_models import small_cfg


def _model_and_params(model_name="conv", **kw):
    cfg = small_cfg(model_name, **kw)
    m = make_model(cfg)
    p = m.init(jax.random.key(0))
    return cfg, m, p


def test_nesting_invariant():
    """rate r's active set is a subset of rate r' for every r < r' (every group)."""
    _, m, p = _model_and_params("resnet18")
    rates = [0.0625, 0.125, 0.25, 0.5, 1.0]
    for g in m.groups.values():
        for lo, hi in zip(rates, rates[1:]):
            a, b = set(active_indices(g, lo).tolist()), set(active_indices(g, hi).tolist())
            assert a <= b, f"group {g.name}: {lo} not nested in {hi}"


def test_extract_embed_matches_mask():
    """embed_sliced(extract_sliced(p)) == mask_params(p): the sliced and masked
    views of distribute are the same object."""
    _, m, p = _model_and_params("conv")
    rate = 0.25
    pn = {k: np.asarray(v) for k, v in p.items()}
    sliced = extract_sliced(pn, m.specs, m.groups, rate)
    back = embed_sliced(sliced, m.specs, m.groups, rate, {k: v.shape for k, v in pn.items()})
    masked = mask_params(p, m.specs, m.groups, rate)
    for k in pn:
        np.testing.assert_allclose(back[k], np.asarray(masked[k]), err_msg=k)


def test_combine_identity_homogeneous():
    """All clients at rate 1 with unchanged params -> global unchanged."""
    _, m, p = _model_and_params("conv")
    lm = jnp.ones(10)
    n_clients = 3
    summed = {k: jnp.zeros_like(v) for k, v in p.items()}
    counts = {k: jnp.zeros_like(v) for k, v in p.items()}
    for _ in range(n_clients):
        cm = client_count_masks(p, m, 1.0, lm)
        local = distribute_masked(p, m, 1.0)
        summed = {k: summed[k] + local[k] * cm[k] for k in p}
        counts = {k: counts[k] + cm[k] for k in p}
    new = combine_counted(p, summed, counts)
    for k in p:
        np.testing.assert_allclose(np.asarray(new[k]), np.asarray(p[k]), rtol=1e-6, err_msg=k)


def test_combine_counted_average_and_stale():
    """Two clients at rates 1 and 0.5 with constant deltas: overlap averages,
    exclusive region takes the sole contributor, untouched keeps global."""
    _, m, p = _model_and_params("conv")
    lm = jnp.ones(10)
    k = "block1.conv.w"  # [3,3,8,16], group h0=8 in, h1=16 out
    c1 = {k2: jnp.full_like(v, 2.0) for k2, v in p.items()}
    c2_full = {k2: jnp.full_like(v, 4.0) for k2, v in p.items()}
    c1m = {k2: c1[k2] * (distribute_masked(p, m, 1.0)[k2] * 0 + 1) for k2 in p}  # rate 1: no mask
    c2m = mask_params(c2_full, m.specs, m.groups, 0.5)
    cm1 = client_count_masks(p, m, 1.0, lm)
    cm2 = client_count_masks(p, m, 0.5, lm)
    summed = {k2: c1m[k2] * cm1[k2] + c2m[k2] * cm2[k2] for k2 in p}
    counts = {k2: cm1[k2] + cm2[k2] for k2 in p}
    new = combine_counted(p, summed, counts)
    w = np.asarray(new[k])
    # overlap: first 4 in-ch x first 8 out-ch -> (2+4)/2 = 3
    assert np.allclose(w[:, :, :4, :8], 3.0)
    # only client1 (rate 1) holds the suffix -> 2
    assert np.allclose(w[:, :, 4:, :], 2.0)
    assert np.allclose(w[:, :, :4, 8:], 2.0)


def test_label_split_restricts_output_rows():
    """Client labels restrict which classifier rows it contributes
    (ref fed.py:193-198): other rows keep the global value."""
    _, m, p = _model_and_params("conv")
    lm = jnp.zeros(10).at[jnp.array([1, 3])].set(1.0)
    local = {k: jnp.full_like(v, 7.0) for k, v in p.items()}
    cm = client_count_masks(p, m, 1.0, lm)
    summed = {k: local[k] * cm[k] for k in p}
    counts = dict(cm)
    new = combine_counted(p, summed, counts)
    wb = np.asarray(new["linear.b"])
    assert np.allclose(wb[[1, 3]], 7.0)
    np.testing.assert_allclose(wb[[0, 2, 4, 5, 6, 7, 8, 9]],
                               np.asarray(p["linear.b"])[[0, 2, 4, 5, 6, 7, 8, 9]])
    ww = np.asarray(new["linear.w"])  # [hidden, classes], label axis 1
    assert np.allclose(ww[:, [1, 3]], 7.0)
    np.testing.assert_allclose(ww[:, [0, 2]], np.asarray(p["linear.w"])[:, [0, 2]])


def test_transformer_label_split_on_embedding_and_decoder():
    cfg = small_cfg("transformer", data_name="WikiText2")
    m = make_model(cfg)
    p = m.init(jax.random.key(0))
    lm = jnp.zeros(50).at[jnp.array([5])].set(1.0)
    local = {k: jnp.full_like(v, 9.0) for k, v in p.items()}
    cm = client_count_masks(p, m, 1.0, lm)
    new = combine_counted(p, {k: local[k] * cm[k] for k in p}, dict(cm))
    tok = np.asarray(new["embedding.tok.w"])  # [51, E] label axis 0
    assert np.allclose(tok[5], 9.0)
    np.testing.assert_allclose(tok[6], np.asarray(p["embedding.tok.w"])[6])
    # the <mask> token row (id 50) is never aggregated
    np.testing.assert_allclose(tok[50], np.asarray(p["embedding.tok.w"])[50])
    dec = np.asarray(new["dec.l2.w"])  # [E, V] label axis 1
    assert np.allclose(dec[:, 5], 9.0)
    np.testing.assert_allclose(dec[:, 6], np.asarray(p["dec.l2.w"])[:, 6])
    # positional embedding has no label restriction
    assert np.allclose(np.asarray(new["embedding.pos.w"]), 9.0)


def test_fix_rates_indexed_by_user_ids():
    """Partial participation must pick the *selected* users' rates
    (ref fed.py self.model_rate[user_idx[m]]), not the first-n users'."""
    cfg = small_cfg("conv", control="1_10_0.5_iid_fix_a1-b1-c1-d1-e1_bn_1_1")
    # users 0-1 -> a, 2-3 -> b, 4-5 -> c, 6-7 -> d, 8-9 -> e
    r = sample_model_rates(jax.random.key(0), cfg, jnp.array([9, 0, 4]))
    np.testing.assert_allclose(np.asarray(r), [0.0625, 1.0, 0.25])


def test_non_a_global_mode_width_rates():
    """Global mode 'b': group sizes are already halved, so masks must use the
    relative rate model_rate/global_rate (ref fed.py:46), not the absolute."""
    from heterofl_tpu.fed import to_width_rates

    cfg = small_cfg("conv", control="1_10_0.5_iid_fix_b1-c1_bn_1_1")
    assert cfg["global_model_rate"] == 0.5
    m = make_model(cfg)  # built at rate 0.5: hidden [8,16] -> [4,8]
    assert m.groups["h0"].size == 4 and m.groups["h1"].size == 8
    rates = sample_model_rates(jax.random.key(0), cfg, jnp.array([0, 9]))
    wr = np.asarray(to_width_rates(rates, cfg))
    np.testing.assert_allclose(wr, [1.0, 0.5])
    # a 'b' client at width_rate 1.0 is the FULL global model
    assert int(m.groups["h1"].active_count(wr[0])) == 8
    # a 'c' client gets ceil(8*0.5)=4 channels, matching ceil(16*0.25)
    assert int(m.groups["h1"].active_count(wr[1])) == 4


def test_validate_width_geometry():
    """Per-head vs prefix slice consistency (ref fed.py:115-131): flagship
    dims pass at every level; a 16-dim 2-head embedding breaks at rate 1/16
    (the 16-device dryrun NaN, round 5) and must raise."""
    from heterofl_tpu.fed.core import validate_width_geometry
    from heterofl_tpu.models import make_model

    from test_models import small_cfg

    cfg = small_cfg("transformer", data_name="WikiText2",
                    control="1_8_0.5_iid_fix_a1-b1-c1_none_1_1")
    model = make_model(cfg)  # emb 32, 4 heads: consistent down to rate 1/4
    validate_width_geometry(model, cfg)
    cfg_bad = small_cfg("transformer", data_name="WikiText2",
                        control="1_8_0.5_iid_fix_a1-e1_none_1_1")  # min rate 1/16
    cfg_bad["transformer"] = {"embedding_size": 16, "num_heads": 2,
                              "hidden_size": 32, "num_layers": 1, "dropout": 0.0}
    bad = make_model(cfg_bad)
    with pytest.raises(ValueError, match="width geometry"):
        validate_width_geometry(bad, cfg_bad)
    # vision models have no per-head groups: always fine
    validate_width_geometry(make_model(small_cfg("conv")), small_cfg("conv"))


def test_sample_model_rates_fix_and_dynamic():
    cfg = small_cfg("conv", control="1_10_0.5_iid_fix_a1-b1_bn_1_1")
    r = sample_model_rates(jax.random.key(0), cfg)
    assert r.shape == (10,)
    assert np.allclose(np.asarray(r)[:5], 1.0) and np.allclose(np.asarray(r)[5:], 0.5)
    cfg_d = small_cfg("conv", control="1_1000_0.5_iid_dynamic_a1-e1_bn_1_1")
    draws = np.asarray(sample_model_rates(jax.random.key(1), cfg_d, jnp.arange(1000)))
    assert set(np.unique(draws).tolist()) <= {1.0, 0.0625}
    assert 0.35 < np.mean(draws == 1.0) < 0.65


# ---------------------------------------------------------------------------
# a group carries its own rule (models/spec.GROUP_RULES, ISSUE 28)
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("group", [
    Group("emb", 24), Group("qkv", 24, kind="per_head", num_heads=4),
    Group("rope", 32, kind="per_head", num_heads=4, multiple=2, coupled=False),
    Group("k_rope", 8, kind="per_head", num_heads=1, multiple=2, coupled=False),
    Group("vocab", 7, kind="full")], ids=lambda g: g.name)
@pytest.mark.parametrize("rate", [1.0, 0.5, 0.25, 0.0625])
def test_group_rule_is_one_rule_in_four_forms(group, rate):
    """Mask, active count, host index set and static slice / zero-pad of a
    group are the same cut, whatever its kind."""
    from heterofl_tpu.fed.core import active_indices, pad_axis, slice_axis

    mask = np.asarray(group.mask(rate))
    idx = active_indices(group, rate)
    assert int(group.active_count(rate)) == len(idx) == int(mask.sum())
    np.testing.assert_array_equal(np.flatnonzero(mask), idx)
    v = jnp.arange(3 * group.size, dtype=jnp.float32).reshape(3, group.size) + 1.0
    cut = slice_axis(v, group, rate, 1)
    np.testing.assert_array_equal(cut, np.asarray(v)[:, idx])
    np.testing.assert_array_equal(pad_axis(cut, group, rate, 1), np.asarray(v) * mask)
    if group.multiple > 1:  # rotary dims travel in whole pairs, per head
        per_head = mask.reshape(group.num_heads, -1).sum(axis=1)
        assert (per_head % group.multiple == 0).all() and per_head.min() >= group.multiple


def test_group_rules_are_a_registry_and_geometry_asks_the_rule():
    from heterofl_tpu.fed.core import validate_width_geometry
    from heterofl_tpu.models import make_model
    from heterofl_tpu.models.spec import GROUP_RULES, GroupRule

    with pytest.raises(ValueError, match="Not valid group kind"):
        Group("x", 8, kind="strided").mask(0.5)

    class EveryOther(GroupRule):  # a new kind is one object, no engine edit
        def mask(self, g, width_rate):
            return (jnp.arange(g.size) % 2 == 0).astype(jnp.float32)

        def active_count(self, g, width_rate):
            return jnp.int32((g.size + 1) // 2)

    GROUP_RULES["every_other"] = EveryOther()
    try:
        g = Group("x", 6, kind="every_other")
        spec = {"w": ParamSpec({0: "x"})}
        out = mask_params({"w": jnp.ones((6, 2))}, spec, {"x": g}, 0.5)
        np.testing.assert_array_equal(out["w"][:, 0], [1, 0, 1, 0, 1, 0])
    finally:
        del GROUP_RULES["every_other"]
    # latent attention's per-head groups have an axis of their own: the
    # coupled-prefix check is not theirs, at any level
    from benchmark.tests import tiny_kanana2 as tiny

    cfg = tiny.program_cfg()
    model = make_model(cfg)
    assert not model.groups["q_rope"].coupled
    validate_width_geometry(model, cfg)


def test_kanana2_counts_follow_width_and_labels_only():
    """A client counts for every element of its slice: for a held expert
    whether or not a token reached it, never for the router's columns or the
    selection bias's entries it... holds all of; embedding rows and head
    columns follow the labels."""
    from benchmark.reference import kanana2 as ref
    from benchmark.tests import tiny_kanana2 as tiny
    from heterofl_tpu.models import make_model
    from heterofl_tpu.models.spec import count_masks

    cfg = tiny.program_cfg()
    model = make_model(cfg)
    shapes = dict(model.meta["shapes"])
    labels = np.zeros(cfg["num_tokens"], np.float32)
    labels[::3] = 1.0
    for rate in (1.0, 0.25, 0.0625):
        cm = count_masks(shapes, model.specs, model.groups, rate, jnp.asarray(labels))
        index = ref.index(shapes, tiny.reference_model(cfg), rate)
        for k, shape in shapes.items():
            want = np.zeros(shape, np.float32)
            want[np.ix_(*index[k])] = 1.0
            if k in ref.LABEL_AXES:
                view = [1] * len(shape)
                view[ref.LABEL_AXES[k]] = -1
                want = want * labels.reshape(view)
            np.testing.assert_array_equal(np.asarray(cm[k]), want, err_msg=f"{k} @ {rate}")
        assert np.asarray(cm["l1.moe.router.w"]).sum(axis=0).min() > 0  # all 16 columns
        assert np.asarray(cm["l1.moe.router.b"]).all()


def test_level_tables_know_the_kanana2_family():
    """`level_param_table` counts the sliced sub-model's own leaves (from
    shapes: nothing is initialised), the FLOP table falls with the level,
    and the grouped engine refuses the family at config resolution."""
    from benchmark.tests import tiny_kanana2 as tiny
    from heterofl_tpu.fed.core import level_byte_table, level_flop_table, level_param_table
    from heterofl_tpu.models import make_model

    cfg = tiny.program_cfg()
    counts = level_param_table(cfg)
    for rate, n in counts.items():
        shapes = jax.eval_shape(make_model(cfg, rate).init, jax.random.key(0))
        assert n == sum(int(np.prod(v.shape)) for v in shapes.values()), rate
    assert level_byte_table(cfg)[1.0]["wire_bytes"] == 2 * 4 * counts[1.0]
    flops = level_flop_table(cfg)
    assert sorted(flops.values(), reverse=True) == [flops[r] for r in sorted(flops, reverse=True)]
