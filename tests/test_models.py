import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from decoder_cases import case
from heterofl_tpu import config as C
from heterofl_tpu.models import make_model
from heterofl_tpu.models.spec import mask_params

_kanana_case = functools.partial(case, "kanana2")


def small_cfg(model_name="conv", data_name="MNIST", norm="bn", control="1_10_0.5_iid_fix_a1_bn_1_1"):
    cfg = C.default_cfg()
    cfg["control"] = C.parse_control_name(control)
    cfg["control"]["norm"] = norm
    cfg["data_name"] = data_name
    cfg["model_name"] = model_name
    cfg = C.process_control(cfg)
    # shrink for CPU tests
    cfg["conv"] = {"hidden_size": [8, 16]}
    cfg["resnet"] = {"hidden_size": [8, 16, 16, 16]}
    cfg["transformer"] = {"embedding_size": 32, "num_heads": 4, "hidden_size": 64,
                          "num_layers": 2, "dropout": 0.0}
    cfg["classes_size"] = 10
    cfg["num_tokens"] = 50
    if "bptt" not in cfg:
        cfg["bptt"] = 16
        cfg["mask_rate"] = 0.15
    return cfg


def vision_batch(cfg, n=4, seed=0):
    rng = np.random.default_rng(seed)
    shape = tuple(cfg["data_shape"])
    return {
        "img": jnp.asarray(rng.normal(size=(n,) + shape), jnp.float32),
        "label": jnp.asarray(rng.integers(0, cfg["classes_size"], n)),
    }


@pytest.mark.parametrize("model_name", ["conv", "resnet18", "resnet50"])
@pytest.mark.parametrize("norm", ["bn", "in", "ln", "gn", "none"])
def test_vision_smoke(model_name, norm):
    cfg = small_cfg(model_name, norm=norm)
    model = make_model(cfg)
    params = model.init(jax.random.key(0))
    batch = vision_batch(cfg)
    out, collected = model.apply(params, batch, train=True)
    assert out["score"].shape == (4, 10)
    assert jnp.isfinite(out["loss"])
    if norm == "bn":
        out2, col = model.apply(params, batch, train=True, bn_mode="collect")
        assert len(col) == len(model.bn_sites) > 0
        state = {k: v for k, v in col.items()}
        out3, _ = model.apply(params, batch, train=False, bn_mode="running", bn_state=state)
        assert jnp.isfinite(out3["loss"])


def test_transformer_smoke():
    cfg = small_cfg("transformer", data_name="WikiText2")
    model = make_model(cfg)
    params = model.init(jax.random.key(0))
    labels = jnp.asarray(np.random.default_rng(0).integers(0, 50, (2, 16)))
    out, _ = model.apply(params, {"label": labels}, train=True, rng=jax.random.key(1))
    assert out["score"].shape == (2, 16, 50)
    assert jnp.isfinite(out["loss"])


def test_label_mask_zero_fill():
    cfg = small_cfg("conv")
    model = make_model(cfg)
    params = model.init(jax.random.key(0))
    batch = vision_batch(cfg)
    lm = jnp.zeros(10).at[jnp.array([1, 3])].set(1.0)
    out, _ = model.apply(params, batch, train=True, label_mask=lm)
    score = np.asarray(out["score"])
    masked_cols = [c for c in range(10) if c not in (1, 3)]
    assert np.all(score[:, masked_cols] == 0.0)
    assert np.any(score[:, [1, 3]] != 0.0)


def test_scaler_train_only():
    cfg = small_cfg("conv")
    model = make_model(cfg)
    params = model.init(jax.random.key(0))
    batch = vision_batch(cfg)
    # with norm='none' the scaler changes the forward; check train != eval scale behavior
    cfg2 = small_cfg("conv", norm="none")
    m2 = make_model(cfg2)
    p2 = m2.init(jax.random.key(0))
    o_tr, _ = m2.apply(p2, batch, train=True, scaler_rate=0.5)
    o_ev, _ = m2.apply(p2, batch, train=False, scaler_rate=0.5)
    assert not np.allclose(o_tr["score"], o_ev["score"])


def test_sample_weight_neutralises_padding():
    cfg = small_cfg("conv", norm="none")
    model = make_model(cfg)
    params = model.init(jax.random.key(0))
    b4 = vision_batch(cfg, n=4)
    # pad with junk + zero weight -> same loss as unpadded
    img6 = jnp.concatenate([b4["img"], 100.0 * jnp.ones((2,) + b4["img"].shape[1:])])
    lab6 = jnp.concatenate([b4["label"], jnp.zeros(2, b4["label"].dtype)])
    w = jnp.array([1, 1, 1, 1, 0, 0], jnp.float32)
    o4, _ = model.apply(params, b4, train=True)
    o6, _ = model.apply(params, {"img": img6, "label": lab6}, train=True, sample_weight=w)
    assert np.allclose(o4["loss"], o6["loss"], rtol=1e-5)


def test_conv2d_im2col_matches_direct():
    """The im2col/bmm conv lowering (cfg conv_impl='im2col') is numerically
    equivalent to lax.conv across the kernel/stride/padding shapes the model
    zoo uses, at the op level and through a full masked ResNet forward +
    gradient."""
    import jax
    import jax.numpy as jnp

    from heterofl_tpu.ops.layers import conv2d

    rng = np.random.default_rng(0)
    x = jnp.asarray(rng.normal(size=(2, 8, 8, 5)).astype(np.float32))
    for kh, kw, stride, pad in ((3, 3, 1, 1), (3, 3, 2, 1), (1, 1, 1, 0), (1, 1, 2, 0)):
        w = jnp.asarray(rng.normal(size=(kh, kw, 5, 7)).astype(np.float32))
        b = jnp.asarray(rng.normal(size=(7,)).astype(np.float32))
        ref = conv2d(x, w, b, stride=stride, padding=pad)
        alt = conv2d(x, w, b, stride=stride, padding=pad, impl="im2col")
        np.testing.assert_allclose(np.asarray(alt), np.asarray(ref),
                                   rtol=1e-5, atol=1e-5,
                                   err_msg=f"k={kh} s={stride} p={pad}")
    # model level: full forward + grad through vmapped per-client kernels
    cfg = small_cfg("resnet18")
    m_dir = make_model(cfg)
    cfg2 = dict(cfg)
    cfg2["conv_impl"] = "im2col"
    m_alt = make_model(cfg2)
    params = m_dir.init(jax.random.key(0))
    batch = vision_batch(cfg)

    def loss(m):
        def f(p):
            out, _ = m.apply(p, batch, train=True)
            return out["loss"]
        return f

    l1, g1 = jax.value_and_grad(loss(m_dir))(params)
    l2, g2 = jax.value_and_grad(loss(m_alt))(params)
    np.testing.assert_allclose(float(l1), float(l2), rtol=1e-5)
    for k in g1:
        np.testing.assert_allclose(np.asarray(g1[k]), np.asarray(g2[k]),
                                   rtol=1e-4, atol=1e-5, err_msg=k)


def test_bf16_compute_dtype_close_to_f32():
    """bfloat16 MXU operands with f32 accumulation stay close to the f32
    forward, and masked zeros remain exactly zero."""
    import jax

    from heterofl_tpu.models.spec import mask_params

    cfg = small_cfg("resnet18")
    m32 = make_model(cfg)
    cfg16 = dict(cfg)
    cfg16["compute_dtype"] = "bfloat16"
    m16 = make_model(cfg16)
    params = m32.init(jax.random.key(0))
    batch = vision_batch(cfg, n=4)
    o32, _ = m32.apply(params, batch, train=True)
    o16, _ = m16.apply(params, batch, train=True)
    assert abs(float(o32["loss"]) - float(o16["loss"])) < 0.05
    # masked suffix stays exactly zero through bf16 forward+grad
    masked = mask_params(params, m16.specs, m16.groups, 0.25)
    g = jax.grad(lambda p: m16.apply(p, batch, train=True, width_rate=0.25,
                                     scaler_rate=0.25)[0]["loss"])(masked)
    import numpy as np

    tail = np.asarray(g["layer3.1.conv2.w"])[:, :, 4:, :]
    assert np.all(tail == 0.0)


def test_augment_cifar_shapes_and_determinism():
    import jax

    from heterofl_tpu.ops.augment import augment_cifar

    rng = np.random.default_rng(0)
    x = jnp.asarray(rng.integers(0, 255, (6, 32, 32, 3)), jnp.uint8)
    a1 = augment_cifar(jax.random.key(3), x)
    a2 = augment_cifar(jax.random.key(3), x)
    a3 = augment_cifar(jax.random.key(4), x)
    assert a1.shape == x.shape
    np.testing.assert_array_equal(np.asarray(a1), np.asarray(a2))  # same key
    assert not np.array_equal(np.asarray(a1), np.asarray(a3))  # new key
    # crop+flip only rearranges pixels from the padded canvas
    assert np.asarray(a1).max() <= 255 and np.asarray(a1).min() >= 0


# ---------------------------------------------------------------------------
# the lane policy of the parameter tables (models/layout.py)
# ---------------------------------------------------------------------------

def test_lane_policy_every_family_compliant():
    """Trailing axes are feature axes (width-group or label) for every
    model family -- the lane-packing convention models/layout.py states."""
    from heterofl_tpu.models import layout as L

    for name in ("conv", "resnet18", "resnet50", "transformer"):
        cfg = small_cfg(name, data_name="WikiText2" if name == "transformer"
                        else "MNIST")
        model = make_model(cfg)
        params = model.init(jax.random.key(0))
        bad = L.check_policy(model.specs,
                             {k: v.shape for k, v in params.items()})
        assert bad == {}, (name, bad)


def test_lane_policy_flags_transposed_weight():
    """A torch-style [out, in] weight (reduction axis in the lanes) fails
    the policy audit."""
    from heterofl_tpu.models import layout as L
    from heterofl_tpu.models.spec import ParamSpec

    assert L.check_policy({"w": ParamSpec(axis_groups={0: "h"})},
                          {"w": (8, 10)}) == {"w": 1}
    assert L.check_policy({"w": ParamSpec(axis_groups={1: "h"})},
                          {"w": (10, 8)}) == {}


def test_conv_dimension_numbers_one_owner():
    """The conv convention has one owner (ops/layers.py) and the layout
    policy re-exports it."""
    from heterofl_tpu.models.layout import CONV_DIMENSION_NUMBERS as A
    from heterofl_tpu.ops.layers import CONV_DIMENSION_NUMBERS as B

    assert A is B == ("NHWC", "HWIO", "NHWC")


# ---------------------------------------------------------------------------
# Kanana-2 (latent attention, shared + routed experts; ISSUE 28) against the
# benchmark's plain reference, at a tiny size
# ---------------------------------------------------------------------------

def _stacked_experts(params, held, layer=1):
    return [jnp.stack([params[f"l{layer}.moe.e{j}.{m}.w"] for j in held]) for m in "gud"]


def test_kanana2_the_shares_add_up():
    """(c) The routed parts that all four shares compute, with what every
    share computes alike (attention, the shared experts) counted once, are
    the uncut layer: per expert layer, y(whole) - shared = sum over shares of
    (y(share) - shared)."""
    from heterofl_tpu.ops import layers as L

    cfg, model, _, tokens, _, _ = _kanana_case(expert_share=[0, 1])
    whole = model.init(jax.random.key(3))
    h = jax.random.normal(jax.random.key(4), (tokens.size, cfg["kanana2"]["hidden_size"]))
    arch, sc = cfg["kanana2"], (lambda x: x)
    sel, w = L.moe_route(h, whole["l1.moe.router.w"], whole["l1.moe.router.b"] + 0.05,
                         arch["num_experts_per_tok"], arch["routed_scaling_factor"])

    def routed(first, n):
        return L.moe_experts(h, sel, w, _stacked_experts(whole, range(first, first + n)),
                             first, sc, tile=8)

    y_whole, c_whole = routed(0, 16)
    parts = [routed(4 * i, 4) for i in range(4)]
    np.testing.assert_allclose(sum(y for y, _ in parts), y_whole, rtol=1e-5, atol=1e-6)
    assert float(c_whole["assign"][1]) == sel.size  # every pair lands somewhere
    assert sum(float(c["assign"][1]) for _, c in parts) == sel.size
    np.testing.assert_array_equal(
        np.concatenate([np.asarray(c["tokens"]) for _, c in parts]), c_whole["tokens"])
    # and through the model: a share's logits differ from the whole model's
    # by what the absent experts add, so the four shares' layers are not alike
    share = make_model(dict(cfg, kanana2=dict(arch, expert_share=[1, 4])))
    sub = {k: whole[k] for k in share.meta["shapes"]}
    out_s, _ = share.apply(sub, {"label": tokens}, train=False)
    out_w, _ = model.apply(whole, {"label": tokens}, train=False)
    assert np.abs(np.asarray(out_s["score"]) - np.asarray(out_w["score"])).max() > 1e-4


@pytest.mark.parametrize("tile", [8, 256])
def test_kanana2_no_token_is_dropped_when_one_expert_takes_everything(tile):
    """(d) A selection bias that sends every token to held expert 5 (and two
    more choices each): its group is every token, four tiles of 8 rows (or
    one of 256), the counters say that every pair was computed, and result
    and gradients are the plain per-expert sum's."""
    from benchmark.reference import kanana2 as ref
    from heterofl_tpu.ops import layers as L

    cfg, model, params, tokens, _, rm = _kanana_case()
    arch = cfg["kanana2"]
    t = tokens.size
    h = jax.random.normal(jax.random.key(5), (t, arch["hidden_size"]))
    bias = jnp.zeros(arch["n_routed_experts"]).at[5].set(100.0)
    sel, w = L.moe_route(h, params["l1.moe.router.w"], bias,
                         arch["num_experts_per_tok"], arch["routed_scaling_factor"])
    assert bool(jnp.all(jnp.any(sel == 5, axis=-1)))
    held = model.meta["held_experts"]

    def grouped(h, w, experts):
        return L.moe_experts(h, sel, w, experts, held[0], lambda x: x / 0.5, tile=tile)

    def plain(h, w, experts):  # one expert at a time over all tokens
        p = {f"e{j}.{m}.w": experts[i][n] for n, j in enumerate(held) for i, m in enumerate("gud")}
        return sum(jnp.sum(jnp.where(sel == j, w, 0.0), -1)[:, None]
                   * ref._ffn(p, f"e{j}", h, 0.5) for j in held)

    experts = _stacked_experts(params, held)
    y, c = grouped(h, w, experts)
    assert float(c["tokens"][5 - held[0]]) == t and float(c["assign"][2]) == 0.0
    np.testing.assert_allclose(y, plain(h, w, experts), rtol=1e-5, atol=1e-6)
    probe = jax.random.normal(jax.random.key(6), y.shape)
    got = jax.grad(lambda *a: jnp.sum(grouped(*a)[0] * probe), argnums=(0, 1, 2))(h, w, experts)
    want = jax.grad(lambda *a: jnp.sum(plain(*a) * probe), argnums=(0, 1, 2))(h, w, experts)
    for g, r in zip(jax.tree_util.tree_leaves(got), jax.tree_util.tree_leaves(want)):
        np.testing.assert_allclose(g, r, rtol=1e-4, atol=1e-5 * float(jnp.abs(r).max()))


def _dispatch_case(load):
    """A sixteenth of 64 experts held (experts 8-11), tiles of 8 rows, so a
    capacity of 64 rows: ``(h, sel, w, experts, first, tile)`` under a named
    load on the held experts.  The weights are powers of two: ``w * y`` is
    then exact, so a multiply and an add that XLA:CPU contracts into one
    rounding in one program and not in another (it does, by what it fuses)
    round alike, and what is compared is the ORDER of a token's sum."""
    from heterofl_tpu.ops import layers as L

    T, K, E, held, first, tile, D, F = 128, 2, 64, 4, 8, 8, 16, 8
    ks = jax.random.split(jax.random.key(11), 6)
    h = jax.random.normal(ks[0], (T, D))
    experts = [jax.random.normal(k, shape) for k, shape in
               zip(ks[1:4], ((held, D, F), (held, D, F), (held, F, D)))]
    if load in ("even", "one-expert"):
        bias = None if load == "even" else jnp.zeros(E).at[first + 1].set(100.0)
        sel, _ = L.moe_route(h, jax.random.normal(ks[4], (D, E)), bias, K, 1.0)
    else:
        # 32 tokens with both choices on held experts, 16 pairs an expert: two
        # whole tiles each, every row of the capacity a pair; the rest elsewhere
        t = np.arange(T)
        sel = np.where((t < 32)[:, None], first + np.stack([t % 4, (t + 1) % 4], 1),
                       np.stack([20 + t % 7, 30 + t % 5], 1))
        if load == "one-row-more":
            sel[32, 0] = first                                    # a third tile for one expert
        sel = jnp.asarray(sel, jnp.int32)
    w = 2.0 ** -jax.random.randint(ks[5], (T, K), 0, 4).astype(jnp.float32)
    return h, sel, w, experts, first, tile


@pytest.mark.parametrize("load, compact", [("even", 1.0), ("one-expert", 0.0),
                                           ("exactly-capacity", 1.0), ("one-row-more", 0.0)])
def test_moe_dispatch_is_compact_up_to_its_capacity_and_full_beyond(load, compact):
    """The dispatch of ``moe_experts`` where a sixteenth of the experts is
    held.  Even routing (4 pairs an expert) and a load that fills the
    capacity to its last row take the compact branch; one row more, or a
    selection bias that sends every token to one held expert, the full one.
    Either way no pair is dropped and result and gradients are the plain
    per-expert sum's.  Where compact runs, its index arrays, its result and
    every cotangent (``h``, ``w``, each expert matrix) EQUAL the full
    branch's own on the same arguments."""
    from heterofl_tpu.ops import layers as L

    h, sel, w, experts, first, tile = _dispatch_case(load)
    (T, K), held = sel.shape, experts[0].shape[0]
    n_rows = (T * K // tile + held) * tile
    cap = L.moe_capacity(held, tile, n_rows)
    assert cap == 2 * held * tile and 2 * cap <= n_rows
    probe = jax.random.normal(jax.random.key(12), h.shape)

    def grouped(h, w, experts):
        return L.moe_experts(h, sel, w, experts, first, lambda x: x / 0.5, tile=tile)

    def plain(h, w, experts):  # one expert at a time over all tokens
        return sum(jnp.sum(jnp.where(sel == first + j, w, 0.0), -1)[:, None]
                   * L.swiglu(h, *(m[j] for m in experts), lambda x: x / 0.5)
                   for j in range(held))

    def both(fn):
        return jax.jit(jax.value_and_grad(lambda *a: jnp.sum(fn(*a) * probe), argnums=(0, 1, 2)))

    y, c = jax.jit(grouped)(h, w, experts)
    on_held = int(((sel >= first) & (sel < first + held)).sum())
    assert c["compact"].tolist() == [compact, 1.0]
    assert c["assign"].tolist() == [T * K, on_held, 0.0] and float(c["tokens"].sum()) == on_held
    if load == "one-expert":
        assert float(c["tokens"][1]) == T
    np.testing.assert_allclose(y, plain(h, w, experts), rtol=1e-5, atol=1e-5)
    got = both(lambda *a: grouped(*a)[0])(h, w, experts)
    want = both(plain)(h, w, experts)
    for g, r in zip(jax.tree_util.tree_leaves(got), jax.tree_util.tree_leaves(want)):
        np.testing.assert_allclose(g, r, rtol=1e-4, atol=1e-5 * float(jnp.abs(r).max()))

    if not compact:
        return

    # the two branches' own functions on the same pairs
    local = sel.reshape(-1) - first
    is_held = (local >= 0) & (local < held)
    e = jnp.where(is_held, local, held)
    full = L._sorted_groups(e, is_held, held, tile, n_rows)
    for a, b in zip(full, L._compact_groups(e, is_held, held, tile, n_rows, cap, K)):
        np.testing.assert_array_equal(a, b)
    _, slot, rows, tile_expert, ends = full
    assert int(ends[-1]) * tile <= cap
    args = (h, w, rows, slot.reshape(T, K), tile_expert, ends[-1], tuple(experts), jnp.float32(2.0))
    fwd = [jax.jit(functools.partial(L._experts_forward, c, L.swiglu, None, tile))(*args)
           for c in (None, cap)]                                   # full, compact
    bwd = [jax.jit(functools.partial(L._experts_backward, c, L.swiglu, None, tile))(*args, probe)
           for c in (None, cap)]
    np.testing.assert_array_equal(y, fwd[1][0])
    for a, b in zip(jax.tree_util.tree_leaves((fwd[0], bwd[0])),
                    jax.tree_util.tree_leaves((fwd[1], bwd[1]))):
        np.testing.assert_array_equal(a, b)


@pytest.mark.parametrize("cell, T, K, E, held, groups, cap", [
    ("keye", 8192, 8, 128, 8, 2, 16384), ("laguna", 8192, 8, 256, 16, 2, 16384),
    ("nemotron_h", 8192, 6, 128, 8, 4, 24576), ("kanana2", 4096, 6, 128, 8, None, 4096),
    ("lfm2", 4096, 4, 32, 8, 2, None)])
def test_moe_dispatch_builds_its_compact_branch_by_shape(cell, T, K, E, held, groups, cap):
    """At the expert cells' shapes (traced, nothing runs): a sixteenth of the
    experts held builds the ``cond`` and its capacity, two tiles a held
    expert; LFM2's quarter on four choices a token keeps one dispatch, no
    ``cond`` in its jaxpr."""
    from heterofl_tpu.models.decoder import expert_tile
    from heterofl_tpu.ops import layers as L

    tile = L.MOE_TILE if groups is None else expert_tile(T, K, E, groups)
    assert L.moe_capacity(held, tile, (T * K // tile + held) * tile) == cap
    D, F = 128, 64
    shapes = [jax.ShapeDtypeStruct(s, t) for s, t in (
        ((T, D), jnp.float32), ((T, K), jnp.int32), ((T, K), jnp.float32),
        ((held, D, F), jnp.float32), ((held, D, F), jnp.float32), ((held, F, D), jnp.float32))]
    text = str(jax.make_jaxpr(lambda h, sel, w, *experts: L.moe_experts(
        h, sel, w, experts, 0, lambda x: x, tile=tile))(*shapes))
    assert ("cond[" in text) == (cap is not None)


@pytest.mark.parametrize("heads", [1, 4], ids=["one-key-head", "four-query-heads"])
def test_rope_swap_of_the_weight_is_the_swap_of_the_product(heads):
    """``swap(h W) = h swap(W)`` exactly (a column of the swapped weight is a
    column of the weight, negated or not), heads holding whole pairs; and the
    turn built from it is a rotation: it keeps each pair's norm, leaves
    position 0 alone, and a zero (masked) pair stays zero."""
    from heterofl_tpu.ops import layers as L

    d, theta = 8, 1e4
    kh, kw = jax.random.split(jax.random.key(8))
    h = jax.random.normal(kh, (2, 6, 16))
    w = jax.random.normal(kw, (16, heads * d)).at[:, 2:4].set(0.0)
    x, swapped = h @ w, h @ L.rope_swap(w)
    np.testing.assert_array_equal(swapped, L.rope_swap(x))
    np.testing.assert_array_equal(swapped[..., 0::2], -x[..., 1::2])
    x, swapped = (t.reshape(2, 6, heads, d) for t in (x, swapped))
    y = L.rope_interleaved(x, swapped, jnp.arange(6), theta)
    np.testing.assert_allclose(y[:, 0], x[:, 0], rtol=1e-6)
    pairs = lambda t: jnp.sum(t.reshape(2, 6, heads, d // 2, 2) ** 2, -1)  # noqa: E731
    np.testing.assert_allclose(pairs(y), pairs(x), rtol=1e-5, atol=1e-6)
    assert not np.any(y[:, :, 0, 2:4])
    # heads first, the positions on axis 2: the same turn
    y_hf = L.rope_interleaved(jnp.swapaxes(x, 1, 2), jnp.swapaxes(swapped, 1, 2),
                              jnp.arange(6), theta, axis=2)
    np.testing.assert_array_equal(jnp.swapaxes(y_hf, 1, 2), y)


def test_causal_latent_attention_in_blocks_is_the_attention_in_one():
    """The query blocks are memory, not mathematics: 16 positions in blocks
    of 8 (and of 5, a ragged last block) against one block."""
    from heterofl_tpu.ops import layers as L

    ks = jax.random.split(jax.random.key(7), 5)
    qn, kn, v = (jax.random.normal(k, (2, 4, 16, 16)) for k in ks[:3])
    qr, kr = jax.random.normal(ks[3], (2, 4, 16, 8)), jax.random.normal(ks[4], (2, 16, 8))
    whole = L.causal_latent_attention(qn, qr, kn, kr, v, 0.2, block=16)
    for block in (8, 5):
        np.testing.assert_allclose(L.causal_latent_attention(qn, qr, kn, kr, v, 0.2, block=block),
                                   whole, rtol=1e-5, atol=1e-6)
