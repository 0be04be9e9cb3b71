import jax
import jax.numpy as jnp
import numpy as np
import pytest

from heterofl_tpu import config as C
from heterofl_tpu.models import make_model


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
# the explicit layout/dtype policy (ISSUE 5 pass 2)
# ---------------------------------------------------------------------------

def test_layout_policy_every_family_compliant():
    """Trailing axes are feature axes (width-group or label) for every
    model family -- the lane-packing convention models/layout.py pins."""
    from heterofl_tpu.models import layout as L

    for name in ("conv", "resnet18", "resnet50", "transformer"):
        cfg = small_cfg(name, data_name="WikiText2" if name == "transformer"
                        else "MNIST")
        model = make_model(cfg)
        params = model.init(jax.random.key(0))
        bad = L.check_policy(model.specs,
                             {k: v.shape for k, v in params.items()})
        assert bad == {}, (name, bad)


def test_layout_policy_flags_transposed_weight():
    """A torch-style [out, in] weight (reduction axis in the lanes) fails
    the policy audit."""
    from heterofl_tpu.models import layout as L
    from heterofl_tpu.models.spec import ParamSpec

    assert L.check_policy({"w": ParamSpec(axis_groups={0: "h"})},
                          {"w": (8, 10)}) == {"w": 1}
    assert L.check_policy({"w": ParamSpec(axis_groups={1: "h"})},
                          {"w": (10, 8)}) == {}


def test_pin_params_cpu_passthrough_and_formats():
    """On the CPU test mesh pin_params is the identity (XLA:CPU ignores
    custom layouts); the Format objects themselves pin row-major
    major-to-minor, and an unknown policy raises."""
    import pytest

    from heterofl_tpu.models.layout import param_formats, pin_params

    cfg = small_cfg("conv")
    model = make_model(cfg)
    params = model.init(jax.random.key(0))
    pinned = pin_params(params, mesh=None, policy="auto")
    assert all(pinned[k] is params[k] for k in params)
    assert pin_params(params, mesh=None, policy="none") is params
    with pytest.raises(ValueError, match="layout_policy"):
        pin_params(params, mesh=None, policy="fastest")
    fmts = param_formats(params)
    for k, v in params.items():
        assert tuple(fmts[k].layout.major_to_minor) == tuple(range(v.ndim)), k


def test_conv_dimension_numbers_one_owner():
    """The conv convention has one owner (ops/layers.py) and the layout
    policy re-exports it."""
    from heterofl_tpu.models.layout import CONV_DIMENSION_NUMBERS as A
    from heterofl_tpu.ops.layers import CONV_DIMENSION_NUMBERS as B

    assert A is B == ("NHWC", "HWIO", "NHWC")
