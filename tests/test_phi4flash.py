"""What is Phi-4-mini-flash's alone (ISSUE 50): the selective scan against the
reference's position-by-position recurrence, across chunk and block
boundaries, forward and gradient; differential attention in each form the rule
can give against the plain formulas; the side values (a layer that reads what
another computed learns through it, and a consumer before its producer is
refused); the slice (one group of inner channels for layers that meet element
by element, a sub-norm that counts its active dims); the published rule and
the parameter count it closes; `gq_plan` with a value width; the cell's four
kinds against the reference at every level; the family's scopes.  The
family's part of the contract every family keeps is `test_decoder_families.py`
and `test_decoder_reference.py`."""

import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from decoder_cases import LEVELS, case, masked_loss_and_grads, reference, round_case, tiny, unseen
from heterofl_tpu import config as C
from heterofl_tpu.models import make_model, phi4flash
from heterofl_tpu.models.spec import mask_params
from heterofl_tpu.ops import layers as L
from heterofl_tpu.ops import pallas_attention as PA
from heterofl_tpu.parallel import RoundEngine, make_mesh


# ---------------------------------------------------------------------------
# the selective scan
# ---------------------------------------------------------------------------

def _scan_inputs(S, seed=0, N=2, E=12, Ns=4):
    ks = jax.random.split(jax.random.key(seed), 5)
    x = jax.random.normal(ks[0], (N, S, E))
    dt = jax.nn.softplus(jax.random.normal(ks[1], (N, S, E)) - 2.0)  # about 0.13
    a = -jnp.exp(jax.random.uniform(ks[2], (E, Ns), minval=0.0, maxval=2.5))
    b, c = (jax.random.normal(k, (N, S, Ns)) for k in ks[3:])
    return x, dt, a, b, c


@pytest.mark.parametrize("S", [64, 50, 16, 7], ids=["four-chunks", "ragged", "one-chunk", "short"])
def test_the_selective_scan_is_the_position_by_position_recurrence(S):
    """`selective_scan` in chunks of 16 and blocks of 4 against `benchmark/
    reference/phi4flash.py` `recurrence`, forward and every gradient, on a row
    that is a whole number of chunks and on rows that are not; `keep` is the
    mean of `exp(dt a)` over the row's own positions (a padded one not
    counted)."""
    ref = reference("phi4flash")
    args = _scan_inputs(S)
    y, keep = L.selective_scan(*args, 16, 4)
    np.testing.assert_allclose(y, ref.recurrence(*args), atol=2e-6)
    x, dt, a, _, _ = args
    assert float(keep[1]) == x.size * a.shape[1]
    np.testing.assert_allclose(keep[0] / keep[1], jnp.mean(jnp.exp(dt[..., None] * a)), rtol=1e-5)
    w = jax.random.normal(jax.random.key(9), y.shape)
    mine = jax.grad(lambda *t: jnp.sum(L.selective_scan(*t, 16, 4)[0] * w), argnums=range(5))(*args)
    theirs = jax.grad(lambda *t: jnp.sum(ref.recurrence(*t) * w), argnums=range(5))(*args)
    for g, r in zip(mine, theirs):
        np.testing.assert_allclose(g, r, atol=1e-5 * float(jnp.abs(r).max()))


def test_an_impulse_at_position_0_is_read_at_the_last_position():
    """The state crosses every block and chunk boundary: with one input at
    position 0, `B` and `C` ones, the last position reads the impulse times the
    product of 63 decays; and a time step large enough to overflow a
    cumulative log-decay form (dt a = -160 a position) stays finite."""
    N, S, E, Ns = 1, 64, 3, 2
    x = jnp.zeros((N, S, E)).at[:, 0].set(1.0)
    dt = jnp.full((N, S, E), 0.05)
    a = -jnp.asarray([[1.0, 2.0]] * E)
    ones = jnp.ones((N, S, Ns))
    y, _ = L.selective_scan(x, dt, a, ones, ones, 16, 4)
    want = 0.05 * jnp.sum(jnp.exp(63 * 0.05 * a), axis=1)
    np.testing.assert_allclose(y[0, -1], want, rtol=1e-5)
    y, _ = L.selective_scan(x, dt * 200.0, a * 16.0, ones, ones, 16, 4)
    assert np.isfinite(np.asarray(y)).all() and float(y[0, 0, 0]) == pytest.approx(20.0)
    g = jax.grad(lambda d: jnp.sum(L.selective_scan(x, d, a * 16.0, ones, ones, 16, 4)[0]))(dt * 200.0)
    assert np.isfinite(np.asarray(g)).all()


# ---------------------------------------------------------------------------
# differential attention
# ---------------------------------------------------------------------------

def _plain_differential(q1, q2, k1, k2, v, lam, lam0, g_sub, window, scale, eps=1e-5):
    """The formulas, heads first, no blocks: two whole softmaxes a pair."""
    S, rep = q1.shape[2], q1.shape[1] // k1.shape[1]
    pos = jnp.arange(S)
    keep = pos[:, None] >= pos[None, :]
    if window is not None:
        keep = keep & (pos[:, None] - pos[None, :] < window)

    def one(q, k):
        s = jnp.einsum("nhqd,nhkd->nhqk", q, jnp.repeat(k, rep, axis=1)) * scale
        p = jax.nn.softmax(jnp.where(keep, s, -jnp.inf), axis=-1)
        return jnp.einsum("nhqk,nhkd->nhqd", p, jnp.repeat(v, rep, axis=1))

    o = one(q1, k1) - lam * one(q2, k2)
    return o / jnp.sqrt(jnp.mean(o * o, axis=-1, keepdims=True) + eps) * g_sub * (1.0 - lam0)


def _diff_inputs(S, d, seed=0, N=1, pairs=4, kv_pairs=2):
    ks = jax.random.split(jax.random.key(seed), 7)
    q1, q2 = (jax.random.normal(k, (N, pairs, S, d)) for k in ks[:2])
    k1, k2 = (jax.random.normal(k, (N, kv_pairs, S, d)) for k in ks[2:4])
    v = jax.random.normal(ks[4], (N, kv_pairs, S, 2 * d))
    g_sub = 1.0 + 0.1 * jax.random.normal(ks[5], (2 * d,))
    return (q1, q2, k1, k2, v), g_sub, jax.random.normal(ks[6], (N, pairs, S, 2 * d))


def _fused_one_call(monkeypatch):
    """The TPU's path here: `gq_plan`'s pair, its kernels interpreted at tiles
    of 128."""
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    monkeypatch.setattr(PA, "TILES", (128,))
    real = PA.fused_gq_attention
    monkeypatch.setattr(PA, "fused_gq_attention",
                        lambda *a, **kw: real(*a, **dict(kw, interpret=True)))


@pytest.mark.parametrize("lam", [None, 0.37], ids=["lam0", "drawn"])
@pytest.mark.parametrize("form, window", [("loop", None), ("loop", 48), ("one-call", None),
                                          ("loop-on-a-tpu", 48)])
def test_differential_attention_is_its_formulas_in_every_form(form, window, lam, monkeypatch):
    """`differential_attention` against two whole softmaxes, their difference,
    the RMSNorm over a pair's value dims and the constant, forward and every
    gradient (`lam`'s and the gain's too), at `lam = lam0` and at a drawn one:
    the `jnp` block loop with and without a window; ONE fused call a softmax
    with the 128-wide value (`gq_attn_fwd` / `gq_attn_bwd` interpreted, bf16
    operands: 2e-2); and the block loop a TPU takes under a window, where no
    pair takes a value wider than its keys.  The rule builds no four-call form:
    the `gq_attn` pair takes the value's own width."""
    S, d = 256, 64
    lam0 = phi4flash.lam0_of(17)
    lam = jnp.float32(lam0 if lam is None else lam)
    ops, g_sub, w = _diff_inputs(S, d)
    scale, mask = 1.0 / np.sqrt(d), jnp.ones(2 * d)
    fused = form == "one-call"
    if form != "loop":
        _fused_one_call(monkeypatch)
        assert L.differential_attention_planned(S, d, 2, 2 * d, window) == fused
    else:
        assert not L.differential_attention_planned(S, d, 2, 2 * d, window)

    def mine(ops, lam, g_sub):
        return jnp.sum(w * L.differential_attention(*ops, lam, lam0, g_sub, window, scale=scale,
                                                    mask=mask, count=2.0 * d, block=64))

    def plain(ops, lam, g_sub):
        return jnp.sum(w * _plain_differential(*ops, lam, lam0, g_sub, window, scale))

    text = str(jax.make_jaxpr(mine)(ops, lam, g_sub))
    assert ("gq_attn_fwd" in text) == fused and text.count("gq_attn_fwd") == (2 if fused else 0)
    tol = 2e-2 if fused else 1e-5
    o = L.differential_attention(*ops, lam, lam0, g_sub, window, scale=scale, mask=mask,
                                 count=2.0 * d, block=64)
    want = _plain_differential(*ops, lam, lam0, g_sub, window, scale)
    np.testing.assert_allclose(o, want, atol=tol * float(jnp.abs(want).max()))
    got = jax.tree_util.tree_leaves(jax.grad(mine, argnums=(0, 1, 2))(ops, lam, g_sub))
    ref = jax.tree_util.tree_leaves(jax.grad(plain, argnums=(0, 1, 2))(ops, lam, g_sub))
    for g, r in zip(got, ref):
        np.testing.assert_allclose(g, r, atol=tol * float(jnp.abs(r).max()) + 1e-6)


def test_the_sub_norm_counts_its_active_dims():
    """A level-e client keeps a prefix of each of a pair's two value heads: the
    masked full-width combine is the sliced one (the mean over the kept dims),
    and zero outside."""
    S, d, kept = 64, 16, 4
    ops, g_sub, _ = _diff_inputs(S, d, seed=3)
    head = (jnp.arange(d) < kept).astype(jnp.float32)
    mask = jnp.concatenate([head, head])
    q1, q2, k1, k2, v = ops
    masked = (q1 * head, q2 * head, k1 * head, k2 * head, v * mask)
    idx = np.flatnonzero(np.asarray(mask))
    small = (q1[..., :kept], q2[..., :kept], k1[..., :kept], k2[..., :kept], v[..., idx])
    lam, lam0, scale = jnp.float32(0.5), 0.79, 1.0 / np.sqrt(kept)
    full = L.differential_attention(*masked, lam, lam0, g_sub * mask, None, scale=scale, mask=mask,
                                    count=2.0 * kept)
    cut = L.differential_attention(*small, lam, lam0, g_sub[idx], None, scale=scale,
                                   mask=jnp.ones(2 * kept), count=2.0 * kept)
    np.testing.assert_allclose(full[..., idx], cut, atol=1e-5)
    assert not np.asarray(full)[..., np.flatnonzero(1 - np.asarray(mask))].any()


# ---------------------------------------------------------------------------
# the side values
# ---------------------------------------------------------------------------

def _grads(cfg, seed=1):
    model = make_model(cfg)
    params = model.init(jax.random.key(seed))
    tokens = jax.random.randint(jax.random.key(seed + 2), (2, cfg["bptt"]), 0, cfg["num_tokens"])
    return jax.grad(lambda p: model.apply(p, {"label": tokens}, train=True)[0]["loss"])(params)


def test_producers_learn_through_their_readers():
    """The gradient of the loss with respect to the Mamba layer's `W_x` (read
    by nothing but the scan whose output the gated memory unit multiplies) and
    the full layer's key and value projections differs when the reading layer
    is taken away: the side values carry gradients back to their producers."""
    preset = tiny("phi4flash")
    whole = _grads(preset.program_cfg(bptt=32, **preset.CELL_LAYERS))

    def without(kinds):
        return _grads(preset.program_cfg(bptt=32, **dict(preset.CELL_LAYERS, layer_types=kinds,
                                                         num_hidden_layers=len(kinds))))

    no_gmu, no_cross = without(["mamba", "full", "cross"]), without(["mamba", "full", "gmu"])
    for leaf, cut, other in (("l0.ssm.x.w", no_gmu, no_cross), ("l1.attn.k.w", no_cross, no_gmu),
                             ("l1.attn.v.w", no_cross, no_gmu)):
        scale = float(jnp.abs(whole[leaf]).max())
        assert float(jnp.abs(whole[leaf] - cut[leaf]).max()) > 1e-2 * scale, leaf
    # and a producer nobody reads writes nothing: the same leaves' gradients with the
    # reader gone are those of a model that never had a side value
    assert "l2.gmu.in.w" in no_cross and "l2.gmu.in.w" not in no_gmu


@pytest.mark.parametrize("kinds, reader", [(["gmu", "mamba", "full", "cross"], "gmu"),
                                           (["mamba", "cross", "full", "gmu"], "cross"),
                                           (["sliding", "gmu"], "gmu")])
def test_a_consumer_before_its_producer_is_refused(kinds, reader):
    preset = tiny("phi4flash")
    cfg = preset.program_cfg(layer_types=kinds, num_hidden_layers=len(kinds))
    with pytest.raises(ValueError, match=f"Not valid layer_types: the '{reader}' layer"):
        make_model(cfg)
    with pytest.raises(ValueError, match="Not valid layer_types"):
        make_model(preset.program_cfg(layer_types=["mamba", "attention"], num_hidden_layers=2))


def test_the_memory_is_the_last_mamba_layers_and_is_kept_once():
    """In the seven-layer preset two Mamba layers precede the gated memory
    unit: the second writes the memory (`producers`), the first none; the
    published 32 layers give layer 16 and layer 17."""
    assert phi4flash.producers(tiny("phi4flash").ARCH["layer_types"]) == {"m": 3, "kv": 4}
    assert phi4flash.producers(C.DECODER_FAMILIES["phi4flash"]["layer_types"]) == {"m": 16, "kv": 17}
    assert phi4flash.producers(["mamba", "sliding"]) == {}


# ---------------------------------------------------------------------------
# the published rule, the count, the slice
# ---------------------------------------------------------------------------

def test_the_rule_gives_the_published_kinds_and_the_layout_closes_the_count():
    row = C.DECODER_FAMILIES["phi4flash"]
    kinds = phi4flash.layer_types(row["num_hidden_layers"], row["mb_per_layer"])
    assert kinds == row["layer_types"] and len(kinds) == 32
    assert [kinds.count(k) for k in phi4flash.KINDS] == [9, 8, 1, 7, 7]
    assert kinds[16:20] == ["mamba", "full", "gmu", "cross"] and kinds[15] == "sliding"
    assert all(k == "mamba" for k in kinds[0:17:2]) and all(k == "gmu" for k in kinds[18::2])
    assert row["dt_rank"] == -(-row["hidden_size"] // 16) and row["layer_offset"] == 0
    cfg = tiny("phi4flash").program_cfg(**row)
    cfg["num_tokens"] = 200064
    shapes = jax.eval_shape(make_model(cfg).init, jax.random.key(0))
    n = sum(int(np.prod(v.shape)) for v in shapes.values())
    assert 3.84e9 < n < 3.86e9  # the published "3.8B"
    assert phi4flash.lam0_of(17) == pytest.approx(0.8 - 0.6 * np.exp(-5.1))


def test_a_masked_inner_channel_stays_zero_through_both_layers():
    """A level-e client's masked channels: zero after the convolution, the
    scan, the skip and the gate, and in the memory the gated memory unit of
    ANOTHER layer multiplies, whose own gate is masked on the same channels
    (one width group); the mixers' outputs are the sliced sub-model's."""
    preset = tiny("phi4flash")
    cfg = preset.program_cfg(bptt=32, **preset.CELL_LAYERS)
    model = make_model(cfg)
    rate, E = 0.0625, 256
    params = mask_params(model.init(jax.random.key(0)), model.specs, model.groups, rate)
    kept = int(np.ceil(E * rate))
    assert model.specs["l0.ssm.out.w"].axis_groups[0] == model.specs["l2.gmu.in.w"].axis_groups[1] \
        == model.specs["l0.ssm.a_log.w"].axis_groups[0] == "inner"
    lp = {k[3:]: v for k, v in params.items() if k.startswith("l0.")}
    h = jax.random.normal(jax.random.key(1), (2, 32, 128)) * model.groups["emb"].mask(rate)
    sc = lambda x: x / rate
    out, m, _ = phi4flash.mamba_mixer(lp, h, rank=8, state=8, sc=sc)
    assert np.abs(np.asarray(m)[..., :kept]).min() > 0 and not np.asarray(m)[..., kept:].any()
    gate = {k[3:]: v for k, v in params.items() if k.startswith("l2.gmu.")}
    inner = jax.nn.silu(sc(h @ gate["gmu.in.w"]))
    assert not np.asarray(m * inner)[..., kept:].any()
    assert np.asarray(gate["gmu.in.w"])[:, kept:].any() == False  # noqa: E712
    small = {k: v[tuple(slice(0, kept if n == E else (8 if n == 128 else n)) for n in v.shape)]
             for k, v in lp.items() if k.startswith("ssm.")}
    out_s, m_s, _ = phi4flash.mamba_mixer(small, h[..., :8], rank=8, state=8, sc=sc)
    np.testing.assert_allclose(np.asarray(m)[..., :kept], m_s, atol=1e-5)
    np.testing.assert_allclose(np.asarray(out)[..., :8], out_s, atol=1e-5)


@pytest.mark.parametrize("rate", LEVELS)
def test_the_cells_four_kinds_are_the_references_at_every_level(rate):
    """The benchmark cell's own cut -- published layers 16-19, `mamba`, `full`,
    `gmu`, `cross` at `layer_offset` 16 -- at the tiny widths: loss and every
    gradient of the masked full-width model against the plain reference on the
    sliced sub-model (the seven-layer preset's five levels are
    `test_decoder_reference.py`'s)."""
    from benchmark.reference import common

    ref, preset = reference("phi4flash"), tiny("phi4flash")
    cfg, model, params, tokens, lm, rm = case("phi4flash", **preset.CELL_LAYERS)
    assert rm["layer_offset"] == 16 and rm["layer_types"] == ["mamba", "full", "gmu", "cross"]
    loss, grads = masked_loss_and_grads(model, params, tokens, lm, rate)
    index = ref.index({k: v.shape for k, v in params.items()}, rm, rate)
    sub = {k: jnp.asarray(v) for k, v in common.take(params, index).items()}
    ref_loss, ref_grads = jax.jit(jax.value_and_grad(
        lambda p: ref.loss_fn(p, tokens, lm, rate, ref.arch_of(rm))))(sub)
    np.testing.assert_allclose(float(loss), float(ref_loss), rtol=1e-5)
    inside = common.take({k: np.asarray(v) for k, v in grads.items()}, index)
    for k, g in ref_grads.items():
        g = np.asarray(g)
        if not unseen("phi4flash", k):
            assert np.abs(g).max() > 0, k
            np.testing.assert_allclose(inside[k], g, atol=1e-3 * np.abs(g).max() + 1e-9, err_msg=k)


@pytest.mark.parametrize("rate", [1.0, 0.125])
def test_the_references_step_a_part_at_a_time_is_its_loss_and_gradients(rate):
    """`benchmark/reference/phi4flash.py` trains a layer's half at a time from
    the host (its programs then fit the chip machine's compile cache): that
    step's loss and gradients -- the side values' cotangents handed from their
    readers back to their producers, the tied table's two uses summed -- are
    `jax.value_and_grad(loss_fn)`'s, and one feed-forward program serves every
    layer of a level."""
    from benchmark.reference import common

    ref = reference("phi4flash")
    cfg, model, params, tokens, lm, rm = case("phi4flash")
    index = ref.index({k: v.shape for k, v in params.items()}, rm, rate)
    sub = {k: jnp.asarray(v) for k, v in common.take(params, index).items()}
    arch = ref.arch_of(rm)
    want_loss, want = jax.jit(jax.value_and_grad(common.highest(
        lambda p: ref.loss_fn(p, tokens, lm, rate, arch))))(sub)
    before = len(ref._COMPILED)
    loss, grads = ref.loss_and_grads(sub, tokens, lm, rate, arch)
    # embed, seven mixers and ONE feed-forward forward and backward, the head, the table's sum
    assert len(ref._COMPILED) - before <= 2 + 2 * (7 + 1) + 1
    np.testing.assert_allclose(float(loss), float(want_loss), rtol=1e-6)
    assert set(grads) == set(want)
    for k, g in want.items():
        if not unseen("phi4flash", k):
            np.testing.assert_allclose(grads[k], g, atol=1e-5 * float(jnp.abs(g).max()) + 1e-9,
                                       err_msg=k)


def test_the_stored_forms_put_the_seeded_scan_where_the_published_rule_does():
    """`benchmark/weights.py`'s one rule on the stored leaves: `A` is -(1..16)
    to 2 % (S4D-real), `softplus(b_dt)` lies in [0.0037, 0.027] (published:
    [0.001, 0.1]), `D` is 1, and each `l*` vector is drawn (std 0.07), not
    zero: at zero none of the four would ever get a gradient."""
    from benchmark import weights

    shapes = {"l0.ssm.a_log.w": (5120, 16), "l0.ssm.dt.b": (1, 5120), "l0.ssm.skip.g": (5120,),
              "l1.attn.lq1.w": (64, 1)}
    p = weights.make_params(shapes, 5000000011)
    a = jnp.exp(p["l0.ssm.a_log.w"] + jnp.log(jnp.arange(1, 17, dtype=jnp.float32)))
    np.testing.assert_allclose(a, np.broadcast_to(np.arange(1, 17), a.shape), rtol=0.02)
    dt = jax.nn.softplus(p["l0.ssm.dt.b"][0] + phi4flash.DT_BIAS_SHIFT)
    assert 0.0036 < float(dt.min()) and float(dt.max()) < 0.028
    assert (np.asarray(p["l0.ssm.skip.g"]) == 1.0).all()
    assert 0.05 < float(jnp.std(p["l1.attn.lq1.w"])) < 0.09
    assert phi4flash.DT_BIAS_SHIFT == reference("phi4flash").DT_BIAS_SHIFT


# ---------------------------------------------------------------------------
# the rule, the engines, the scopes
# ---------------------------------------------------------------------------

def test_the_rule_takes_a_value_width_and_leaves_every_accepted_answer():
    """`gq_plan` with the value's width as its own argument: every call an
    accepted cell makes answers as before (with no width, and with the
    query's); a differential pair's softmax -- 64-wide queries and keys in
    groups of 2 against a 128-wide value at 8,192 positions -- takes the
    `gq_attn` pair in ONE call; under a window no pair takes a value of its
    own, and a level-e client's narrow heads take the block loop."""
    accepted = [((2048, 64, 4), ("gq", 512, 512)), ((2048, 128, 1), ("gq", 512, 512)),
                ((8192, 128, 8, 512), ("band", 256, 256)), ((8192, 128, 6), ("band", 512, 512)),
                ((8192, 128, 8), ("band", 512, 512)), ((8192, 128, 16), ("band", 256, 512)),
                ((8192, 64, 4), ("gq", 512, 512)), ((2048, 64, 8, 512), None),
                ((2048, 32, 8, 512), None), ((2000, 128, 8, 512), None)]
    for args, want in accepted:
        S, d, group, window = (args + (None,))[:4]
        assert PA.gq_plan(*args) == want == PA.gq_plan(S, d, group, window, d), args
    assert PA.gq_plan(8192, 64, 2, None, 128) == ("gq", 512, 512) == PA.gq_plan(8192, 64, 2)
    assert PA.gq_plan(8192, 64, 2, 512, 128) is None   # the sliding kind: the block loop
    assert PA.gq_plan(8192, 128, 8, 512, 256) is None and PA.gq_plan(8192, 128, 8, None, 256) is None
    assert PA.gq_plan(8192, 4, 2, None, 8) is None and PA.gq_plan(8192, 64, 2, None, 96) is None


def _other_family_text(family):
    kwargs = dict(num_hidden_layers=2) if family == "kanana2" else {}
    cfg, model, params, tokens, lm, _ = case(family, **kwargs)
    fn = jax.value_and_grad(lambda p: model.apply(
        p, {"label": tokens}, train=True, width_rate=0.25, scaler_rate=0.25,
        label_mask=lm)[0]["loss"])
    return re.sub(r"0x[0-9a-f]+", "0x", str(jax.make_jaxpr(fn)(params)))


#: sha256 of the parent's (87f8af6) text of each family's tiny loss and
#: gradients at rate 0.25, first 16 hex digits (`_other_family_text`)
PARENTS_TEXT = {"kanana2": "fee23ce5722fd659", "lfm2": "df22a25730cb2020",
                "keye": "3d3da88f6f50e898", "ouro": "bdcd7d5706446277",
                "laguna": "de7be86bcb01e368", "nemotron_h": "8a9d6c00ff919c64"}


@pytest.mark.parametrize("family", list(PARENTS_TEXT))
def test_the_six_accepted_families_programs_are_the_parents_as_text(family, monkeypatch):
    """What this PR changed in shared code -- `decoder()`'s final norm,
    `Call.layer_norm`, `gq_plan`'s value width, the `gq_attn` calls' own value
    block -- leaves the six accepted families' traced programs, the jaxpr of a
    tiny model's loss and gradients, the parent's as text; and with every entry
    of this family's made to raise, none of them reaches one."""
    import hashlib

    def unreachable(*a, **kw):
        raise AssertionError("Phi-4-flash's alone")

    for name in ("selective_scan", "differential_attention", "gated_memory_unit",
                 "differential_attention_planned"):
        monkeypatch.setattr(L, name, unreachable)
    text = _other_family_text(family)
    assert hashlib.sha256(text.encode()).hexdigest()[:16] == PARENTS_TEXT[family]


def test_nothing_in_the_engines_names_the_family():
    """`parallel/` and `fed/` take the family through `ModelDef` alone: the
    slice needed nothing there (a group shared by leaves of different layers is
    a group; a leaf that enters through an exponential is a leaf)."""
    import pathlib

    import heterofl_tpu

    root = pathlib.Path(heterofl_tpu.__file__).parent
    hits = [str(p) for d in ("parallel", "fed") for p in (root / d).glob("*.py")
            if re.search(r"phi4|sambay|selective_scan|differential|gmu", p.read_text().lower())]
    assert not hits


def test_the_family_carries_its_names():
    """`SAMBAY_SCOPES` is disjoint from the seven older tuples, which stay as
    the accepted benchmark mirrors them; its names and the ones the family
    shares reach the round program's `op_name`s under `step/model`, forward
    and backward: the scan and the convolution inside `ssm` (no `ssm/norm`),
    the gated memory unit, the attention's projections under `gqa`, its
    softmaxes under `attn` and `swa`, the combine under `diff`."""
    from heterofl_tpu.obs import trace

    older = trace.SCOPES + trace.EXTRA_SCOPES + trace.MIXER_SCOPES + trace.SPARSE_SCOPES \
        + trace.LOOP_SCOPES + trace.WINDOW_SCOPES + trace.SSM_SCOPES
    assert trace.SAMBAY_SCOPES == ("gmu", "diff")
    assert not set(trace.SAMBAY_SCOPES) & set(older)
    for s in older + trace.SAMBAY_SCOPES:
        trace.scope(s)
    with pytest.raises(ValueError, match="Not valid scope"):
        trace.scope("gmu/gate")
    cfg, data = round_case("phi4flash")
    cfg = dict(cfg, round_chunk=1)
    model = make_model(cfg)
    eng = RoundEngine(model, cfg, make_mesh(1, 1))
    users = np.arange(8, dtype=np.int32)
    fix = (eng.fix_rates,) if eng.fix_rates is not None else ()
    args = (model.init(jax.random.key(0)), jax.random.key(0), np.float32(0.1), users, users,
            *data, *fix)
    names = ["/" + n for n in re.findall(
        r'op_name="([^"]+)"', eng._build_train().lower(*args).compile().as_text())]
    for s in ("ssm/ssm/scan", "ssm/ssm/conv", "ssm/linear", "gmu/linear", "gqa/linear", "attn",
              "swa", "diff"):
        mine = [n for n in names if f"/{s}/" in n and "step/model" in n]
        assert any("/jvp(step/model)/" in n for n in mine), s
        assert any("transpose(jvp(step/model))" in n for n in mine), s
    assert not [n for n in names if "/ssm/norm/" in n or "/rope/" in n or "/moe/" in n]
    assert not [n for n in names if "/ssm/" in n and ("/gmu/" in n or "/attn/" in n)]
