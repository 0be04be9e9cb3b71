"""What is Nemotron-H's alone (ISSUE 46): the chunked selective scan against the
reference's position-by-position recurrence, across chunk boundaries, forward
and gradient; masked channels through convolution, scan and gated norm; the
two-matrix squared-`relu` expert through the one routed loop, and that loop
unchanged for the four SwiGLU families; the shares of the expert layer; the
attention layer's row of `gq_plan` (a group of 16); the family's scopes.  The
family's part of the contract every family keeps is `test_decoder_families.py`
and `test_decoder_reference.py`."""

import re
from functools import partial

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax import lax

from decoder_cases import case, round_case, tiny_case
from heterofl_tpu.models import make_model
from heterofl_tpu.ops import layers as L
from heterofl_tpu.parallel import RoundEngine, make_mesh


# ---------------------------------------------------------------------------
# the chunked scan
# ---------------------------------------------------------------------------

def _scan_inputs(S, seed=0, N=2, H=4, P=8, G=2, Ns=4):
    ks = jax.random.split(jax.random.key(seed), 5)
    x = jax.random.normal(ks[0], (N, S, H, P))
    dt = jax.nn.softplus(jax.random.normal(ks[1], (N, S, H)) - 3.0)  # about 0.05
    a = -jnp.exp(jax.random.uniform(ks[2], (H,), minval=0.0, maxval=2.0))
    b, c = (jax.random.normal(k, (N, S, G, Ns)) for k in ks[3:])
    return x, dt, a, b, c


@pytest.mark.parametrize("S", [64, 50, 16, 7], ids=["four-chunks", "ragged", "one-chunk", "short"])
def test_the_chunked_scan_is_the_position_by_position_recurrence(S):
    """`ssm_chunked_scan` in chunks of 16 against `benchmark/reference/
    nemotron_h.py` `recurrence` (a `lax.scan` over positions: no chunks, no
    cumulative decays), on rows that are whole chunks and rows that are not:
    the output and every input's gradient, float32 on both sides (1e-5 of the
    largest entry: the two differ by the order of their sums; a state lost at
    a chunk's edge is off by 1e-1)."""
    from benchmark.reference import nemotron_h as ref

    args = _scan_inputs(S)
    probe = jax.random.normal(jax.random.key(9), args[0].shape)

    def chunked(*a):
        return jnp.sum(L.ssm_chunked_scan(*a, 16)[0] * probe)

    def plain(*a):
        return jnp.sum(ref.recurrence(*a) * probe)

    with jax.default_matmul_precision("highest"):
        y, keep = L.ssm_chunked_scan(*args, 16)
        got = jax.value_and_grad(chunked, argnums=(0, 1, 2, 3, 4))(*args)
        want = jax.value_and_grad(plain, argnums=(0, 1, 2, 3, 4))(*args)
        y_ref = ref.recurrence(*args)
    np.testing.assert_allclose(y, y_ref, atol=1e-5 * float(jnp.abs(y_ref).max()))
    np.testing.assert_allclose(got[0], want[0], rtol=1e-5)
    for g, w, name in zip(got[1], want[1], ("x", "dt", "a", "b", "c")):
        np.testing.assert_allclose(g, w, atol=2e-5 * float(jnp.abs(w).max()), err_msg=name)
    # the share of the state a position keeps: exp(dt a), summed, and its count
    np.testing.assert_allclose(keep[0], jnp.sum(jnp.exp(args[1] * args[2])), rtol=1e-6)
    assert float(keep[1]) == args[1].size


def test_an_impulse_at_position_0_is_read_at_the_last_position():
    """The state must cross every chunk boundary: one write at position 0
    (`x`, `B` nonzero there alone) read by `C` at position 63, four chunks on,
    is `C . B x dt_0 exp(sum_{t=1..63} dt_t a)`; every other position of `y`
    reads what the decay left of it too, and nothing else."""
    N, S, H, P, G, Ns = 1, 64, 2, 3, 1, 4
    x = jnp.zeros((N, S, H, P)).at[0, 0].set(jnp.arange(1.0, 1.0 + H * P).reshape(H, P))
    b = jnp.zeros((N, S, G, Ns)).at[0, 0, 0].set(jnp.array([1.0, -2.0, 0.5, 3.0]))
    c = jnp.ones((N, S, G, Ns))
    dt = jnp.full((N, S, H), 0.02)
    a = jnp.array([-1.0, -3.0])
    with jax.default_matmul_precision("highest"):
        y, _ = L.ssm_chunked_scan(x, dt, a, b, c, 16)
    t = jnp.arange(S, dtype=jnp.float32)
    decay = jnp.exp(0.02 * a[None, :] * t[:, None])                  # exp(sum_{1..t} dt a)
    want = decay[None, :, :, None] * (x[:, :1] * 0.02) * jnp.sum(b[0, 0, 0])
    np.testing.assert_allclose(y, want, rtol=1e-5)
    assert float(jnp.abs(y[0, -1]).min()) > 1e-3  # it arrived


def test_masked_channels_stay_zero_and_the_norm_counts_the_active_ones():
    """A level-e client's mixer in the masked full-width model: the `x`
    channels outside its per-head prefix are zero after the convolution (taps
    and bias masked), after the scan and the skip, and after the gated norm;
    and the norm's mean square runs over a group's ACTIVE channels -- the
    sliced sub-model's group width -- not over its 128."""
    cfg, model, params, *_ = tiny_case("nemotron_h")
    rate = 0.0625
    g = model.groups["ssm_head"]
    mask = np.asarray(g.mask(rate))
    assert int(mask.sum()) == 8 * 2 and int(g.active_count(rate)) == 16  # 2 dims of each of 8 heads
    ks = jax.random.split(jax.random.key(1), 3)
    x = jax.random.normal(ks[0], (2, 32, 256)) * mask
    taps, bias = params["l1.ssm.conv.x.w"] * mask, (params["l1.ssm.conv.x.b"] + 0.3) * mask
    conv = L.causal_conv_silu(x, taps, bias)
    assert not np.asarray(conv)[..., mask == 0].any() and np.asarray(conv)[..., mask == 1].all()
    _, dt, a, b, c = _scan_inputs(32, N=2, H=8, P=32, G=2, Ns=8)
    xh = conv.reshape(2, 32, 8, 32)
    y = (L.ssm_chunked_scan(xh, dt, a, b, c, 16)[0] + xh).reshape(2, 32, 256)
    assert not np.asarray(y)[..., mask == 0].any()
    z = jax.random.normal(ks[1], (2, 32, 256)) * mask
    gain = (1.0 + 0.1 * jax.random.normal(ks[2], (256,))) * mask
    out = L.gated_group_rms_norm(y, z, gain, mask, jnp.float32(8.0), 2, 1e-5)
    assert not np.asarray(out)[..., mask == 0].any()
    # the sliced sub-model's norm: 2 groups of 4 heads x 2 dims
    idx = np.flatnonzero(mask)
    v = np.asarray(y * jax.nn.silu(z))[..., idx].reshape(2, 32, 2, 8)
    want = (v / np.sqrt((v * v).mean(-1, keepdims=True) + 1e-5)).reshape(2, 32, 16) \
        * np.asarray(gain)[idx]
    np.testing.assert_allclose(np.asarray(out)[..., idx], want, rtol=1e-5, atol=1e-6)


def test_the_pattern_is_read_and_every_layer_of_the_published_one_is_lone(monkeypatch):
    """Kinds come from `hybrid_override_pattern` letter by letter (a bad letter
    or a wrong length is refused); in the published pattern no two neighbours
    are alike, so `alike_runs` scans nothing; a pattern with a repeated
    neighbour scans that run."""
    import heterofl_tpu.models.nemotron_h as family
    from benchmark.tests import tiny_nemotron_h as tiny
    from heterofl_tpu import config as C

    published = C.DECODER_FAMILIES["nemotron_h"]["hybrid_override_pattern"]
    assert len(published) == 52 and not [i for i in range(51) if published[i] == published[i + 1]]
    assert (published.count("M"), published.count("E"), published.count("*")) == (23, 23, 6)
    for bad in (dict(hybrid_override_pattern="EMEMEM-"), dict(num_hidden_layers=6)):
        with pytest.raises(ValueError, match="hybrid_override_pattern"):
            make_model(tiny.program_cfg(**bad))
    scanned, real = [], family.alike_runs

    def spy(*a, **kw):
        for run in real(*a, **kw):
            scanned.append(run[2])
            yield run

    monkeypatch.setattr(family, "alike_runs", spy)
    tokens = jnp.zeros((1, 16), jnp.int32)
    for pattern, want in (("EMEMEM*", [False] * 7), ("MMEM", [True, False, False])):
        model = make_model(tiny.program_cfg(hybrid_override_pattern=pattern,
                                            num_hidden_layers=len(pattern)))
        del scanned[:]
        jax.eval_shape(lambda p: model.apply(p, {"label": tokens}, train=True)[0]["loss"],
                       jax.eval_shape(model.init, jax.random.key(0)))
        assert scanned == want, pattern


# ---------------------------------------------------------------------------
# the experts: two matrices and a squared relu through the one routed loop
# ---------------------------------------------------------------------------

def test_relu2_experts_through_the_routed_loop_are_a_dense_loop():
    """`moe_experts(body=relu2_ffn)` on two stacked matrices against a dense
    loop over the held experts (every expert over ALL tokens, masked): output
    and gradients of the tokens, the weights and both matrices, with a Scaler;
    no (token, held expert) pair is dropped."""
    T, D, F, held, first, K = 48, 16, 24, 4, 2, 3
    ks = jax.random.split(jax.random.key(0), 5)
    h = jax.random.normal(ks[0], (T, D))
    sel = jnp.argsort(jax.random.uniform(ks[1], (T, 8)), axis=1)[:, :K].astype(jnp.int32)
    w = jax.random.uniform(ks[2], (T, K))
    wu, wd = jax.random.normal(ks[3], (held, D, F)) / 4, jax.random.normal(ks[4], (held, F, D)) / 4

    def sc(v):
        return v / 0.5

    def routed(h, w, wu, wd):
        y, counters = L.moe_experts(h, sel, w, [wu, wd], first, sc, tile=8, body=L.relu2_ffn)
        return jnp.sum(y * jnp.cos(jnp.arange(D))), (y, counters)

    def dense(h, w, wu, wd):
        y = 0.0
        for e in range(held):
            w_e = jnp.sum(jnp.where(sel == first + e, w, 0.0), axis=1)
            y = y + w_e[:, None] * sc(jnp.square(jax.nn.relu(sc(h @ wu[e]))) @ wd[e])
        return jnp.sum(y * jnp.cos(jnp.arange(D))), y

    with jax.default_matmul_precision("highest"):
        (_, (y, counters)), got = jax.value_and_grad(routed, argnums=(0, 1, 2, 3), has_aux=True)(
            h, w, wu, wd)
        (_, y_ref), want = jax.value_and_grad(dense, argnums=(0, 1, 2, 3), has_aux=True)(
            h, w, wu, wd)
    np.testing.assert_allclose(y, y_ref, rtol=1e-5, atol=1e-5)
    for g, r, name in zip(got, want, ("h", "w", "wu", "wd")):
        np.testing.assert_allclose(g, r, atol=1e-5 * float(jnp.abs(r).max()) + 1e-7, err_msg=name)
    on_held = int(((sel >= first) & (sel < first + held)).sum())
    assert counters["assign"].tolist() == [T * K, on_held, 0.0]  # routed, on held experts, dropped
    assert counters["tokens"].tolist() == [int((sel == first + e).sum()) for e in range(held)]


# The parent's routed loop, SwiGLU's three matrices written into it, letter for
# letter (ops/layers.py at PR 45): the four accepted expert families' programs
# must be what they were with it.

def _parent_expert_tile(compute_dtype, x, wg, wu, wd, inv):
    return L.swiglu(x, wg, wu, wd, lambda v: v * inv, compute_dtype)


@partial(jax.custom_vjp, nondiff_argnums=(0, 1))
def _parent_grouped_swiglu(compute_dtype, tile, h, w, rows, slot, tile_expert, n_tiles, wg, wu,
                           wd, inv):
    K = w.shape[1]

    def step(i, carry):
        y, done = carry
        with L.scope("moe/dispatch"):
            r = lax.dynamic_slice(rows, (i * tile,), (tile,))
            x_t = L._gather_rows(h, r // K)
        with L.scope("moe/experts"):
            y_t = _parent_expert_tile(compute_dtype, x_t,
                                      *L._tile_weights(tile_expert[i], wg, wu, wd), inv)
        return (lax.dynamic_update_slice(y, y_t, (i * tile, 0)),
                done + jnp.sum((r >= 0).astype(jnp.int32)))

    y, done = lax.fori_loop(0, n_tiles, step,
                            (jnp.zeros((rows.shape[0], h.shape[1]), h.dtype), jnp.int32(0)))
    with L.scope("moe/dispatch"):
        return sum(w[:, k, None] * L._gather_rows(y, slot[:, k]) for k in range(K)), done


def _parent_fwd(compute_dtype, tile, *args):
    return _parent_grouped_swiglu(compute_dtype, tile, *args), args


def _parent_bwd(compute_dtype, tile, res, cts):
    h, w, rows, slot, tile_expert, n_tiles, wg, wu, wd, inv = res
    dout, K = cts[0], w.shape[1]
    w_flat = w.reshape(-1)

    def step(i, carry):
        dx, dw_rows, *dws = carry
        with L.scope("moe/dispatch"):
            r = lax.dynamic_slice(rows, (i * tile,), (tile,))
            x_t, d_t = L._gather_rows(h, r // K), L._gather_rows(dout, r // K)
            w_t = L._gather_rows(w_flat, r)
        with L.scope("moe/experts"):
            e = tile_expert[i]
            y_t, vjp = jax.vjp(partial(_parent_expert_tile, compute_dtype), x_t,
                               *L._tile_weights(e, wg, wu, wd), inv)
            dx_t, *dws_t, _ = vjp(d_t * w_t[:, None])
            dws = [lax.dynamic_update_index_in_dim(
                acc, lax.dynamic_index_in_dim(acc, e, 0, keepdims=False) + d, e, 0)
                for acc, d in zip(dws, dws_t)]
        return (lax.dynamic_update_slice(dx, dx_t, (i * tile, 0)),
                lax.dynamic_update_slice(dw_rows, jnp.sum(y_t * d_t, axis=-1), (i * tile,)),
                *dws)

    dx, dw_rows, dwg, dwu, dwd = lax.fori_loop(
        0, n_tiles, step,
        (jnp.zeros((rows.shape[0], h.shape[1]), h.dtype), jnp.zeros(rows.shape, w.dtype),
         jnp.zeros_like(wg), jnp.zeros_like(wu), jnp.zeros_like(wd)))
    with L.scope("moe/dispatch"):
        dh = sum(L._gather_rows(dx, slot[:, k]) for k in range(K))
        dw = L._gather_rows(dw_rows, slot)
    return dh, dw, None, None, None, None, dwg, dwu, dwd, jnp.zeros_like(inv)


_parent_grouped_swiglu.defvjp(_parent_fwd, _parent_bwd)


SWIGLU_FAMILIES = ("kanana2", "lfm2", "keye", "laguna")


def _loss_and_grads_text(family):
    """The jaxpr of a family's tiny model's loss and gradients, as text."""
    if family in ("resnet18", "transformer"):
        from test_models import small_cfg, vision_batch

        cfg = small_cfg(family, data_name="WikiText2" if family == "transformer" else "MNIST")
        model = make_model(cfg)
        params = model.init(jax.random.key(0))
        batch = {"label": jnp.arange(32).reshape(2, 16) % 50} if family == "transformer" \
            else vision_batch(cfg)
        extra = dict(rng=jax.random.key(1)) if family == "transformer" else {}
        fn = jax.value_and_grad(lambda p: model.apply(p, batch, train=True, **extra)[0]["loss"])
    else:
        kwargs = dict(num_hidden_layers=2) if family == "kanana2" else {}
        cfg, model, params, tokens, lm, _ = case(family, **kwargs)
        fn = jax.value_and_grad(lambda p: model.apply(
            p, {"label": tokens}, train=True, width_rate=0.25, scaler_rate=0.25,
            label_mask=lm)[0]["loss"])
    return re.sub(r"0x[0-9a-f]+", "0x", str(jax.make_jaxpr(fn)(params)))


@pytest.mark.parametrize("family", SWIGLU_FAMILIES + ("ouro", "resnet18", "transformer"))
def test_the_other_families_programs_are_the_parents_as_text(family, monkeypatch):
    """What this family's PRs changed in shared code leaves the seven other
    families' traced programs -- the jaxpr of a tiny model's loss and
    gradients -- the parent's as text.  PR 46: the routed loop takes its
    expert's body as an argument; for a family whose body is SwiGLU the text
    with the parent's loop (above) in the loop's place is the same but for the
    loop's own name.  PR 47: the chunked scan picks a kernel pair by backend
    and shape; with every entry of it made to raise (`ssm_chunked_scan`, its
    plan, the kernels' module), on a backend that reports a TPU, no other
    family's trace reaches one, and the text is what it was."""
    from heterofl_tpu.ops import pallas_ssm

    mine = _loss_and_grads_text(family)
    assert "ssm_scan" not in mine

    def unreachable(*a, **kw):
        raise AssertionError("the chunked scan is Nemotron-H's alone")

    for module, name in ((L, "ssm_chunked_scan"), (L, "ssm_scan_plan"),
                         (pallas_ssm, "ssm_plan"), (pallas_ssm, "fused_ssm_scan")):
        monkeypatch.setattr(module, name, unreachable)
    assert _loss_and_grads_text(family) == mine
    if family not in SWIGLU_FAMILIES:
        return

    def parents(body, compute_dtype, tile, h, w, rows, slot, tile_expert, n_tiles, ws, inv):
        assert body is L.swiglu and len(ws) == 3
        return _parent_grouped_swiglu(compute_dtype, tile, h, w, rows, slot, tile_expert,
                                      n_tiles, *ws, inv)

    monkeypatch.setattr(L, "_grouped_experts", parents)
    theirs = _loss_and_grads_text(family)
    assert "_parent_grouped_swiglu" in theirs and "_grouped_experts" in mine
    for name, word in (("_grouped_experts_fwd", "FWD"), ("_grouped_experts_bwd", "BWD"),
                       ("_grouped_experts", "LOOP")):
        mine = mine.replace(name, word)
    for name, word in (("_parent_fwd", "FWD"), ("_parent_bwd", "BWD"),
                       ("_parent_grouped_swiglu", "LOOP"), ("_parent_expert_tile", "_expert_tile")):
        theirs = theirs.replace(name, word)
    assert mine == theirs


@pytest.mark.parametrize("backend, fused", [("tpu", 3.0), ("cpu", 0.0)])
def test_the_mixers_take_the_scan_kernels_on_a_tpu_and_say_so(backend, fused, monkeypatch):
    """The tiny model at shapes `ssm_plan` takes (chunks and a state of 128;
    four heads of 32 a group fill a lane tile) on a backend that reports a TPU,
    the kernels in interpret mode: every mixer's scan is the kernel pair (three
    `ssm_scan_fwd` in the traced loss, none off a TPU), `ssm_fused` reads 3 of
    3 (0 of 3 off a TPU), and loss and gradients are the `jnp` form's at
    float32 products."""
    from heterofl_tpu.ops import pallas_ssm

    cfg, model, params, tokens, lm, _ = case("nemotron_h", bptt=128, chunk_size=128,
                                             ssm_state_size=128)

    def loss(p):
        out, _ = model.apply(p, {"label": tokens}, train=True, width_rate=0.5, scaler_rate=0.5,
                             label_mask=lm)
        return out["loss"], out["counters"]

    with jax.default_matmul_precision("highest"):
        (want, _), want_grads = jax.value_and_grad(loss, has_aux=True)(params)
        monkeypatch.setattr(jax, "default_backend", lambda: backend)
        monkeypatch.setattr(pallas_ssm, "fused_ssm_scan",
                            partial(pallas_ssm.fused_ssm_scan, interpret=True))
        assert str(jax.make_jaxpr(loss)(params)).count("name=ssm_scan_fwd") == fused
        (got, counters), grads = jax.value_and_grad(loss, has_aux=True)(params)
    assert np.asarray(counters["ssm_fused"]).tolist() == [fused, 3.0]
    assert model.meta["counters"]["ssm_fused"] == ((2,), "ratio")
    np.testing.assert_allclose(got, want, rtol=1e-6)
    for k, w in want_grads.items():
        np.testing.assert_allclose(grads[k], w, rtol=0, atol=5e-5 * float(jnp.abs(w).max()) + 1e-9,
                                   err_msg=k)


def test_the_sixteen_shares_add_up_to_the_uncut_reference_layer():
    """The guide's share test: the routed parts that the shares of a 16-way
    expert-parallel layer compute (the program's `moe_route` and `moe_experts`,
    each share told its one expert) add up to the UNCUT reference's expert
    layer, with what every share computes alike (the router, the shared
    expert) counted once: for the reference's whole layer ``x -> x + y``, ``y =
    shared(h) + sum over shares of moe_experts(share)``."""
    from benchmark.reference import nemotron_h as ref
    from benchmark.tests import tiny_nemotron_h as tiny

    cfg = tiny.program_cfg(expert_share=[0, 1])
    arch, rm = cfg["nemotron_h"], tiny.reference_model(cfg)
    model = make_model(cfg)
    assert model.meta["held_experts"] == list(range(16))
    whole = model.init(jax.random.key(3))
    whole["l0.moe.router.b"] = 0.2 * jax.random.normal(jax.random.key(5), (16,))  # read by top-k
    x = jax.random.normal(jax.random.key(4), (2, tiny.BPTT, arch["hidden_size"]))
    a = ref.arch_of(rm)
    lp = ref._layer_leaves(whole, 0, dict(a)["held"])
    with jax.default_matmul_precision("highest"):
        y_ref = (ref.layer(lp, x, 1.0, a, "experts") - x).reshape(2 * tiny.BPTT, -1)
        hf = ref._rms(x, lp["norm.g"], 1e-5).reshape(2 * tiny.BPTT, -1)
        sel, w = L.moe_route(hf, whole["l0.moe.router.w"], whole["l0.moe.router.b"],
                             arch["num_experts_per_tok"], arch["routed_scaling_factor"])
        np.testing.assert_allclose(np.asarray(w).sum(axis=1), 2.5, rtol=1e-6)  # renormalised, scaled
        parts = [L.moe_experts(hf, sel, w, [whole[f"l0.moe.e{i}.{m}.w"][None] for m in "ud"], i,
                               lambda v: v, tile=8, body=L.relu2_ffn) for i in range(16)]
        shared = L.relu2_ffn(hf, whole["l0.moe.shared.u.w"], whole["l0.moe.shared.d.w"],
                             lambda v: v)
    np.testing.assert_allclose(shared + sum(y for y, _ in parts), y_ref, rtol=1e-4, atol=1e-5)
    assert sum(float(c["assign"][1]) for _, c in parts) == sel.size  # every pair once
    assert float(jnp.abs(y_ref - shared).max()) > 1e-3  # the routed experts add something
    # the bias is read by the choice and not by the weights
    sel0, _ = L.moe_route(hf, whole["l0.moe.router.w"], None, 2, 2.5)
    assert (np.sort(np.asarray(sel0), axis=1) != np.sort(np.asarray(sel), axis=1)).any()


@pytest.mark.parametrize("share", [3])
def test_a_share_of_the_model_is_the_reference_given_that_share(share):
    """The program told it holds one sixteenth of the experts against the
    reference told the same: loss and the held expert's gradients."""
    from benchmark.reference import common, nemotron_h as ref
    from decoder_cases import masked_loss_and_grads

    cfg, model, params, tokens, lm, rm = case("nemotron_h", expert_share=[share, 16])
    assert model.meta["held_experts"] == [share]
    loss, grads = masked_loss_and_grads(model, params, tokens, lm, 1.0)
    ref_loss, ref_grads = jax.jit(jax.value_and_grad(common.highest(
        lambda p: ref.loss_fn(p, tokens, lm, 1.0, ref.arch_of(rm)))))(params)
    np.testing.assert_allclose(float(loss), float(ref_loss), rtol=1e-5)
    for k in (f"l2.moe.e{share}.u.w", f"l2.moe.e{share}.d.w", "l2.moe.router.w",
              "l2.moe.shared.u.w", "l3.ssm.a_log.w", "l3.ssm.dt_bias.w", "l3.ssm.skip.g"):
        g = np.asarray(ref_grads[k])
        np.testing.assert_allclose(grads[k], g, atol=1e-3 * np.abs(g).max() + 1e-9, err_msg=k)


def test_the_stored_shift_puts_the_seeded_decay_where_the_published_rule_does():
    """`A_log` and `dt_bias` are stored shifted (a slope of one): a leaf uniform
    in [-1, 1], the package's rule for a `[1, H]` matrix and the benchmark's,
    gives `A` inside the published initialisation's [1, 16] and a bias whose
    softplus is inside [`time_step_min`, `time_step_max`]; program and
    reference hold the same two constants."""
    from benchmark.reference import nemotron_h as ref
    from heterofl_tpu.models import nemotron_h as family

    assert (family.A_LOG_SHIFT, family.DT_BIAS_SHIFT) == (ref.A_LOG_SHIFT, ref.DT_BIAS_SHIFT)
    u = np.array([-1.0, 1.0])
    a = np.exp(u + family.A_LOG_SHIFT)
    dt = np.log1p(np.exp(u + family.DT_BIAS_SHIFT))
    assert 1.0 < a[0] < a[1] < 16.0 and 0.001 < dt[0] < dt[1] < 0.1
    _, model, *_ = tiny_case("nemotron_h")
    p = model.init(jax.random.key(0))
    assert p["l1.ssm.a_log.w"].shape == (1, 8) and float(jnp.abs(p["l1.ssm.a_log.w"]).max()) <= 1.0
    assert float(jnp.abs(p["l1.ssm.dt_bias.w"]).max()) <= 1.0 and (p["l1.ssm.skip.g"] == 1).all()


# ---------------------------------------------------------------------------
# the attention layer: a group of 16, no position encoding
# ---------------------------------------------------------------------------

def test_the_rule_gives_a_group_of_16_the_band_pair():
    """32 query heads on 2 key/value heads of 128 at 8,192 positions: `dq` of
    a group would be 16 x 128 x 8,192 x 4 B = 67 MB, over `GQ_RESIDENT_BYTES`,
    so `gq_plan` gives the band pair with no window, at a query tile of 256
    (16 x 256 = 4,096 columns side by side) and a key tile of 512."""
    from heterofl_tpu.ops import pallas_attention as PA

    assert 16 * 128 * 8192 * 4 > PA.GQ_RESIDENT_BYTES
    assert PA.gq_plan(8192, 128, 16) == ("band", 256, 512)
    assert PA.gq_plan(8192, 8, 16) is None  # a level-e client's 8-dim heads: the block loop


def test_band_kernels_at_a_group_of_16_are_the_block_loop():
    """`band_attn_fwd` / `band_attn_bwd` (interpret mode) at 16 query heads a
    key/value head, two key/value heads, no window, against the block loop on
    bfloat16-rounded operands: output and the three gradients (tolerances and
    reasons: `test_laguna.test_band_kernels_in_interpret_mode_are_the_block_loop`)."""
    from heterofl_tpu.ops import pallas_attention as PA

    S, d = 256, 128
    ks = jax.random.split(jax.random.key(3), 4)
    q, k, v, probe = (jax.random.normal(kk, (1, n, S, d)) for kk, n in zip(ks, (32, 2, 2, 32)))

    def rounded(x):
        return x.astype(jnp.bfloat16).astype(jnp.float32)

    def fused(q, k, v):
        return jnp.sum(PA.fused_band_attention(q, k, v, 0.3, None, block_q=128, block_k=128,
                                               interpret=True) * probe)

    def loop(q, k, v):
        return jnp.sum(L.blockwise_gq_attention(rounded(q * 0.3), rounded(k), rounded(v), 1.0,
                                                64) * probe)

    got = jax.value_and_grad(fused, argnums=(0, 1, 2))(q, k, v)
    want = jax.value_and_grad(loop, argnums=(0, 1, 2))(q, k, v)
    np.testing.assert_allclose(got[0], want[0], rtol=5e-3)
    for g, w, name in zip(got[1], want[1], "qkv"):
        np.testing.assert_allclose(g, w, atol=1e-2 * float(jnp.abs(w).max()), err_msg=name)


def test_the_attention_layer_has_no_position_encoding():
    """`gq_attention(theta=None)` turns nothing: the layer's output at a
    position depends on the SET of positions before it and not on their order
    (keys and values permuted together leave the last query's output where it
    was), and the traced program holds no `rope` scope."""
    from heterofl_tpu.models.decoder import gq_attention

    D, H, Hkv, hd, S = 32, 4, 2, 8, 12
    ks = jax.random.split(jax.random.key(0), 5)
    lp = {"attn.q.w": jax.random.normal(ks[0], (D, H * hd)) / 4,
          "attn.k.w": jax.random.normal(ks[1], (D, Hkv * hd)) / 4,
          "attn.v.w": jax.random.normal(ks[2], (D, Hkv * hd)) / 4,
          "attn.o.w": jax.random.normal(ks[3], (H * hd, D)) / 4}
    h = jax.random.normal(ks[4], (1, S, D))
    attend = partial(gq_attention, lp, heads=H, kv_heads=Hkv, head_dim=None, theta=None,
                     scale=hd ** -0.5, sc=lambda v: v)
    perm = jnp.concatenate([jax.random.permutation(jax.random.key(7), S - 1), jnp.array([S - 1])])
    np.testing.assert_allclose(attend(h)[:, -1], attend(h[:, perm])[:, -1], rtol=1e-5, atol=1e-6)
    assert "rope" not in str(jax.make_jaxpr(attend)(h))


# ---------------------------------------------------------------------------
# the engines and the scopes
# ---------------------------------------------------------------------------

def test_nothing_in_the_engines_names_the_family():
    """`parallel/` and `fed/` take the family through `ModelDef` alone: no file
    of either names it or its mixer (the issue's "nothing beyond what the
    slice needs": the slice needed nothing there)."""
    import pathlib

    import heterofl_tpu

    root = pathlib.Path(heterofl_tpu.__file__).parent
    hits = [str(p) for d in ("parallel", "fed") for p in (root / d).glob("*.py")
            if re.search(r"nemotron|mamba|ssm_", p.read_text().lower())]
    assert not hits


def test_the_mixer_carries_its_names():
    """`SSM_SCOPES` is disjoint from the six older tuples, which stay as the
    accepted benchmark mirrors them; its names reach the round program's
    `op_name`s under `step/model`, forward and backward: the scan, the
    convolution and the norm inside `ssm`, the mixer's products under
    `ssm/linear`, the attention layer under `gqa` / `attn` with no `rope`, the
    experts under `moe/*`; no instruction is under both `ssm` and `moe` or
    `attn`."""
    from heterofl_tpu.obs import trace

    older = trace.SCOPES + trace.EXTRA_SCOPES + trace.MIXER_SCOPES + trace.SPARSE_SCOPES \
        + trace.LOOP_SCOPES + trace.WINDOW_SCOPES
    assert trace.SSM_SCOPES == ("ssm", "ssm/conv", "ssm/scan", "ssm/norm")
    assert not set(trace.SSM_SCOPES) & set(older)
    for s in older + trace.SSM_SCOPES:
        trace.scope(s)
    with pytest.raises(ValueError, match="Not valid scope"):
        trace.scope("ssm/state")
    cfg, data = round_case("nemotron_h")
    cfg = dict(cfg, round_chunk=1)
    model = make_model(cfg)
    eng = RoundEngine(model, cfg, make_mesh(1, 1))
    users = np.arange(8, dtype=np.int32)
    fix = (eng.fix_rates,) if eng.fix_rates is not None else ()
    args = (model.init(jax.random.key(0)), jax.random.key(0), np.float32(0.1), users, users,
            *data, *fix)
    names = ["/" + n for n in re.findall(
        r'op_name="([^"]+)"', eng._build_train().lower(*args).compile().as_text())]
    for s in ("ssm/ssm/scan", "ssm/ssm/conv", "ssm/ssm/norm", "ssm/linear", "attn", "gqa",
              "moe/experts", "moe/shared", "moe/router"):
        mine = [n for n in names if f"/{s}/" in n and "step/model" in n]
        assert any("/jvp(step/model)/" in n for n in mine), s
        assert any("transpose(" in n for n in mine), s
    assert not [n for n in names if "/rope/" in n]
    assert not [n for n in names if "/ssm/" in n and ("/moe/" in n or "/attn/" in n)]
    assert any(re.search(r"/ssm/ssm/scan/.*dot_general", n) for n in names)
