"""Laguna (``models/laguna.py``, ISSUE 42: sliding-window layers of one head
count among full-attention layers of another, a RoPE a kind -- YaRN on half a
head in the full layers --, a per-head output gate, sigmoid-routed experts
beside a shared one) at a tiny size on the CPU: against the benchmark's plain
reference, the window's edges, the band kernels against the block loop, the
rule that picks a kernel pair, its slicing rules, the expert shares, and
through the engines and the entry point.  A file of its own so that the test
runner's per-file workers share the family's compiles evenly."""

import functools
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from decoder_cases import case, masked_loss_and_grads, round_case, tiny_case
from heterofl_tpu import config as C
from heterofl_tpu.models import make_model
from heterofl_tpu.ops import layers as L
from heterofl_tpu.parallel import RoundEngine, make_mesh

_laguna_case = functools.partial(case, "laguna")
_masked_loss_and_grads = masked_loss_and_grads
_tiny_case = functools.partial(tiny_case, "laguna")
_round_case = functools.partial(round_case, "laguna")


# ---------------------------------------------------------------------------
# the model against the benchmark's plain reference
# ---------------------------------------------------------------------------

def test_the_references_step_a_part_at_a_time_is_its_whole_gradient():
    """`benchmark/reference/laguna.py` trains with `loss_and_grads`, the chain
    rule a layer's part at a time from the host (every kind of part compiled
    once a level: its programs fit the chip machine's compile cache), where
    the tests above differentiate `loss_fn`, the Python loop over the layers:
    the same loss and, leaf by leaf, the same gradient."""
    from benchmark.reference import common, laguna as ref

    cfg, model, params, tokens, lm, rm = _tiny_case()
    index = ref.index({k: v.shape for k, v in params.items()}, rm, 0.25)
    sub = {k: jnp.asarray(v) for k, v in common.take(params, index).items()}
    arch = ref.arch_of(rm)
    loss, grads = ref.loss_and_grads(sub, tokens, lm, 0.25, arch)
    want_loss, want = jax.jit(jax.value_and_grad(common.highest(
        lambda p: ref.loss_fn(p, tokens, lm, 0.25, arch))))(sub)
    np.testing.assert_allclose(float(loss), float(want_loss), rtol=1e-6)
    assert set(grads) == set(want)
    for k, g in want.items():
        np.testing.assert_allclose(grads[k], g, atol=1e-5 * float(jnp.abs(g).max()) + 1e-12,
                                   err_msg=k)


def test_the_sixteen_shares_add_up_to_the_uncut_reference_layer():
    """The guide's share test: the routed parts that the shares of a 16-way
    expert-parallel layer compute (the program's `moe_route` and `moe_experts`,
    each share told its one expert) add up to the UNCUT reference's expert
    layer, with what every share computes alike (the gated sliding attention,
    the router, the shared expert) counted once: for the reference's whole
    layer ``x -> x1 + y`` (``x1`` the state after the attention), ``y =
    shared(h) + sum over shares of moe_experts(share)``."""
    from benchmark.reference import common, laguna as ref
    from benchmark.tests import tiny_laguna as tiny

    cfg = tiny.program_cfg(expert_share=[0, 1])
    arch, rm = cfg["laguna"], tiny.reference_model(cfg)
    model = make_model(cfg)
    assert model.meta["held_experts"] == list(range(16))
    whole = model.init(jax.random.key(3))
    x = jax.random.normal(jax.random.key(4), (2, tiny.BPTT, arch["hidden_size"]))
    a = ref.arch_of(rm)
    kind, heads, mlp = dict(a)["layers"][1]
    assert (kind, mlp) == ("sliding_attention", "sparse")
    lp = ref._layer_leaves(whole, 1, dict(a)["held"])
    with jax.default_matmul_precision("highest"):
        x1 = x + ref.attention_mixer(lp, ref._rms(x, lp["norm1.g"], 1e-6), 1.0, a, kind, heads)
        y_ref = (ref.layer(lp, x, 1.0, a, kind, heads, True) - x1).reshape(2 * tiny.BPTT, -1)
    hf = ref._rms(x1, lp["norm2.g"], 1e-6).reshape(2 * tiny.BPTT, -1)
    sel, w = L.moe_route(hf, whole["l1.moe.router.w"], None, arch["num_experts_per_tok"],
                         arch["moe_routed_scaling_factor"])
    np.testing.assert_allclose(np.asarray(w).sum(axis=1), 2.5, rtol=1e-6)  # renormalised, scaled
    parts = [L.moe_experts(hf, sel, w, [whole[f"l1.moe.e{i}.{m}.w"][None] for m in "gud"], i,
                           lambda v: v, tile=8) for i in range(16)]
    shared = L.swiglu(hf, *(whole[f"l1.moe.shared.{m}.w"] for m in "gud"), lambda v: v)
    np.testing.assert_allclose(shared + sum(y for y, _ in parts), y_ref, rtol=1e-4, atol=1e-5)
    assert sum(float(c["assign"][1]) for _, c in parts) == sel.size  # every pair once
    assert float(jnp.abs(y_ref - shared).max()) > 1e-3  # the routed experts add something


@pytest.mark.parametrize("share", [5])
def test_a_share_of_the_model_is_the_reference_given_that_share(share):
    """The program told it holds one sixteenth of the experts against the
    reference told the same: loss and the held expert's gradients."""
    from benchmark.reference import common, laguna as ref

    cfg, model, params, tokens, lm, rm = _laguna_case(expert_share=[share, 16])
    assert model.meta["held_experts"] == [share]
    loss, grads = _masked_loss_and_grads(model, params, tokens, lm, 1.0)
    ref_loss, ref_grads = jax.jit(jax.value_and_grad(common.highest(
        lambda p: ref.loss_fn(p, tokens, lm, 1.0, ref.arch_of(rm)))))(params)
    np.testing.assert_allclose(float(loss), float(ref_loss), rtol=1e-5)
    for k in (f"l2.moe.e{share}.g.w", f"l2.moe.e{share}.d.w", "l2.moe.router.w",
              "l2.moe.shared.u.w"):
        g = np.asarray(ref_grads[k])
        np.testing.assert_allclose(grads[k], g, atol=1e-3 * np.abs(g).max() + 1e-9, err_msg=k)


# ---------------------------------------------------------------------------
# the window
# ---------------------------------------------------------------------------

def _qkv(S, H=4, Hkv=2, d=16, seed=0):
    ks = jax.random.split(jax.random.key(seed), 3)
    return [jax.random.normal(k, (1, n, S, d)) for k, n in zip(ks, (H, Hkv, Hkv))]


def _dense_window(q, k, v, scale, window):
    """Every pair's score under explicit boolean masks, nothing in blocks."""
    G = q.shape[1] // k.shape[1]
    k, v = (jnp.repeat(t, G, axis=1) for t in (k, v))
    i, j = jnp.arange(q.shape[2])[:, None], jnp.arange(q.shape[2])[None, :]
    keep = (j <= i) & (i - j < window)
    s = jnp.where(keep, jnp.einsum("nhqd,nhkd->nhqk", q, k) * scale, -jnp.inf)
    return jnp.einsum("nhqk,nhkd->nhqd", jax.nn.softmax(s, axis=-1), v)


@pytest.mark.parametrize("S, window, block", [
    (48, 64, 16), (64, 64, 16), (64, 64, 64), (70, 16, 32), (96, 16, 16), (600, 512, 256)],
    ids=["S<window", "S=window", "one-block", "S-not-a-multiple-of-the-block",
         "S>window+block", "query-512-at-window-512"])
def test_the_block_loop_under_a_window_is_the_masked_dense_form(S, window, block):
    """`blockwise_gq_attention(window=)` against every pair under boolean
    masks; where the window holds every causal pair (S <= window) it is the
    causal block loop TO THE BIT, by the same program."""
    q, k, v = _qkv(S)
    got = L.blockwise_gq_attention(q, k, v, 0.25, block, window=window)
    np.testing.assert_allclose(got, _dense_window(q, k, v, 0.25, window), atol=2e-6)
    if S <= window:
        np.testing.assert_array_equal(got, L.blockwise_gq_attention(q, k, v, 0.25, block))


def test_query_511_sees_key_0_and_query_512_does_not():
    """A window of 512 = itself and the 511 before it: moving key 0's value
    moves the output of queries 0..511 and of no later one."""
    q, k, v = _qkv(600, H=2, Hkv=1, d=8)
    base = L.blockwise_gq_attention(q, k, v, 0.35, 256, window=512)
    moved = L.blockwise_gq_attention(q, k, v.at[:, :, 0].add(10.0), 0.35, 256, window=512)
    changed = np.asarray(jnp.abs(moved - base).max(axis=(0, 1, 3)) > 0)
    assert changed[:512].all() and not changed[512:].any()


def test_a_block_under_a_window_never_slices_the_keys_below_the_band(monkeypatch):
    """The block loop's key blocks start at the band's lower edge: with S = 96,
    a window of 16 and blocks of 16, no block reads more than 31 keys (its own
    16 and the 15 before its first query), where the causal loop reads up to
    96."""
    seen = []
    real = jnp.einsum

    def spy(spec, *ops, **kw):
        if spec == "ngjqd,ngkd->ngjqk":
            seen.append(ops[1].shape[-2])
        return real(spec, *ops, **kw)

    monkeypatch.setattr(jnp, "einsum", spy)
    q, k, v = _qkv(96)
    L.blockwise_gq_attention(q, k, v, 0.25, 16, window=16)
    assert seen == [16] + [31] * 5
    del seen[:]
    L.blockwise_gq_attention(q, k, v, 0.25, 16)
    assert seen == [16, 32, 48, 64, 80, 96]


@pytest.mark.parametrize("heads, kv_heads, window, tiles", [
    (8, 1, 128, (128, 128)), (8, 1, 200, (256, 128)), (6, 1, None, (128, 128)),
    (4, 2, 512, (128, 256))],
    ids=["G8-window128", "G8-window200-tq256", "G6-diagonal", "G2-window>=S"])
def test_band_kernels_in_interpret_mode_are_the_block_loop(heads, kv_heads, window, tiles):
    """`band_attn_fwd` / `band_attn_bwd` (interpret mode) against the block
    loop on bfloat16-rounded operands (the kernels' own cast), output and the
    three gradients: what is left is the probabilities' bfloat16 rounding
    before the value product (1e-2 of the largest entry; a tile skipped or a
    mask edge off by one is off by 1e-1)."""
    from heterofl_tpu.ops import pallas_attention as PA

    S, d = 512 if window == 200 else 256, 128
    ks = jax.random.split(jax.random.key(3), 4)
    q, k, v, probe = (jax.random.normal(kk, (1, n, S, d))
                      for kk, n in zip(ks, (heads, kv_heads, kv_heads, heads)))

    def rounded(x):
        return x.astype(jnp.bfloat16).astype(jnp.float32)

    def fused(q, k, v):
        return jnp.sum(PA.fused_band_attention(q, k, v, 0.3, window, block_q=tiles[0],
                                               block_k=tiles[1], interpret=True) * probe)

    def loop(q, k, v):
        return jnp.sum(L.blockwise_gq_attention(rounded(q * 0.3), rounded(k), rounded(v), 1.0,
                                                64, window=window) * probe)

    got = jax.value_and_grad(fused, argnums=(0, 1, 2))(q, k, v)
    want = jax.value_and_grad(loop, argnums=(0, 1, 2))(q, k, v)
    np.testing.assert_allclose(got[0], want[0], rtol=5e-3)
    for g, w, name in zip(got[1], want[1], "qkv"):
        np.testing.assert_allclose(g, w, atol=1e-2 * float(jnp.abs(w).max()), err_msg=name)


def test_a_window_of_none_gives_the_accepted_kernels_numbers():
    """The band pair without a window against `gq_attn_fwd` / `gq_attn_bwd`
    (both in interpret mode, the same tiles): the same products in the same
    precision, tile by tile in the same order along the keys, so the output
    agrees to float32 rounding and the gradients to the order of their sums."""
    from heterofl_tpu.ops import pallas_attention as PA

    ks = jax.random.split(jax.random.key(5), 4)
    q, k, v, probe = (jax.random.normal(kk, (1, n, 256, 128)) for kk, n in zip(ks, (4, 2, 2, 4)))

    def loss(fn, **kw):
        return jax.value_and_grad(lambda q, k, v: jnp.sum(fn(q, k, v, 0.3, **kw) * probe),
                                  argnums=(0, 1, 2))(q, k, v)

    kw = dict(block_q=128, block_k=128, interpret=True)
    band, gq = loss(PA.fused_band_attention, window=None, **kw), loss(PA.fused_gq_attention, **kw)
    np.testing.assert_allclose(band[0], gq[0], rtol=1e-6)
    for a, b in zip(band[1], gq[1]):
        np.testing.assert_allclose(a, b, atol=1e-5 * float(jnp.abs(b).max()))


def test_the_rule_gives_every_cell_its_pair():
    """`pallas_attention.gq_plan`, the one rule: the LFM2 and Ouro cells keep
    `gq_attn_*` at tiles of 512 (their `dq` is 2 MB and 1 MB), both kinds of
    Laguna layer take the band pair (`dq` would be 34 MB and 25 MB), a client's
    narrow slice and ragged rows take the block loop, and a window that holds
    the whole row is no window."""
    from heterofl_tpu.ops import pallas_attention as PA

    assert PA.gq_plan(2048, 64, 4) == ("gq", 512, 512) == PA.gq_plan(2048, 128, 1)
    assert PA.gq_plan(8192, 128, 8, 512)[0] == PA.gq_plan(8192, 128, 6)[0] == "band"
    assert PA.gq_plan(8192, 128, 8, 512)[1:] == (256, 256)  # at most half the window
    assert PA.gq_plan(8192, 128, 6)[1:] == (512, 512) and PA.gq_plan(8192, 128, 8, 128)[1] == 128
    assert PA.gq_plan(8192, 128, 8) == ("band", 512, 512)  # the Keye shape under the diagonal
    assert PA.gq_plan(8192, 64, 4) == ("gq", 512, 512)    # 64-wide heads stay where they were
    assert PA.gq_plan(2048, 128, 1, 4096) == PA.gq_plan(2048, 128, 1)
    assert PA.gq_plan(2048, 32, 8, 512) is None and PA.gq_plan(2000, 128, 8, 512) is None
    assert PA.gq_plan(2048, 64, 8, 512) is None  # a window on 64-wide heads: the block loop
    for S, tq, tk, window, want in [(8192, 512, 512, 512, (31, 136)), (8192, 256, 256, 512, (93, 528)),
                                    (8192, 512, 512, None, (136, 136)), (64, 256, 256, 16, (1, 1)),
                                    (96, 16, 16, 16, (11, 21))]:
        assert PA.band_extent(S, tq, tk, window) == want, (S, tq, tk, window)
        if S % tq == 0:
            assert PA._band_steps(S, tq, tk, window) == (S // tk if window is None else
                                                         {512: 2, 256: 3, 16: 2}[tk])


# ---------------------------------------------------------------------------
# the two turns
# ---------------------------------------------------------------------------

def test_yarns_table_is_the_references_and_the_published_numbers():
    """`models.laguna.rope_frequencies` against the reference's own
    (`frequencies`, `yarn_range`) at the published numbers: low 5, high 16, the
    factor 0.1 ln 64 + 1, pairs below `low` untouched, pairs from `high` on
    divided by 64, and the sliding layers' table the plain one."""
    from benchmark.reference import laguna as ref
    from heterofl_tpu.models.laguna import rope_frequencies

    rope = C.process_control(_control_cfg())["laguna"]["rope_parameters"]
    full, sliding = rope["full_attention"], rope["sliding_attention"]
    assert ref.yarn_range(full, 64) == (5, 16)
    freqs, factor = rope_frequencies(full, 64)
    want, want_factor = ref.frequencies(full, 64)
    np.testing.assert_allclose(freqs, want, rtol=1e-12)
    assert factor == want_factor == pytest.approx(0.1 * np.log(64) + 1, rel=1e-9)
    plain = 5e5 ** (-2.0 * np.arange(32) / 64)
    np.testing.assert_allclose(freqs[:6], plain[:6], rtol=1e-12)
    np.testing.assert_allclose(freqs[16:], plain[16:] / 64, rtol=1e-12)
    assert (np.diff(freqs) < 0).all()
    freqs, factor = rope_frequencies(sliding, 128)
    np.testing.assert_allclose(freqs, 1e4 ** (-2.0 * np.arange(64) / 128), rtol=1e-12)
    assert factor is None and ref.frequencies(sliding, 128)[1] == 1.0
    with pytest.raises(ValueError, match="Not valid rope_type"):
        rope_frequencies(dict(full, rope_type="linear"), 64)


def _control_cfg():
    cfg = C.default_cfg()
    cfg["control"] = C.parse_control_name("1_10_0.5_iid_fix_a1-b1-c1-d1-e1_bn_1_1")
    cfg["data_name"], cfg["model_name"] = "WikiText2", "laguna"
    return cfg


def test_a_half_rotary_heads_pass_through_dims_do_not_turn():
    """A full layer's query and key reach the attention as [turned half |
    pass-through half]: the second half is the `q.n` / `k.n` product itself,
    whatever the position; the first half is the table's turn times the
    factor; a sliding layer's whole head turns."""
    from heterofl_tpu.models.laguna import gated_gq_attention, rope_frequencies

    cfg, model, params, _, _, _ = _laguna_case()
    rope = cfg["laguna"]["rope_parameters"]
    h = jax.random.normal(jax.random.key(4), (1, 64, 128))
    seen = {}

    def attend(q, k, v, scale):
        seen.update(q=q, k=k)
        return jnp.zeros(q.shape[:-1] + (v.shape[-1],))

    lp = {k[3:]: v for k, v in params.items() if k.startswith("l0.")}
    freqs, factor = rope_frequencies(rope["full_attention"], 16)
    gated_gq_attention(lp, h, heads=6, kv_heads=2, freqs=freqs, factor=factor, scale=1.0,
                       sc=lambda x: x, attend=attend)
    for m, n in (("q", 6), ("k", 2)):
        rest = (h @ lp[f"attn.{m}.n.w"]).reshape(1, 64, n, 16).swapaxes(1, 2)
        np.testing.assert_allclose(seen[m][..., 16:], rest, atol=1e-6)
        raw = (h @ lp[f"attn.{m}.r.w"]).reshape(1, 64, n, 16).swapaxes(1, 2)
        np.testing.assert_allclose(seen[m][:, :, 0, :16], factor * raw[:, :, 0], rtol=1e-5,
                                   atol=1e-6)
        ang = 5 * freqs[0]  # position 5, pair 0 = stored dims 0 and 1
        turned = factor * (raw[:, :, 5, 0] * np.cos(ang) - raw[:, :, 5, 1] * np.sin(ang))
        np.testing.assert_allclose(seen[m][:, :, 5, 0], turned, rtol=1e-4, atol=1e-6)
    lp = {k[3:]: v for k, v in params.items() if k.startswith("l1.")}
    assert "attn.q.n.w" not in lp and lp["attn.q.r.w"].shape == (128, 8 * 32)


def test_rope_interleaved_with_no_table_is_what_it_was():
    """The shared turn without `freqs` / `factor` traces to the jaxpr it traced
    to before it took them (the LFM2, Keye, Ouro and Kanana-2 programs), and a
    table equal to theta's gives its numbers."""
    x = jax.random.normal(jax.random.key(0), (1, 2, 8, 16))
    pos = jnp.arange(8)
    base = L.rope_interleaved(x, L.rope_swap(x), pos, 1e4, axis=2, full=32)
    table = 1e4 ** (-2.0 * np.arange(16) / 32)
    np.testing.assert_allclose(
        L.rope_interleaved(x, L.rope_swap(x), pos, None, axis=2, freqs=table), base, rtol=1e-5,
        atol=1e-6)
    np.testing.assert_allclose(
        L.rope_interleaved(x, L.rope_swap(x), pos, None, axis=2, freqs=table, factor=1.5),
        1.5 * base, rtol=1e-5, atol=1e-6)
    text = str(jax.make_jaxpr(lambda x: L.rope_interleaved(x, x, pos, 1e4, axis=2, full=32))(x))
    assert "repeat" not in text and "1.5" not in text  # no table, no factor: nothing of either


# ---------------------------------------------------------------------------
# slicing
# ---------------------------------------------------------------------------

def test_the_lists_are_read_and_no_period_is_assumed():
    """The layer kinds, head counts and feed-forwards come from the three
    lists: any order builds (two sliding layers first, the dense layer last)
    and matches the reference; lists of the wrong length or an unknown kind
    are refused, as heads that do not divide over the key/value heads are."""
    from benchmark.reference import common, laguna as ref
    from benchmark.tests import tiny_laguna as tiny

    odd = dict(num_hidden_layers=2, layer_types=["sliding_attention", "full_attention"],
               mlp_layer_types=["sparse", "dense"], num_attention_heads_per_layer=[4, 2])
    cfg, model, params, tokens, lm, rm = _laguna_case(**odd)
    assert "l1.mlp.g.w" in params and "l0.moe.router.w" in params and "l0.attn.q.n.w" not in params
    loss = jax.jit(lambda p: model.apply(p, {"label": tokens}, train=True,
                                         label_mask=lm)[0]["loss"])(params)
    want = jax.jit(common.highest(
        lambda p: ref.loss_fn(p, tokens, lm, 1.0, ref.arch_of(rm))))(params)
    np.testing.assert_allclose(float(loss), float(want), rtol=1e-5)
    for bad in (dict(layer_types=["full_attention"] * 4), dict(mlp_layer_types=["moe"] * 5),
                dict(layer_types=["linear_attention"] * 5),
                dict(num_attention_heads_per_layer=[6, 8, 8, 8, 5])):
        with pytest.raises(ValueError, match="layer lists|do not divide"):
            make_model(tiny.program_cfg(**bad))


# ---------------------------------------------------------------------------
# through the engines and the entry point
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("level", ["a", "e"])
def test_laguna_one_whole_local_step_is_the_references(level):
    """A round of one client of a level, its local step through the masked
    engine (global-norm clip, momentum SGD with weight decay, the counted
    average), against the plain reference's round on the same client: every
    leaf within 1e-4 of its largest entry (3e-4 at level e, whose norms run
    over 8 dims: the gradients' own float32 rounding, as in the test above)."""
    from benchmark.reference import common, laguna as ref
    from benchmark.tests import tiny_laguna as tiny

    cfg, data = _round_case()
    cfg = dict(cfg, round_chunk=1)
    rate = C.MODEL_SPLIT_RATE[level]
    user = next(u for u in range(8) if cfg["model_rate"][u] == rate)
    model = make_model(cfg)
    params0 = model.init(jax.random.key(0))
    before = {k: np.asarray(v) for k, v in params0.items()}
    eng = RoundEngine(model, cfg, make_mesh(1, 1))
    out, ms = eng.train_round(params0, jax.random.key(5), 0.1, np.full(8, user), data)
    config = {"model": tiny.reference_model(cfg),
              "optimizer": {"momentum": cfg["momentum"], "weight_decay": cfg["weight_decay"]}}
    client = {"rate": rate, "labels": np.flatnonzero(np.asarray(data[1][user])), "epochs": 1,
              "rows": np.asarray(data[0][user]), "copies": 1}
    want, losses = common.run_round(ref, config, before, [client], 0.1, 0)
    np.testing.assert_allclose(np.asarray(ms["loss_sum"])[0] / np.asarray(ms["n"])[0], losses[0],
                               rtol=1e-5)
    tol = 3e-4 if level == "e" else 1e-4
    for k, v in want.items():
        np.testing.assert_allclose(np.asarray(out[k]), v, atol=tol * np.abs(v).max() + 1e-9,
                                   err_msg=k)
        assert (np.asarray(out[k]) != before[k]).any(), k


def test_nothing_in_the_engines_names_the_family():
    """`parallel/` and `fed/` take the family through `ModelDef` alone: no file
    of either names it (the issue's "nothing should change")."""
    import pathlib

    import heterofl_tpu

    root = pathlib.Path(heterofl_tpu.__file__).parent
    hits = [str(p) for d in ("parallel", "fed") for p in (root / d).glob("*.py")
            if "laguna" in p.read_text().lower()]
    assert not hits


@pytest.mark.parametrize("S, window, block, fused, below_one", [
    (64, 16, 256, False, False), (96, 16, 16, False, True), (8192, 512, 256, False, True),
    (8192, 512, 256, True, True)],
    ids=["one-block", "S>window+block", "cell-block-loop", "cell-kernels"])
def test_swa_tiles_is_below_one_once_the_band_leaves_tiles_under_it(S, window, block, fused,
                                                                    below_one, monkeypatch):
    """`sliding_attention_tiles`, what the model's `swa_tiles` counts, from the
    grid's extents: 1 while one block holds the row, below 1 when S > window +
    block; at the cell's shapes the kernels (tiles of 256) and the block loop
    (blocks of 256) both visit 93 of 528 key tiles a head's row (3 a query tile
    but the first two)."""
    if fused:
        monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    took, visited, causal = L.sliding_attention_tiles(S, 128, 8, window, block)
    assert took is fused and (visited < causal) is below_one
    if S == 8192:
        assert (visited, causal) == (93, 528)


def test_laguna_model_takes_the_band_kernels_where_a_tpu_gives_them_tiles(monkeypatch):
    """Steered to "tpu" at shapes the kernels tile (heads of 128, rows of 256,
    a window of 128), both kinds of layer go through `fused_band_attention`
    (here in interpret mode), the sliding ones with the window and the full
    ones without, `swa_fused` reads 1 and loss and gradients stay the block
    loop's up to the kernels' bfloat16 operands."""
    from heterofl_tpu.ops import pallas_attention as PA

    tiled = dict(bptt=256, head_dim=128, sliding_window=128, hidden_size=64,
                 num_hidden_layers=2, layer_types=["full_attention", "sliding_attention"],
                 mlp_layer_types=["dense", "sparse"],
                 num_attention_heads_per_layer=[3, 4], num_key_value_heads=1,
                 intermediate_size=64)
    cfg, model, params, tokens, lm, _ = _laguna_case(**tiled)

    def run():
        def loss(p):
            out, _ = model.apply(p, {"label": tokens}, train=True, label_mask=lm)
            return out["loss"], out["counters"]
        return jax.jit(jax.value_and_grad(loss, has_aux=True))(params)

    (base, base_c), base_g = run()
    assert base_c["swa_fused"].tolist() == [0.0, 2.0]  # 1 sliding layer x 2 rows
    calls = []
    real = PA.fused_band_attention
    # the rule asks for 64 MB of residency before it leaves the gq pair: lower
    # the bar so that the full layer's tiny group goes to the band pair too
    monkeypatch.setattr(PA, "GQ_RESIDENT_BYTES", 0)
    monkeypatch.setattr(PA, "fused_band_attention", lambda q, k, v, scale, window, **kw: (
        calls.append((q.shape[1], window, kw["block_q"], kw["block_k"])),
        real(q, k, v, scale, window, interpret=True, **kw))[1])
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    (loss, counters), grads = run()
    assert set(calls) == {(3, None, 256, 256), (4, 128, 128, 128)}  # half the window
    assert counters["swa_fused"].tolist() == [2.0, 2.0]
    assert counters["swa_tiles"].tolist() == [6.0, 6.0]  # 2 rows x 3 tiles, none under the band
    assert counters["swa_pairs"][0] < counters["swa_pairs"][1]
    np.testing.assert_allclose(float(loss), float(base), rtol=2e-3)
    for k, g in base_g.items():
        np.testing.assert_allclose(grads[k], g, atol=3e-2 * float(jnp.abs(g).max()) + 1e-9,
                                   err_msg=k)


def test_the_cut_configuration_has_the_parameters_it_states():
    """490,297,344 from `jax.eval_shape` of the model's own `init` at the
    configuration's sizes: layer 0 79,794,176, three sliding layers of
    91,885,568, the full layer with experts 83,464,192, the final norm and the
    untied vocabulary twice."""
    from benchmark.tests import test_laguna

    test_laguna.test_the_stated_parameter_count_is_the_programs()


# ---------------------------------------------------------------------------
# the scope ISSUE 42 added (obs.trace.WINDOW_SCOPES)
# ---------------------------------------------------------------------------

def test_the_window_carries_its_name():
    """`swa` reaches the round program's `op_name`s under `step/model`, forward
    and backward, and holds the sliding layers' score / softmax / value part
    alone: the full layers' stays under `attn`, the projections and the gate
    under `gqa`, both turns under `rope`, the experts under `moe/*`; no
    instruction is under both `swa` and `attn`."""
    from heterofl_tpu.obs import trace

    assert trace.WINDOW_SCOPES == ("swa",) and trace.SCOPE_VERSION >= 7
    cfg, data = _round_case()
    cfg = dict(cfg, round_chunk=1)
    model = make_model(cfg)
    eng = RoundEngine(model, cfg, make_mesh(1, 1))
    users = np.arange(8, dtype=np.int32)
    fix = (eng.fix_rates,) if eng.fix_rates is not None else ()
    args = (model.init(jax.random.key(0)), jax.random.key(0), np.float32(0.1), users, users,
            *data, *fix)
    names = ["/" + n for n in re.findall(
        r'op_name="([^"]+)"', eng._build_train().lower(*args).compile().as_text())]
    for s in ("swa", "attn", "gqa", "moe/experts", "moe/shared", "moe/router"):
        mine = [n for n in names if f"/{s}/" in n and "step/model" in n]
        assert any("/jvp(step/model)/" in n for n in mine), s
        assert any("transpose(" in n for n in mine), s
    assert any("/rope/" in n for n in names)
    assert not [n for n in names if "/swa/" in n and "/attn/" in n]
    assert not [n for n in names if "/swa/" in n and "/gqa/" in n]
    assert any(re.search(r"/gqa/linear/dot_general", n) for n in names)


# ---------------------------------------------------------------------------
# what a layer's checkpoint keeps by name (ISSUE 44)
# ---------------------------------------------------------------------------

#: shapes the band kernels tile (heads of 128, rows of 256, a window of 128)
TILED = dict(bptt=256, head_dim=128, sliding_window=128, hidden_size=64, num_key_value_heads=1,
             intermediate_size=64)


def _layers(*kinds):
    """``arch`` of a model whose layers are of these kinds: a full layer on 3
    query heads and a dense SwiGLU, a sliding one on 4 and experts."""
    full = [k == "full_attention" for k in kinds]
    return dict(TILED, num_hidden_layers=len(kinds), layer_types=list(kinds),
                mlp_layer_types=["dense" if f else "sparse" for f in full],
                num_attention_heads_per_layer=[3 if f else 4 for f in full])


#: a lone full layer, a scanned run of sliding layers, and both at once
LAYOUTS = {"lone-full": _layers("full_attention"),
           "scanned-sliding": _layers("sliding_attention", "sliding_attention"),
           "both": _layers("full_attention", "sliding_attention", "sliding_attention")}


def _bare_checkpoints(monkeypatch):
    """The model as it was before ISSUE 44: every layer under a bare
    ``jax.checkpoint`` that keeps its input alone."""
    from heterofl_tpu.models import laguna

    monkeypatch.setattr(laguna, "kept", lambda: None)


def _reports_a_tpu(monkeypatch, interpret=False):
    """jax reporting a TPU, and the rule sending a tiny group's full layer to
    the band pair too (it asks for 8 MiB of resident ``dq`` before it leaves
    the ``gq`` pair); ``interpret``: the kernels run, in interpret mode."""
    from heterofl_tpu.ops import pallas_attention as PA

    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    monkeypatch.setattr(PA, "GQ_RESIDENT_BYTES", 0)
    if interpret:
        monkeypatch.setattr(PA, "fused_band_attention",
                            functools.partial(PA.fused_band_attention, interpret=True))


def _loss_counters_and_grads(model, params, tokens, lm):
    def loss(p):
        out, _ = model.apply(p, {"label": tokens}, train=True, label_mask=lm)
        return out["loss"], out["counters"]

    (value, counters), grads = jax.value_and_grad(loss, has_aux=True)(params)
    return value, counters, grads


@pytest.mark.parametrize("path", ["block loop", "kernels"])
@pytest.mark.parametrize("layout", list(LAYOUTS))
def test_laguna_keeping_the_named_values_changes_no_number(layout, path, monkeypatch):
    """Loss and every leaf's gradient under the policy against the bare
    checkpoint's: a kept value is the value the second forward would have
    computed from the same inputs.  On the CPU's block loop, which names
    nothing, equal to the bit; on the interpreted kernels to float32 round-off
    (another compiled program rounds them in another order)."""
    _, model, params, tokens, lm, _ = _laguna_case(**LAYOUTS[layout])
    if path == "kernels":
        _reports_a_tpu(monkeypatch, interpret=True)
    got, _, got_grads = jax.jit(lambda p: _loss_counters_and_grads(model, p, tokens, lm))(params)
    _bare_checkpoints(monkeypatch)
    want, _, want_grads = jax.jit(lambda p: _loss_counters_and_grads(model, p, tokens, lm))(params)
    if path == "block loop":
        assert float(got) == float(want)
    else:
        assert float(got) == pytest.approx(float(want), rel=1e-6)
    for name, w in want_grads.items():
        if path == "block loop":
            np.testing.assert_array_equal(got_grads[name], w, err_msg=name)
        else:
            np.testing.assert_allclose(got_grads[name], w, rtol=0, err_msg=name,
                                       atol=1e-5 * float(jnp.abs(w).max()) + 1e-12)


def _head_products(jaxpr):
    """The products of the form `linear_heads` writes (the weight ``[K, H,
    d]`` against ``[N, S, K]`` over ``K``: a layer's q / k / v projections,
    forward or computed again; no cotangent's product has the form)."""
    from heterofl_tpu.staticcheck.jaxpr_walk import iter_eqns

    found = 0
    for e in iter_eqns(jaxpr):
        if e.primitive.name == "dot_general":
            (lc, rc), (lb, _) = e.params["dimension_numbers"]
            found += (e.invars[0].aval.ndim, e.invars[1].aval.ndim, tuple(lc), tuple(rc),
                      tuple(lb)) == (3, 3, (0,), (2,), ())
    return found


def _kernels_by_scan_body(jaxpr):
    """(the Pallas kernels a program calls outside any `scan`, those each
    `scan` body calls, in the program's order; a body that calls none is left
    out)."""
    from heterofl_tpu.staticcheck.jaxpr_walk import _sub_jaxprs

    bodies = []

    def walk(jaxpr, mine):
        for e in jaxpr.eqns:
            if e.primitive.name == "pallas_call":
                mine.append(e.params["name"])
            for sub in _sub_jaxprs(e.params):
                if e.primitive.name == "scan":
                    body = walk(sub, [])
                    if body:
                        bodies.append(sorted(body))
                else:
                    walk(sub, mine)
        return mine

    return sorted(walk(jaxpr.jaxpr, [])), bodies


FWD, BWD = "band_attn_fwd", "band_attn_bwd"


@pytest.mark.parametrize("layout, policy, outside, bodies, products", [
    # a lone full layer keeps the kernels' results and operands: one forward
    # kernel, and its five q / k / v products (two column-split) once
    ("lone-full", "kept", [BWD, FWD], [], 5),
    ("lone-full", "bare", [BWD, FWD, FWD], [], 10),
    ("scanned-sliding", "kept", [], [[FWD], [BWD]], 3),
    ("scanned-sliding", "bare", [], [[FWD], [BWD, FWD]], 6),
    ("both", "kept", [BWD, FWD], [[FWD], [BWD]], 8),
    ("both", "bare", [BWD, FWD, FWD], [[FWD], [BWD, FWD]], 16)])
def test_gradient_on_the_band_kernels_runs_no_second_forward_where_the_layer_keeps_its_results(
        layout, policy, outside, bodies, products, monkeypatch):
    """A model at shapes the band kernels tile, jax reporting a TPU: the
    gradient's program of a layer that keeps ``BAND_OUT`` / ``BAND_LSE`` calls
    ``band_attn_bwd`` and no second ``band_attn_fwd``, and with ``BAND_OPS``
    kept none of the layer's q / k / v products a second time (a full layer's
    five, two of them column-split; a sliding layer's three); under a bare
    checkpoint (before ISSUE 44) both are there.  A scanned run's calls lie in
    its two scan bodies, a lone layer's outside any."""
    if policy == "bare":
        _bare_checkpoints(monkeypatch)
    _reports_a_tpu(monkeypatch)
    _, model, params, tokens, lm, _ = _laguna_case(**LAYOUTS[layout])
    jaxpr = jax.make_jaxpr(jax.grad(lambda p: model.apply(
        p, {"label": tokens}, train=True, label_mask=lm)[0]["loss"]))(params)
    assert _kernels_by_scan_body(jaxpr) == (outside, bodies)
    assert _head_products(jaxpr) == products


@pytest.mark.parametrize("path", ["block loop", "kernels"])
@pytest.mark.parametrize("policy", ["kept", "bare"])
def test_laguna_named_values_are_the_layers_saved_residuals(policy, path, monkeypatch, capsys):
    """What a layer hands its backward: its input ``[N, S, D]`` and, on the
    kernels under the policy, exactly what :func:`models.laguna.kept` names
    for its kind: ``o`` ``[N, H, d, S]`` float32, the log-sum-exp and the three
    bfloat16 operands (positions minor); a scanned run's each as ONE residual
    ``[L, ...]``.  On the block loop, or under a bare checkpoint, the input
    alone."""
    from jax.ad_checkpoint import print_saved_residuals

    if policy == "bare":
        _bare_checkpoints(monkeypatch)
    if path == "kernels":
        _reports_a_tpu(monkeypatch)
    _, model, params, tokens, lm, _ = _laguna_case(**LAYOUTS["both"])
    print_saved_residuals(
        lambda p: model.apply(p, {"label": tokens}, train=True, label_mask=lm)[0]["loss"], params)
    lines = capsys.readouterr().out.splitlines()
    of_the_scan = sorted(line.split()[0] for line in lines if "output of scan" in line)
    # the lone layer's: what carries a name, and `o` (a primal output too,
    # which jax hands on through a `reduce_precision`)
    of_the_lone = sorted(line.split()[0] for line in lines
                         if " named " in line or "output of reduce_precision" in line)
    named = policy == "kept" and path == "kernels"
    assert of_the_scan == sorted(["f32[2,2,256,64]"] + named * (
        ["f32[2,2,4,128,256]", "f32[2,2,1,2,1,512]", "bf16[2,2,4,128,256]"]
        + ["bf16[2,2,1,128,256]"] * 2))
    assert of_the_lone == sorted(named * (
        ["f32[2,3,128,256]", "f32[2,1,1,1,768]", "bf16[2,3,128,256]"] + ["bf16[2,1,128,256]"] * 2))


@pytest.mark.parametrize("reports, policy, want", [
    ("cpu", "kept", [0.0, 0.0]), ("tpu", "kept", [3.0, 3.0]), ("tpu", "bare", [0.0, 3.0])])
def test_band_kept_counts_the_layers_that_kept_their_kernels_results(reports, policy, want,
                                                                     monkeypatch):
    """`band_kept` = (layers whose checkpoint kept their kernels' results,
    layers whose attention took the band pair): 0 of 0 on a CPU, where the
    block loop runs and a name is the identity; 3 of 3 with tiles and the
    policy, 0 of 3 under a bare checkpoint; `obs.split_probes` divides."""
    from heterofl_tpu.obs import split_probes

    if policy == "bare":
        _bare_checkpoints(monkeypatch)
    if reports == "tpu":
        _reports_a_tpu(monkeypatch, interpret=True)
    _, model, params, tokens, lm, _ = _laguna_case(**LAYOUTS["both"])
    assert model.meta["counters"]["band_kept"] == ((2,), "ratio")
    out, _ = model.apply(params, {"label": tokens}, train=True, label_mask=lm)
    assert out["counters"]["band_kept"].tolist() == want
    _, rounds = split_probes({"obs_band_kept": np.asarray(out["counters"]["band_kept"])}, 1,
                             counters=model.meta["counters"])
    assert rounds[0]["band_kept"] == (want[0] / want[1] if want[1] else 0.0)


@pytest.mark.parametrize("family", ["lfm2", "ouro"])
def test_the_band_pairs_names_are_inert_in_the_other_families(family, monkeypatch):
    """`_gq_named` took the band pair's names as an argument (ISSUE 44); the
    families on the ``gq`` pair never pass it: their gradient's program on the
    kernels (interpret mode, jax reporting a TPU) lowers to the same text with
    the helper as it is and as it was."""
    from jax.ad_checkpoint import checkpoint_name

    from heterofl_tpu.ops import pallas_attention as PA

    if family == "lfm2":
        from benchmark.tests import tiny_lfm2 as tiny

        cfg = tiny.program_cfg(head_dim=64)
    else:
        from benchmark.tests import tiny_ouro as tiny

        cfg = tiny.program_cfg(bptt=128, head_dim=128, num_attention_heads=2,
                               num_key_value_heads=2, hidden_size=64, intermediate_size=64,
                               num_hidden_layers=3, total_ut_steps=2)
    model = make_model(cfg)
    params = model.init(jax.random.key(1))
    tokens = jnp.zeros((2, 128), jnp.int32)
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    monkeypatch.setattr(PA, "fused_gq_attention",
                        functools.partial(PA.fused_gq_attention, interpret=True))

    def lowered():
        return jax.jit(jax.grad(lambda p: model.apply(
            p, {"label": tokens}, train=True)[0]["loss"])).lower(params).as_text()

    now = lowered()

    def as_it_was(ops, o, lse):
        o, lse = checkpoint_name(o, PA.GQ_OUT), checkpoint_name(lse, PA.GQ_LSE)
        return o, (checkpoint_name(ops, PA.GQ_OPS), o, lse)

    monkeypatch.setattr(PA, "_gq_named", as_it_was)
    assert lowered() == now
