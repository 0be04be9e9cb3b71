"""Keye (``models/keye.py``, ISSUE 35: a learned sparse-attention indexer over
grouped-query attention, softmax-routed experts, untied head) at a tiny size
on the CPU, rows LONGER than ``topk`` so that the selection binds: against the
benchmark's plain reference, the selection and the attention over it, its
slicing rules, and through the engines and the entry point.  A file of its
own so that the test runner's per-file workers share the family's compiles
evenly."""

import functools
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from decoder_cases import case, masked_loss_and_grads, round_case
from heterofl_tpu import config as C
from heterofl_tpu.models import make_model
from heterofl_tpu.models.spec import mask_params
from heterofl_tpu.ops import layers as L
from heterofl_tpu.parallel import RoundEngine, make_mesh

_keye_case = functools.partial(case, "keye")
_masked_loss_and_grads = masked_loss_and_grads
_round_case = functools.partial(round_case, "keye")


# ---------------------------------------------------------------------------
# the model against the benchmark's plain reference
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("level", ["a", "c", "e"])
def test_keye_one_whole_local_step_is_the_references(level):
    """A round of one client of a level, its one local step through the
    masked engine (gradient, global-norm clip, momentum SGD with weight
    decay, the counted average), against the plain reference's round on the
    same client: every leaf within 1e-5 of its largest entry (float32, lr
    0.1; a step that skipped the clip or decayed the wrong entries is off by
    1e-3 or more), and the indexer's leaves moved by weight decay alone."""
    from benchmark.reference import common, keye as ref
    from benchmark.tests import tiny_keye as tiny

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
    for k, v in want.items():
        np.testing.assert_allclose(np.asarray(out[k]), v, atol=1e-5 * np.abs(v).max() + 1e-9,
                                   err_msg=k)
    index = ref.index({k: v.shape for k, v in before.items()}, config["model"], rate)
    held = common.take(before, index)["l0.idx.q.w"]
    np.testing.assert_allclose(common.take({k: np.asarray(v) for k, v in out.items()},
                                           index)["l0.idx.q.w"],
                               held * (1 - 0.1 * cfg["weight_decay"]), rtol=1e-6)


def test_keye_the_two_shares_add_up():
    """The guide's share test: the routed parts that the shares of a 2-way
    expert-parallel layer compute add up to the UNCUT reference's expert
    layer, with what every share computes alike (the indexer, the attention,
    the router) counted once: for the reference's whole layer ``x -> x1 + y``
    (``x1`` the state after the attention), ``y = sum over shares of
    moe_experts(share)``."""
    from benchmark.reference import keye as ref
    from benchmark.tests import tiny_keye as tiny

    cfg, model, _, tokens, _, _ = _keye_case(expert_share=[0, 1])
    arch, rm = cfg["keye"], tiny.reference_model(cfg)
    whole = model.init(jax.random.key(3))
    x = jax.random.normal(jax.random.key(4), tokens.shape + (arch["hidden_size"],))
    a = ref.arch_of(rm)
    lp = ref._layer_leaves(whole, 1, dict(a)["held"])
    x1 = x + ref.attention_mixer(lp, ref._rms(x, lp["norm1.g"], 1e-6), 1.0, dict(a))
    y_ref = (ref.layer(lp, x, 1.0, a) - x1).reshape(tokens.size, -1)
    hf = ref._rms(x1, lp["norm2.g"], 1e-6).reshape(tokens.size, -1)
    sel, w = L.moe_route(hf, whole["l1.moe.router.w"], None, arch["num_experts_per_tok"], 1.0,
                         softmax=True)
    np.testing.assert_allclose(np.asarray(w).sum(axis=1), 1.0, rtol=1e-6)  # renormalised
    parts = [L.moe_experts(hf, sel, w, [jnp.stack([whole[f"l1.moe.e{j}.{m}.w"]
                                                   for j in range(4 * i, 4 * i + 4)])
                                        for m in "gud"], 4 * i, lambda v: v, tile=8)
             for i in range(2)]
    np.testing.assert_allclose(sum(y for y, _ in parts), y_ref, rtol=1e-4, atol=1e-5)
    assert sum(float(c["assign"][1]) for _, c in parts) == sel.size  # every pair once
    # and through the model: a share's logits differ from the whole model's
    # by what the absent experts add
    share = make_model(dict(cfg, keye=dict(arch, expert_share=[1, 2])))
    assert share.meta["held_experts"] == [4, 5, 6, 7]
    sub = {k: whole[k] for k in share.meta["shapes"]}
    out_s, _ = share.apply(sub, {"label": tokens}, train=False)
    out_w, _ = model.apply(whole, {"label": tokens}, train=False)
    assert np.abs(np.asarray(out_s["score"]) - np.asarray(out_w["score"])).max() > 1e-5


# ---------------------------------------------------------------------------
# the selection, and the attention over it
# ---------------------------------------------------------------------------

def _indexer_inputs(seed, n=2, s=64, hi=4, di=8, ties=False):
    kq, kk, kw = jax.random.split(jax.random.key(seed), 3)
    qi, ki = jax.random.normal(kq, (n, hi, s, di)), jax.random.normal(kk, (n, s, di))
    wi = jax.random.normal(kw, (n, hi, s))
    if ties:  # whole numbers: many equal scores, among them at the k-th place
        qi, ki, wi = jnp.round(qi), jnp.round(ki), jnp.round(wi)
    return qi, ki, wi


def _selected_sets(qi, ki, wi, topk):
    """[N, S, S] 0/1 by `lax.top_k` on the scores as the issue defines them
    (and every causal key where a query has no more than ``topk``)."""
    s = ki.shape[1]
    score = jnp.einsum("nhqk,nhq->nqk", jax.nn.relu(jnp.einsum(
        "nhqd,nkd->nhqk", qi, ki, precision="highest")), wi, precision="highest")
    causal = np.tril(np.ones((s, s), bool))
    _, idx = jax.lax.top_k(jnp.where(causal, score, -jnp.inf), topk)
    chosen = np.zeros(score.shape, bool)
    np.put_along_axis(chosen, np.asarray(idx), True, axis=-1)
    return chosen & causal


@pytest.mark.parametrize("ties", [False, True], ids=["distinct", "ties"])
@pytest.mark.parametrize("block", [16, 24, 64])
def test_select_keys_is_lax_top_k_under_the_causal_mask(block, ties):
    """The program's selection (counting passes, a mask) is the set
    `lax.top_k` returns, equal scores to the lower position, block by block
    (24: a ragged last block and one that straddles ``topk``; 64: one block);
    the blocks that end at or before ``topk`` carry no mask; the counts are
    the sets' sizes."""
    qi, ki, wi = _indexer_inputs(7, ties=ties)
    want = _selected_sets(qi, ki, wi, 16)
    select, pairs = jax.jit(lambda *a: L.select_keys(*a, 16, block))(qi, ki, wi)
    got = np.broadcast_to(np.tril(np.ones((64, 64), bool)), want.shape).copy()
    for i, m in enumerate(select):
        start, end = i * block, min((i + 1) * block, 64)
        assert (m is None) == (end <= 16)
        if m is not None:
            assert m.shape == (2, end - start, end) and m.dtype == bool
            got[:, start:end, :end] &= np.asarray(m)
    np.testing.assert_array_equal(got, want)
    assert want.sum(axis=-1).tolist() == [[min(t + 1, 16) for t in range(64)]] * 2
    assert [float(p) for p in pairs] == [want.sum(), 2 * 64 * 65 // 2]


@pytest.mark.parametrize("k", [1, 5, 16, 50])
def test_top_k_mask_breaks_ties_as_lax_top_k(k):
    """`top_k_mask` alone, on rows with many equal values, -inf entries and
    both zeros: the set of `lax.top_k`'s indices."""
    x = jnp.round(jax.random.normal(jax.random.key(k), (3, 7, 50)) * 3) / 3
    hole = jax.random.uniform(jax.random.key(k + 9), x.shape) < 0.3
    x = jnp.where(hole & (jnp.arange(50) >= 10), -jnp.inf, x)
    x = x.at[0, 0, :5].set(-0.0).at[0, 0, 5:9].set(0.0)
    want = np.zeros(x.shape, bool)
    np.put_along_axis(want, np.asarray(jax.lax.top_k(x, k)[1]), True, axis=-1)
    np.testing.assert_array_equal(jax.jit(L.top_k_mask, static_argnums=1)(x, k), want)


def test_selected_attention_reads_the_selected_keys_only():
    """`selected_gq_attention` against attention written query by query over
    the GATHERED keys of each query's set: a softmax over every causal key,
    or over another set, is off by 1e-2 or more; and a key outside a query's
    set does not reach that query's output at all (bit-equal when its value
    changes), while one inside does."""
    kq, kk, kv = jax.random.split(jax.random.key(14), 3)
    q = jax.random.normal(kq, (2, 4, 64, 6))
    k, v = jax.random.normal(kk, (2, 2, 64, 6)), jax.random.normal(kv, (2, 2, 64, 6))
    qi, ki, wi = _indexer_inputs(8)
    sets = _selected_sets(qi, ki, wi, 16)
    select, _ = L.select_keys(qi, ki, wi, 16, 16)
    got = np.asarray(L.selected_gq_attention(q, k, v, 0.3, select, 16))
    want, (q_, k_, v_) = np.zeros_like(got), (np.asarray(t, np.float64) for t in (q, k, v))
    for n in range(2):
        for h in range(4):
            for t in range(64):
                keys = np.flatnonzero(sets[n, t])
                p = np.exp(k_[n, h // 2, keys] @ q_[n, h, t] * 0.3)
                want[n, h, t] = p / p.sum() @ v_[n, h // 2, keys]
    np.testing.assert_allclose(got, want, rtol=1e-5, atol=1e-6)
    dense = np.asarray(L.causal_gq_attention(q, k, v, 0.3, block=16))
    assert np.abs(dense[:, :, 16:] - got[:, :, 16:]).max() > 1e-2  # the selection binds
    np.testing.assert_array_equal(dense[:, :, :16], got[:, :, :16])  # and not before topk
    t = 40
    out_key = int(np.flatnonzero(~sets[0, t, :t])[0])
    in_key = int(np.flatnonzero(sets[0, t])[0])
    moved = lambda s: np.asarray(L.selected_gq_attention(  # noqa: E731
        q, k, v.at[0, :, s].add(1.0), 0.3, select, 16))[0, :, t]
    np.testing.assert_array_equal(moved(out_key), got[0, :, t])
    assert np.abs(moved(in_key) - got[0, :, t]).max() > 1e-3


def test_a_row_no_longer_than_topk_is_causal_gq_attention_bit_for_bit():
    """Rows of ``topk`` positions: `select_keys` has no mask to give,
    `selected_gq_attention` is the block loop of `causal_gq_attention` to the
    bit, and the model -- which then takes `causal_gq_attention` itself and
    runs no indexer -- gives the loss and the gradients of the same model
    with a ``topk`` no row reaches, bit for bit; its counters say every causal
    pair is selected."""
    kq, kk, kv = jax.random.split(jax.random.key(15), 3)
    q = jax.random.normal(kq, (2, 4, 16, 6))
    k, v = jax.random.normal(kk, (2, 2, 16, 6)), jax.random.normal(kv, (2, 2, 16, 6))
    qi, ki, wi = _indexer_inputs(9, s=16)
    select, pairs = L.select_keys(qi, ki, wi, 16, 8)
    assert select == [None, None] and [float(p) for p in pairs] == [2 * 136, 2 * 136]
    np.testing.assert_array_equal(L.selected_gq_attention(q, k, v, 0.3, select, 8),
                                  L.causal_gq_attention(q, k, v, 0.3, block=8))
    cfg, model, params, tokens, lm, _ = _keye_case(bptt=16)
    far = make_model(dict(cfg, keye=dict(cfg["keye"], index_topk=10 ** 6)))
    a, b = (jax.value_and_grad(lambda p, m=m: m.apply(
        p, {"label": tokens}, train=True, label_mask=lm)[0]["loss"])(params) for m in (model, far))
    assert float(a[0]) == float(b[0])
    for name in a[1]:
        np.testing.assert_array_equal(a[1][name], b[1][name], err_msg=name)
    out, _ = model.apply(params, {"label": tokens}, train=False)
    assert [float(c) for c in out["counters"]["sparse_kept_share"]] == [2 * 2 * 136.0] * 2


# ---------------------------------------------------------------------------
# what a layer keeps for its backward (ISSUE 39): the frozen indexer's choice
# by name, so the backward runs no second indexer and no second top-k
# ---------------------------------------------------------------------------

def _bare_checkpoint(monkeypatch):
    """The model as it was before ISSUE 39: each layer under a bare
    ``jax.checkpoint`` that keeps its input alone."""
    from heterofl_tpu.models import keye

    monkeypatch.setattr(keye, "kept", lambda: None)


def _selection_ops(text):
    """(indexer score products, `top_k_mask` value loops, its position loops)
    in a lowered program's text at the tiny size: a block's score is the one
    "highest" product whose result is ``[2 rows, 4 indexer heads, 16 queries,
    keys]``; a value loop carries the k-th value ``[2, 16, 1]`` uint32, a
    position loop the ties' mask."""
    return (len(re.findall(r"dot_general.*HIGHEST, HIGHEST.*-> tensor<2x4x16x\d+xf32>", text)),
            len(re.findall(r"stablehlo\.while.*tensor<2x16x1xui32>$", text, re.M)),
            len(re.findall(r"stablehlo\.while.*tensor<2x16x\d+xi1>.*tensor<2x16x1xi32>$", text,
                           re.M)))


@pytest.mark.parametrize("rate", [1.0, 0.25])
def test_keye_what_the_layer_keeps_changes_no_bit(rate, monkeypatch):
    """Loss and every leaf's gradient of the masked model at a level, rows
    longer than ``topk``: bitwise those of the same model whose layers keep
    only their input (the second pass could only repeat the choice)."""
    _, model, params, tokens, lm, _ = _keye_case()
    got, got_grads = jax.jit(lambda p: _masked_loss_and_grads(model, p, tokens, lm, rate))(params)
    _bare_checkpoint(monkeypatch)
    want, want_grads = jax.jit(lambda p: _masked_loss_and_grads(model, p, tokens, lm, rate))(params)
    assert float(got) == float(want)
    for name, w in want_grads.items():
        np.testing.assert_array_equal(got_grads[name], w, err_msg=name)


@pytest.mark.parametrize("rate", [1.0, 0.25])
def test_keye_backward_runs_no_second_indexer_and_no_second_top_k(rate, monkeypatch):
    """The lowered gradient program holds ONE score product and one pair of
    `top_k_mask` loops a selecting block (three of four blocks a row; the
    layers are one scan), as the forward-only program does; with the policy
    taken off it holds two of each, the layer's recomputation's beside the
    forward's."""
    _, model, params, tokens, lm, _ = _keye_case()

    def lowered(fn):
        return jax.jit(fn).lower(params).as_text()

    def forward(p):
        pm = mask_params(p, model.specs, model.groups, rate)
        return model.apply(pm, {"label": tokens}, train=True, width_rate=rate, scaler_rate=rate,
                           label_mask=lm)[0]["loss"]

    def gradient():  # a function of its own a call: jit's trace cache goes by identity
        return lowered(lambda p: _masked_loss_and_grads(model, p, tokens, lm, rate))

    assert _selection_ops(lowered(forward)) == (3, 3, 3)
    assert _selection_ops(gradient()) == (3, 3, 3)
    _bare_checkpoint(monkeypatch)
    assert _selection_ops(gradient()) == (6, 6, 6)


@pytest.mark.parametrize("policy", ["kept", "bare"])
def test_keye_named_blocks_are_the_layers_saved_residuals(policy, monkeypatch, capsys):
    """What the scan over the layers hands the backward: the layer's input
    ``[L, N, S, D]``, the only float activation of the hidden size, and, under
    the policy, the three selecting blocks' 0/1 choice ``[L, N, 16, keys]`` as
    booleans; without it no boolean at all."""
    from jax.ad_checkpoint import print_saved_residuals

    if policy == "bare":
        _bare_checkpoint(monkeypatch)
    _, model, params, tokens, lm, _ = _keye_case()
    print_saved_residuals(
        lambda p: model.apply(p, {"label": tokens}, train=True, label_mask=lm)[0]["loss"], params)
    from_scan = [line.split()[0] for line in capsys.readouterr().out.splitlines()
                 if "output of scan" in line]
    blocks = [f"bool[2,2,16,{keys}]" for keys in (32, 48, 64)]
    assert sorted(from_scan) == sorted(["f32[2,2,64,64]"] + (blocks if policy == "kept" else []))


@pytest.mark.parametrize("bptt, blocks", [(64, 3), (48, 2), (16, 0)])
def test_keye_sparse_saved_counts_the_selecting_blocks(bptt, blocks):
    """`sparse_saved` = (selecting query blocks whose choice the layer named
    for its backward, selecting query blocks), 2 layers x 2 rows x the blocks
    that end after ``topk`` 16; a row no longer than ``topk`` reads 0 of 0,
    which `obs.split_probes` finishes as 0.0."""
    from heterofl_tpu.obs import split_probes

    _, model, params, tokens, _, _ = _keye_case(bptt=bptt)
    out, _ = model.apply(params, {"label": tokens}, train=True)
    saved = out["counters"]["sparse_saved"]
    assert [float(c) for c in saved] == [2.0 * 2 * blocks] * 2
    assert model.meta["counters"]["sparse_saved"] == ((2,), "ratio")
    _, rounds = split_probes({"obs_sparse_saved": np.asarray(saved)}, 1,
                             counters=model.meta["counters"])
    assert rounds[0]["sparse_saved"] == (1.0 if blocks else 0.0)


# ---------------------------------------------------------------------------
# what the block loop and the router were before: their callers are left alone
# ---------------------------------------------------------------------------

def _parent_causal_blocks(scores, values, qs, ks, v, scale, block):
    """`ops.layers._causal_blocks` as the parent commit had it, word for word."""
    S = v.shape[-2]
    outs = []
    for start in range(0, S, block):
        end = min(start + block, S)

        def one(qs_b, ks_b, v_b, start=start, end=end):
            s = scores(*qs_b, *ks_b).astype(jnp.float32) * scale
            keep = jnp.arange(start, end)[:, None] >= jnp.arange(end)[None, :]
            s = jnp.where(keep, s, -jnp.inf)
            return values(jax.nn.softmax(s, axis=-1), v_b)

        outs.append(jax.checkpoint(one)(tuple(q[..., start:end, :] for q in qs),
                                        tuple(k[..., :end, :] for k in ks), v[..., :end, :]))
    return outs[0] if len(outs) == 1 else jnp.concatenate(outs, axis=-2)


def _parent_moe_route(h, w_router, bias, top_k, scaling, sum_eps=0.0):
    """`ops.layers.moe_route` as the parent commit had it (under its scope)."""
    from heterofl_tpu.obs.trace import scope

    with scope("moe/router"):
        s = jax.nn.sigmoid(jnp.dot(h.astype(jnp.float32), w_router.astype(jnp.float32),
                                   precision=jax.lax.Precision.HIGHEST))
        _, sel = jax.lax.top_k(s + jax.lax.stop_gradient(bias), top_k)
        w = jnp.take_along_axis(s, sel, axis=-1)
        total = jnp.sum(w, axis=-1, keepdims=True)
        if sum_eps:
            total = total + sum_eps
        return sel.astype(jnp.int32), w / total * scaling


def _parent_gq_attention(lp, h, *, heads, kv_heads, head_dim, theta, scale, sc, head_norm,
                         compute_dtype=None, attend=L.causal_gq_attention):
    """`models.lfm2.gq_attention` as the parent commit had it, word for word
    (before ISSUE 40 gave ``head_norm`` a None for a family without one)."""
    from functools import partial

    from heterofl_tpu.obs.trace import scope
    from heterofl_tpu.ops.layers import heads_linear, linear_heads, rope_interleaved, rope_swap

    q_heads = partial(linear_heads, heads=heads, compute_dtype=compute_dtype)
    kv = partial(linear_heads, heads=kv_heads, compute_dtype=compute_dtype)
    pos = jnp.arange(h.shape[1])
    with scope("gqa"):
        q = head_norm(sc(q_heads(h, lp["attn.q.w"])), lp["attn.q_norm.g"])
        k = head_norm(sc(kv(h, lp["attn.k.w"])), lp["attn.k_norm.g"])
        v = sc(kv(h, lp["attn.v.w"]))
    q = rope_interleaved(q, rope_swap(q), pos, theta, axis=2, full=head_dim)
    k = rope_interleaved(k, rope_swap(k), pos, theta, axis=2, full=head_dim)
    if compute_dtype is not None:
        q, k, v = (t.astype(compute_dtype) for t in (q, k, v))
    o = attend(q, k, v, scale)
    with scope("gqa"):
        return sc(heads_linear(o.astype(jnp.float32), lp["attn.o.w"], compute_dtype))


def _gq_layer(h, wq, wk, wv, wo, gq, gk, head_norm="rms"):
    """A layer's grouped-query attention as the LFM2 and Keye models call it
    (4 query heads on 2 key/value heads of 6, an RMSNorm on every head); with
    ``head_norm`` None, as Ouro calls it."""
    from heterofl_tpu.models import decoder

    lp = {"attn.q.w": wq, "attn.k.w": wk, "attn.v.w": wv, "attn.o.w": wo,
          "attn.q_norm.g": gq, "attn.k_norm.g": gk}
    norm = None if head_norm is None else (
        lambda x, g: L.masked_rms_norm(x, g, jnp.ones(6), 6.0, 1e-5))
    return decoder.gq_attention(lp, h, heads=4, kv_heads=2, head_dim=6, theta=1e4, scale=0.4,
                             sc=lambda x: x / 0.5, head_norm=norm)


def _seeded(seed, *shapes):
    return [jax.random.normal(k, s) for k, s in
            zip(jax.random.split(jax.random.key(seed), len(shapes)), shapes)]


UNCHANGED = {
    "blockwise_latent_attention": (
        lambda *a: L.blockwise_latent_attention(*a, 0.3, 8),
        _seeded(21, (2, 4, 24, 6), (2, 4, 24, 4), (2, 4, 24, 6), (2, 24, 4), (2, 4, 24, 5))),
    "blockwise_gq_attention": (
        lambda *a: L.blockwise_gq_attention(*a, 0.3, 8),
        _seeded(22, (2, 4, 24, 6), (2, 2, 24, 6), (2, 2, 24, 6))),
    "sigmoid_moe_route": (
        lambda h, w, b: L.moe_route(h, w, 0.1 * b, 4, 2.5, 1e-6)[1],
        _seeded(23, (40, 12), (12, 16), (16,))),
    # ISSUE 40: `gq_attention(head_norm=None)`; a caller that passes its head norm
    "gq_attention_with_its_head_norm": (
        _gq_layer, _seeded(24, (2, 24, 16), (16, 24), (16, 12), (16, 12), (24, 16), (6,), (6,))),
}


@pytest.mark.parametrize("name", sorted(UNCHANGED))
def test_the_block_loop_and_the_router_give_their_callers_what_the_parent_gave(name, monkeypatch):
    """`_causal_blocks` gained a mask, `moe_route` softmax scoring and (ISSUE
    40) `gq_attention` a None for its head norm; without them a caller traces
    to the SAME program as at the parent commit (the jaxprs of value and
    gradient are equal as text) and returns the same bits.  The parents'
    bodies are kept above, word for word, and stand in for this tree's the
    second time round."""
    fn, args = UNCHANGED[name]

    def run():
        def probe(*a):
            out = fn(*a)
            return jnp.sum(out * jnp.arange(out.size, dtype=jnp.float32).reshape(out.shape))
        both = jax.value_and_grad(probe, argnums=tuple(range(len(args))))
        return jax.tree_util.tree_leaves(both(*args)), str(jax.make_jaxpr(both)(*args))

    got, got_text = run()
    monkeypatch.setattr(L, "_causal_blocks", lambda *a: _parent_causal_blocks(*a[:7]))
    monkeypatch.setattr(L, "moe_route", _parent_moe_route)
    monkeypatch.setattr("heterofl_tpu.models.decoder.gq_attention", _parent_gq_attention)
    want, want_text = run()
    assert got_text == want_text
    for a, b in zip(got, want):
        np.testing.assert_array_equal(a, b)


def test_gq_attention_without_a_head_norm_is_the_layer_with_an_identity_for_one():
    """`gq_attention(head_norm=None)`, Ouro's call: the parent's layer handed
    an identity for its head norm, to the bit, value and gradients; the two
    gains are then not read."""
    args = UNCHANGED["gq_attention_with_its_head_norm"][1]

    def probe(fn):
        def total(*a):
            out = fn(*a)
            return jnp.sum(out * jnp.arange(out.size, dtype=jnp.float32).reshape(out.shape))
        return jax.value_and_grad(total, argnums=tuple(range(len(args))))(*args)

    def parent(h, wq, wk, wv, wo, gq, gk):
        lp = {"attn.q.w": wq, "attn.k.w": wk, "attn.v.w": wv, "attn.o.w": wo,
              "attn.q_norm.g": gq, "attn.k_norm.g": gk}
        return _parent_gq_attention(lp, h, heads=4, kv_heads=2, head_dim=6, theta=1e4, scale=0.4,
                                    sc=lambda x: x / 0.5, head_norm=lambda x, g: x)

    got, want = probe(lambda *a: _gq_layer(*a, head_norm=None)), probe(parent)
    for a, b in zip(jax.tree_util.tree_leaves(got), jax.tree_util.tree_leaves(want)):
        np.testing.assert_array_equal(a, b)
    assert not np.asarray(got[1][5]).any() and not np.asarray(got[1][6]).any()
    with_norm = probe(_gq_layer)
    assert np.abs(np.asarray(with_norm[0]) - np.asarray(got[0])) > 1e-3 * np.abs(np.asarray(got[0]))


# ---------------------------------------------------------------------------
# slicing: two head families, the untied leaves
# ---------------------------------------------------------------------------

# ---------------------------------------------------------------------------
# through the engines and the entry point
# ---------------------------------------------------------------------------

def test_keye_model_takes_the_selected_kernels_where_a_tpu_gives_them_tiles(monkeypatch):
    """The model at shapes the fused kernels tile (heads of 128, rows of 256
    positions, ``topk`` 128) with jax reporting a TPU -- the kernels in
    interpret mode, the one thing steered here: loss and every leaf's
    gradient are the block loop's of the same model on the CPU to the
    kernels' bfloat16 probabilities, the indexer's leaves still get exactly
    zero, and `sparse_fused` counts every query tile (2 layers x 2 rows x 2
    blocks of 128), which `obs.split_probes` finishes as 1.0."""
    from functools import partial

    from heterofl_tpu.obs import split_probes
    from heterofl_tpu.ops import pallas_attention as PA

    _, model, params, tokens, lm, _ = _keye_case(bptt=256, head_dim=128, index_topk=128)

    def loss_grads_counters():
        def loss(p):
            out, _ = model.apply(p, {"label": tokens}, train=True, label_mask=lm)
            return out["loss"], out["counters"]
        (value, counters), grads = jax.value_and_grad(loss, has_aux=True)(params)
        return value, grads, counters

    want, want_grads, on_cpu = loss_grads_counters()
    assert [float(c) for c in on_cpu["sparse_fused"]] == [0.0, 8.0]
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    monkeypatch.setattr(PA, "fused_selected_attention",
                        partial(PA.fused_selected_attention, interpret=True))
    got, got_grads, counters = loss_grads_counters()
    assert [float(c) for c in counters["sparse_fused"]] == [8.0, 8.0]
    _, rounds = split_probes({"obs_sparse_fused": np.asarray(counters["sparse_fused"])}, 1,
                             counters=model.meta["counters"])
    assert rounds[0]["sparse_fused"] == 1.0
    assert float(got) == pytest.approx(float(want), rel=2e-3)
    for name, w in want_grads.items():
        scale = float(jnp.abs(w).max())
        np.testing.assert_allclose(got_grads[name], w, rtol=0, atol=3e-2 * scale + 1e-12,
                                   err_msg=name)
        if ".idx." in name:
            assert not np.any(got_grads[name]), name


@pytest.mark.parametrize("policy, kernels", [
    ("kept", ["sel_attn_bwd", "sel_attn_fwd"]),
    ("bare", ["sel_attn_bwd", "sel_attn_fwd", "sel_attn_fwd"])])
def test_keye_gradient_on_the_kernels_runs_one_forward_kernel_a_layer(policy, kernels,
                                                                      monkeypatch):
    """The model at shapes the fused kernels tile, jax reporting a TPU: the
    gradient's program calls ``sel_attn_fwd`` in the forward scan's body and
    ``sel_attn_bwd`` alone in the backward's, whose residuals ``o`` and the
    log-sum-exp the layer kept by name; a layer that keeps its input alone
    (before ISSUE 39) calls the forward kernel again beside the backward."""
    from heterofl_tpu.staticcheck.jaxpr_walk import iter_eqns

    if policy == "bare":
        _bare_checkpoint(monkeypatch)
    _, model, params, tokens, lm, _ = _keye_case(bptt=256, head_dim=128, index_topk=128)
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    jaxpr = jax.make_jaxpr(jax.grad(lambda p: model.apply(
        p, {"label": tokens}, train=True, label_mask=lm)[0]["loss"]))(params)
    assert sorted(e.params["name"] for e in iter_eqns(jaxpr)
                  if e.primitive.name == "pallas_call") == kernels


def test_the_cut_configuration_has_the_parameters_it_states():
    """373,546,880: five layers of 59,150,720 (attention 18,874,624 with its
    head norms, indexer 2,261,120, router 262,144, eight experts of
    4,718,592, two norms), an eighth of the untied vocabulary twice and the
    final norm, from `jax.eval_shape` of the model's own `init`."""
    from benchmark.tests import test_keye

    test_keye.test_the_stated_parameter_count_is_the_programs()


# ---------------------------------------------------------------------------
# the scopes ISSUE 35 added (obs.trace.SPARSE_SCOPES)
# ---------------------------------------------------------------------------

def test_the_indexer_carries_its_names():
    """`sparse/index` and `sparse/select` reach the round program's `op_name`s
    under `step/model` in the forward only: the indexer has no backward, and
    the layer's recomputation runs none of it since the layer keeps the
    choice (ISSUE 39); `sparse/index` holds the indexer's products, its
    LayerNorm and its turn, `sparse/select` no product at all; the attention
    stays under `gqa` / `rope` / `attn` and the experts under the shared
    code's scopes."""
    from heterofl_tpu.obs import trace

    assert trace.SPARSE_SCOPES == ("sparse/index", "sparse/select")
    assert not set(trace.SPARSE_SCOPES) & set(
        trace.SCOPES + trace.EXTRA_SCOPES + trace.MIXER_SCOPES)
    assert trace.SCOPE_VERSION >= 5  # bumped with the new names (the compile cache's key)
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
    for s in trace.SPARSE_SCOPES:
        mine = [n for n in names if f"/{s}/" in n]
        assert any("/jvp(step/model)/" in n for n in mine), s
        # no gradient passes it, and the backward's recomputation does not repeat it
        assert not any("transpose(" in n for n in mine), s
    index = [n for n in names if "/sparse/index/" in n]
    assert any("dot_general" in n for n in index)
    assert any("/sparse/index/norm/" in n for n in index)
    assert any("/sparse/index/rope/" in n for n in index)
    assert not any("dot_general" in n for n in names if "/sparse/select/" in n)
    for s in ("gqa", "rope", "attn", "moe/router", "moe/dispatch", "moe/experts"):
        for wrap in ("jvp(step/model)", "transpose(jvp(step/model))"):
            if s == "moe/router" and wrap.startswith("transpose"):
                continue  # top-k has no backward; the scores' lies under it
            assert any(f"/{wrap}/" in n and f"/{s}/" in n and "/sparse/" not in n
                       for n in names), (s, wrap)
    attn = [n for n in names if "/attn/" in n]
    assert attn and not any("/linear/" in n or "/gqa/" in n or "/sparse/" in n for n in attn)
    with pytest.raises(ValueError, match="Not valid scope"):
        trace.scope("sparse/topk")
