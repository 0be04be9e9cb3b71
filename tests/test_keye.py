"""Keye (``models/keye.py``, ISSUE 35: a learned sparse-attention indexer over
grouped-query attention, softmax-routed experts, untied head) at a tiny size
on the CPU, rows LONGER than ``topk`` so that the selection binds: against the
benchmark's plain reference, the selection and the attention over it, its
slicing rules, and through the engines and the entry point.  A file of its
own so that the test runner's per-file workers share the family's compiles
evenly."""

import json
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from heterofl_tpu import config as C
from heterofl_tpu.models import make_model
from heterofl_tpu.models.spec import Group, count_masks, mask_params
from heterofl_tpu.ops import layers as L
from heterofl_tpu.parallel import RoundEngine, make_mesh

LEVELS = [1.0, 0.5, 0.25, 0.125, 0.0625]


def _keye_case(seed=1, bptt=None, **arch):
    """(cfg, model, seeded params with the gains and the indexer's LayerNorm
    bias moved off their constants, tokens, a label mask with holes, the
    reference's model description)."""
    from benchmark.tests import tiny_keye as tiny

    cfg = tiny.program_cfg(bptt=bptt or tiny.BPTT, **arch)
    model = make_model(cfg)
    params = model.init(jax.random.key(seed))
    keys = jax.random.split(jax.random.key(seed + 1), len(params))
    params = {k: v + 0.1 * jax.random.normal(kk, v.shape) if v.ndim == 1 else v
              for (k, v), kk in zip(sorted(params.items()), keys)}
    tokens = jax.random.randint(jax.random.key(seed + 2), (2, cfg["bptt"]), 0,
                                cfg["num_tokens"])
    label_mask = jnp.ones(cfg["num_tokens"]).at[jnp.arange(0, cfg["num_tokens"], 7)].set(0.0)
    return cfg, model, params, tokens, label_mask, tiny.reference_model(cfg)


def _masked_loss_and_grads(model, params, tokens, lm, rate):
    def system_loss(p):
        pm = mask_params(p, model.specs, model.groups, rate)
        out, _ = model.apply(pm, {"label": tokens}, train=True, width_rate=rate,
                             scaler_rate=rate, label_mask=lm)
        return out["loss"]

    return jax.value_and_grad(system_loss)(params)


# ---------------------------------------------------------------------------
# the model against the benchmark's plain reference
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("rate", LEVELS)
def test_keye_masked_model_is_the_references_dense_submodel(rate):
    """Loss and gradients of the masked full-width model at rate r against the
    plain reference on the sliced sub-model: rate 1 is the published layer
    (half-split RoPE on the un-permuted heads, repeated key/value heads,
    `lax.top_k` for the selection), every other level HeteroFL's slice of it.
    float32 on both sides, so the two differ by summation order alone --
    amplified by the Scaler's 1/r and, at a near-tie of two router or indexer
    scores, by a different choice; 1e-3 of a leaf's largest gradient holds
    both, and a bfloat16 product, a softmax over every causal key or a
    mis-sliced head is off by 1e-2 or more.  The indexer's leaves get exactly
    zero, inside the slice and outside."""
    from benchmark.reference import common, keye as ref

    cfg, model, params, tokens, lm, rm = _keye_case()
    loss, grads = _masked_loss_and_grads(model, params, tokens, lm, rate)
    index = ref.index({k: v.shape for k, v in params.items()}, rm, rate)
    sub = {k: jnp.asarray(v) for k, v in common.take(params, index).items()}
    ref_loss, ref_grads = jax.value_and_grad(
        lambda p: ref.loss_fn(p, tokens, lm, rate, ref.arch_of(rm)))(sub)
    np.testing.assert_allclose(float(loss), float(ref_loss), rtol=1e-5)
    inside = common.take(grads, index)
    for k, g in ref_grads.items():
        g = np.asarray(g)
        np.testing.assert_allclose(inside[k], g, atol=1e-3 * np.abs(g).max() + 1e-9,
                                   err_msg=k)
        outside = np.ones(grads[k].shape, bool)
        outside[np.ix_(*index[k])] = False
        assert not np.asarray(grads[k])[outside].any(), k  # nothing outside the slice
        if ".idx." in k:  # frozen by construction, in program and reference alike
            assert not np.asarray(grads[k]).any() and not g.any(), k
    assert sum(".idx." in k for k in grads) == 5 * cfg["keye"]["num_hidden_layers"]


@pytest.mark.parametrize("rate", LEVELS)
def test_keye_sliced_submodel_is_the_masked_model(rate):
    """HeteroFL's equivalence inside the program: the dense sub-model built at
    rate r (`make_model(cfg, r)`, what the grouped and sliced engines train)
    on the slice of the parameters gives the masked full-width model's loss
    and, inside the slice, its gradients; same float32 sums in another order,
    so 1e-5 relative on the loss and 1e-4 of a leaf's largest gradient."""
    from benchmark.reference import common, keye as ref

    cfg, model, params, tokens, lm, rm = _keye_case()
    loss, grads = _masked_loss_and_grads(model, params, tokens, lm, rate)
    index = ref.index({k: v.shape for k, v in params.items()}, rm, rate)
    sub = {k: jnp.asarray(v) for k, v in common.take(params, index).items()}
    small = make_model(cfg, rate)
    assert {k: tuple(v.shape) for k, v in sub.items()} == small.meta["shapes"]
    sub_loss, sub_grads = jax.value_and_grad(lambda p: small.apply(
        p, {"label": tokens}, train=True, scaler_rate=rate, label_mask=lm)[0]["loss"])(sub)
    np.testing.assert_allclose(float(sub_loss), float(loss), rtol=1e-5)
    inside = common.take(grads, index)
    for k, g in sub_grads.items():
        g = np.asarray(g)
        np.testing.assert_allclose(inside[k], g, atol=1e-4 * np.abs(g).max() + 1e-9,
                                   err_msg=k)


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
    assert model.meta["counters"]["sparse_saved"] == (2,)
    _, rounds = split_probes({"obs_sparse_saved": np.asarray(saved)}, 1)
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
    from heterofl_tpu.models import lfm2

    lp = {"attn.q.w": wq, "attn.k.w": wk, "attn.v.w": wv, "attn.o.w": wo,
          "attn.q_norm.g": gq, "attn.k_norm.g": gk}
    norm = None if head_norm is None else (
        lambda x, g: L.masked_rms_norm(x, g, jnp.ones(6), 6.0, 1e-5))
    return lfm2.gq_attention(lp, h, heads=4, kv_heads=2, head_dim=6, theta=1e4, scale=0.4,
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
    monkeypatch.setattr("heterofl_tpu.models.lfm2.gq_attention", _parent_gq_attention)
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

@pytest.mark.parametrize("rate", LEVELS)
def test_keye_both_head_families_keep_equal_dims_and_whole_pairs(rate):
    """The attention's 4 query heads, 2 key/value heads and head norms keep
    the SAME dims of a head at every level, in whole rotary pairs, and so do
    -- with a head size of their own -- the indexer's 4 query heads, its key
    head and that head's LayerNorm; the indexer's per-head weights and the
    router's columns are never cut; the geometry check holds each family."""
    from heterofl_tpu.fed.core import validate_width_geometry

    cfg, model, _, _, _, _ = _keye_case()
    for family, names, hd in (("head", ("q_head", "kv_head", "head"), 16),
                              ("index", ("iq_head", "ik_head"), 8)):
        kept = {}
        for name in names:
            g = model.groups[name]
            assert g.family == family
            m = np.asarray(g.mask(rate)).reshape(g.num_heads, hd)
            assert (m == m[0]).all(), name  # every head alike
            k = int(m[0].sum())
            assert m[0, :k].all() and k % 2 == 0, (name, k)  # a prefix of whole pairs
            assert int(g.active_count(rate)) == g.num_heads * k
            kept[name] = k
        assert set(kept.values()) == {max(2, int(np.ceil(hd * rate)))}
    for never in ("index", "router"):
        assert np.asarray(model.groups[never].mask(rate)).all()
    validate_width_geometry(model, cfg)
    model.groups["ik_head"] = Group("ik_head", 8, kind="per_head", num_heads=1, multiple=1,
                                    coupled=False, family="index")
    with pytest.raises(ValueError, match="head family 'index' is inconsistent at rate 0.0625"):
        validate_width_geometry(model, cfg)


def test_keye_counts_follow_width_and_labels():
    """A client counts for every element of its slice (the indexer's leaves
    too, though no gradient moves them); embedding rows and head columns
    follow the labels the client holds."""
    from benchmark.reference import keye as ref
    from benchmark.tests import tiny_keye as tiny

    cfg = tiny.program_cfg()
    model = make_model(cfg)
    shapes = dict(model.meta["shapes"])
    assert ref.LABEL_AXES == {k: s.label_axis for k, s in model.specs.items()
                              if s.label_axis is not None}
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
        assert np.asarray(cm["l1.moe.router.w"]).sum(axis=0).min() > 0  # all 8 columns
        assert np.asarray(cm["l1.idx.w.w"]).sum(axis=0).min() > 0      # all 4 weights


def test_level_tables_know_the_keye_family():
    """`level_param_table` counts the sliced sub-model's own leaves and the
    FLOP table falls with the level."""
    from benchmark.tests import tiny_keye as tiny
    from heterofl_tpu.fed.core import level_flop_table, level_param_table

    cfg = tiny.program_cfg()
    for rate, n in level_param_table(cfg).items():
        shapes = jax.eval_shape(make_model(cfg, rate).init, jax.random.key(0))
        assert n == sum(int(np.prod(v.shape)) for v in shapes.values()), rate
    flops = level_flop_table(cfg)
    assert sorted(flops.values(), reverse=True) == [flops[r] for r in sorted(flops, reverse=True)]


# ---------------------------------------------------------------------------
# through the engines and the entry point
# ---------------------------------------------------------------------------

def _round_case():
    """(cfg, data) of 8 users with 2 rows of 64 tokens each; every client
    lacks every fifth token and nobody holds token 3 or 4."""
    from benchmark.tests import tiny_keye as tiny

    cfg = tiny.program_cfg(control="1_8_0.5_iid_fix_a1-b1-c1-e1_bn_1_1")
    vocab = cfg["num_tokens"]
    rows = np.random.default_rng(0).integers(5, vocab, size=(8, 2, 64)).astype(np.int64)
    lm = np.ones((8, vocab), np.float32)
    lm[:, :5] = 0.0
    lm[:, ::5] = 0.0
    return cfg, (jnp.asarray(rows), jnp.asarray(lm))


def _round(cfg, data, chunk, n_dev=1, users=np.arange(8), **extra):
    cfg = dict(cfg, round_chunk=chunk, **extra)
    model = make_model(cfg)
    eng = RoundEngine(model, cfg, make_mesh(n_dev, 1))
    params0 = model.init(jax.random.key(0))
    before = {k: np.asarray(v) for k, v in params0.items()}  # the round donates its input
    out, ms = eng.train_round(params0, jax.random.key(5), 0.5, users, data)
    return (before, {k: np.asarray(v) for k, v in out.items()},
            {k: np.asarray(v) for k, v in ms.items()})


@pytest.fixture(scope="module")
def masked_round():
    cfg, data = _round_case()
    return (cfg, data) + _round(cfg, data, 1)


def test_keye_masked_round_in_chunks_of_one_is_the_unchunked_round(masked_round):
    """`round_chunk` 1, the cell's setting: one slot at a time is the round of
    one vmap over all 8 slots up to the order of float32 sums (1e-5 relative
    / 1e-6 absolute; a lost or doubled slot is off by 1e-2)."""
    cfg, data, _, out, ms = masked_round
    _, base, base_ms = _round(cfg, data, None)
    for k in base:
        np.testing.assert_allclose(out[k], base[k], rtol=1e-5, atol=1e-6, err_msg=k)
    for k in ("loss_sum", "n", "rate"):
        np.testing.assert_allclose(ms[k], base_ms[k], rtol=1e-5)
    assert np.isfinite(ms["loss_sum"]).all() and (ms["n"] == 2).all()


def test_keye_a_level_e_round_leaves_everything_outside_its_slice(masked_round):
    """The slicing round-trips: a round of the smallest level alone moves
    entries inside its slice -- the indexer's by weight decay, which the
    frozen leaves are not spared -- and leaves everything outside bit for bit,
    indexer included; rows of tokens nobody holds come back as they were."""
    from benchmark.reference import keye as ref
    from benchmark.tests import tiny_keye as tiny

    cfg, data, before, out, _ = masked_round
    held = np.asarray(data[1]).max(axis=0) > 0
    changed = out["embedding.tok.w"] != before["embedding.tok.w"]
    assert not changed[~held].any() and changed[held].any(axis=1).all()
    small = [u for u in range(8) if cfg["model_rate"][u] == min(cfg["model_rate"])]
    _, new, _ = _round(cfg, data, 1, users=np.resize(small, 8))
    index = ref.index({k: v.shape for k, v in before.items()}, tiny.reference_model(cfg),
                      min(cfg["model_rate"]))
    for k, b in before.items():
        inside = np.zeros(b.shape, bool)
        inside[np.ix_(*index[k])] = True
        moved = new[k] != b
        assert not moved[~inside].any(), k
        assert moved[inside].any() or k.endswith(".b"), k  # a zero bias decays to zero
    assert (new["l0.idx.q.w"] != before["l0.idx.q.w"]).any()


def test_keye_grouped_engine_trains_the_family_and_refuses_the_chunk(masked_round):
    """The grouped engine's per-level dense programs take the family as any
    other (no validator tests a model's name): its round is the masked
    engine's up to the order of float32 sums through a step at lr 0.5.  What
    it lacks is the chunked cohort, refused by key at config resolution."""
    from heterofl_tpu.parallel.grouped import GroupedRoundEngine

    cfg, data, _, base, _ = masked_round
    cfg = dict(cfg, strategy="grouped")
    model, users = make_model(cfg), np.arange(8)
    rates = np.asarray([cfg["model_rate"][u] for u in users], np.float32)
    out = GroupedRoundEngine(cfg, make_mesh(1, 1)).train_round(
        model.init(jax.random.key(0)), users, rates, data, 0.5, jax.random.key(5))[0]
    for k in base:
        np.testing.assert_allclose(out[k], base[k], atol=5e-3, err_msg=k)
    with pytest.raises(ValueError, match="round_chunk"):
        C.resolve_chunk_cfg(dict(cfg, round_chunk=1))


def test_keye_counters_ride_the_metrics():
    """telemetry='on' carries the indexer's counters out beside the expert
    layers': `obs_sparse_selected`, `obs_sparse_kept_share`,
    `obs_sparse_fused` and `obs_sparse_saved`, each a (numerator, denominator)
    pair of sums a device, finished by `obs.split_probes` as keys selected a
    query, selected over causal pairs -- at 64 positions and ``topk`` 16: 904
    / 64 and 904 / 2,080 -- the share of the selected attention's query tiles
    that went through the fused kernels: none on the CPU -- and the share of
    the selecting query blocks whose choice the layer kept: all."""
    from heterofl_tpu.obs import split_probes

    cfg, data = _round_case()
    _, _, ms = _round(cfg, data, 1, n_dev=2, telemetry="on")
    assert ms["obs_sparse_selected"].shape == ms["obs_sparse_kept_share"].shape == (2 * 2,)
    # 8 clients x 2 layers x 2 rows x 4 query blocks of 16, none through the kernels
    assert ms["obs_sparse_fused"].reshape(2, 2).sum(axis=0).tolist() == [0.0, 8 * 2 * 2 * 4]
    # of those four blocks the three that end after topk select, and their choice is kept
    assert ms["obs_sparse_saved"].reshape(2, 2).sum(axis=0).tolist() == [8 * 2 * 2 * 3] * 2
    assert ms["obs_moe_tokens"].shape == (2 * 4,) and ms["obs_moe_assign"].shape == (2 * 3,)
    clean, rounds = split_probes(dict(ms), 2)
    rec = rounds[0]
    assert rec["sparse_selected"] == pytest.approx(904 / 64, rel=1e-6)
    assert rec["sparse_kept_share"] == pytest.approx(904 / 2080, rel=1e-6)
    assert rec["sparse_fused"] == 0.0 and rec["sparse_saved"] == 1.0
    # 8 clients x 1 step x (2 rows x 64 tokens) x top-2, in each of 2 layers
    assert rec["moe_assign"][0] == 8 * 128 * 2 * 2
    assert rec["moe_dropped"] == 0 and sum(rec["moe_tokens"]) == rec["moe_assign"][1]
    assert not [k for k in clean if k.startswith("obs_")]


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
    _, rounds = split_probes({"obs_sparse_fused": np.asarray(counters["sparse_fused"])}, 1)
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


def test_keye_trains_and_evaluates_through_the_entry_point(tmp_path):
    """One whole `FedExperiment.train_round` (masked engine, `round_chunk` 1)
    and one `evaluate`, built as `entry.common.run_main` builds them from the
    command line: `--model_name keye` is all that names the family."""
    from benchmark.tests import tiny_keye as tiny
    from heterofl_tpu.entry.common import FedExperiment, build_cli, cfg_from_args
    from heterofl_tpu.utils.logger import Logger

    override = {"keye": dict(tiny.ARCH), "bptt": 64,
                "batch_size": {"train": 20, "test": 10}, "round_chunk": 1,
                "num_epochs": {"global": 2, "local": 1}}
    argv = ["--control_name", "1_10_0.5_iid_fix_a1-b1-c1-d1-e1_bn_1_1",
            "--model_name", "keye", "--data_name", "WikiText2", "--synthetic", "1",
            "--synthetic_sizes", json.dumps({"train": 20 * 64, "test": 10 * 64}),
            "--mesh", json.dumps({"clients": 1, "data": 1}),
            "--output_dir", str(tmp_path), "--override", json.dumps(override)]
    cfg = C.process_control(cfg_from_args(build_cli("test").parse_args(argv)))
    exp = FedExperiment(cfg, cfg["init_seed"])
    assert exp.kind == "transformer" and exp.engine.is_lm and exp.engine._chunk == 1
    data_split, label_split = exp.make_splits()
    exp.stage(data_split, label_split)
    logger = Logger(str(tmp_path / "log"))
    params = exp.model.init(jax.random.key(0))
    before = {k: np.asarray(v) for k, v in params.items()}
    params = exp.train_round(params, 1, 0.1, logger)
    moved = [k for k, v in params.items() if not np.array_equal(np.asarray(v), before[k])]
    assert len(moved) > len(before) // 2
    named = exp.evaluate(params, 1, logger, label_split)
    assert np.isfinite(named["Global-Loss"]) and named["Global-Perplexity"] > 1.0


def test_keye_tiny_cell_is_correct_and_its_control_is_not(monkeypatch, capsys):
    """`benchmark/checks.compare` on the tiny configuration, through the
    benchmark's own command: sound as returned, not `correct` once the check
    rounds' result has passed through bfloat16 (the test lives with the
    benchmark's; run here so that the gate holds it)."""
    from benchmark.tests import test_keye

    test_keye.test_a_sound_run_of_the_tiny_cell_is_correct_and_the_control_is_not(
        monkeypatch, capsys)


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

def test_the_indexer_carries_its_names(masked_round):
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
    cfg, data = masked_round[:2]
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
