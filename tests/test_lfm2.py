"""LFM2 (``models/lfm2.py``, ISSUE 32: gated short convolutions among
grouped-query attention, bias-routed experts, a tied head) at a tiny size on
the CPU: against the benchmark's plain reference, its slicing rules, and
through the engines and the entry point.  A file of its own so that the test
runner's per-file workers share the family's compiles evenly."""

import functools
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from decoder_cases import case, round_case, run_round
from heterofl_tpu.models import make_model
from heterofl_tpu.parallel import RoundEngine, make_mesh

_lfm2_case = functools.partial(case, "lfm2")
_round_case = functools.partial(round_case, "lfm2")
_round = run_round


def _stacked_experts(params, held, layer):
    return [jnp.stack([params[f"l{layer}.moe.e{j}.{m}.w"] for j in held]) for m in "gud"]


# ---------------------------------------------------------------------------
# the model against the benchmark's plain reference
# ---------------------------------------------------------------------------


def test_lfm2_the_four_shares_add_up():
    """The routed parts that the four shares of a 4-way expert-parallel layer
    compute add up to the UNCUT reference's expert layer, with what every
    share computes alike (the mixer, the router) counted once: for the
    reference's whole layer ``x -> x1 + y`` (``x1`` the state after the
    mixer), ``y = sum over shares of moe_experts(share)``."""
    from benchmark.reference import lfm2 as ref
    from heterofl_tpu.ops import layers as L

    cfg, model, _, tokens, _, _ = _lfm2_case(expert_share=[0, 1])
    from benchmark.tests import tiny_lfm2 as tiny

    arch, rm = cfg["lfm2"], tiny.reference_model(cfg)
    whole = model.init(jax.random.key(3))
    whole["l2.moe.router.b"] = 0.05 * jax.random.normal(jax.random.key(8), (arch["num_experts"],))
    x = jax.random.normal(jax.random.key(4), tokens.shape + (arch["hidden_size"],))
    a = ref.arch_of(rm)
    lp = ref._layer_leaves(whole, 2, dict(a)["held"])
    x1 = x + ref.conv_mixer(lp, ref._rms(x, lp["norm1.g"], 1e-5), 1.0)
    y_ref = (ref.layer(lp, x, 1.0, a, "conv", False) - x1).reshape(tokens.size, -1)
    hf = ref._rms(x1, lp["norm2.g"], 1e-5).reshape(tokens.size, -1)
    sel, w = L.moe_route(hf, whole["l2.moe.router.w"], whole["l2.moe.router.b"],
                         arch["num_experts_per_tok"], arch["routed_scaling_factor"], 1e-6)
    parts = [L.moe_experts(hf, sel, w, _stacked_experts(whole, range(4 * i, 4 * i + 4), 2),
                           4 * i, lambda v: v, tile=8) for i in range(4)]
    np.testing.assert_allclose(sum(y for y, _ in parts), y_ref, rtol=1e-4, atol=1e-5)
    assert sum(float(c["assign"][1]) for _, c in parts) == sel.size  # every pair once
    # and through the model: a share's logits differ from the whole model's
    # by what the absent experts add
    share = make_model(dict(cfg, lfm2=dict(arch, expert_share=[1, 4])))
    assert share.meta["held_experts"] == [4, 5, 6, 7]
    sub = {k: whole[k] for k in share.meta["shapes"]}
    out_s, _ = share.apply(sub, {"label": tokens}, train=False)
    out_w, _ = model.apply(whole, {"label": tokens}, train=False)
    assert np.abs(np.asarray(out_s["score"]) - np.asarray(out_w["score"])).max() > 1e-5


@pytest.mark.parametrize("t", [0, 5, 15])
def test_short_conv_is_causal_and_rows_do_not_mix(t):
    """Perturb token ``t`` of row 0: the conv mixer's outputs before ``t``
    and every output of row 1 are BIT-equal, positions ``t .. t + 2`` (three
    taps) move and nothing after them does."""
    from heterofl_tpu.models.lfm2 import conv_mixer

    cfg, model, params, _, _, _ = _lfm2_case()
    lp = {k[len("l0."):]: v for k, v in params.items() if k.startswith("l0.conv.")}
    h = jax.random.normal(jax.random.key(11), (2, 16, cfg["lfm2"]["hidden_size"]))
    base = np.asarray(conv_mixer(lp, h, sc=lambda v: v))
    moved = np.asarray(conv_mixer(lp, h.at[0, t].add(1.0), sc=lambda v: v))
    np.testing.assert_array_equal(moved[1], base[1])
    np.testing.assert_array_equal(moved[0, :t], base[0, :t])
    changed = np.flatnonzero(np.any(moved[0] != base[0], axis=-1))
    assert changed.tolist() == [p for p in (t, t + 1, t + 2) if p < 16]
    # the op alone against its definition, tap by tap
    from heterofl_tpu.ops import layers as L

    b, c, u = (jax.random.normal(k, (2, 6, 4)) for k in jax.random.split(jax.random.key(12), 3))
    taps = jax.random.normal(jax.random.key(13), (3, 4))
    z = np.asarray(b * u)
    want = np.zeros((2, 6, 4), np.float32)
    for pos in range(6):
        for j in range(3):
            if pos - 2 + j >= 0:
                want[:, pos] += np.asarray(taps[j]) * z[:, pos - 2 + j]
    np.testing.assert_allclose(L.short_conv(b, c, u, taps), np.asarray(c) * want,
                               rtol=1e-5, atol=1e-6)


def test_grouped_query_attention_reads_each_key_head_once():
    """``causal_gq_attention`` against plain causal attention over key/value
    heads repeated for their query group, in one block and in blocks of 8 and
    of 5 (a ragged last block: the loop latent attention's ``jnp`` form
    shares)."""
    from heterofl_tpu.ops import layers as L

    kq, kk, kv = jax.random.split(jax.random.key(14), 3)
    q = jax.random.normal(kq, (2, 8, 16, 6))
    k, v = jax.random.normal(kk, (2, 2, 16, 6)), jax.random.normal(kv, (2, 2, 16, 6))
    kr, vr = (jnp.repeat(t, 4, axis=1) for t in (k, v))
    s = jnp.einsum("nhqd,nhkd->nhqk", q, kr) * 0.3
    s = jnp.where(jnp.arange(16)[:, None] >= jnp.arange(16)[None, :], s, -jnp.inf)
    want = jnp.einsum("nhqk,nhkd->nhqd", jax.nn.softmax(s, axis=-1), vr)
    for block in (16, 8, 5):
        np.testing.assert_allclose(L.causal_gq_attention(q, k, v, 0.3, block=block), want,
                                   rtol=1e-5, atol=1e-6)


@pytest.mark.parametrize("kept", [16, 8, 2])
def test_stored_pairs_turn_as_the_published_half_split_rope(kept):
    """The fixed permutation: a head stored with its rotary pairs adjacent
    (stored 2i = published i, stored 2i + 1 = published i + d/2) and turned
    by ``rope_interleaved`` gives the dot products of the published layout
    under half-split RoPE, at full width and for a sliced prefix of ``kept``
    dims with the full width's frequencies."""
    from benchmark.reference import lfm2 as ref
    from heterofl_tpu.ops import layers as L

    full, theta = 16, 1e4
    q, k = (jax.random.normal(kk, (1, 6, 3, kept)) for kk in jax.random.split(jax.random.key(15)))
    pos = jnp.arange(6)
    qs, ks = (L.rope_interleaved(t, L.rope_swap(t), pos, theta, full=full) for t in (q, k))
    qp, kp = (ref._rope_half(ref._published_order(t), theta, full) for t in (q, k))
    np.testing.assert_allclose(jnp.einsum("nqhd,nkhd->nhqk", qs, ks),
                               jnp.einsum("nqhd,nkhd->nhqk", qp, kp), rtol=1e-5, atol=1e-5)


# ---------------------------------------------------------------------------
# slicing: the head family, the tied leaf, the level tables
# ---------------------------------------------------------------------------

# ---------------------------------------------------------------------------
# through the engines and the entry point
# ---------------------------------------------------------------------------

@pytest.fixture(scope="module")
def masked_round():
    cfg, data = _round_case()
    return (cfg, data) + _round(cfg, data, 1)


def test_lfm2_tied_rows_aggregate_once_over_the_labels_held(masked_round):
    """The tied leaf through a whole round: rows of tokens no client holds
    come back bit for bit (weight decay moved them in every client, the
    counted average does not take them), rows that are held moved, and only
    inside the widest client's columns; the selection bias got no gradient."""
    cfg, data, before, out, _ = masked_round
    held = np.asarray(data[1]).max(axis=0) > 0
    changed = out["tok.w"] != before["tok.w"]
    assert not changed[~held].any() and changed[held].any(axis=1).all()
    assert not np.asarray(out["l1.moe.router.b"]).any()
    # a round of the smallest level alone leaves everything outside its slice
    small = [u for u in range(8) if cfg["model_rate"][u] == min(cfg["model_rate"])]
    model = make_model(cfg)
    eng = RoundEngine(model, dict(cfg, round_chunk=1), make_mesh(1, 1))
    new, _ = eng.train_round({k: jnp.asarray(v) for k, v in before.items()}, jax.random.key(5),
                             0.5, np.resize(small, 8), data)
    kept = int(np.ceil(cfg["lfm2"]["hidden_size"] * min(cfg["model_rate"])))
    moved = np.asarray(new["tok.w"]) != before["tok.w"]
    assert moved[:, :kept].any() and not moved[:, kept:].any()


# ---------------------------------------------------------------------------
# the scopes ISSUE 32 added (obs.trace.MIXER_SCOPES)
# ---------------------------------------------------------------------------

def test_the_mixers_carry_their_names(masked_round):
    """`shortconv`, `shortconv/gate` and `gqa` reach the round program's
    `op_name`s under `step/model`, forward and backward, nested as the model
    nests them: the conv mixer's projections are `shortconv/linear`, its gates
    and taps `shortconv/shortconv/gate` with no product among them; `gqa`
    holds the projections and the head norms, `rope` and `attn` lie beside it
    and `attn` holds no projection; the expert layer's scopes are entered by
    the shared code as for `kanana2`."""
    from heterofl_tpu.obs import trace

    assert not set(trace.MIXER_SCOPES) & set(trace.SCOPES + trace.EXTRA_SCOPES)
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
    for s in trace.MIXER_SCOPES + ("rope", "attn", "moe/router", "moe/dispatch", "moe/experts"):
        for wrap in ("jvp(step/model)", "transpose(jvp(step/model))"):
            if s == "moe/router" and wrap.startswith("transpose"):
                continue  # top-k has no backward; the scores' lies under it
            assert any(f"/{wrap}/" in n and f"/{s}/" in n for n in names), (s, wrap)
    assert any("/shortconv/linear/" in n for n in names)
    gate = [n for n in names if "/shortconv/shortconv/gate/" in n]
    assert gate and not any("/linear/" in n or "dot_general" in n for n in gate)
    assert any("/gqa/linear/" in n for n in names) and any("/gqa/norm/" in n for n in names)
    attn = [n for n in names if "/attn/" in n]
    assert attn and not any("/linear/" in n or "/gqa/" in n for n in attn)
    with pytest.raises(ValueError, match="Not valid scope"):
        trace.scope("shortconv/taps")
