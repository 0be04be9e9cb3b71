"""Ouro (``models/ouro.py``, ISSUE 40: a looped language model whose layer
stack runs ``total_ut_steps`` times on shared weights, an exit gate and the
head after every pass, an expected loss over the passes, sandwich norms) at a
tiny size on the CPU: against the benchmark's plain reference, the loop against
its unrolled self, the exit distribution, its slicing rules, and through the
engines and the entry point.  A file of its own so that the test runner's
per-file workers share the family's compiles evenly."""

import functools
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from decoder_cases import case, round_case
from heterofl_tpu import config as C
from heterofl_tpu.models import make_model
from heterofl_tpu.ops import layers as L
from heterofl_tpu.parallel import RoundEngine, make_mesh

_ouro_case = functools.partial(case, "ouro")
_round_case = functools.partial(round_case, "ouro")


# ---------------------------------------------------------------------------
# the model against the benchmark's plain reference
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("level", ["a", "c", "e"])
def test_ouro_one_whole_local_step_is_the_references(level):
    """A round of one client of a level, its local steps through the masked
    engine (gradient summed over a leaf's three uses, global-norm clip,
    momentum SGD with weight decay, the counted average), against the plain
    reference's round on the same client: every leaf within 1e-4 of its
    largest entry (float32, lr 0.1; a step that skipped the clip, decayed the
    wrong entries or took one pass's gradient alone is off by 1e-3 or more)."""
    from benchmark.reference import common, ouro as ref
    from benchmark.tests import tiny_ouro as tiny

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
        np.testing.assert_allclose(np.asarray(out[k]), v, atol=1e-4 * np.abs(v).max() + 1e-9,
                                   err_msg=k)
        assert (np.asarray(out[k]) != before[k]).any(), k


# ---------------------------------------------------------------------------
# the loop: shared weights, the exit distribution, one pass
# ---------------------------------------------------------------------------

def test_the_loop_is_the_unrolled_model(monkeypatch):
    """``R`` = 3 passes over ``N`` = 2 layers on SHARED weights give the loss
    of the plain reference run on ``R x N`` = 6 DISTINCT layers that hold
    copies (its two Python loops, `passes_unrolled`, handed a fresh layer at
    every application),
    and a shared leaf's gradient is the sum of its three copies' gradients --
    which differ from each other, so the sum is no multiple of one pass's."""
    from benchmark.reference import ouro as ref

    cfg, model, params, tokens, lm, rm = _ouro_case()
    n, r = cfg["ouro"]["num_hidden_layers"], cfg["ouro"]["total_ut_steps"]
    loss, grads = jax.value_and_grad(lambda p: model.apply(
        p, {"label": tokens}, train=True, label_mask=lm)[0]["loss"])(params)
    unrolled = {k: v for k, v in params.items() if not re.match(r"l\d+\.", k)}
    for t in range(r):
        for i in range(n):
            unrolled.update({f"l{t * n + i}.{k[len(f'l{i}.'):]}": v for k, v in params.items()
                             if k.startswith(f"l{i}.")})
    assert len(unrolled) - 5 == r * (len(params) - 5)
    real, applied = ref._layer_leaves, iter(range(r * n))
    monkeypatch.setattr(ref, "_layer_leaves", lambda p, i: real(p, next(applied)))
    want, copies = jax.value_and_grad(lambda p: ref.loss_fn(
        p, tokens, lm, 1.0, ref.arch_of(rm), states_of=ref.passes_unrolled))(unrolled)
    assert next(applied, None) is None  # every application took a layer of its own
    np.testing.assert_allclose(float(loss), float(want), rtol=1e-5)
    for k, g in grads.items():
        m = re.match(r"l(\d+)\.(.*)", k)
        if m is None:
            np.testing.assert_allclose(g, copies[k], atol=1e-3 * np.abs(copies[k]).max(), err_msg=k)
            continue
        mine = [np.asarray(copies[f"l{t * n + int(m[1])}.{m[2]}"]) for t in range(r)]
        total = sum(mine)
        np.testing.assert_allclose(g, total, atol=1e-3 * np.abs(total).max() + 1e-9, err_msg=k)
        assert np.abs(mine[0] - mine[1]).max() > 1e-2 * np.abs(total).max(), k


def test_one_pass_is_the_plain_decoder_under_next_token_loss():
    """``total_ut_steps`` 1: ``p_1`` = 1 and ``H`` = 0, so the loss is the
    plain `next_token_loss` of the one read-out (padded positions and the
    masked logits as in every family) and the gate, which nothing reads, gets
    no gradient; the counters say one pass, always."""
    cfg, model, params, tokens, lm, _ = _ouro_case(total_ut_steps=1)
    assert model.meta["profile"]["passes"] == 1
    w = jnp.ones(tokens.shape).at[1, 20:].set(0.0)  # a padded tail

    def loss(p):
        return model.apply(p, {"label": tokens}, train=True, label_mask=lm, sample_weight=w)[0]

    out = loss(params)
    # `next_token_loss` with the read-out's own (masked) logits as its head
    plain = L.next_token_loss(out["score"], tokens, lambda z: z, w)
    np.testing.assert_allclose(float(out["loss"]), float(plain), rtol=1e-6)
    wt = w[:, 1:] * w[:, :-1]
    grads = jax.grad(lambda p: loss(p)["loss"])(params)
    assert not np.asarray(grads["exit.w"]).any() and not np.asarray(grads["exit.b"]).any()
    assert np.asarray(grads["l0.norm2.g"]).any()
    c = out["counters"]
    assert [float(v) for v in c["loop_exit_share"]] == [float(jnp.sum(wt))] * 2
    assert [float(v) for v in c["loop_passes"]] == [float(jnp.sum(wt))] * 2


@pytest.mark.parametrize("bias", [0.0, 40.0, -40.0], ids=["seeded", "saturated-open", "saturated-shut"])
def test_the_exit_distribution_sums_to_one(bias):
    """`exit_log_probs` against the products of sigmoids it stands for; the
    ``p_t`` of every position sum to 1 whatever the gates say, the last pass
    takes what is left, and a saturated gate (``lam`` = 1 or 0 in float32)
    leaves the log and its gradient finite."""
    gate = jax.random.normal(jax.random.key(3), (4, 2, 5)) + bias
    logp = L.exit_log_probs(gate)
    p = np.exp(np.asarray(logp, np.float64))
    np.testing.assert_allclose(p.sum(axis=0), 1.0, rtol=1e-6)
    lam = 1.0 / (1.0 + np.exp(-np.asarray(gate, np.float64)))
    stay = np.cumprod(1.0 - lam[:-1], axis=0)
    want = np.concatenate([lam[:1], lam[1:-1] * stay[:-1], stay[-1:]])
    np.testing.assert_allclose(p, want, rtol=1e-5, atol=1e-12)
    g = jax.grad(lambda a: jnp.sum(jnp.exp(L.exit_log_probs(a)) * L.exit_log_probs(a)))(gate)
    assert np.isfinite(np.asarray(logp)).all() and np.isfinite(np.asarray(g)).all()
    assert not np.asarray(g[-1]).any()  # the last pass's own gate is not read
    assert np.asarray(L.exit_log_probs(gate[:1])).tolist() == np.zeros((1, 2, 5)).tolist()


def test_out_of_training_a_token_reads_the_pass_the_threshold_names():
    """The published exit rule: at threshold 1 every token reads the last
    pass (its loss is that pass's negative log-likelihood and `score` its
    logits, the entropy term gone); at threshold 0 the first; in between the
    first pass at which the running sum of the exit distribution reaches
    it."""
    cfg, model, params, tokens, lm, _ = _ouro_case()
    outs = {}
    for threshold in (1.0, 0.0, 0.6):
        m = make_model(dict(cfg, ouro=dict(cfg["ouro"], early_exit_threshold=threshold)))
        outs[threshold] = m.apply(params, {"label": tokens}, train=False, label_mask=lm)[0]
    positions = float(tokens.shape[0] * (tokens.shape[1] - 1))
    for threshold, out in outs.items():
        c = {k: np.asarray(v) for k, v in out["counters"].items()}
        nll = c["loop_pass_nll"][:-1] / positions
        share = c["loop_exit_share"][:-1] / positions
        assert c["loop_pass_nll"][-1] == positions
        np.testing.assert_allclose(share.sum(), 1.0, rtol=1e-6)
        if threshold == 0.6:
            assert (share > 0).all()  # some tokens leave at every pass
            continue
        assert share.tolist() == ([0.0, 0.0, 1.0] if threshold == 1.0 else [1.0, 0.0, 0.0])
        np.testing.assert_allclose(float(out["loss"]), float(share @ nll), rtol=1e-5)
    train = model.apply(params, {"label": tokens}, train=True, label_mask=lm)[0]
    np.testing.assert_array_equal(train["score"], outs[1.0]["score"])  # the last pass's
    assert np.abs(np.asarray(outs[0.0]["score"]) - np.asarray(outs[1.0]["score"])).max() > 1e-3
    # in training the loss is the mixture less beta times the entropy
    c = {k: np.asarray(v) for k, v in train["counters"].items()}
    assert (c["loop_exit_share"][:-1] > 0).all()
    assert 1.0 < c["loop_passes"][0] / c["loop_passes"][1] < 3.0


# ---------------------------------------------------------------------------
# slicing
# ---------------------------------------------------------------------------

# ---------------------------------------------------------------------------
# through the engines and the entry point
# ---------------------------------------------------------------------------

#: shapes the fused kernels tile: heads of 128 in groups of ONE query head a
#: key/value head, rows of 128 positions (three layers, two passes: no two of
#: the passes, the layers and the rows are as many)
TILED = dict(bptt=128, head_dim=128, num_attention_heads=2, num_key_value_heads=2,
             hidden_size=64, intermediate_size=64, total_ut_steps=2, num_hidden_layers=3)


def _bare_checkpoint(monkeypatch):
    """The model as it was before ISSUE 41: each layer application under a
    bare ``jax.checkpoint`` that keeps its input alone."""
    from heterofl_tpu.models import ouro

    monkeypatch.setattr(ouro, "kept", lambda: None)


#: both sides of the rule by which a pass applies its stack (ISSUE 43): the
#: three layers of `TILED` as a Python loop, and as the inner `lax.scan`
STACKS = ["unrolled", "scanned"]


def _stack(monkeypatch, stack):
    """The rule's constant as it stands (``TILED``'s three layers lie under
    it), or moved below them: the one thing steered, read when `apply` runs."""
    from heterofl_tpu.models import ouro

    assert TILED["num_hidden_layers"] <= ouro.UNROLL_LAYERS
    if stack == "scanned":
        monkeypatch.setattr(ouro, "UNROLL_LAYERS", TILED["num_hidden_layers"] - 1)


def _on_the_kernels(monkeypatch):
    """jax reports a TPU and the kernels run in interpret mode: the one thing
    steered in the tests below."""
    from functools import partial

    from heterofl_tpu.ops import pallas_attention as PA

    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    monkeypatch.setattr(PA, "fused_gq_attention", partial(PA.fused_gq_attention, interpret=True))


def _loss_counters_and_grads(model, params, tokens, lm):
    def loss(p):
        out, _ = model.apply(p, {"label": tokens}, train=True, label_mask=lm)
        return out["loss"], out["counters"]

    (value, counters), grads = jax.value_and_grad(loss, has_aux=True)(params)
    return value, counters, grads


@pytest.mark.parametrize("policy", ["kept", "bare"])
def test_ouro_model_takes_the_gq_kernels_where_a_tpu_gives_them_tiles(policy, monkeypatch):
    """The model at shapes the fused kernels tile with jax reporting a TPU:
    the gradient's program calls `gq_attn_fwd` / `gq_attn_bwd`, and loss and
    every leaf's gradient are the block loop's of the same model on the CPU to
    the kernels' bfloat16 operands, whether the layer keeps the kernel's
    results for its backward (ISSUE 41) or its input alone.  `loop_kept`
    counts the 2 x 3 layer applications that kept them: all under the policy
    on the kernels, none on the block loop or under a bare checkpoint."""
    from heterofl_tpu.obs import split_probes
    from heterofl_tpu.ops import pallas_attention as PA
    from heterofl_tpu.staticcheck.jaxpr_walk import iter_eqns

    if policy == "bare":
        _bare_checkpoint(monkeypatch)
    _, model, params, tokens, lm, _ = _ouro_case(**TILED)
    assert PA.gq_tile_for(128, 128) == 128
    assert model.meta["counters"]["loop_kept"] == ((2,), "ratio")
    want, counters, want_grads = _loss_counters_and_grads(model, params, tokens, lm)
    assert counters["loop_kept"].tolist() == [0.0, 6.0]
    _on_the_kernels(monkeypatch)
    jaxpr = jax.make_jaxpr(lambda p: _loss_counters_and_grads(model, p, tokens, lm)[2])(params)
    kernels = [e.params["name"] for e in iter_eqns(jaxpr) if e.primitive.name == "pallas_call"]
    assert set(kernels) == {"gq_attn_fwd", "gq_attn_bwd"}, kernels
    got, counters, got_grads = _loss_counters_and_grads(model, params, tokens, lm)
    assert counters["loop_kept"].tolist() == [6.0 if policy == "kept" else 0.0, 6.0]
    _, rounds = split_probes({"obs_loop_kept": np.asarray(counters["loop_kept"])}, 1,
                             counters=model.meta["counters"])
    assert rounds[0]["loop_kept"] == (1.0 if policy == "kept" else 0.0)
    assert float(got) == pytest.approx(float(want), rel=2e-3)
    for name, w in want_grads.items():
        scale = float(jnp.abs(w).max())
        np.testing.assert_allclose(got_grads[name], w, rtol=0, atol=3e-2 * scale + 1e-12,
                                   err_msg=name)


def _kernels_by_scan_body(jaxpr):
    """(the Pallas kernels a program calls outside any `scan`, those each
    `scan` body calls itself -- not through a scan inside it --, in the
    program's order; a body that calls none is left out)."""
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


@pytest.mark.parametrize("family, policy, stack, outside, bodies", [
    ("ouro", "kept", "scanned", [], [["gq_attn_fwd"], ["gq_attn_bwd"]]),
    ("ouro", "bare", "scanned", [], [["gq_attn_fwd"], ["gq_attn_bwd", "gq_attn_fwd"]]),
    ("ouro", "kept", "unrolled", [], [["gq_attn_fwd"] * 3, ["gq_attn_bwd"] * 3]),
    ("ouro", "bare", "unrolled", [], [["gq_attn_fwd"] * 3, ["gq_attn_bwd"] * 3 + ["gq_attn_fwd"] * 3]),
    ("lfm2", "its own", "its own", ["gq_attn_bwd", "gq_attn_fwd", "gq_attn_fwd"], [])])
def test_gradient_on_the_gq_kernels_runs_one_forward_kernel_where_the_layer_keeps_its_results(
        family, policy, stack, outside, bodies, monkeypatch):
    """A model at shapes the fused kernels tile, jax reporting a TPU.  Ouro:
    the gradient's program calls ``gq_attn_fwd`` in the forward scan's body
    and ``gq_attn_bwd`` ALONE in the backward's, whose residuals ``o`` and the
    log-sum-exp the layer kept by name; a layer that keeps its input alone
    (before ISSUE 41) calls the forward kernel again beside the backward.
    Scanned, the bodies are the inner scan's, one layer each; unrolled (ISSUE
    43) they are the PASSES' scan's, which then holds the three layers' calls
    and no scan.  LFM2's one attention layer, a lone layer under ITS bare
    checkpoint, still calls the forward kernel twice: there the names are the
    identity."""
    if family == "lfm2":
        from benchmark.tests import tiny_lfm2 as tiny

        cfg = tiny.program_cfg(head_dim=64)
    else:
        cfg = _ouro_case(**TILED)[0]
        _stack(monkeypatch, stack)
    if policy == "bare":
        _bare_checkpoint(monkeypatch)
    model = make_model(cfg)
    params = model.init(jax.random.key(1))
    tokens = jnp.zeros((2, 128), jnp.int32)
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    jaxpr = jax.make_jaxpr(jax.grad(lambda p: model.apply(
        p, {"label": tokens}, train=True)[0]["loss"]))(params)
    assert _kernels_by_scan_body(jaxpr) == (outside, bodies)


@pytest.mark.parametrize("stack", STACKS)
@pytest.mark.parametrize("path", ["block loop", "kernels"])
@pytest.mark.parametrize("policy", ["kept", "bare"])
def test_ouro_named_values_are_the_layers_saved_residuals(policy, path, stack, monkeypatch, capsys):
    """What the passes' scan hands the backward of a layer application: the
    layer's input ``[N, S, D]`` and, under the policy, the SwiGLU's
    down-projected output, as large, and where the kernels run their ``o``
    ``[N, H, d, S]``, log-sum-exp and three bfloat16 operands; nothing else,
    and under a bare checkpoint the input alone.  Scanned, each is ONE
    residual ``[R, L, ...]``, the inner scan's stack stacked again; unrolled
    (ISSUE 43), ``L`` residuals ``[R, ...]`` and none of ``[R, L, ...]``: one
    level of stacking.  Beside them the pass's own six (the final norm's),
    alike on both sides."""
    from jax.ad_checkpoint import print_saved_residuals

    if policy == "bare":
        _bare_checkpoint(monkeypatch)
    if path == "kernels":
        monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    _stack(monkeypatch, stack)
    _, model, params, tokens, lm, _ = _ouro_case(**TILED)
    print_saved_residuals(
        lambda p: model.apply(p, {"label": tokens}, train=True, label_mask=lm)[0]["loss"], params)
    of_the_scan = [line.split()[0] for line in capsys.readouterr().out.splitlines()
                   if "output of scan" in line]
    of_a_pass = ["f32[2,2,128,64]"] * 3 + ["f32[2,2,128,1]"] * 3
    layer_input = mlp_out = "f32[%s2,128,64]"
    of_the_kernels = ["f32[%s2,2,128,128]", "f32[%s2,2,1,1,128]"] + ["bf16[%s2,2,128,128]"] * 3
    named = [mlp_out] + (of_the_kernels if path == "kernels" else [])
    of_a_layer = [layer_input] + (named if policy == "kept" else [])
    lead, times = ("2,3,", 1) if stack == "scanned" else ("2,", 3)
    assert sorted(of_the_scan) == sorted(of_a_pass + [r % lead for r in of_a_layer] * times)
    assert (stack == "scanned") == any(re.match(r"\w+\[2,3,", r) for r in of_the_scan)


@pytest.mark.parametrize("stack", STACKS)
@pytest.mark.parametrize("path", ["block loop", "kernels"])
def test_ouro_keeping_the_named_values_changes_no_number(path, stack, monkeypatch):
    """Loss and every leaf's gradient under the policy against the bare
    checkpoint's, the stack unrolled or scanned: a kept value is the value
    the second forward would have
    computed from the same inputs.  On the CPU's block loop (where the
    SwiGLU's output alone carries a name) equal to the bit; on the interpreted
    kernels to float32 round-off (another compiled program round them)."""
    _stack(monkeypatch, stack)
    _, model, params, tokens, lm, _ = _ouro_case(**TILED)
    if path == "kernels":
        _on_the_kernels(monkeypatch)
    run = jax.jit(lambda p: _loss_counters_and_grads(model, p, tokens, lm))
    got, _, got_grads = run(params)
    _bare_checkpoint(monkeypatch)
    run = jax.jit(lambda p: _loss_counters_and_grads(model, p, tokens, lm))
    want, _, want_grads = run(params)
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


@pytest.mark.parametrize("arch, reports, share", [
    (dict(TILED), "tpu", 1.0),
    (dict(TILED), "cpu", 0.0),
    (dict(TILED, head_dim=32), "tpu", 0.0),   # a head the kernels do not tile
    (dict(TILED, bptt=96), "tpu", 0.0)],      # no whole tile of positions
    ids=["tiles", "cpu", "narrow-head", "short-row"])
def test_ouro_loop_kept_counts_the_applications_on_the_named_kernel(arch, reports, share,
                                                                   monkeypatch):
    """`loop_kept` = (layer applications whose attention ran the kernel that
    names its results under the policy, layer applications): 2 passes x 3
    layers where a TPU gives the shape tiles, 0 of 6 on a CPU or for a shape
    the kernels do not tile, where the block loop carries no name."""
    from heterofl_tpu.obs import split_probes

    _, model, params, tokens, lm, _ = _ouro_case(**arch)
    if reports == "tpu":
        _on_the_kernels(monkeypatch)
    out, _ = model.apply(params, {"label": tokens}, train=True, label_mask=lm)
    assert out["counters"]["loop_kept"].tolist() == [6.0 * share, 6.0]
    _, rounds = split_probes({"obs_loop_kept": np.asarray(out["counters"]["loop_kept"])}, 1,
                             counters=model.meta["counters"])
    assert rounds[0]["loop_kept"] == share


@pytest.mark.parametrize("path", ["block loop", "kernels"])
@pytest.mark.parametrize("policy", ["kept", "bare"])
def test_the_unrolled_stack_is_the_scanned_stack(policy, path, monkeypatch):
    """ISSUE 43 moves where a pass's layers are applied from, not what they
    compute: loss, the counters that say nothing of the stack's form and every
    leaf's gradient -- still the sum over its passes -- of the unrolled stack
    against the scanned one's.  On the CPU's block loop to 1e-6 of a leaf's
    largest entry (the same float32 operations; the sum over the passes is
    formed leaf by leaf and not stack by stack, and the compiler fuses round
    it as it likes), on the interpreted kernels the same."""
    if policy == "bare":
        _bare_checkpoint(monkeypatch)
    if path == "kernels":
        _on_the_kernels(monkeypatch)
    _, model, params, tokens, lm, _ = _ouro_case(**TILED)
    run = jax.jit(lambda p: _loss_counters_and_grads(model, p, tokens, lm))
    got, got_counters, got_grads = run(params)
    _stack(monkeypatch, "scanned")
    run = jax.jit(lambda p: _loss_counters_and_grads(model, p, tokens, lm))
    want, want_counters, want_grads = run(params)
    assert got_counters.pop("loop_unrolled").tolist() == [6.0, 6.0]
    assert want_counters.pop("loop_unrolled").tolist() == [0.0, 6.0]
    assert float(got) == pytest.approx(float(want), rel=1e-6)
    for name, w in want_counters.items():
        np.testing.assert_allclose(got_counters[name], w, rtol=1e-6, err_msg=name)
    for name, w in want_grads.items():
        np.testing.assert_allclose(got_grads[name], w, rtol=0, err_msg=name,
                                   atol=1e-6 * float(jnp.abs(w).max()) + 1e-12)


def _scans(jaxpr, inside=()):
    """(the lengths of the scans it lies in, its own length) of every `scan`
    of a program, outermost first."""
    from heterofl_tpu.staticcheck.jaxpr_walk import _sub_jaxprs

    found = []
    for e in jaxpr.eqns:
        mine = inside
        if e.primitive.name == "scan":
            mine = inside + (e.params["length"],)
            found.append(mine)
        for sub in _sub_jaxprs(e.params):
            found += _scans(sub, mine)
    return found


def _stacked_leaves(jaxpr, model, layers):
    """The values of a program that have the shape of a layer's leaf with an
    axis of ``layers`` in front: a stack of that leaf over the layers."""
    from heterofl_tpu.staticcheck.jaxpr_walk import iter_eqns

    stacks = {(layers,) + tuple(shape) for name, shape in model.meta["shapes"].items()
              if name.startswith("l0.")}
    return sorted({tuple(v.aval.shape) for e in iter_eqns(jaxpr) for v in e.outvars
                   if tuple(getattr(v.aval, "shape", ())) in stacks})


@pytest.mark.parametrize("stack", STACKS)
def test_a_short_stack_is_one_level_of_stacking(stack, monkeypatch):
    """The forward program of 2 passes over 3 layers, and the gradient's
    (beside the loop, the head's scan over its one block of rows).  Unrolled: ONE scan, the passes', with no
    scan inside it, and no value anywhere shaped as a stack of a layer's leaf
    over the layers -- no weight is stacked, so none is sliced and no stacked
    gradient is filled or accumulated.  Scanned: the layers' scan inside the
    passes', over the stacks of the seven matrices and the four gains."""
    _stack(monkeypatch, stack)
    _, model, params, tokens, lm, _ = _ouro_case(**TILED)
    def loss(p):
        return model.apply(p, {"label": tokens}, train=True, label_mask=lm)[0]["loss"]

    for program in (loss, jax.grad(loss)):
        jaxpr = jax.make_jaxpr(program)(params)
        of_the_loop = {s for s in _scans(jaxpr.jaxpr) if 2 in s or 3 in s}
        stacks = _stacked_leaves(jaxpr, model, 3)
        if stack == "unrolled":
            assert of_the_loop == {(2,)} and not stacks
        else:
            # (the gradient's program also runs what of a layer depends on no
            # state once for the three layers, ahead of the passes)
            assert {(2,), (2, 3)} <= of_the_loop <= {(2,), (2, 3), (3,)}
            # the gains', `q` / `k` / `v`'s, `o`'s and (a SwiGLU as wide as the model) its three's
            assert stacks == [(3, 64), (3, 64, 64), (3, 64, 256), (3, 256, 64)]


@pytest.mark.parametrize("layers, under", [(3, True), (48, False)], ids=["a-stage", "published-depth"])
def test_the_rule_is_read_off_the_depth(layers, under):
    """`loop_unrolled` = (layer applications run from an unrolled stack, layer
    applications): all of them for a stack of at most `UNROLL_LAYERS` layers,
    as the benchmark cell's 4, none for a longer one -- the published 48
    layers, at tiny widths here, still build the two nested scans.  No key, no
    flag, no variable says which: the depth alone."""
    from heterofl_tpu.models import ouro
    from heterofl_tpu.obs import split_probes

    assert 4 <= ouro.UNROLL_LAYERS < 48
    assert (layers <= ouro.UNROLL_LAYERS) == under
    _, model, params, tokens, lm, _ = _ouro_case(num_hidden_layers=layers)
    assert model.meta["counters"]["loop_unrolled"] == ((2,), "ratio")
    forward = jax.jit(lambda p: model.apply(p, {"label": tokens}, train=True, label_mask=lm)[0])
    nested = [s for s in _scans(jax.make_jaxpr(forward)(params).jaxpr) if len(s) == 2]
    assert nested == ([] if under else [(3, 48)])
    counters = forward(params)["counters"]
    assert counters["loop_unrolled"].tolist() == [3.0 * layers * under, 3.0 * layers]
    _, rounds = split_probes({"obs_loop_unrolled": np.asarray(counters["loop_unrolled"])}, 1,
                             counters=model.meta["counters"])
    assert rounds[0]["loop_unrolled"] == float(under)


def test_the_cut_configuration_has_the_parameters_it_states():
    """406,884,353: four layers of 51,388,416 (attention 16,777,216, SwiGLU
    34,603,008, four gains 8,192), the untied vocabulary twice, the final norm
    and the gate's 2,049, from `jax.eval_shape` of the model's own `init`."""
    from benchmark.tests import test_ouro

    test_ouro.test_the_stated_parameter_count_is_the_programs()


# ---------------------------------------------------------------------------
# the scopes ISSUE 40 added (obs.trace.LOOP_SCOPES)
# ---------------------------------------------------------------------------

def test_the_loop_carries_its_names():
    """`loop/pass`, `loop/head` and `loop/exit` reach the round program's
    `op_name`s under `step/model`, forward and backward; the attention stays
    under `gqa` / `rope` / `attn` INSIDE `loop/pass` -- and ONE pass's code
    is there, the loop is a loop --, the head's product and
    the cross entropy under `loop/head`, the final norm under `loop/exit`."""
    from heterofl_tpu.obs import trace

    assert trace.LOOP_SCOPES == ("loop/pass", "loop/head", "loop/exit")
    assert not set(trace.LOOP_SCOPES) & set(
        trace.SCOPES + trace.EXTRA_SCOPES + trace.MIXER_SCOPES + trace.SPARSE_SCOPES)
    assert trace.SCOPE_VERSION >= 6  # bumped with the new names (the compile cache's key)
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
    for s in trace.LOOP_SCOPES:
        mine = [n for n in names if f"/{s}/" in n]
        assert any("/jvp(step/model)/" in n for n in mine), s
        assert any("transpose(" in n for n in mine), s
    for s in ("gqa", "rope", "attn"):
        # (the turn's position tables depend on nothing a step computes, and
        # the compiler's tracing lifts them out of `step/model` altogether)
        mine = [n for n in names if f"/{s}/" in n and "step/model" in n]
        assert mine and all("/loop/pass/" in n for n in mine), s
    assert any(re.search(r"/loop/head/.*/linear/dot_general", n) for n in names)
    assert any(re.search(r"/loop/head/.*/loss/", n) for n in names)
    assert any("/loop/exit/norm/" in n for n in names)
    assert not any("/loop/pass/" in n and "/loop/head/" in n for n in names)
    with pytest.raises(ValueError, match="Not valid scope"):
        trace.scope("loop")
