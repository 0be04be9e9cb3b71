"""The decoder families' contract (ISSUE 45): every row of
``config.DECODER_FAMILIES`` at its tiny preset -- that the table is the one
source, its slicing rules, counts and level tables, a masked-engine round in
chunks of one (the benchmark cells' setting), a round of the smallest level
alone, the grouped engine, the counters' ride on the metrics, `FedExperiment`
and the benchmark's tiny cell; one case a family (and a level where the levels
differ), each a test of its own, a family's rounds built once.  The model
against the plain reference is ``test_decoder_reference.py``; what is one
family's alone is ``test_<family>.py``.  A new family adds its cases here, not
a file.  (Two modules and not one: the driver runs ``--dist loadfile``, which
hands out whole files, those with the most tests first, and all these cases
in one file are a third of the suite on one worker.  Tests that build the same
program stay in one file: ``conftest.py`` on the compile cache.)"""

import functools
import importlib
import json
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from decoder_cases import FAMILIES, LEVELS, ROUNDS, reference, round_case, run_round, tiny
from heterofl_tpu import config as C
from heterofl_tpu.models import make_model
from heterofl_tpu.models.spec import Group, count_masks
from heterofl_tpu.parallel import make_mesh


def test_the_family_table_is_the_one_source():
    """`MODEL_NAMES`, `LM_MODEL_NAMES`, `process_control`'s `cfg[<family>]` and
    what `make_model` can build are read off `config.DECODER_FAMILIES`: a
    family is its row and the module of its name with the one maker of the
    shared signature; a name outside the table is refused as before."""
    import inspect

    assert C.LM_MODEL_NAMES == ("transformer",) + FAMILIES
    assert C.MODEL_NAMES[-len(C.LM_MODEL_NAMES):] == C.LM_MODEL_NAMES
    cfg = C.default_cfg()
    cfg["control"] = C.parse_control_name("1_10_0.5_iid_fix_a1-e1_bn_1_1")
    cfg["data_name"] = "WikiText2"
    cfg = C.process_control(cfg)
    for family in FAMILIES:
        assert cfg[family] == C.DECODER_FAMILIES[family]
        cfg[family]["hidden_size"] = 0  # a run's copy: the table is not the run's to change
        assert C.DECODER_FAMILIES[family]["hidden_size"] > 0
        maker = getattr(importlib.import_module(f"heterofl_tpu.models.{family}"), f"make_{family}")
        assert list(inspect.signature(maker).parameters) == [
            "num_tokens", "arch", "model_rate", "mask", "compute_dtype"]
        model = make_model(tiny(family).program_cfg())
        assert model.name == model.meta["kind"] == family and model.is_lm
    with pytest.raises(ValueError, match="Not valid model name"):
        make_model(dict(tiny("lfm2").program_cfg(), model_name="lfm3"))


def _nothing(*a):
    pass


# ---------------------------------------------------------------------------
# slicing, counting and the level tables
# ---------------------------------------------------------------------------

def _head_families(model, rate):
    """family of head groups -> the dims of a head its groups keep at ``rate``:
    every head of a group alike, a prefix in whole ``multiple``s."""
    kept = {}
    for name, g in model.groups.items():
        if g.kind == "per_head":
            m = np.asarray(g.mask(rate)).reshape(g.num_heads, g.size // g.num_heads)
            assert (m == m[0]).all(), name  # every head alike
            k = int(m[0].sum())
            assert m[0, :k].all() and k % g.multiple == 0, (name, k)  # a prefix, whole pairs
            assert int(g.active_count(rate)) == g.num_heads * k
            kept.setdefault(g.family, set()).add(k)
    return kept


def _pairs(hd, rate):
    return max(2, int(np.ceil(hd * rate)))


#: family -> (rate -> the dims a head of each family of head groups keeps; the
#: groups no level cuts; a group cut by another rule than its family's, and the
#: family the geometry check then refuses)
HEADS = {
    "lfm2": (lambda r: {"head": _pairs(16, r)}, ("router",),
             Group("kv_head", 2 * 16, kind="per_head", num_heads=2, multiple=1, coupled=False,
                   family="head")),
    "keye": (lambda r: {"head": _pairs(16, r), "index": _pairs(8, r)}, ("index", "router"),
             Group("ik_head", 8, kind="per_head", num_heads=1, multiple=1, coupled=False,
                   family="index")),
    "ouro": (lambda r: {"head": _pairs(32, r)}, ("gate",), None),
    "laguna": (lambda r: {"full.rope": _pairs(16, r), "full.nope": int(np.ceil(16 * r)),
                          "sliding.rope": _pairs(32, r), "head": _pairs(32, r)},
               ("full6.gate", "sliding8.gate", "router"), None),
    "nemotron_h": (lambda r: {"ssm": int(np.ceil(32 * r)), "head": int(np.ceil(16 * r))},
                   ("router",),
                   Group("kv_head", 2 * 16, kind="per_head", num_heads=2, multiple=2, coupled=False,
                         family="head")),
    # the query, key and value heads and the sub-norm's two: one family
    "phi4flash": (lambda r: {"head": int(np.ceil(16 * r))}, (),
                  Group("sub", 2 * 16, kind="per_head", num_heads=2, multiple=2, coupled=False,
                        family="head")),
}


@pytest.mark.parametrize("rate", LEVELS)
@pytest.mark.parametrize("family", list(HEADS))
def test_heads_keep_equal_dims_and_whole_pairs(family, rate):
    """Each family of head groups (query, key/value and head-norm heads; an
    indexer's; a kind's rotary and pass-through dims) keeps the SAME dims of a
    head at every level, the rotary ones in whole pairs (a prefix of the
    stored, pair-adjacent order); gates, routers and per-head weights are
    never cut; the geometry check holds the family to it, and refuses a group
    cut by another rule than its family's."""
    from heterofl_tpu.fed.core import validate_width_geometry

    want, never, odd = HEADS[family]
    cfg = tiny(family).program_cfg()
    model = make_model(cfg)
    assert _head_families(model, rate) == {k: {v} for k, v in want(rate).items()}
    for name in never:
        assert np.asarray(model.groups[name].mask(rate)).all()
    validate_width_geometry(model, cfg)
    if odd is not None:
        model.groups[odd.name] = odd
        with pytest.raises(ValueError, match=f"head family '{odd.family}' is inconsistent "
                                             "at rate 0.0625"):
            validate_width_geometry(model, cfg)


def _lfm2_counts(ref, shapes, cm):
    if cm is None:  # ONE label axis for the look-up and the head, no leaf beside it
        assert ref.LABEL_AXES == {"tok.w": 0}
        assert not [k for k in shapes if k.startswith(("head.", "embedding."))]
    else:
        assert np.asarray(cm["l1.moe.router.w"]).sum(axis=0).min() > 0  # all 16 columns
        assert np.asarray(cm["l1.moe.router.b"]).all()


def _keye_counts(ref, shapes, cm):
    if cm is not None:
        assert np.asarray(cm["l1.moe.router.w"]).sum(axis=0).min() > 0  # all 8 columns
        assert np.asarray(cm["l1.idx.w.w"]).sum(axis=0).min() > 0      # all 4 weights


def _nemotron_h_counts(ref, shapes, cm):
    if cm is not None:  # what no level cuts: the router's 16 columns, B, C, dt, the heads' vectors
        for k in ("l0.moe.router.w", "l1.ssm.in.b.w", "l1.ssm.in.c.w", "l1.ssm.in.dt.w"):
            assert np.asarray(cm[k]).sum(axis=0).min() > 0, k
        for k in ("l0.moe.router.b", "l1.ssm.a_log.w", "l1.ssm.dt_bias.w", "l1.ssm.skip.g",
                  "l1.ssm.conv.b.w", "l1.ssm.conv.c.b"):
            assert np.asarray(cm[k]).all(), k


def _phi4flash_counts(ref, shapes, cm):
    if cm is None:  # tied, as lfm2: ONE label axis, no leaf beside it
        assert ref.LABEL_AXES == {"tok.w": 0}
        assert not [k for k in shapes if k.startswith(("head.", "embedding."))]
    else:  # what no level cuts: the l* vectors, W_x's 8 + 8 + 8 columns, the state's 8, the rank's 8
        for k in ("l0.attn.lq1.w", "l4.attn.lk2.w", "l6.attn.lq2.w"):
            assert np.asarray(cm[k]).all(), k
        for k in ("l1.ssm.x.w", "l3.ssm.a_log.w"):
            assert np.asarray(cm[k]).sum(axis=0).min() > 0, k
        assert np.asarray(cm["l1.ssm.dt.w"]).sum(axis=1).min() > 0
        # ONE group for the inner channels of every mamba and gmu layer
        assert (np.asarray(cm["l3.ssm.skip.g"]) == np.asarray(cm["l5.gmu.in.w"])[0]).all()


def _ouro_counts(ref, shapes, cm):
    if cm is not None:
        assert np.asarray(cm["exit.b"]).all()


@pytest.mark.parametrize("family, extra", [("lfm2", _lfm2_counts), ("keye", _keye_counts),
                                           ("ouro", _ouro_counts), ("laguna", _nothing),
                                           ("nemotron_h", _nemotron_h_counts),
                                           ("phi4flash", _phi4flash_counts)])
def test_counts_follow_width_and_labels(family, extra):
    """A client counts for every element of its slice -- a frozen leaf's, a
    leaf's used several times a step once, an expert's it holds whether or not
    a token reached it --; embedding rows and head columns (a tied leaf's rows,
    once) follow the labels the client holds."""
    ref = reference(family)
    cfg = tiny(family).program_cfg()
    model = make_model(cfg)
    shapes = dict(model.meta["shapes"])
    assert ref.LABEL_AXES == {k: s.label_axis for k, s in model.specs.items()
                              if s.label_axis is not None}
    extra(ref, shapes, None)
    labels = np.zeros(cfg["num_tokens"], np.float32)
    labels[::3] = 1.0
    for rate in (1.0, 0.25, 0.0625):
        cm = count_masks(shapes, model.specs, model.groups, rate, jnp.asarray(labels))
        index = ref.index(shapes, tiny(family).reference_model(cfg), rate)
        for k, shape in shapes.items():
            want = np.zeros(shape, np.float32)
            want[np.ix_(*index[k])] = 1.0
            if k in ref.LABEL_AXES:
                view = [1] * len(shape)
                view[ref.LABEL_AXES[k]] = -1
                want = want * labels.reshape(view)
            np.testing.assert_array_equal(np.asarray(cm[k]), want, err_msg=f"{k} @ {rate}")
        extra(ref, shapes, cm)


def _lfm2_rows(cfg):
    """The tied head's product, which no leaf of its own shows, is counted."""
    from heterofl_tpu.analysis.summary import module_table

    rows = {r[0]: r for r in module_table(cfg, 1.0, 2)}
    a, t = cfg["lfm2"], 2 * cfg["bptt"]
    assert rows["head"][4] == t * a["hidden_size"] * cfg["num_tokens"]
    assert rows["l0.conv.taps"][4] == t * 3 * a["conv_dim"]
    assert rows["l1.attn.qk"][4] == 2 * 4 * (16 * 17 // 2) * a["head_dim"]
    assert rows["l2.moe.e4.g"][4] == t * 0.25 * a["hidden_size"] * a["moe_intermediate_size"]


def _ouro_rows(cfg):
    """`module_table` reads ``meta["profile"]["passes"]``: its matmul rows
    (every 2-D leaf but the embedding, and the attention's two products) hold
    `benchmark/flops/ouro.py`'s forward FLOPs at rate 1, every pass counted."""
    from benchmark import harness
    from heterofl_tpu.analysis.summary import module_table

    flops = harness.load_module("flops", "ouro")
    model, rows = tiny("ouro").reference_model(cfg), 2
    for passes in (3, 1):
        c = dict(cfg, ouro=dict(cfg["ouro"], total_ut_steps=passes))
        by_name = {r[0]: r for r in module_table(c, 1.0, rows)}
        macs = sum(r[4] for name, r in by_name.items()  # not the look-up, the gains, the bias
                   if name != "embedding" and not re.search(r"norm\d*\.g$|^exit\.b$", name))
        want = rows * flops.forward_flops(dict(model, total_ut_steps=passes), 1.0)
        assert 2 * macs == want, passes
        assert by_name["head"][4] == passes * rows * 32 * 128 * 96
        assert by_name["l1.attn.qk"][4] == passes * rows * 4 * (32 * 33 // 2) * 32
        assert by_name["embedding"][4] == rows * 32 * 128  # looked up once


def _laguna_rows(cfg):
    """`module_table` reads a site's window from ``meta["profile"]``: its
    matmul rows hold `benchmark/flops/laguna.py`'s forward FLOPs at rate 1, a
    sliding layer's two products over the band pairs at its 8 heads, a full
    layer's over the causal pairs at its 6."""
    from benchmark import harness
    from heterofl_tpu.analysis.summary import module_table

    flops = harness.load_module("flops", "laguna")
    model, rows = tiny("laguna").reference_model(cfg), 2
    by_name = {r[0]: r for r in module_table(cfg, 1.0, rows)}
    macs = sum(r[4] for name, r in by_name.items()
               if name != "embedding" and not re.search(r"norm\d*\.g$", name))
    assert 2 * macs == rows * flops.forward_flops(model, 1.0)
    band, causal = 16 * 17 // 2 + 48 * 16, 64 * 65 // 2
    assert flops.band_pairs(model) == band and flops.causal_pairs(model) == causal
    assert by_name["l1.attn.qk"][4] == rows * 8 * band * 32
    assert by_name["l0.attn.av"][4] == by_name["l4.attn.qk"][4] == rows * 6 * causal * 32


def _nemotron_h_rows(cfg):
    """`module_table` holds the family's matrices (a mixer's five in-projections,
    a routed expert at its share of the tokens) and the attention layer's two
    products; the scan's own products are no leaf's and it has no row for them
    (`benchmark/flops/nemotron_h.py` counts them)."""
    from heterofl_tpu.analysis.summary import module_table

    by_name = {r[0]: r for r in module_table(cfg, 1.0, 2)}
    a, t = cfg["nemotron_h"], 2 * cfg["bptt"]
    inner = a["mamba_num_heads"] * a["mamba_head_dim"]
    assert by_name["l1.ssm.in.x"][4] == by_name["l1.ssm.out"][4] == t * a["hidden_size"] * inner
    assert by_name["l1.ssm.in.dt"][4] == t * a["hidden_size"] * a["mamba_num_heads"]
    assert by_name["l0.moe.e2.u"][4] == t * (2 / 16) * a["hidden_size"] * a["moe_intermediate_size"]
    assert by_name["l0.moe.shared.d"][4] == t * 64 * a["hidden_size"]
    assert by_name["l6.attn.qk"][4] == by_name["l6.attn.av"][4] == 2 * 8 * (64 * 65 // 2) * 16
    assert not [n for n in by_name if "scan" in n]


def _phi4flash_rows(cfg):
    """`module_table` holds the family's matrices (the tied head's product
    too) and, for each attention layer, differential attention's two softmaxes
    a query pair -- 64-wide scores against a 128-wide value, here 16 and 32 --
    over the pairs a query sees: a sliding layer's band, every causal pair of
    the full and the cross layer; the scan is no leaf's and has no row."""
    from heterofl_tpu.analysis.summary import module_table

    by_name = {r[0]: r for r in module_table(cfg, 1.0, 2)}
    a, t = cfg["phi4flash"], 2 * cfg["bptt"]
    assert by_name["l1.ssm.in.x"][4] == by_name["l1.ssm.out"][4] == t * 128 * 256
    assert by_name["l1.ssm.x"][4] == t * 256 * (8 + 2 * 8) and by_name["l1.ssm.dt"][4] == t * 8 * 256
    assert by_name["l5.gmu.in"][4] == by_name["l5.gmu.out"][4] == t * 128 * 256
    assert by_name["l6.mlp.d"][4] == t * 256 * 128 and by_name["head"][4] == t * 128 * cfg["num_tokens"]
    causal, band = 64 * 65 // 2, 16 * 17 // 2 + (64 - 16) * 16
    assert a["sliding_window"] == 16
    for site, pairs in (("l0", band), ("l2", band), ("l4", causal), ("l6", causal)):
        assert by_name[f"{site}.attn.qk"][4] == 2 * 8 * pairs * 16, site
        assert by_name[f"{site}.attn.av"][4] == 2 * 8 * pairs * 32, site
    assert not [n for n in by_name if "scan" in n]


@pytest.mark.parametrize("family, rows", [("lfm2", _lfm2_rows), ("keye", _nothing),
                                          ("ouro", _ouro_rows), ("laguna", _laguna_rows),
                                          ("nemotron_h", _nemotron_h_rows),
                                          ("phi4flash", _phi4flash_rows)])
def test_level_tables_know_the_family(family, rows):
    """`level_param_table` counts the sliced sub-model's own leaves, the FLOP
    table falls with the level, and `analysis.summary.module_table` holds what
    the family's ``meta["profile"]`` says of it (``rows``)."""
    from heterofl_tpu.fed.core import level_flop_table, level_param_table

    cfg = tiny(family).program_cfg()
    for rate, n in level_param_table(cfg).items():
        shapes = jax.eval_shape(make_model(cfg, rate).init, jax.random.key(0))
        assert n == sum(int(np.prod(v.shape)) for v in shapes.values()), rate
    flops = level_flop_table(cfg)
    assert sorted(flops.values(), reverse=True) == [flops[r] for r in sorted(flops, reverse=True)]
    rows(cfg)


# ---------------------------------------------------------------------------
# through the engines
# ---------------------------------------------------------------------------

@functools.lru_cache(maxsize=None)
def masked_round(family):
    """(cfg, data, the parameters before, after, the metrics) of the family's
    round in chunks of one."""
    cfg, data = round_case(family)
    return (cfg, data) + run_round(cfg, data, 1)


@pytest.mark.parametrize("family", FAMILIES)
def test_masked_round_in_chunks_of_one_is_the_unchunked_round(family):
    """`round_chunk` 1, the cells' setting: one slot at a time is the round of
    one vmap over all 8 slots up to the order of float32 sums (1e-5 relative
    / 3e-6 absolute; a lost or doubled slot is off by 1e-2); with telemetry
    off no counter rides the metrics."""
    cfg, data, _, out, ms = masked_round(family)
    _, base, base_ms = run_round(cfg, data, None)
    for k in base:
        np.testing.assert_allclose(out[k], base[k], rtol=1e-5, atol=3e-6, err_msg=k)
    for k in ("loss_sum", "n", "rate"):
        np.testing.assert_allclose(ms[k], base_ms[k], rtol=1e-5)
    assert np.isfinite(ms["loss_sum"]).all() and (ms["n"] == 2 * ROUNDS[family][2]).all()
    assert not [k for k in ms if k.startswith("obs_")]


def _keye_moved(k, moved, new, before):
    # the indexer's leaves move by weight decay, which the frozen leaves are not
    # spared; a zero bias decays to zero
    assert moved or k.endswith(".b"), k
    assert (new["l0.idx.q.w"] != before["l0.idx.q.w"]).any()


def _every_leaf_moved(k, moved, new, before):
    assert moved, k


def _all_but_the_selection_bias_moved(k, moved, new, before):
    # the mixers' vectors a head, their convolution's bias and every expert's matrices move;
    # the router's selection bias gets no gradient and, zero, does not decay
    assert moved or k.endswith("moe.router.b"), k


@pytest.mark.parametrize("family, inside_moved", [
    ("keye", _keye_moved), ("ouro", _every_leaf_moved), ("laguna", _every_leaf_moved),
    ("nemotron_h", _all_but_the_selection_bias_moved)])
def test_a_level_e_round_leaves_everything_outside_its_slice(family, inside_moved):
    """The slicing round-trips: a round of the smallest level alone moves
    entries inside its slice -- a shared or a frozen leaf's as any leaf's -- and
    leaves everything outside bit for bit; rows (and head columns) of tokens
    nobody holds come back as they were."""
    ref = reference(family)
    cfg, data, before, out, _ = masked_round(family)
    held = np.asarray(data[1]).max(axis=0) > 0
    changed = out["embedding.tok.w"] != before["embedding.tok.w"]
    assert not changed[~held].any() and changed[held].any(axis=1).all()
    changed = out["head.w"] != before["head.w"]
    assert not changed[:, ~held].any() and changed[:, held].any(axis=0).all()
    small = [u for u in range(8) if cfg["model_rate"][u] == min(cfg["model_rate"])]
    _, new, _ = run_round(cfg, data, 1, users=np.resize(small, 8))
    index = ref.index({k: v.shape for k, v in before.items()}, tiny(family).reference_model(cfg),
                      min(cfg["model_rate"]))
    for k, b in before.items():
        inside = np.zeros(b.shape, bool)
        inside[np.ix_(*index[k])] = True
        moved = new[k] != b
        assert not moved[~inside].any(), k
        inside_moved(k, moved[inside].any(), new, before)


@pytest.mark.parametrize("family", FAMILIES)
def test_grouped_engine_trains_the_family_and_refuses_the_chunk(family):
    """The grouped engine's per-level dense programs take the family as any
    other (no validator tests a model's name): its round is the masked
    engine's up to the order of float32 sums through a round's steps at lr
    0.5.  What it lacks is the chunked cohort, refused by key at config
    resolution."""
    from heterofl_tpu.parallel.grouped import GroupedRoundEngine

    cfg, data, _, base, _ = masked_round(family)
    cfg = dict(cfg, strategy="grouped")
    model, users = make_model(cfg), np.arange(8)
    rates = np.asarray([cfg["model_rate"][u] for u in users], np.float32)
    out = GroupedRoundEngine(cfg, make_mesh(1, 1)).train_round(
        model.init(jax.random.key(0)), users, rates, data, 0.5, jax.random.key(5))[0]
    for k in base:
        np.testing.assert_allclose(out[k], base[k], atol=5e-3, err_msg=k)
    with pytest.raises(ValueError, match="round_chunk"):
        C.resolve_chunk_cfg(dict(cfg, round_chunk=1))


# ---------------------------------------------------------------------------
# the counters ride the metrics
# ---------------------------------------------------------------------------

def _reported(rec, declared, tmp_path):
    """`obs.report`'s summary and lines of a run of this one round: its
    `run-start` event carries the folds the model declares, as a run's does."""
    from heterofl_tpu.obs import report

    folds = {k: fold for k, (_, fold) in declared.items()}
    events = tmp_path / "events.jsonl"
    events.write_text("".join(json.dumps(e) + "\n" for e in (
        {"v": 1, "t": 0.0, "name": "run-start", "ph": "i", "args": {"counters": folds}},
        {"v": 1, "t": 0.0, "name": "probes", "cat": "obs", "ph": "i", "args": rec})))
    ev = report.summarize_events(str(events))
    return ev, report.render_events(ev)


def _experts(top_k, layers):
    def check(ms, rec, declared, tmp_path):
        """An expert layer's: tokens per held expert, pairs routed / on held
        experts / not computed -- the last always 0."""
        assert ms["obs_moe_tokens"].shape == (2 * 4,) and ms["obs_moe_assign"].shape == (2 * 3,)
        # 8 clients x 2 steps x (2 rows x 16 tokens) x top-k, in each of the expert layers
        assert rec["moe_assign"][0] == 8 * 2 * 32 * top_k * layers
        assert rec["moe_dropped"] == 0 and sum(rec["moe_tokens"]) == rec["moe_assign"][1]
        assert 0.0 < rec["moe_held_share"] < 1.0
        _no_compact_dispatch(ms, rec, 8 * 2 * layers)
    return check


def _no_compact_dispatch(ms, rec, applications):
    """`moe_compact` = (expert layer applications whose dispatch was the
    compact one, applications): at the tiny shapes (a quarter of the experts
    held, a tile as long as every pair) no compact branch is built, so none."""
    assert ms["obs_moe_compact"].shape == (2 * 2,)
    assert rec["moe_compact"] == [0.0, applications] and rec["moe_compact_share"] == 0.0


def _keye_counters(ms, rec, declared, tmp_path):
    """The indexer's beside the experts': each a (numerator, denominator) pair
    of sums a device, finished as keys selected a query, selected over causal
    pairs -- at 64 positions and ``topk`` 16: 904 / 64 and 904 / 2,080 -- the
    share of the selected attention's query tiles that went through the fused
    kernels: none on the CPU -- and the share of the selecting query blocks
    whose choice the layer kept: all."""
    assert ms["obs_sparse_selected"].shape == ms["obs_sparse_kept_share"].shape == (2 * 2,)
    # 8 clients x 2 layers x 2 rows x 4 query blocks of 16, none through the kernels
    assert ms["obs_sparse_fused"].reshape(2, 2).sum(axis=0).tolist() == [0.0, 8 * 2 * 2 * 4]
    # of those four blocks the three that end after topk select, and their choice is kept
    assert ms["obs_sparse_saved"].reshape(2, 2).sum(axis=0).tolist() == [8 * 2 * 2 * 3] * 2
    assert rec["sparse_selected"] == pytest.approx(904 / 64, rel=1e-6)
    assert rec["sparse_kept_share"] == pytest.approx(904 / 2080, rel=1e-6)
    assert rec["sparse_fused"] == 0.0 and rec["sparse_saved"] == 1.0
    assert ms["obs_moe_tokens"].shape == (2 * 4,) and ms["obs_moe_assign"].shape == (2 * 3,)
    # 8 clients x 1 step x (2 rows x 64 tokens) x top-2, in each of 2 layers
    assert rec["moe_assign"][0] == 8 * 128 * 2 * 2
    assert rec["moe_dropped"] == 0 and sum(rec["moe_tokens"]) == rec["moe_assign"][1]
    _no_compact_dispatch(ms, rec, 8 * 2)


def _ouro_counters(ms, rec, declared, tmp_path):
    """The loop's: `obs_loop_exit_share` and `obs_loop_pass_nll` (a sum a pass
    over the target positions and their count, a device) finished as the exit
    distribution's mean a pass -- which sums to 1 -- and each pass's mean
    negative log-likelihood; `obs_loop_passes` (a pair) as the expected pass,
    between 1 and 3; `obs_loop_kept`: 0 of the 8 clients' 3 x 2 layer
    applications here, where the block loop names nothing; `obs_loop_unrolled`:
    all 48 of 48, two layers being a short stack; `obs.report` renders them."""
    assert ms["obs_loop_exit_share"].shape == ms["obs_loop_pass_nll"].shape == (2 * 4,)
    assert ms["obs_loop_passes"].shape == ms["obs_loop_kept"].shape == (2 * 2,)
    assert ms["obs_loop_unrolled"].shape == (2 * 2,)
    assert ms["obs_loop_kept"].reshape(2, 2).sum(axis=0).tolist() == [0.0, 8 * 3 * 2]
    assert ms["obs_loop_unrolled"].reshape(2, 2).sum(axis=0).tolist() == [8 * 3 * 2, 8 * 3 * 2]
    # 8 clients x 1 step x 2 rows x 31 target positions, over the two devices
    assert ms["obs_loop_exit_share"].reshape(2, 4)[:, -1].sum() == 8 * 2 * 31
    assert len(rec["loop_exit_share"]) == len(rec["loop_pass_nll"]) == 3
    assert sum(rec["loop_exit_share"]) == pytest.approx(1.0, rel=1e-5)
    assert all(p > 0 for p in rec["loop_exit_share"])
    assert all(3.0 < v < 6.0 for v in rec["loop_pass_nll"])  # near log 96 = 4.56
    assert rec["loop_passes"] == pytest.approx(
        sum((t + 1) * p for t, p in enumerate(rec["loop_exit_share"])), rel=1e-5)
    assert rec["loop_kept"] == 0.0 and rec["loop_unrolled"] == 1.0
    ev, lines = _reported(rec, declared, tmp_path)
    assert ev["loop"]["rounds"] == 1 and ev["loop"]["exit_share"] == rec["loop_exit_share"]
    assert any(line.startswith("  loop over 1 rounds: expected pass") for line in lines)


def _laguna_counters(ms, rec, declared, tmp_path):
    """The window's three pairs beside the experts': `swa_pairs` = band over
    causal pairs (a window of 16 on rows of 32: 392 / 528), `swa_tiles` = 1
    here (one block holds the row) and `swa_fused` = 0 (the block loop);
    `obs.report` renders them."""
    for k in ("obs_swa_fused", "obs_swa_pairs", "obs_swa_tiles"):
        assert ms[k].shape == (2 * 2,), k
    # 8 clients x 1 step x 3 sliding layers x 2 rows
    assert ms["obs_swa_pairs"].reshape(2, 2).sum(axis=0).tolist() == [48 * 392.0, 48 * 528.0]
    assert rec["swa_pairs"] == pytest.approx(392 / 528) and rec["swa_tiles"] == 1.0
    assert rec["swa_fused"] == 0.0
    assert rec["moe_dropped"] == 0 and 0.0 < rec["moe_held_share"] < 1.0
    assert len(rec["moe_tokens"]) == 4
    _no_compact_dispatch(ms, rec, rec["moe_assign"][0] / (2 * 32 * 2))  # pairs over a layer's
    ev, lines = _reported(rec, declared, tmp_path)
    assert ev["swa"]["rounds"] == 1 and ev["swa"]["pairs"] == rec["swa_pairs"]
    assert any(line.startswith("  sliding layers over 1 rounds: band over causal pairs 0.7424")
               for line in lines)


def _nemotron_h_counters(ms, rec, declared, tmp_path):
    """The scan's three beside the experts': `ssm_keep` (a sum of `exp(dt A)`
    and its count, a device) finished as the mean share of the state a position
    keeps, inside (0, 1) and near 1 (dt about 0.01); `ssm_chunks`, chunks
    scanned: rows of 32 are two chunks of 16; `ssm_fused`, the share of the
    state-space layers whose scan the fused kernels took: 0 of 24 off a TPU."""
    assert ms["obs_ssm_keep"].shape == (2 * 2,) and ms["obs_ssm_chunks"].shape == (2 * 1,)
    # 8 clients x 1 step x 3 mixers
    assert ms["obs_ssm_fused"].reshape(2, 2).sum(axis=0).tolist() == [0.0, 24.0]
    assert rec["ssm_fused"] == 0.0
    # 8 clients x 1 step x 3 mixers x 2 rows x 2 chunks; x 32 positions x 8 heads
    assert rec["ssm_chunks"] == [8 * 3 * 2 * 2]
    assert ms["obs_ssm_keep"].reshape(2, 2)[:, 1].sum() == 8 * 3 * 2 * 32 * 8
    assert len(rec["ssm_keep"]) == 1 and 0.8 < rec["ssm_keep"][0] < 1.0
    assert ms["obs_moe_tokens"].shape == (2 * 4,) and ms["obs_moe_assign"].shape == (2 * 3,)
    # 8 clients x 1 step x (2 rows x 32 tokens) x top-2, in each of 3 expert layers
    assert rec["moe_assign"][0] == 8 * 64 * 2 * 3
    assert rec["moe_dropped"] == 0 and sum(rec["moe_tokens"]) == rec["moe_assign"][1]
    assert 0.0 < rec["moe_held_share"] < 1.0
    _no_compact_dispatch(ms, rec, 8 * 3)


def _phi4flash_counters(ms, rec, declared, tmp_path):
    """The scan's two as Nemotron-H carries them, and the family's three:
    `diff_lambda` (a sum of the attention layers' `lam` and their number, a
    device) finished as their mean, near the mean of `lam0` at published layers
    13, 15, 17, 19 (the four vectors start small); `diff_fused`, softmaxes a
    fused kernel pair took over softmaxes: none off a TPU; `side_reads`, layers
    that read another layer's value: the gated memory unit and the cross layer."""
    from heterofl_tpu.models.phi4flash import lam0_of

    assert ms["obs_ssm_keep"].shape == ms["obs_diff_lambda"].shape == (2 * 2,)
    # 8 clients x 1 step x 2 mamba layers x 2 rows x 1 chunk; x 32 positions x 256 channels x 8
    assert rec["ssm_chunks"] == [8 * 2 * 2]
    assert ms["obs_ssm_keep"].reshape(2, 2)[:, 1].sum() == 8 * 2 * 2 * 32 * 256 * 8
    assert len(rec["ssm_keep"]) == 1 and 0.8 < rec["ssm_keep"][0] < 1.0
    # 8 clients x 4 attention layers, two softmaxes each
    assert ms["obs_diff_lambda"].reshape(2, 2)[:, 1].sum() == 8 * 4
    assert rec["diff_lambda"][0] == pytest.approx(np.mean([lam0_of(i) for i in (13, 15, 17, 19)]),
                                                  abs=0.05)
    assert ms["obs_diff_fused"].reshape(2, 2).sum(axis=0).tolist() == [0.0, 8 * 4 * 2]
    assert rec["diff_fused"] == 0.0
    assert rec["side_reads"] == [8 * 2]


@pytest.mark.parametrize("family, check", [
    ("kanana2", _experts(3, 1)), ("lfm2", _experts(4, 3)), ("keye", _keye_counters),
    ("ouro", _ouro_counters), ("laguna", _laguna_counters),
    ("nemotron_h", _nemotron_h_counters), ("phi4flash", _phi4flash_counters)])
def test_counters_ride_the_metrics(family, check, tmp_path):
    """telemetry='on' carries the counters a model declares out of a round on
    two devices as per-device partial sums; `obs.split_probes` finishes each by
    the fold its model declares (``meta['counters']``) and leaves no probe in
    the metrics."""
    from heterofl_tpu.obs import split_probes

    cfg, data = round_case(family)
    _, _, ms = run_round(cfg, data, 1, n_dev=2, telemetry="on")
    declared = make_model(cfg).meta["counters"]
    assert {"obs_" + k for k in declared} <= set(ms)
    clean, rounds = split_probes(dict(ms), 2, counters=declared)
    assert not [k for k in clean if k.startswith("obs_")]
    check(ms, rounds[0], declared, tmp_path)


# ---------------------------------------------------------------------------
# the entry point
# ---------------------------------------------------------------------------

#: family -> (overrides of the tiny shape, bptt, test tokens a row, a check of
#: (leaves moved, leaves, the parameters after a round))
def _most_moved(moved, n, params):
    assert moved > n // 2


def _most_moved_and_the_selection_bias_got_no_gradient(moved, n, params):
    assert moved > n // 2 and not np.asarray(params["l1.moe.router.b"]).any()


def _all_moved(moved, n, params):
    assert moved == n


def _all_but_the_three_selection_biases_moved(moved, n, params):
    assert moved == n - 3 and not any(
        np.asarray(params[f"l{i}.moe.router.b"]).any() for i in (0, 2, 4))


def _all_moved_but_perhaps_a_key_bias(moved, n, params):
    # three layers have keys of their own, whose bias no softmax sees: its gradient is rounding noise
    assert moved >= n - 3


ENTRY = {
    "kanana2": (dict(num_hidden_layers=2), 16, 16,
                _most_moved_and_the_selection_bias_got_no_gradient),
    "lfm2": ({}, 16, 16, _most_moved_and_the_selection_bias_got_no_gradient),
    "keye": ({}, 64, 64, _most_moved),
    "ouro": ({}, 32, 32, _all_moved),
    "laguna": ({}, 32, 32, _all_moved),
    "nemotron_h": ({}, 32, 32, _all_but_the_three_selection_biases_moved),
    "phi4flash": ({}, 32, 32, _all_moved_but_perhaps_a_key_bias),
}


@pytest.mark.parametrize("family", FAMILIES)
def test_trains_and_evaluates_through_the_entry_point(family, tmp_path):
    """One whole `FedExperiment.train_round` (masked engine, `round_chunk` 1)
    and one `evaluate`, built as `entry.common.run_main` builds them from the
    command line: `--model_name <family>` is all that names the family."""
    from heterofl_tpu.entry.common import FedExperiment, build_cli, cfg_from_args
    from heterofl_tpu.utils.logger import Logger

    arch, bptt, test_tokens, check = ENTRY[family]
    override = {family: dict(tiny(family).ARCH, **arch), "bptt": bptt,
                "batch_size": {"train": 20, "test": 10}, "round_chunk": 1,
                "num_epochs": {"global": 2, "local": 1}}
    argv = ["--control_name", "1_10_0.5_iid_fix_a1-b1-c1-d1-e1_bn_1_1",
            "--model_name", family, "--data_name", "WikiText2", "--synthetic", "1",
            "--synthetic_sizes", json.dumps({"train": 20 * max(32, bptt),
                                             "test": 10 * test_tokens}),
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
    check(len(moved), len(before), params)
    named = exp.evaluate(params, 1, logger, label_split)
    assert np.isfinite(named["Global-Loss"]) and named["Global-Perplexity"] > 1.0


# ---------------------------------------------------------------------------
# the benchmark's tiny cell
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("family", FAMILIES)
def test_tiny_cell_is_correct_and_its_control_is_not(family, monkeypatch, capsys):
    """`benchmark/checks.compare` on the tiny configuration, through the
    benchmark's own command: sound as returned, not `correct` once the check
    rounds' result has passed through bfloat16 (the test lives with the
    benchmark's; run here so that the gate holds it)."""
    importlib.import_module(f"benchmark.tests.test_{family}") \
        .test_a_sound_run_of_the_tiny_cell_is_correct_and_the_control_is_not(monkeypatch, capsys)
