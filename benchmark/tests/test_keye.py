"""The Keye cell's files on the CPU at a tiny size: the comparison that decides
`correct` on a sound run and under the control, the configuration against the
catalog's row and the program's own shapes, the FLOP file against the model's
matrices and the selection's arithmetic, the seven readers on a trace with the
scopes and on one without."""

import json
import os

import numpy as np
import pytest

from benchmark import harness, scope_reduce, scope_reduce_keye, scope_reduce_lfm2, scope_reduce_moe
from benchmark import run as bench_run
from benchmark.tests import tiny_keye as tiny

NAME = tiny.NAME
METRICS = ("indexer_ms.step", "select_ms.step", "sparse_attn_ms.step", "sparse_attn_roofline_pct",
           "keye_router_ms.step", "keye_experts_ms.step", "keye_experts_roofline_pct")
CATALOG = "/opt/skills/guides/model-configs/architectures.jsonl"


def test_a_sound_run_of_the_tiny_cell_is_correct_and_the_control_is_not(monkeypatch, capsys):
    """One process, both verdicts: the check rounds as returned are sound by
    every limit; passed through bfloat16 they fail."""
    cell, config = tiny.cell()
    monkeypatch.setattr(harness, "load_cell", lambda n: (cell, config))
    real = harness.load_json
    monkeypatch.setattr(harness, "load_json", lambda *p: (
        {"cpu": {"bf16_flops_per_s": 1e12}} if p[-1] == "peaks.json" else real(*p)))
    args = bench_run.parse(["--workload", NAME, "--seed", "3500000017",
                            "--seconds", "1", "--trace", "0", "--control", "program_bf16"])
    assert bench_run.run(args, require_tpu=False) == 0
    out = capsys.readouterr().out
    line = json.loads(out.strip().splitlines()[-1])
    assert line["correct"] is False and line["failed"] == 0, out
    failed = {l.split()[2].rstrip(":") for l in out.splitlines()
              if l.startswith("benchmark: control ") and l.endswith("FAILED")}
    assert {"identity_ulp", "update_norm_gap", "outside_slice_changed"} <= failed, out
    assert not [l for l in out.splitlines()
                if l.startswith("benchmark: check ") and l.endswith("FAILED")], out
    for name in ("identity_ulp", "level_loss_gap", "update_norm_gap",
                 "outside_slice_changed", "window_compiles"):
        assert f"check {name}:" in out
    assert set(line["metrics"]) == {"round_s", "client_steps_per_s", "setup_s"}


def test_the_configuration_states_the_published_shape_and_its_cuts():
    manifest = harness.load_json("..", "BENCHMARK.json")
    entry = next(c for c in manifest["configs"] if c["name"] == "keye-vl-2-30b-a3b")
    config = harness.load_json("configs", "keye-vl-2-30b-a3b.json")
    cut = {"num_hidden_layers": 5, "num_experts": 8, "vocab_size": 18992}
    assert entry["reduced"] == config["reduced"] and set(config["reduced"]) == set(cut)
    assert set(config["reduced"]) <= set(config["reduced_notes"])
    assert "16-way expert-parallel" in config["reduced_notes"]["deployment"]
    assert entry["source"] == config["source"]
    if os.path.exists(CATALOG):  # every key of the catalog's row, the cuts apart
        with open(CATALOG, encoding="utf-8") as f:
            row = next(r for r in map(json.loads, f) if r["name"] == "Keye-VL-2.0-30B-A3B")
        assert row["source_url"] == config["source"]
        for key, value in row["config"].items():
            assert config[key] == cut.get(key, value), key
        assert 8 * cut["vocab_size"] == row["config"]["vocab_size"]  # an eighth, the floor
    m, sa = config["model"], config["sa_config"]
    for key in ("hidden_size", "moe_intermediate_size", "num_attention_heads", "head_dim",
                "num_key_value_heads", "num_experts_per_tok", "rms_norm_eps", "rope_theta"):
        assert config[key] == m[key], key
    assert (m["index_n_heads"], m["index_head_dim"], m["index_topk"]) == (
        sa["indexer_num_heads"], sa["indexer_head_dim"], sa["topk"]) == (16, 64, 2048)
    assert sa["indexer_num_kv_heads"] == 1
    assert m["num_experts"] == config["num_local_experts"] == 128
    assert m["expert_share"] == [0, 16] and m["experts_held"] == config["num_experts"] == 8
    assert m["num_tokens"] == config["vocab_size"] == config["data"]["sizes"]["types"]
    assert config["cfg_overrides"]["keye"] == {"num_hidden_layers": 5, "expert_share": [0, 16]}
    cell = harness.load_json("workloads", NAME + ".json")
    assert cell["traffic"]["cfg_overrides"] == {"round_chunk": 1} and cell["chips"] == 1
    rows, tokens = config["federation"]["batch_rows"], config["data"]["sizes"]["train"]
    assert tokens // rows == m["bptt"] == 8192 > m["index_topk"] and tokens % 33 == 0  # 1 local step
    assert config["federation"]["rows_per_user"] * config["federation"]["num_users"] == rows
    lfm2 = harness.load_json("configs", "lfm2-8b-a1b.json")
    assert config["data"]["sizes"]["train"] == lfm2["data"]["sizes"]["train"]
    assert config["data"]["sizes"]["test"] == lfm2["data"]["sizes"]["test"]


def _cell_cfg(config):
    m = config["model"]
    cfg = tiny.program_cfg(bptt=m["bptt"], **{k: m[k] for k in m if k in
                                               {**tiny.ARCH, "rms_norm_eps": 0, "rope_theta": 0}})
    cfg["num_tokens"] = m["num_tokens"]
    return cfg


def test_the_stated_parameter_count_is_the_programs():
    import jax

    from heterofl_tpu.models import make_model

    config = harness.load_json("configs", "keye-vl-2-30b-a3b.json")
    shapes = jax.eval_shape(make_model(_cell_cfg(config)).init, jax.random.key(0))
    assert sum(int(np.prod(v.shape)) for v in shapes.values()) == config["parameters"] == 373546880
    layer = sum(int(np.prod(v.shape)) for k, v in shapes.items() if k.startswith("l0."))
    assert layer == 59150720 and 5 * layer + 2 * 18992 * 2048 + 2048 == config["parameters"]
    assert sum(int(np.prod(v.shape)) for k, v in shapes.items() if k.startswith("l0.idx.")) == 2261120


@pytest.mark.parametrize("rate", [1.0, 0.25])
def test_flops_count_the_models_own_matrices_and_the_selected_pairs(rate):
    """At rate r the FLOP file's widths are the program's sub-model's: the
    multiply-adds a token outside the attention's and the indexer's scores are
    the 2-D leaves' sizes (a routed expert at its expected share of the
    tokens; the indexer's three counted once, the rest three times), and
    what is left is the two products over the SELECTED pairs and the
    indexer's score of every causal pair."""
    import jax

    from heterofl_tpu.models import make_model

    config = harness.load_json("configs", "keye-vl-2-30b-a3b.json")
    flops = harness.load_module("flops", "keye")
    m = config["model"]
    assert flops.selected_pairs(m) == 14681088 and flops.causal_pairs(m) == 33558528
    assert flops.selected_pairs(m) / m["bptt"] == pytest.approx(1792.125)
    assert flops.selected_pairs(dict(m, bptt=2048)) == flops.causal_pairs(dict(m, bptt=2048))
    assert flops.index_forward_flops(dict(m, bptt=2048), 1.0) == 0  # no indexer runs there
    shapes = jax.eval_shape(make_model(_cell_cfg(config), rate).init, jax.random.key(0))
    trained = index = routed = 0.0
    for name, v in shapes.items():
        if v.ndim != 2 or name.startswith("embedding."):
            continue
        n = 2.0 * float(np.prod(v.shape))
        if ".moe.e" in name:  # a routed expert sees top_k / n_experts of the tokens
            n *= m["num_experts_per_tok"] / m["num_experts"]
            routed += n
        if ".idx." in name:
            index += n
        else:
            trained += n
    s = m["bptt"]
    hd, di = (-(-int(np.ceil(m[k] * rate)) // 2) * 2 for k in ("head_dim", "index_head_dim"))
    assert flops.trained_forward_flops(m, rate) - s * trained == pytest.approx(
        5 * 2 * 2 * 14681088 * 32 * hd, rel=1e-9)
    assert flops.index_forward_flops(m, rate) - s * index == pytest.approx(
        5 * 2 * 33558528 * 16 * di, rel=1e-9)
    assert flops.routed_forward_flops(m, rate) == pytest.approx(s * routed, rel=1e-12)
    assert flops.step_flops(config, rate) == pytest.approx(
        3 * flops.trained_forward_flops(m, rate) + flops.index_forward_flops(m, rate))
    if rate == 1.0:
        whole = flops.executed_step_flops(config)
        assert whole == flops.step_flops(config, 1.0)
        assert flops.executed_sparse_attn_step_flops(config) == 3 * 5 * 4 * 14681088 * 4096
        assert 0.30 < flops.executed_sparse_attn_step_flops(config) / whole < 0.34  # "a third"
        assert 0.04 < flops.executed_index_step_flops(config) / whole < 0.05
        assert 0.04 < flops.executed_routed_step_flops(config) / whole < 0.06


def _rows(*paths):
    """A by-scope table as `scope_reduce.reduce_scopes` gives it, 2 rounds."""
    return {"rows": [[p, d, "fusion", s, True] for p, d, s in paths],
            "total_s": sum(s for _, _, s in paths), "rounds": 2}


INFO = {"name": NAME, "steps_per_round": 1, "peak_flops_per_s": 197e12}
BASE = "round/chunk/round/local_train/step/model/"


@pytest.mark.parametrize("metric", METRICS)
def test_a_reader_finds_its_scopes_and_reports_nothing_without_them(metric, monkeypatch):
    """On a table with the new scopes each of the seven metrics reads its own
    rows (seconds over 2 rounds and 1 step a round, as milliseconds): the
    indexer's turn is the indexer's and not the attention's; on a parent's
    table, where no path carries them, and without a traced run it returns
    None and does not raise."""
    mod = harness.load_module("layer_metrics", metric)
    with_scopes = _rows((BASE + "sparse/index", "fwd", 0.4),
                        (BASE + "sparse/index/rope", "fwd", 0.1),
                        (BASE + "sparse/index/norm", "fwd", 0.1),
                        (BASE + "sparse/select", "fwd", 0.2),
                        (BASE + "gqa/linear", "fwd", 0.08), (BASE + "rope", "fwd", 0.02),
                        (BASE + "attn", "bwd", 1.0), (BASE + "moe/router", "fwd", 0.03),
                        (BASE + "moe/dispatch", "bwd", 0.05),
                        (BASE + "moe/experts/linear", "bwd", 0.3),
                        (BASE + "linear", "fwd", 1.0), ("round/aggregate", "", 0.5))
    monkeypatch.setitem(scope_reduce_keye._memo, "table", with_scopes)
    value = mod.compute({"busy_s": 1.0}, [], INFO)
    config = harness.load_json("configs", "keye-vl-2-30b-a3b.json")
    flops = harness.load_module("flops", "keye")
    want = {"indexer_ms.step": 300.0, "select_ms.step": 100.0, "sparse_attn_ms.step": 550.0,
            "keye_router_ms.step": 40.0, "keye_experts_ms.step": 150.0,
            "sparse_attn_roofline_pct":
                100 * 10 * flops.executed_sparse_attn_step_flops(config) / 197e12 / 0.5,
            "keye_experts_roofline_pct":
                100 * 10 * flops.executed_routed_step_flops(config) / 197e12 / 0.150}[metric]
    assert value == pytest.approx(want, rel=1e-9)
    parent = _rows((BASE + "linear", "fwd", 1.0), (BASE + "norm", "bwd", 0.1))
    for table in (parent, None):  # no row of its scopes; no table at all
        monkeypatch.setitem(scope_reduce_keye._memo, "table", table)
        assert mod.compute({"busy_s": 1.0}, [], INFO) is None
    assert mod.compute(None, [], INFO) is None
    assert scope_reduce_lfm2._memo is not scope_reduce_keye._memo  # lent, and put back


def test_the_reader_lends_the_longer_list_for_one_read_and_puts_it_back(monkeypatch):
    from heterofl_tpu.obs import trace

    assert scope_reduce_keye.SPARSE_SCOPES == trace.SPARSE_SCOPES
    seen = {}

    def fake_table():
        seen["scopes"] = scope_reduce_moe.EXTRA_SCOPES
        seen["pairs"], seen["singles"] = scope_reduce_moe._widened()
        return None

    monkeypatch.setattr(scope_reduce_moe, "table", fake_table)
    monkeypatch.setattr(scope_reduce_keye, "_memo", {})
    kept = scope_reduce_lfm2._memo
    kept["table"] = "kept"
    try:
        assert scope_reduce_keye.table() is None
        assert scope_reduce_lfm2._memo is kept and kept == {"table": "kept"}
    finally:
        kept.clear()
    assert seen["scopes"] == trace.EXTRA_SCOPES + trace.MIXER_SCOPES + trace.SPARSE_SCOPES
    assert scope_reduce_lfm2.MIXER_SCOPES == trace.MIXER_SCOPES
    assert scope_reduce_moe.EXTRA_SCOPES == trace.EXTRA_SCOPES
    assert {("sparse", "index"), ("sparse", "select")} <= seen["pairs"]
    # a path as the compiled program writes it, under the widened vocabulary
    name = ("jit(body)/round/chunk/round/local_train/while/body/closed_call/"
            "jvp(step/model)/while/body/checkpoint/sparse/index/rope/mul")
    before = scope_reduce._PAIRS, scope_reduce._SINGLES
    scope_reduce._PAIRS, scope_reduce._SINGLES = seen["pairs"], seen["singles"]
    try:
        assert scope_reduce.scope_of(name) == (BASE + "sparse/index/rope", "fwd")
    finally:
        scope_reduce._PAIRS, scope_reduce._SINGLES = before
    assert scope_reduce.scope_of(name)[0] == "round/local_train/step/model"
