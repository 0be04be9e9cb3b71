"""The LFM2 cell's files on the CPU at a tiny size: the comparison that decides
`correct` on a sound run and under the control, the configuration against the
catalog's row and the program's own shapes, the FLOP file against the model's
matrices, the six readers on a trace with the scopes and on one without."""

import json
import os

import numpy as np
import pytest

from benchmark import harness, scope_reduce, scope_reduce_lfm2, scope_reduce_moe
from benchmark import run as bench_run
from benchmark.tests import tiny_lfm2 as tiny

NAME = tiny.NAME
METRICS = ("shortconv_ms.step", "shortconv_roofline_pct", "gqa_ms.step",
           "lfm2_router_ms.step", "lfm2_experts_ms.step", "lfm2_experts_roofline_pct")
CATALOG = "/opt/skills/guides/model-configs/architectures.jsonl"


def test_a_sound_run_of_the_tiny_cell_is_correct_and_the_control_is_not(monkeypatch, capsys):
    """One process, both verdicts: the check rounds as returned are sound by
    every limit; passed through bfloat16 they fail."""
    cell, config = tiny.cell()
    monkeypatch.setattr(harness, "load_cell", lambda n: (cell, config))
    real = harness.load_json
    monkeypatch.setattr(harness, "load_json", lambda *p: (
        {"cpu": {"bf16_flops_per_s": 1e12}} if p[-1] == "peaks.json" else real(*p)))
    args = bench_run.parse(["--workload", NAME, "--seed", "3200000017",
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
    entry = next(c for c in manifest["configs"] if c["name"] == "lfm2-8b-a1b")
    config = harness.load_json("configs", "lfm2-8b-a1b.json")
    cut = {"num_hidden_layers": 5, "num_dense_layers": 1, "num_experts": 8, "vocab_size": 16384,
           "layer_types": ["conv", "full_attention", "conv", "conv", "conv"]}
    assert entry["reduced"] == config["reduced"] and set(config["reduced"]) == set(cut)
    assert set(config["reduced"]) <= set(config["reduced_notes"])
    assert "4-way expert-parallel" in config["reduced_notes"]["deployment"]
    assert entry["source"] == config["source"]
    if os.path.exists(CATALOG):  # every key of the catalog's row, the cuts apart
        with open(CATALOG, encoding="utf-8") as f:
            row = next(r for r in map(json.loads, f) if r["name"] == "LFM2-8B-A1B")
        assert row["source_url"] == config["source"]
        for key, value in row["config"].items():
            assert config[key] == cut.get(key, value), key
        assert row["config"]["layer_types"][1:6] == cut["layer_types"]
    m = config["model"]
    for key in ("hidden_size", "intermediate_size", "moe_intermediate_size", "conv_L_cache",
                "num_attention_heads", "num_key_value_heads", "num_experts_per_tok", "norm_eps"):
        assert config[key] == m[key], key
    assert m["num_experts"] == 32 and m["expert_share"] == [0, 4] and m["experts_held"] == 8
    assert m["head_dim"] * m["num_attention_heads"] == m["hidden_size"] == m["conv_dim"]
    assert m["num_tokens"] == config["vocab_size"] == config["data"]["sizes"]["types"]
    assert config["cfg_overrides"]["lfm2"]["layer_types"] == m["layer_types"] == cut["layer_types"]
    cell = harness.load_json("workloads", NAME + ".json")
    assert cell["traffic"]["cfg_overrides"] == {"round_chunk": 1}  # no inert layout_policy
    rows, tokens = config["federation"]["batch_rows"], config["data"]["sizes"]["train"]
    assert tokens // rows == 2 * m["bptt"] and tokens % 33 == 0  # 2 local steps


def _cell_cfg(config):
    m = config["model"]
    cfg = tiny.program_cfg(**{k: m[k] for k in m if k in
                              {**tiny.ARCH, "conv_L_cache": 0, "norm_eps": 0, "rope_theta": 0}})
    cfg["num_tokens"] = m["num_tokens"]
    return cfg


def test_the_stated_parameter_count_is_the_programs():
    import jax

    from heterofl_tpu.models import make_model

    config = harness.load_json("configs", "lfm2-8b-a1b.json")
    shapes = jax.eval_shape(make_model(_cell_cfg(config)).init, jax.random.key(0))
    assert sum(int(np.prod(v.shape)) for v in shapes.values()) == config["parameters"] == 507820288


@pytest.mark.parametrize("rate", [1.0, 0.25])
def test_flops_count_the_models_own_matrices(rate):
    """At rate r the FLOP file's widths are the program's sub-model's: the
    multiply-adds a token outside attention's scores are the 2-D leaves'
    sizes (a routed expert at its expected share of the tokens, the tied leaf
    as the head), and what is left is causal attention's."""
    import jax

    from heterofl_tpu.models import make_model

    config = harness.load_json("configs", "lfm2-8b-a1b.json")
    flops = harness.load_module("flops", "lfm2")
    m = config["model"]
    shapes = jax.eval_shape(make_model(_cell_cfg(config), rate).init, jax.random.key(0))
    per_token = conv = routed = 0.0
    for name, v in shapes.items():
        if v.ndim != 2:
            continue
        n = 2.0 * float(np.prod(v.shape))
        if ".moe.e" in name:  # a routed expert sees top_k / n_experts of the tokens
            n *= m["num_experts_per_tok"] / m["num_experts"]
            routed += n
        if ".conv.in." in name or ".conv.out." in name:
            conv += n
        per_token += n
    s = m["bptt"]
    hd = -(-int(np.ceil(m["head_dim"] * rate)) // 2) * 2
    attn = flops.forward_flops(m, rate) - s * per_token
    assert attn == pytest.approx(2 * 2 * (s * (s + 1) // 2) * 32 * hd, rel=1e-9)
    assert flops.shortconv_forward_flops(m, rate) == pytest.approx(s * conv, rel=1e-12)
    assert flops.routed_forward_flops(m, rate) == pytest.approx(s * routed, rel=1e-12)
    if rate == 1.0:
        assert flops.executed_step_flops(config) == flops.step_flops(config, 1.0)
        whole = flops.executed_step_flops(config)
        assert 0.19 < flops.executed_routed_step_flops(config) / whole < 0.24  # "about a fifth"
        assert 0.30 < flops.executed_shortconv_step_flops(config) / whole < 0.36


def _rows(*paths):
    """A by-scope table as `scope_reduce.reduce_scopes` gives it, 2 rounds."""
    return {"rows": [[p, d, "fusion", s, True] for p, d, s in paths],
            "total_s": sum(s for _, _, s in paths), "rounds": 2}


INFO = {"name": NAME, "steps_per_round": 2, "peak_flops_per_s": 197e12}
BASE = "round/chunk/round/local_train/step/model/"


@pytest.mark.parametrize("metric", METRICS)
def test_a_reader_finds_its_scopes_and_reports_nothing_without_them(metric, monkeypatch):
    """On a table with the new scopes each of the six metrics reads its own
    rows (seconds over 2 rounds and 2 steps a round, as milliseconds); on a
    parent's table, where no path carries them, and without a traced run it
    returns None and does not raise."""
    mod = harness.load_module("layer_metrics", metric)
    with_scopes = _rows((BASE + "shortconv/linear", "fwd", 0.4),
                        (BASE + "shortconv/shortconv/gate", "bwd", 0.2),
                        (BASE + "gqa/linear", "fwd", 0.08), (BASE + "rope", "fwd", 0.02),
                        (BASE + "attn", "bwd", 0.1), (BASE + "moe/router", "fwd", 0.03),
                        (BASE + "moe/dispatch", "bwd", 0.05),
                        (BASE + "moe/experts/linear", "bwd", 0.3),
                        (BASE + "linear", "fwd", 1.0), ("round/aggregate", "", 0.5))
    monkeypatch.setitem(scope_reduce_lfm2._memo, "table", with_scopes)
    value = mod.compute({"busy_s": 1.0}, [], INFO)
    config = harness.load_json("configs", "lfm2-8b-a1b.json")
    flops = harness.load_module("flops", "lfm2")
    want = {"shortconv_ms.step": 150.0, "gqa_ms.step": 50.0, "lfm2_router_ms.step": 20.0,
            "lfm2_experts_ms.step": 75.0,
            "shortconv_roofline_pct":
                100 * 10 * flops.executed_shortconv_step_flops(config) / 197e12 / 0.150,
            "lfm2_experts_roofline_pct":
                100 * 10 * flops.executed_routed_step_flops(config) / 197e12 / 0.075}[metric]
    assert value == pytest.approx(want, rel=1e-9)
    parent = _rows((BASE + "linear", "fwd", 1.0), (BASE + "norm", "bwd", 0.1))
    for table in (parent, None):  # no row of its scopes; no table at all
        monkeypatch.setitem(scope_reduce_lfm2._memo, "table", table)
        assert mod.compute({"busy_s": 1.0}, [], INFO) is None
    assert mod.compute(None, [], INFO) is None


def test_the_reader_widens_the_vocabulary_for_one_read_and_puts_it_back(monkeypatch):
    from heterofl_tpu.obs import trace

    assert scope_reduce_lfm2.MIXER_SCOPES == trace.MIXER_SCOPES
    seen = {}

    def fake_table():
        seen["scopes"] = scope_reduce_moe.EXTRA_SCOPES
        seen["pairs"], seen["singles"] = scope_reduce_moe._widened()
        return None

    monkeypatch.setattr(scope_reduce_moe, "table", fake_table)
    monkeypatch.setattr(scope_reduce_lfm2, "_memo", {})
    scope_reduce_moe._memo["table"] = "kept"
    try:
        assert scope_reduce_lfm2.table() is None
        assert scope_reduce_moe._memo == {"table": "kept"}
    finally:
        scope_reduce_moe._memo.clear()
    assert seen["scopes"] == trace.EXTRA_SCOPES + trace.MIXER_SCOPES
    assert scope_reduce_moe.EXTRA_SCOPES == trace.EXTRA_SCOPES
    assert ("shortconv", "gate") in seen["pairs"] and {"shortconv", "gqa"} <= seen["singles"]
    # a path as the compiled program writes it, under the widened vocabulary
    name = ("jit(body)/round/chunk/round/local_train/while/body/closed_call/"
            "transpose(jvp(step/model))/checkpoint/shortconv/shortconv/gate/mul")
    kept = scope_reduce._PAIRS, scope_reduce._SINGLES
    scope_reduce._PAIRS, scope_reduce._SINGLES = seen["pairs"], seen["singles"]
    try:
        assert scope_reduce.scope_of(name) == (BASE + "shortconv/shortconv/gate", "bwd")
    finally:
        scope_reduce._PAIRS, scope_reduce._SINGLES = kept
    assert scope_reduce.scope_of(name)[0] == "round/local_train/step/model"
