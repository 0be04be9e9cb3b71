"""The Phi-4-mini-flash cell's files on the CPU at a tiny size: the comparison
that decides `correct` on a sound run and under the control, the configuration
against the catalog's row and the program's own shapes, the FLOP file against
the model's matrices, differential attention's least form and the scan's
bytes, the eight readers on a trace with the scopes and on one without."""

import json
import os

import numpy as np
import pytest

from benchmark import (harness, scope_reduce, scope_reduce_lfm2, scope_reduce_moe,
                       scope_reduce_nemotron_h, scope_reduce_phi4flash)
from benchmark import run as bench_run
from benchmark.tests import tiny_phi4flash as tiny

NAME = tiny.NAME
CONFIG = "phi-4-mini-flash-reasoning"
METRICS = ("phi4_ssm_ms.step", "phi4_scan_ms.step", "phi4_scan_roofline_pct", "phi4_conv_ms.step",
           "gmu_ms.step", "diff_attn_ms.step", "diff_attn_roofline_pct", "phi4_ffn_ms.step")
CATALOG = "/opt/skills/guides/model-configs/architectures.jsonl"
CUT = {"num_hidden_layers": 4, "vocab_size": 25008}


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
    from heterofl_tpu import config as C
    from heterofl_tpu.models.phi4flash import layer_types

    manifest = harness.load_json("..", "BENCHMARK.json")
    entry = next(c for c in manifest["configs"] if c["name"] == CONFIG)
    config = harness.load_json("configs", CONFIG + ".json")
    assert entry["reduced"] == config["reduced"] == ["num_hidden_layers", "layer_types",
                                                     "vocab_size"]
    assert set(config["reduced"]) <= set(config["reduced_notes"])
    assert "eight-stage" in config["reduced_notes"]["deployment"]
    assert "sliding" in config["reduced_notes"]["num_hidden_layers"]  # what the hinge leaves out
    assert entry["source"] == config["source"]
    if os.path.exists(CATALOG):  # every key of the catalog's row, the cuts apart
        with open(CATALOG, encoding="utf-8") as f:
            row = next(r for r in map(json.loads, f) if r["name"] == "Phi-4-mini-flash-reasoning")
        assert row["source_url"] == config["source"]
        for key, value in row["config"].items():
            assert config[key] == CUT.get(key, value), key
        assert 8 * CUT["vocab_size"] == row["config"]["vocab_size"]  # an eighth, the floor
        assert config["published"]["num_hidden_layers"] == row["config"]["num_hidden_layers"]
    # the 32 kinds are the published rule's, and the cut is its entries 16-19
    published = config["published"]["layer_types"]
    assert published == layer_types(32, config["mb_per_layer"]) \
        == C.DECODER_FAMILIES["phi4flash"]["layer_types"]
    assert config["layer_types"] == published[16:20] == ["mamba", "full", "gmu", "cross"]
    assert config["layer_offset"] == 16 and config["published"]["vocab_size"] == 200064
    m = config["model"]
    for key in ("hidden_size", "num_hidden_layers", "layer_types", "layer_offset", "mb_per_layer",
                "num_attention_heads", "num_key_value_heads", "sliding_window",
                "intermediate_size", "layer_norm_eps"):
        assert config[key] == m[key], key
    family = C.DECODER_FAMILIES["phi4flash"]
    for key in family:  # every width as published: the cut is the depth's three keys alone
        if key not in ("num_hidden_layers", "layer_types", "layer_offset"):
            assert m[key] == family[key], key
    assert m["num_tokens"] == config["vocab_size"] == config["data"]["sizes"]["types"]
    assert config["cfg_overrides"]["phi4flash"] == {
        "num_hidden_layers": 4, "layer_types": published[16:20], "layer_offset": 16}
    for key in ("mamba", "attention", "position", "side_values", "initialisation", "slicing",
                "scale", "optimizer", "job", "corpus", "weights"):
        assert key in config["assumed"], key
    cell = harness.load_json("workloads", NAME + ".json")
    assert cell["traffic"]["cfg_overrides"] == {"round_chunk": 1} and cell["chips"] == 1
    rows, tokens = config["federation"]["batch_rows"], config["data"]["sizes"]["train"]
    assert tokens // rows == m["bptt"] == 8192 and tokens % 33 == 0
    assert config["federation"]["rows_per_user"] * config["federation"]["num_users"] == rows
    keye = harness.load_json("configs", "keye-vl-2-30b-a3b.json")
    assert config["data"]["sizes"]["train"] == keye["data"]["sizes"]["train"]
    assert config["data"]["sizes"]["test"] == keye["data"]["sizes"]["test"]
    per_layer = [p for p in manifest["per_layer"] if p["name"] in METRICS]
    assert [p["name"] for p in per_layer] == list(METRICS)
    assert all(p["workloads"] == [NAME] and p["moves"] == "client_steps_per_s" for p in per_layer)


def _cell_cfg(config, **arch):
    m = config["model"]
    cfg = tiny.program_cfg(bptt=m["bptt"], **dict({k: m[k] for k in m if k in {
        **tiny.ARCH, "layer_norm_eps": 0, "mb_per_layer": 0}}, **arch))
    cfg["num_tokens"] = m["num_tokens"]
    return cfg


def test_the_stated_parameter_count_is_the_programs():
    import jax

    from heterofl_tpu import config as C
    from heterofl_tpu.models import make_model

    config = harness.load_json("configs", CONFIG + ".json")

    def shapes_of(num_tokens=None, **arch):
        cfg = _cell_cfg(config, **arch)
        cfg["num_tokens"] = num_tokens or cfg["num_tokens"]
        return jax.eval_shape(make_model(cfg).init, jax.random.key(0))

    def count(prefix, of):
        return sum(int(np.prod(v.shape)) for k, v in of.items() if k.startswith(prefix))

    shapes = shapes_of()
    assert count("", shapes) == config["parameters"] == 478876928
    ffn = 3 * 2560 * 10240 + 4 * 2560  # a layer's feed-forward and its two norms
    assert count("l0.ssm.", shapes) == 41241600 and count("l0.", shapes) == 41241600 + ffn
    assert count("l1.attn.", shapes) == 19668864 and count("l1.", shapes) == 19668864 + ffn
    assert count("l2.gmu.", shapes) == 26214400 and count("l3.attn.", shapes) == 13112704
    assert count("tok.", shapes) == 25008 * 2560 and count("norm.", shapes) == 2 * 2560
    # the layout closes the published count: 3.85 B, the published "3.8B"
    family = C.DECODER_FAMILIES["phi4flash"]
    whole = shapes_of(200064, **{k: family[k] for k in ("num_hidden_layers", "layer_types",
                                                         "layer_offset")})
    n = count("", whole)
    assert n == 9 * (41241600 + ffn) + 9 * (19668864 + ffn) + 7 * (26214400 + ffn) \
        + 7 * (13112704 + ffn) + 200064 * 2560 + 2 * 2560
    assert 3.84e9 < n < 3.86e9
    # the five-kind cut (layers 15-19) is what the notes say it is, and was not offered
    five = count("", shapes_of(num_hidden_layers=5, layer_offset=15,
                               layer_types=family["layer_types"][15:20]))
    assert five == 577199232 and "577.2 M" in config["reduced_notes"]["num_hidden_layers"]


@pytest.mark.parametrize("rate", [1.0, 0.25])
def test_flops_count_the_models_own_matrices_and_the_attentions_least_form(rate):
    """At rate r the FLOP file's widths are the program's sub-model's: the
    multiply-adds a token outside the attention's scores and values are the
    2-D leaves' sizes (the tied table once, as the head; the [1, E] bias, the
    [E, 16] decay and the [64, 1] vectors are no products), and what is left
    is differential attention's least form over the causal pairs."""
    import jax

    from heterofl_tpu.models import make_model

    config = harness.load_json("configs", CONFIG + ".json")
    flops = harness.load_module("flops", "phi4flash")
    m = config["model"]
    shapes = jax.eval_shape(make_model(_cell_cfg(config), rate).init, jax.random.key(0))
    linear = sum(2.0 * float(np.prod(v.shape)) for name, v in shapes.items()
                 if v.ndim == 2 and v.shape[0] != 1 and v.shape[1] != 1
                 and not name.endswith("a_log.w"))
    s, hd = m["bptt"], int(np.ceil(64 * rate))
    pairs = s * (s + 1) // 2
    assert flops.seen_pairs(m, "full") == flops.seen_pairs(m, "cross") == pairs == 33558528
    assert flops.seen_pairs(m, "sliding") == 512 * 513 // 2 + (s - 512) * 512
    attn = 2 * pairs * 20 * 2 * (2 * hd + 2 * 2 * hd)  # two layers, 20 pairs, two softmaxes
    assert flops.diff_attn_forward_flops(m, rate) == attn
    assert flops.forward_flops(m, rate) - s * linear == pytest.approx(attn, rel=1e-9)
    assert flops.step_flops(config, rate) == 3 * flops.forward_flops(m, rate)
    e = int(np.ceil(5120 * rate))
    assert flops.scan_forward_bytes(m, rate) == 4 * s * (3 * e + 32)
    assert flops.scan_forward_flops(m, rate) == 6 * s * e * 16
    if rate == 1.0:
        whole = flops.executed_step_flops(config)
        assert whole == flops.step_flops(config, 1.0) == pytest.approx(3 * 8.874e12, rel=1e-3)
        assert 0.57 < flops.executed_ffn_step_flops(config) / whole < 0.59
        assert 0.11 < 3 * flops.head_forward_flops(m, 1.0) / whole < 0.13
        assert flops.executed_diff_attn_step_flops(config) == pytest.approx(3 * 1.031e12, rel=1e-3)
        assert flops.executed_scan_step_bytes(config) == pytest.approx(1.513e9, rel=1e-3)
        # the memory side binds: 0.06 ms of elementwise work against 1.85 ms of bytes on a v5e
        assert flops.executed_scan_step_flops(config) / 197e12 \
            < flops.executed_scan_step_bytes(config) / 819e9


def _rows(*paths):
    """A by-scope table as `scope_reduce.reduce_scopes` gives it, 2 rounds."""
    return {"rows": [[p, d, "fusion", s, True] for p, d, s in paths],
            "total_s": sum(s for _, _, s in paths), "rounds": 2}


INFO = {"name": NAME, "steps_per_round": 1, "peak_flops_per_s": 197e12}
BASE = "round/chunk/round/local_train/step/model/"


@pytest.mark.parametrize("metric", METRICS)
def test_a_reader_finds_its_scopes_and_reports_nothing_without_them(metric, monkeypatch):
    """On a table with the scopes each of the eight metrics reads its own rows
    (seconds over 2 rounds and 1 step a round, as milliseconds): the scan and
    the convolution are `ssm`'s too, the mixer's projections `ssm`'s alone, a
    feed-forward's rows those under no mixer, norm, embedding or loss; on a
    parent's table, where no path carries them, and without a traced run it
    returns None and does not raise."""
    mod = harness.load_module("layer_metrics", metric)
    with_scopes = _rows((BASE + "ssm/ssm/scan", "fwd", 0.2), (BASE + "ssm/ssm/scan", "bwd", 0.4),
                        (BASE + "ssm/ssm/conv", "bwd", 0.06), (BASE + "ssm/linear", "bwd", 0.3),
                        (BASE + "gmu/linear", "bwd", 0.1), (BASE + "gmu", "fwd", 0.02),
                        (BASE + "attn", "bwd", 1.0), (BASE + "diff", "fwd", 0.04),
                        (BASE + "gqa/linear", "fwd", 0.08), (BASE + "norm", "fwd", 0.05),
                        (BASE + "loss/linear", "fwd", 0.3), (BASE + "embed", "fwd", 0.01),
                        (BASE + "linear", "fwd", 1.0), (BASE.rstrip("/"), "bwd", 0.2),
                        ("round/aggregate", "", 0.5))
    monkeypatch.setitem(scope_reduce_phi4flash._memo, "table", with_scopes)
    value = mod.compute({"busy_s": 1.0}, [], INFO)
    config = harness.load_json("configs", CONFIG + ".json")
    flops = harness.load_module("flops", "phi4flash")
    want = {"phi4_ssm_ms.step": 480.0, "phi4_scan_ms.step": 300.0, "phi4_conv_ms.step": 30.0,
            "gmu_ms.step": 60.0, "diff_attn_ms.step": 520.0, "phi4_ffn_ms.step": 600.0,
            "phi4_scan_roofline_pct":
                100 * 10 * flops.executed_scan_step_bytes(config) / 819e9 / 0.3,
            "diff_attn_roofline_pct":
                100 * 10 * flops.executed_diff_attn_step_flops(config) / 197e12 / 0.52}[metric]
    assert value == pytest.approx(want, rel=1e-9)
    if metric != "phi4_ffn_ms.step":  # a parent's feed-forward rows are there to read
        parent = _rows((BASE + "linear", "fwd", 1.0), (BASE + "norm", "bwd", 0.1))
        monkeypatch.setitem(scope_reduce_phi4flash._memo, "table", parent)
        assert mod.compute({"busy_s": 1.0}, [], INFO) is None
    monkeypatch.setitem(scope_reduce_phi4flash._memo, "table", None)  # no table at all
    assert mod.compute({"busy_s": 1.0}, [], INFO) is None
    assert mod.compute(None, [], INFO) is None
    # lent, and put back
    assert scope_reduce_nemotron_h._memo is not scope_reduce_phi4flash._memo
    assert scope_reduce_lfm2._memo is not scope_reduce_phi4flash._memo


def test_the_reader_lends_the_longer_list_for_one_read_and_puts_it_back(monkeypatch):
    from heterofl_tpu.obs import trace

    assert scope_reduce_phi4flash.SAMBAY_SCOPES == trace.SAMBAY_SCOPES
    assert scope_reduce_phi4flash.WINDOW_SCOPES == trace.WINDOW_SCOPES
    seen = {}

    def fake_table():
        seen["scopes"] = scope_reduce_moe.EXTRA_SCOPES
        seen["pairs"], seen["singles"] = scope_reduce_moe._widened()
        return None

    monkeypatch.setattr(scope_reduce_moe, "table", fake_table)
    monkeypatch.setattr(scope_reduce_phi4flash, "_memo", {})
    kept = scope_reduce_nemotron_h._memo
    kept["table"] = "kept"
    try:
        assert scope_reduce_phi4flash.ms({"busy_s": 1.0}, lambda row: True) is None
        assert scope_reduce_nemotron_h._memo is kept and kept == {"table": "kept"}
    finally:
        kept.clear()
    assert seen["scopes"] == trace.EXTRA_SCOPES + trace.MIXER_SCOPES + trace.SSM_SCOPES \
        + trace.SAMBAY_SCOPES + trace.WINDOW_SCOPES
    assert scope_reduce_nemotron_h.SSM_SCOPES == trace.SSM_SCOPES
    assert scope_reduce_lfm2.MIXER_SCOPES == trace.MIXER_SCOPES
    assert scope_reduce_moe.EXTRA_SCOPES == trace.EXTRA_SCOPES
    assert {"gmu", "diff", "ssm"} <= seen["singles"] and ("ssm", "scan") in seen["pairs"]
    # a path as the compiled program writes it, under the widened vocabulary
    name = ("jit(body)/round/chunk/round/local_train/while/body/closed_call/"
            "transpose(jvp(step/model))/checkpoint/gmu/linear/dot_general")
    before = scope_reduce._PAIRS, scope_reduce._SINGLES
    scope_reduce._PAIRS, scope_reduce._SINGLES = seen["pairs"], seen["singles"]
    try:
        assert scope_reduce.scope_of(name) == (BASE + "gmu/linear", "bwd")
    finally:
        scope_reduce._PAIRS, scope_reduce._SINGLES = before
    assert scope_reduce.scope_of(name)[0] == "round/local_train/step/model/linear"
