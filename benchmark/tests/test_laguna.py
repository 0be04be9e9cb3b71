"""The Laguna cell's files on the CPU at a tiny size: the comparison that
decides `correct` on a sound run and under the control, the configuration
against the catalog's row and the program's own shapes, the FLOP file against
the model's matrices and the band's arithmetic, the nine readers on a trace
with the scopes and on one without."""

import json
import os

import numpy as np
import pytest

from benchmark import harness, scope_reduce, scope_reduce_laguna, scope_reduce_lfm2, scope_reduce_moe
from benchmark import run as bench_run
from benchmark.tests import tiny_laguna as tiny

NAME = tiny.NAME
METRICS = ("swa_ms.step", "swa_roofline_pct", "laguna_full_attn_ms.step",
           "laguna_full_attn_roofline_pct", "laguna_gqa_ms.step", "laguna_router_ms.step",
           "laguna_experts_ms.step", "laguna_experts_roofline_pct", "laguna_shared_ms.step")
CATALOG = "/opt/skills/guides/model-configs/architectures.jsonl"
LISTS = ("layer_types", "mlp_layer_types", "num_attention_heads_per_layer")


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
    entry = next(c for c in manifest["configs"] if c["name"] == "laguna-xs.2")
    config = harness.load_json("configs", "laguna-xs.2.json")
    cut = {"num_hidden_layers": 5, "num_experts": 16, "vocab_size": 12544}
    assert entry["reduced"] == config["reduced"] and set(config["reduced"]) == set(cut) | set(LISTS)
    assert set(config["reduced"]) <= set(config["reduced_notes"])
    assert "16-way expert-parallel" in config["reduced_notes"]["deployment"]
    assert entry["source"] == config["source"]
    if os.path.exists(CATALOG):  # every key of the catalog's row, the cuts apart
        with open(CATALOG, encoding="utf-8") as f:
            row = next(r for r in map(json.loads, f) if r["name"] == "Laguna-XS.2")
        assert row["source_url"] == config["source"]
        for key, value in row["config"].items():
            want = value[:5] if key in LISTS else cut.get(key, value)  # entries 0-4, as the depth
            assert config[key] == want, key
        assert 8 * cut["vocab_size"] == row["config"]["vocab_size"]  # an eighth, the floor
        assert 16 * cut["num_experts"] == row["config"]["num_experts"]
    m = config["model"]
    for key in ("hidden_size", "intermediate_size", "moe_intermediate_size", "head_dim",
                "shared_expert_intermediate_size", "num_key_value_heads", "num_experts_per_tok",
                "rms_norm_eps", "sliding_window", "moe_routed_scaling_factor", "rope_parameters",
                "num_hidden_layers") + LISTS:
        assert config[key] == m[key], key
    assert m["layer_types"] == ["full_attention"] + ["sliding_attention"] * 3 + ["full_attention"]
    assert m["mlp_layer_types"] == ["dense"] + ["sparse"] * 4
    assert m["num_attention_heads_per_layer"] == [48, 64, 64, 64, 48]
    assert m["num_experts"] == 256  # the router's width; the top-level key counts those held
    assert m["expert_share"] == [0, 16] and m["experts_held"] == config["num_experts"] == 16
    assert m["num_tokens"] == config["vocab_size"] == config["data"]["sizes"]["types"]
    assert config["cfg_overrides"]["laguna"] == {
        "num_hidden_layers": 5, "expert_share": [0, 16], **{k: m[k] for k in LISTS}}
    for key in ("gating", "router", "heads", "rope", "slicing", "scale", "optimizer", "job",
                "corpus", "weights"):
        assert key in config["assumed"], key
    cell = harness.load_json("workloads", NAME + ".json")
    assert cell["traffic"]["cfg_overrides"] == {"round_chunk": 1} and cell["chips"] == 1
    rows, tokens = config["federation"]["batch_rows"], config["data"]["sizes"]["train"]
    assert tokens // rows == m["bptt"] == 8192 > m["sliding_window"] and tokens % 33 == 0
    assert config["federation"]["rows_per_user"] * config["federation"]["num_users"] == rows
    keye = harness.load_json("configs", "keye-vl-2-30b-a3b.json")
    assert config["data"]["sizes"]["train"] == keye["data"]["sizes"]["train"]
    assert config["data"]["sizes"]["test"] == keye["data"]["sizes"]["test"]
    per_layer = [p for p in manifest["per_layer"] if p["name"] in METRICS]
    assert [p["name"] for p in per_layer] == list(METRICS)
    assert all(p["workloads"] == [NAME] and p["moves"] == "client_steps_per_s" for p in per_layer)


def _cell_cfg(config):
    m = config["model"]
    cfg = tiny.program_cfg(bptt=m["bptt"], **{k: m[k] for k in m if k in
                                               {**tiny.ARCH, "rms_norm_eps": 0,
                                                "rope_parameters": 0}})
    cfg["num_tokens"] = m["num_tokens"]
    return cfg


def test_the_stated_parameter_count_is_the_programs():
    import jax

    from heterofl_tpu.models import make_model

    config = harness.load_json("configs", "laguna-xs.2.json")
    shapes = jax.eval_shape(make_model(_cell_cfg(config)).init, jax.random.key(0))

    def count(prefix):
        return sum(int(np.prod(v.shape)) for k, v in shapes.items() if k.startswith(prefix))

    assert count("") == config["parameters"] == 490297344
    assert count("l0.") == 79794176 and count("l0.attn.") == 29458432
    assert count("l0.attn.gate.") == 98304 and count("l0.mlp.") == 50331648
    assert count("l1.") == count("l2.") == count("l3.") == 91885568
    assert count("l1.attn.") == 37879808 and count("l1.attn.gate.") == 131072
    assert count("l1.moe.router.") == 524288 and count("l1.moe.shared.") == 3145728
    assert count("l1.moe.e") == 50331648 and count("l4.") == 83464192
    assert 79794176 + 3 * 91885568 + 83464192 + 2048 + 2 * 12544 * 2048 == config["parameters"]
    # with 8 held (the issue's fallback, not taken) it would be 389,634,048
    assert config["parameters"] - 4 * 8 * 3 * 2048 * 512 == 389634048


@pytest.mark.parametrize("rate", [1.0, 0.25])
def test_flops_count_the_models_own_matrices_and_the_bands_pairs(rate):
    """At rate r the FLOP file's widths are the program's sub-model's: the
    multiply-adds a token outside the attention's scores are the 2-D leaves'
    sizes (a routed expert at its expected share of the tokens), and what is
    left is the two products over the BAND pairs in the three sliding layers
    of 64 heads and over the causal pairs in the two full layers of 48."""
    import jax

    from heterofl_tpu.models import make_model

    config = harness.load_json("configs", "laguna-xs.2.json")
    flops = harness.load_module("flops", "laguna")
    m = config["model"]
    assert flops.band_pairs(m) == 4063488 and flops.causal_pairs(m) == 33558528
    assert flops.band_pairs(m) / flops.causal_pairs(m) == pytest.approx(0.121, abs=1e-3)
    assert flops.band_pairs(dict(m, bptt=512)) == flops.causal_pairs(dict(m, bptt=512))
    shapes = jax.eval_shape(make_model(_cell_cfg(config), rate).init, jax.random.key(0))
    linear = routed = 0.0
    for name, v in shapes.items():
        if v.ndim != 2 or name.startswith("embedding."):
            continue
        n = 2.0 * float(np.prod(v.shape))
        if ".moe.e" in name:  # a routed expert sees top_k / n_experts of the tokens
            n *= m["num_experts_per_tok"] / m["num_experts"]
            routed += n
        linear += n
    s = m["bptt"]
    hd = -(-int(np.ceil(128 * rate)) // 2) * 2
    attn = 3 * 2 * 4063488 * 64 * 2 * hd + 2 * 2 * 33558528 * 48 * 2 * hd
    assert flops.forward_flops(m, rate) - s * linear == pytest.approx(attn, rel=1e-9)
    assert flops.routed_forward_flops(m, rate) == pytest.approx(s * routed, rel=1e-12)
    assert flops.step_flops(config, rate) == 3 * flops.forward_flops(m, rate)
    if rate == 1.0:
        whole = flops.executed_step_flops(config)
        assert whole == flops.step_flops(config, 1.0) == pytest.approx(19.4e12, rel=2e-3)
        assert flops.executed_window_step_flops(config) == 3 * 3 * 4 * 4063488 * 64 * 128
        assert flops.executed_full_attn_step_flops(config) == 3 * 2 * 4 * 33558528 * 48 * 128
        # the three sliding layers need 24 % of the two full layers' FLOPs; causal they would 200 %
        ratio = flops.executed_window_step_flops(config) / flops.executed_full_attn_step_flops(config)
        assert ratio == pytest.approx(0.242, abs=1e-3)
        assert 0.31 < (flops.executed_window_step_flops(config)
                       + flops.executed_full_attn_step_flops(config)) / whole < 0.33
        assert 0.01 < flops.executed_routed_step_flops(config) / whole < 0.02


def _rows(*paths):
    """A by-scope table as `scope_reduce.reduce_scopes` gives it, 2 rounds."""
    return {"rows": [[p, d, "fusion", s, True] for p, d, s in paths],
            "total_s": sum(s for _, _, s in paths), "rounds": 2}


INFO = {"name": NAME, "steps_per_round": 1, "peak_flops_per_s": 197e12}
BASE = "round/chunk/round/local_train/step/model/"


@pytest.mark.parametrize("metric", METRICS)
def test_a_reader_finds_its_scopes_and_reports_nothing_without_them(metric, monkeypatch):
    """On a table with the scopes each of the nine metrics reads its own rows
    (seconds over 2 rounds and 1 step a round, as milliseconds): the sliding
    layers' kernels are `swa`'s and not `attn`'s; on a parent's table, where no
    path carries them, and without a traced run it returns None and does not
    raise."""
    mod = harness.load_module("layer_metrics", metric)
    with_scopes = _rows((BASE + "swa", "fwd", 0.2), (BASE + "swa", "bwd", 0.4),
                        (BASE + "attn", "bwd", 1.0), (BASE + "gqa/linear", "fwd", 0.08),
                        (BASE + "rope", "fwd", 0.02), (BASE + "moe/router", "fwd", 0.03),
                        (BASE + "moe/dispatch", "bwd", 0.05),
                        (BASE + "moe/experts/linear", "bwd", 0.3),
                        (BASE + "moe/shared/linear", "bwd", 0.1),
                        (BASE + "linear", "fwd", 1.0), ("round/aggregate", "", 0.5))
    monkeypatch.setitem(scope_reduce_laguna._memo, "table", with_scopes)
    value = mod.compute({"busy_s": 1.0}, [], INFO)
    config = harness.load_json("configs", "laguna-xs.2.json")
    flops = harness.load_module("flops", "laguna")
    want = {"swa_ms.step": 300.0, "laguna_full_attn_ms.step": 500.0, "laguna_gqa_ms.step": 50.0,
            "laguna_router_ms.step": 40.0, "laguna_experts_ms.step": 150.0,
            "laguna_shared_ms.step": 50.0,
            "swa_roofline_pct":
                100 * 10 * flops.executed_window_step_flops(config) / 197e12 / 0.3,
            "laguna_full_attn_roofline_pct":
                100 * 10 * flops.executed_full_attn_step_flops(config) / 197e12 / 0.5,
            "laguna_experts_roofline_pct":
                100 * 10 * flops.executed_routed_step_flops(config) / 197e12 / 0.150}[metric]
    assert value == pytest.approx(want, rel=1e-9)
    parent = _rows((BASE + "linear", "fwd", 1.0), (BASE + "norm", "bwd", 0.1))
    for table in (parent, None):  # no row of its scopes; no table at all
        monkeypatch.setitem(scope_reduce_laguna._memo, "table", table)
        assert mod.compute({"busy_s": 1.0}, [], INFO) is None
    assert mod.compute(None, [], INFO) is None
    assert scope_reduce_lfm2._memo is not scope_reduce_laguna._memo  # lent, and put back


def test_the_reader_lends_the_longer_list_for_one_read_and_puts_it_back(monkeypatch):
    from heterofl_tpu.obs import trace

    assert scope_reduce_laguna.WINDOW_SCOPES == trace.WINDOW_SCOPES
    seen = {}

    def fake_table():
        seen["scopes"] = scope_reduce_moe.EXTRA_SCOPES
        seen["pairs"], seen["singles"] = scope_reduce_moe._widened()
        return None

    monkeypatch.setattr(scope_reduce_moe, "table", fake_table)
    monkeypatch.setattr(scope_reduce_laguna, "_memo", {})
    kept = scope_reduce_lfm2._memo
    kept["table"] = "kept"
    try:
        assert scope_reduce_laguna.table() is None
        assert scope_reduce_lfm2._memo is kept and kept == {"table": "kept"}
    finally:
        kept.clear()
    assert seen["scopes"] == trace.EXTRA_SCOPES + trace.MIXER_SCOPES + trace.WINDOW_SCOPES
    assert scope_reduce_lfm2.MIXER_SCOPES == trace.MIXER_SCOPES
    assert scope_reduce_moe.EXTRA_SCOPES == trace.EXTRA_SCOPES
    assert "swa" in seen["singles"]
    # a path as the compiled program writes it, under the widened vocabulary
    name = ("jit(body)/round/chunk/round/local_train/while/body/closed_call/"
            "transpose(jvp(step/model))/while/body/checkpoint/swa/band_attn_bwd/pallas_call")
    before = scope_reduce._PAIRS, scope_reduce._SINGLES
    scope_reduce._PAIRS, scope_reduce._SINGLES = seen["pairs"], seen["singles"]
    try:
        assert scope_reduce.scope_of(name) == (BASE + "swa", "bwd")
    finally:
        scope_reduce._PAIRS, scope_reduce._SINGLES = before
    assert scope_reduce.scope_of(name)[0] == "round/local_train/step/model"
