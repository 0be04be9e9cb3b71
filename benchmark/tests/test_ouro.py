"""The Ouro cell's files on the CPU at a tiny size: the comparison that decides
`correct` on a sound run and under the control, the configuration against the
catalog's row and the program's own shapes, the FLOP file against the model's
matrices, the passes and the causal pairs, the five readers on a trace with
the scopes and on one without."""

import json
import os

import numpy as np
import pytest

from benchmark import harness, scope_reduce, scope_reduce_lfm2, scope_reduce_moe, scope_reduce_ouro
from benchmark import run as bench_run
from benchmark.tests import tiny_ouro as tiny

NAME = tiny.NAME
METRICS = ("loop_pass_ms.step", "loop_head_ms.step", "loop_exit_ms.step", "ouro_attn_ms.step",
           "ouro_attn_roofline_pct")
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


def test_the_configuration_states_the_published_shape_and_its_cut():
    manifest = harness.load_json("..", "BENCHMARK.json")
    entry = next(c for c in manifest["configs"] if c["name"] == "ouro-2.6b")
    config = harness.load_json("configs", "ouro-2.6b.json")
    cut = {"num_hidden_layers": 4}
    assert entry["reduced"] == config["reduced"] == ["num_hidden_layers"]
    assert set(config["reduced"]) <= set(config["reduced_notes"])
    assert "pipeline stage" in config["reduced_notes"]["deployment"]
    assert entry["source"] == config["source"]
    if os.path.exists(CATALOG):  # every key of the catalog's row, the cut apart
        with open(CATALOG, encoding="utf-8") as f:
            row = next(r for r in map(json.loads, f) if r["name"] == "Ouro-2.6B")
        assert row["source_url"] == config["source"]
        for key, value in row["config"].items():
            assert config[key] == cut.get(key, value), key
    m = config["model"]
    for key in ("hidden_size", "intermediate_size", "num_attention_heads", "head_dim",
                "num_key_value_heads", "num_hidden_layers", "rms_norm_eps", "rope_theta",
                "total_ut_steps", "early_exit_threshold"):
        assert config[key] == m[key], key
    assert m["num_tokens"] == config["vocab_size"] == config["data"]["sizes"]["types"] == 49152
    assert config["cfg_overrides"]["ouro"] == {"num_hidden_layers": 4}
    for key in ("loop", "exit_gate", "loss", "slicing", "scale", "optimizer", "corpus", "weights"):
        assert key in config["assumed"], key
    assert m["exit_entropy_beta"] == 0.1 and "beta = 0.1" in config["assumed"]["loss"]
    cell = harness.load_json("workloads", NAME + ".json")
    work = next(w for w in manifest["workloads"] if w["name"] == NAME)
    assert (work["config"], work["traffic"], work["chips"]) == ("ouro-2.6b", "fix-a1-e1.train-2k", 1)
    assert cell["traffic"]["cfg_overrides"] == {"round_chunk": 1} and cell["chips"] == 1
    assert cell["traffic"]["eval_every"] == 0 and cell["traffic"]["strategy"] == "masked"
    rows, tokens = config["federation"]["batch_rows"], config["data"]["sizes"]["train"]
    assert tokens // rows == m["bptt"] == 2048 and tokens == 6207 * 33  # 1 local step a client
    assert config["federation"]["rows_per_user"] * config["federation"]["num_users"] == rows
    mine = [p for p in manifest["per_layer"] if p.get("workloads") == [NAME]]
    assert [p["name"] for p in mine] == list(METRICS)
    assert {p["moves"] for p in mine} == {"client_steps_per_s"}


def _cell_cfg(config):
    m = config["model"]
    keys = {**tiny.ARCH, "rms_norm_eps": 0, "rope_theta": 0, "early_exit_threshold": 0,
            "exit_entropy_beta": 0}
    cfg = tiny.program_cfg(bptt=m["bptt"], **{k: m[k] for k in m if k in keys})
    cfg["num_tokens"] = m["num_tokens"]
    return cfg


def test_the_stated_parameter_count_is_the_programs():
    import jax

    from heterofl_tpu.models import make_model

    config = harness.load_json("configs", "ouro-2.6b.json")
    shapes = jax.eval_shape(make_model(_cell_cfg(config)).init, jax.random.key(0))
    assert sum(int(np.prod(v.shape)) for v in shapes.values()) == config["parameters"] == 406884353
    layer = {k: int(np.prod(v.shape)) for k, v in shapes.items() if k.startswith("l0.")}
    assert sum(layer.values()) == 51388416
    assert sum(v for k, v in layer.items() if ".attn." in k) == 16777216
    assert sum(v for k, v in layer.items() if ".mlp." in k) == 34603008
    assert sum(v for k, v in layer.items() if ".norm" in k) == 8192
    assert 4 * 51388416 + 2 * 49152 * 2048 + 2048 + 2049 == config["parameters"]


@pytest.mark.parametrize("rate", [1.0, 0.25])
def test_flops_count_every_pass_of_the_models_own_matrices(rate):
    """At rate r the FLOP file's widths are the program's sub-model's: the
    multiply-adds a token outside the attention's scores are the 2-D leaves'
    sizes, every leaf but the embedding `total_ut_steps` times; what is left
    is the two products over the causal pairs, every layer application."""
    import jax

    from heterofl_tpu.models import make_model

    config = harness.load_json("configs", "ouro-2.6b.json")
    flops = harness.load_module("flops", "ouro")
    m = config["model"]
    assert flops.causal_pairs(m) == 2098176 and flops.applications(m) == 16
    shapes = jax.eval_shape(make_model(_cell_cfg(config), rate).init, jax.random.key(0))
    used = sum(2.0 * float(np.prod(v.shape)) for k, v in shapes.items()
               if v.ndim == 2 and not k.startswith("embedding."))
    hd = -(-int(np.ceil(m["head_dim"] * rate)) // 2) * 2
    attn = 16 * 2 * 2 * 2098176 * 16 * hd
    assert flops.attn_forward_flops(m, rate) == attn
    assert flops.forward_flops(m, rate) == pytest.approx(4 * m["bptt"] * used + attn, rel=1e-12)
    assert flops.step_flops(config, rate) == 3 * flops.forward_flops(m, rate)
    once = dict(m, total_ut_steps=1)  # one pass is a quarter of everything
    assert flops.forward_flops(m, rate) == 4 * flops.forward_flops(once, rate)
    if rate == 1.0:
        whole = flops.executed_step_flops(config)
        assert whole == flops.step_flops(config, 1.0)
        assert flops.executed_attn_step_flops(config) == 3 * attn
        assert 15.8e12 < whole < 15.9e12  # the issue's 15.9 TFLOP a client step
        assert 0.30 < flops.head_forward_flops(m, 1.0) / flops.forward_flops(m, 1.0) < 0.32
        deep = dict(m, num_hidden_layers=48)  # the published depth pays its head 4 %
        assert 0.03 < flops.head_forward_flops(deep, 1.0) / flops.forward_flops(deep, 1.0) < 0.04


def test_the_references_two_scans_are_its_two_python_loops():
    """What the chip compiles of the reference (`passes`: the passes and the
    layers as two `lax.scan`s, so that a level's program fits the compile
    cache) is the mathematics as written (`passes_unrolled`: two Python loops
    over the same leaves): the states of every pass, the loss and every
    leaf's gradient, to float32 rounding."""
    import jax
    import jax.numpy as jnp

    from benchmark.reference import common, ouro as ref
    from heterofl_tpu.models import make_model

    cfg = tiny.program_cfg()
    rm, rate = tiny.reference_model(cfg), 0.5
    params = make_model(cfg).init(jax.random.key(7))
    sub = {k: jnp.asarray(v) for k, v in common.take(
        params, ref.index({k: v.shape for k, v in params.items()}, rm, rate)).items()}
    tokens = jax.random.randint(jax.random.key(8), (2, cfg["bptt"]), 0, cfg["num_tokens"])
    lm = jnp.ones(cfg["num_tokens"]).at[::5].set(0.0)
    arch = ref.arch_of(rm)
    for a, b in zip(ref.passes(sub, tokens, rate, arch), ref.passes_unrolled(sub, tokens, rate, arch)):
        np.testing.assert_allclose(a, b, rtol=1e-5, atol=1e-5)  # states of unit scale
    got, want = (jax.value_and_grad(lambda p, f=f: ref.loss_fn(p, tokens, lm, rate, arch, states_of=f))(sub)
                 for f in (ref.passes, ref.passes_unrolled))
    np.testing.assert_allclose(float(got[0]), float(want[0]), rtol=1e-6)
    for k, g in want[1].items():
        np.testing.assert_allclose(got[1][k], g, atol=1e-4 * np.abs(g).max() + 1e-9, err_msg=k)


def _rows(*paths):
    """A by-scope table as `scope_reduce.reduce_scopes` gives it, 2 rounds."""
    return {"rows": [[p, d, "fusion", s, True] for p, d, s in paths],
            "total_s": sum(s for _, _, s in paths), "rounds": 2}


INFO = {"name": NAME, "steps_per_round": 1, "peak_flops_per_s": 197e12}
BASE = "round/chunk/round/local_train/step/model/"


@pytest.mark.parametrize("metric", METRICS)
def test_a_reader_finds_its_scopes_and_reports_nothing_without_them(metric, monkeypatch):
    """On a table with the new scopes each of the five metrics reads its own
    rows (seconds over 2 rounds and 1 step a round, as milliseconds): the
    attention lies inside `loop/pass` and is counted there too; on a parent's
    table, where no path carries them, and without a traced run it returns
    None and does not raise."""
    mod = harness.load_module("layer_metrics", metric)
    with_scopes = _rows((BASE + "loop/pass/norm", "fwd", 0.2),
                        (BASE + "loop/pass/linear", "bwd", 1.0),
                        (BASE + "loop/pass/gqa/linear", "fwd", 0.3),
                        (BASE + "loop/pass/rope", "fwd", 0.1),
                        (BASE + "loop/pass/attn", "bwd", 0.4),
                        (BASE + "loop/head/linear", "bwd", 0.6),
                        (BASE + "loop/head/loss", "fwd", 0.2),
                        (BASE + "loop/exit/norm", "fwd", 0.05),
                        (BASE + "loop/exit", "bwd", 0.05),
                        (BASE + "embed", "fwd", 0.1), ("round/aggregate", "", 0.5))
    monkeypatch.setitem(scope_reduce_ouro._memo, "table", with_scopes)
    value = mod.compute({"busy_s": 1.0}, [], INFO)
    config = harness.load_json("configs", "ouro-2.6b.json")
    flops = harness.load_module("flops", "ouro")
    want = {"loop_pass_ms.step": 1000.0, "loop_head_ms.step": 400.0, "loop_exit_ms.step": 50.0,
            "ouro_attn_ms.step": 400.0,
            "ouro_attn_roofline_pct":
                100 * 10 * flops.executed_attn_step_flops(config) / 197e12 / 0.2}[metric]
    assert value == pytest.approx(want, rel=1e-9)
    parent = _rows((BASE + "linear", "fwd", 1.0), (BASE + "norm", "bwd", 0.1))
    for table in (parent, None):  # no row of its scopes; no table at all
        monkeypatch.setitem(scope_reduce_ouro._memo, "table", table)
        assert mod.compute({"busy_s": 1.0}, [], INFO) is None
    assert mod.compute(None, [], INFO) is None
    assert scope_reduce_lfm2._memo is not scope_reduce_ouro._memo  # lent, and put back


def test_the_reader_lends_the_longer_list_for_one_read_and_puts_it_back(monkeypatch):
    from heterofl_tpu.obs import trace

    assert scope_reduce_ouro.LOOP_SCOPES == trace.LOOP_SCOPES
    seen = {}

    def fake_table():
        seen["scopes"] = scope_reduce_moe.EXTRA_SCOPES
        seen["pairs"], seen["singles"] = scope_reduce_moe._widened()
        return None

    monkeypatch.setattr(scope_reduce_moe, "table", fake_table)
    monkeypatch.setattr(scope_reduce_ouro, "_memo", {})
    kept = scope_reduce_lfm2._memo
    kept["table"] = "kept"
    try:
        assert scope_reduce_ouro.table() is None
        assert scope_reduce_lfm2._memo is kept and kept == {"table": "kept"}
    finally:
        kept.clear()
    assert seen["scopes"] == trace.EXTRA_SCOPES + trace.MIXER_SCOPES + trace.LOOP_SCOPES
    assert scope_reduce_lfm2.MIXER_SCOPES == trace.MIXER_SCOPES
    assert scope_reduce_moe.EXTRA_SCOPES == trace.EXTRA_SCOPES
    assert {("loop", "pass"), ("loop", "head"), ("loop", "exit")} <= seen["pairs"]
    # a path as the compiled program writes it, under the widened vocabulary
    name = ("jit(body)/round/chunk/round/local_train/while/body/closed_call/"
            "transpose(jvp(step/model))/loop/pass/while/body/checkpoint/gqa/linear/dot_general")
    before = scope_reduce._PAIRS, scope_reduce._SINGLES
    scope_reduce._PAIRS, scope_reduce._SINGLES = seen["pairs"], seen["singles"]
    try:
        assert scope_reduce.scope_of(name) == (BASE + "loop/pass/gqa/linear", "bwd")
    finally:
        scope_reduce._PAIRS, scope_reduce._SINGLES = before
    assert scope_reduce.scope_of(name)[0] == "round/local_train/step/model/linear"
