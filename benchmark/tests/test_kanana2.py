"""The Kanana-2 cell's files on the CPU at a tiny size: the comparison that
decides `correct` on a sound run and under the control, the FLOP file against
the program's own shapes, the scope reader against the program's vocabulary."""

import json

import numpy as np
import pytest

from benchmark import harness, scope_reduce, scope_reduce_moe
from benchmark import run as bench_run
from benchmark.tests import tiny_kanana2 as tiny

NAME = "kanana-2-30b-a3b.fix-a1-e1.train"


def _run_tiny(monkeypatch, capsys, *extra):
    cell, config = tiny.cell()
    monkeypatch.setattr(harness, "load_cell", lambda n: (cell, config))
    real = harness.load_json
    monkeypatch.setattr(harness, "load_json", lambda *p: (
        {"cpu": {"bf16_flops_per_s": 1e12}} if p[-1] == "peaks.json" else real(*p)))
    args = bench_run.parse(["--workload", NAME, "--seed", "3000000019",
                            "--seconds", "1", "--trace", "0", *extra])
    assert bench_run.run(args, require_tpu=False) == 0
    out = capsys.readouterr().out
    return json.loads(out.strip().splitlines()[-1]), out


def test_a_sound_run_of_the_tiny_cell_is_correct_and_the_control_is_not(monkeypatch, capsys):
    """(g) One process, both verdicts: the check rounds as returned are sound
    by every limit; passed through bfloat16 they fail."""
    line, out = _run_tiny(monkeypatch, capsys, "--control", "program_bf16")
    assert line["correct"] is False and line["failed"] == 0, out
    failed = {l.split()[2].rstrip(":") for l in out.splitlines()
              if l.startswith("benchmark: control ") and l.endswith("FAILED")}
    assert {"identity_ulp", "update_norm_gap", "outside_slice_changed"} <= failed, out
    assert not [l for l in out.splitlines()
                if l.startswith("benchmark: check ") and l.endswith("FAILED")], out
    for name in ("identity_ulp", "level_loss_gap", "update_norm_gap",
                 "outside_slice_changed", "window_compiles"):
        assert f"check {name}:" in out


def test_the_configuration_states_the_published_shape_and_its_cuts():
    manifest = harness.load_json("..", "BENCHMARK.json")
    entry = next(c for c in manifest["configs"] if c["name"] == "kanana-2-30b-a3b")
    config = harness.load_json("configs", "kanana-2-30b-a3b.json")
    assert entry["reduced"] == config["reduced"] == [
        "num_hidden_layers", "n_routed_experts", "vocab_size"]
    assert entry["source"] == config["source"]
    # the three cuts, and the widths as published
    assert (config["num_hidden_layers"], config["n_routed_experts"],
            config["vocab_size"]) == (5, 8, 16032)
    for key, value in {"hidden_size": 2048, "intermediate_size": 6144,
                       "moe_intermediate_size": 768, "kv_lora_rank": 512,
                       "qk_nope_head_dim": 128, "qk_rope_head_dim": 64,
                       "v_head_dim": 128, "num_attention_heads": 32,
                       "num_experts_per_tok": 6, "n_shared_experts": 2}.items():
        assert config[key] == value == config["model"].get(key, value), key
    m = config["model"]
    assert m["n_routed_experts"] == 128 and m["expert_share"] == [0, 16]
    assert m["num_tokens"] == config["vocab_size"] == config["data"]["sizes"]["types"]
    cell = harness.load_json("workloads", NAME + ".json")
    assert cell["traffic"]["cfg_overrides"]["round_chunk"] == 1
    rows, tokens = config["federation"]["batch_rows"], config["data"]["sizes"]["train"]
    assert tokens // rows == 2 * m["bptt"] and tokens % 33 == 0  # 2 local steps


def test_flops_count_the_models_own_matrices():
    """At rate r the FLOP file's widths are the program's sub-model's: the
    projections' multiply-adds a token are the matrix leaves' sizes."""
    import jax

    from heterofl_tpu.models import make_model

    config = harness.load_json("configs", "kanana-2-30b-a3b.json")
    flops = harness.load_module("flops", "kanana2")
    m = config["model"]
    cfg = tiny.program_cfg(**{k: m[k] for k in tiny.ARCH if k in m})
    cfg["num_tokens"] = m["num_tokens"]
    for rate in (1.0, 0.25):
        shapes = jax.eval_shape(make_model(cfg, rate).init, jax.random.key(0))
        held = m["n_routed_experts"] // m["expert_share"][1]
        per_token = 0.0
        for name, v in shapes.items():
            if v.ndim != 2 or name.startswith("embedding."):
                continue
            n = float(np.prod(v.shape))
            if ".moe.e" in name:  # a routed expert sees top_k / n_experts of the tokens
                n *= m["num_experts_per_tok"] / m["n_routed_experts"]
            per_token += 2.0 * n
        s = m["bptt"]
        attn = flops.forward_flops(m, rate) - s * per_token
        dn, dr, dv = (int(np.ceil(m[k] * rate)) for k in
                      ("qk_nope_head_dim", "qk_rope_head_dim", "v_head_dim"))
        want = m["num_hidden_layers"] * 2 * (s * (s + 1) // 2) * 32 * (dn + dr + dv)
        assert attn == pytest.approx(want, rel=1e-9), rate
        assert held == 8
    assert flops.executed_step_flops(config) == flops.step_flops(config, 1.0)
    share = flops.executed_routed_step_flops(config) / flops.executed_step_flops(config)
    assert 0.01 < share < 0.06  # "about 3 % of the matmul FLOPs"


def test_the_reader_keeps_the_new_scopes_and_puts_the_vocabulary_back():
    from heterofl_tpu.obs import trace

    assert scope_reduce_moe.EXTRA_SCOPES == trace.EXTRA_SCOPES
    name = ("jit(body)/round/chunk/round/local_train/while/body/closed_call/"
            "transpose(jvp(step/model))/checkpoint/moe/experts/linear/dot_general")
    assert scope_reduce.scope_of(name)[0] == "round/local_train/step/model/linear"
    kept = scope_reduce._PAIRS, scope_reduce._SINGLES
    scope_reduce._PAIRS, scope_reduce._SINGLES = scope_reduce_moe._widened()
    try:
        assert scope_reduce.scope_of(name) == (
            "round/chunk/round/local_train/step/model/moe/experts/linear", "bwd")
    finally:
        scope_reduce._PAIRS, scope_reduce._SINGLES = kept
    pred = scope_reduce_moe.any_of("mla", "rope", "attn")
    assert pred(["round/local_train/step/model/mla/linear", "fwd", "fusion", 1.0, True])
    assert not pred(["round/local_train/step/model/moe/shared/linear", "", "fusion", 1.0, True])
    # without a traced run every metric of the new scopes reports nothing
    assert scope_reduce_moe.ms(None, pred) is None
    for metric in ("attn_ms.step", "router_ms.step", "experts_ms.step",
                   "shared_ms.step", "experts_roofline_pct"):
        mod = harness.load_module("layer_metrics", metric)
        assert mod.compute(None, [], {"name": NAME, "steps_per_round": 2,
                                      "peak_flops_per_s": 1e12}) is None
