"""The window loop, the metric arithmetic and the comparison that decides
`correct`, driven as functions on tiny cells on the CPU; the command itself
refuses to run there."""

import json
import os
import subprocess
import sys

import numpy as np
import pytest

from benchmark import checks, harness
from benchmark import run as bench_run
from benchmark.tests import tiny

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


class FakeExp:
    """Rounds of exactly one second on a clock of its own."""

    kind, num_active = "vision", 2
    cfg = {"num_epochs": {"local": 1}, "batch_size": {"train": 10}}
    train_data = (np.zeros((4, 50, 1)),)

    def __init__(self, clock, n_per_round=100.0):
        from heterofl_tpu.parallel.staging import PhaseTimer

        self.clock, self.n, self.phase_timer = clock, n_per_round, PhaseTimer()
        self.evaluated = []

    def scheduler(self, epoch):
        return 0.1

    def train_round(self, params, epoch, lr, log):
        self.clock.t += 1.0
        log.append({"Local-Loss": 2.0 - 0.1 * epoch}, "train", n=self.n)
        return params

    def evaluate(self, params, epoch, log, label_split):
        self.clock.t += 0.5
        self.evaluated.append(epoch)


class Clock:
    t = 0.0

    def __call__(self):
        return self.t


def test_window_holds_whole_rounds_and_counts_the_programs_samples(tmp_path):
    clock = Clock()
    exp, log = FakeExp(clock), harness.make_round_log(str(tmp_path))
    w = harness.run_window(exp, {}, 2.5, log, clock=clock)
    assert w["round_s"] == [1.0, 1.0, 1.0] and w["window_s"] == 3.0
    attempted, failed, steps = harness.window_counts(w, exp)
    # 2 active clients x 5 steps x 10 samples = 100 a round: 10 steps a round
    assert (attempted, failed, steps) == (3, 0, 30.0)
    m = harness.end_to_end(w, exp, setup_s=7.0)
    assert m["round_s"]["value"] == 1.0 and m["setup_s"]["value"] == 7.0
    assert m["client_steps_per_s"] == {"value": 10.0, "unit": "steps/s"}


def test_a_round_that_trained_other_samples_or_lost_its_loss_fails(tmp_path):
    clock = Clock()
    exp, log = FakeExp(clock, n_per_round=90.0), harness.make_round_log(str(tmp_path))
    w = harness.run_window(exp, {}, 1.5, log, clock=clock)
    assert harness.window_counts(w, exp)[:2] == (2, 2)
    w["rounds"] = [{"n": 100.0, "loss": float("nan")}, {"n": 100.0, "loss": 1.0}]
    assert harness.window_counts(w, exp)[:2] == (2, 1)


def test_eval_every_runs_inside_the_window_and_counts_against_the_rate(tmp_path):
    clock = Clock()
    exp, log = FakeExp(clock), harness.make_round_log(str(tmp_path))
    w = harness.run_window(exp, {}, 2.9, log, eval_every=1, clock=clock)
    assert exp.evaluated == [harness.WINDOW_EPOCH, harness.WINDOW_EPOCH + 1]
    assert w["round_s"] == [1.0, 1.0] and w["window_s"] == 3.0
    assert harness.end_to_end(w, exp, 0.0)["client_steps_per_s"]["value"] == \
        pytest.approx(20.0 / 3.0)


def test_a_compile_inside_the_window_turns_correct_false():
    import jax
    import jax.numpy as jnp

    counter = harness.CompileCounter()
    x = jnp.ones(7).block_until_ready()
    f = jax.jit(lambda x_: x_ * 3.25 + 1.5)
    before = counter.compiles
    f(x).block_until_ready()
    assert counter.compiles == before + 1
    f(x).block_until_ready()  # the second call finds its program
    assert counter.compiles == before + 1
    window = {"rounds": [{"loss": 1.0}, {"loss": 1.2}]}
    assert all(r[3] for r in checks.window_rows(window, 0, first_loss=2.3))
    assert not all(r[3] for r in checks.window_rows(
        window, counter.compiles - before, first_loss=2.3))
    assert not all(r[3] for r in checks.window_rows(window, 0, first_loss=1.2))


def test_identity_bound_fails_when_the_aggregate_passes_through_bf16():
    rng = np.random.default_rng(0)
    before = {"w": rng.standard_normal((64, 64)).astype(np.float32) * 0.05,
              "b": np.zeros(8, np.float32)}
    k = 10
    # the counted average of k equal float32 values, summed in float32
    total = {n: np.sum(np.stack([v] * k), axis=0, dtype=np.float32)
             for n, v in before.items()}
    after = {n: (t / np.float32(k)).astype(np.float32) for n, t in total.items()}
    assert checks.identity_ulp(before, after) <= k
    assert checks.identity_ulp(before, checks.through_bf16(after)) > 1000 * k
    assert checks.identity_ulp(before, {n: v.astype(np.float16) for n, v in after.items()}) \
        == float("inf")  # another type than float32 comes back


def test_update_norm_gap_is_one_when_the_state_comes_back_unchanged():
    rng = np.random.default_rng(1)
    before = {n: rng.standard_normal(s).astype(np.float32) for n, s in
              (("a", (8, 8)), ("b", (8,)), ("c", (4, 4)))}
    ref = {n: v + 0.01 * rng.standard_normal(v.shape).astype(np.float32)
           for n, v in before.items()}
    assert checks.update_norm_gap(before, ref, ref)[0] == 0.0
    assert checks.update_norm_gap(before, before, ref)[0] == pytest.approx(1.0)


def _run_tiny(monkeypatch, capsys, cell, config, name, *extra):
    monkeypatch.setattr(harness, "load_cell", lambda n: (cell, config))
    real = harness.load_json
    monkeypatch.setattr(harness, "load_json", lambda *p: (
        {"cpu": {"bf16_flops_per_s": 1e12}} if p[-1] == "peaks.json" else real(*p)))
    args = bench_run.parse(["--workload", name, "--seed", "3000000019",
                            "--seconds", "1", "--trace", "0", *extra])
    assert bench_run.run(args, require_tpu=False) == 0
    out = capsys.readouterr().out
    return json.loads(out.strip().splitlines()[-1]), out


def test_a_sound_run_of_the_tiny_resnet_cell_is_correct(monkeypatch, capsys):
    cell, config = tiny.resnet()
    line, out = _run_tiny(monkeypatch, capsys, cell, config,
                          "resnet18-cifar10.fix-a1-e1.train")
    assert line["correct"] is True and line["failed"] == 0, out
    assert set(line["metrics"]) == {"round_s", "client_steps_per_s", "setup_s"}
    assert line["attempted"] >= 1 and set(line["device"]) >= {
        "platform", "kind", "count", "memory_peak_bytes"}
    for name in ("identity_ulp", "level_loss_gap", "update_norm_gap",
                 "outside_slice_changed", "window_compiles"):
        assert f"check {name}:" in out  # each number compared, beside its limit


def test_the_check_rounds_hold_one_client_of_each_level_over_again(monkeypatch, capsys):
    """Five slots, two levels in the cohort: every slot trains, the reference
    follows the first client of each level and counts it as often as it is
    held (3 + 2 copies)."""
    rates = np.array([0.25, 0.5, 0.25, 1.0, 1.0, 0.25, 0.25, 1.0, 0.25, 0.5])
    users, distinct = checks.check_cohort(np.array([7, 3, 9, 1, 4]), 5, rates)
    assert users.tolist() == [7, 9, 7, 9, 7] and distinct.tolist() == [7, 9]
    cell, config = tiny.transformer()
    line, out = _run_tiny(monkeypatch, capsys, cell, config,
                          "transformer-wikitext2.fix-a1-e1.train")
    assert line["correct"] is True, out


@pytest.mark.parametrize("make", [tiny.resnet, tiny.transformer])
def test_the_control_comes_out_not_correct(monkeypatch, capsys, make):
    """What the program's check rounds return, passed through bfloat16."""
    cell, config = make()
    line, out = _run_tiny(monkeypatch, capsys, cell, config, cell["name"],
                          "--control", "program_bf16")
    assert line["correct"] is False
    failed = {l.split()[2].rstrip(":") for l in out.splitlines()
              if l.startswith("benchmark: control ") and l.endswith("FAILED")}
    assert {"identity_ulp", "update_norm_gap", "outside_slice_changed"} <= failed, out
    assert not [l for l in out.splitlines()  # the same rounds, as returned, are sound
                if l.startswith("benchmark: check ") and l.endswith("FAILED")], out


def test_a_broken_timed_path_comes_out_not_correct(monkeypatch, capsys):
    """The round program returns its state unchanged (the metrics are real)."""
    from heterofl_tpu.parallel.round_engine import RoundEngine

    real = RoundEngine.train_round

    def unchanged(self, params, *a, **kw):
        import jax

        keep = jax.tree_util.tree_map(lambda x: x + 0, params)
        _, ms = real(self, params, *a, **kw)
        return keep, ms

    monkeypatch.setattr(RoundEngine, "train_round", unchanged)
    cell, config = tiny.transformer()
    line, out = _run_tiny(monkeypatch, capsys, cell, config,
                          "transformer-wikitext2.fix-a1-e1.train")
    assert line["correct"] is False
    assert "check update_norm_gap: 1.0" in out and "check inside_slice_moved: 0" in out
    assert "check window_last_loss:" in out


def test_the_command_refuses_to_run_without_a_tpu():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    p = subprocess.run(
        [sys.executable, os.path.join(ROOT, "benchmark", "run.py"), "--workload",
         "resnet18-cifar10.fix-a1-e1.train", "--seed", "1", "--seconds", "1",
         "--trace", "0"], cwd=ROOT, env=env, capture_output=True, text=True,
        timeout=120)
    assert p.returncode != 0
    assert "{" not in p.stdout, p.stdout  # no result line
    assert "TPU" in p.stderr


def test_manifest_names_files_that_exist():
    manifest = harness.load_json(os.pardir, "BENCHMARK.json")
    for w in manifest["workloads"]:
        cell, config = harness.load_cell(w["name"])
        assert cell["why"] == w["why"] and config["name"] == w["config"]
        for kind, key in (("data", "writer"),):
            harness.load_module(kind, config["data"][key])
        harness.load_module("reference", config["reference"])
        harness.load_module("flops", config["flops"])
        assert set(config["limits"]) == {"level_loss_gap", "update_norm_gap"}
    for m in manifest["per_layer"]:
        assert callable(harness.load_module("layer_metrics", m["name"]).compute)
    for c in manifest["configs"]:
        assert os.path.exists(os.path.join(ROOT, c["file"]))
        assert c["reduced"] == harness.load_json("configs", c["name"] + ".json")["reduced"]
