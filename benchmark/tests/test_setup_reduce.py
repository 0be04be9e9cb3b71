"""Set-up seconds by span: the program's reduction of a recorded span record (a ResNet
set-up in small: the experiment with its parts, split, stage, the first round
with a miss under `dispatch`, a hit under `stage`, an operation compiled while
the round program was traced, and the benchmark's own programs under no span),
the five `*.setup` metric files against it, what they return without the
program's module, and the record of a program this process ran."""

import sys

import pytest

from benchmark import harness, setup_reduce

METRICS = ("dataset_s.setup", "build_s.setup", "stage_s.setup",
           "compile_s.setup", "compile_misses.setup")

#: (id, name, t0, dt, parent, args) as `heterofl_tpu.obs.spans` records them,
#: in closing order
RECORDED = [
    (1, "setup/dataset", 0.0, 2.0, 0, {"bytes_in_use": 0}),
    (2, "setup/model", 2.0, 0.5, 0, {"bytes_in_use": 0}),
    (4, "compile/backend", 2.6, 0.25, 3, {"program": "jit(_threefry)", "cache": "hit",
                                          "load_s": 0.01}),
    (3, "setup/engine", 2.5, 1.0, 0, {"bytes_in_use": 512}),
    (0, "setup/experiment", 0.0, 4.0, None, {"bytes_in_use": 512}),
    (5, "setup/split", 4.0, 0.25, None, {"bytes_in_use": 512}),
    (7, "setup/stage/train", 4.25, 1.0, 6, {"bytes_in_use": 4096}),
    (8, "setup/stage/eval", 5.25, 0.5, 6, {"bytes_in_use": 4096}),
    (6, "setup/stage", 4.25, 1.5, None, {"bytes_in_use": 4096}),
    # the benchmark's weights: under no span
    (9, "compile/trace", 6.0, 0.125, None, {"program": "make_params"}),
    (10, "compile/backend", 6.125, 0.375, None, {"program": "jit(make_params)",
                                                 "cache": "miss", "written": True}),
    # the first round
    (13, "compile/backend", 7.0, 0.125, 12, {"program": "jit(_commit)", "cache": "hit"}),
    (12, "stage", 7.0, 0.25, 11, {}),
    (17, "compile/backend", 7.5, 0.0625, 16, {"program": "jit(iota)", "cache": "miss"}),
    (16, "compile/trace", 7.4, 0.5, 15, {"program": "eager_arange"}),
    (15, "compile/trace", 7.25, 1.0, 14, {"program": "body"}),
    (18, "compile/lower", 8.25, 0.5, 14, {"program": "jit(body)"}),
    (19, "compile/backend", 8.75, 8.0, 14, {"program": "jit(body)", "cache": "miss",
                                            "written": True}),
    (14, "dispatch", 7.25, 9.75, 11, {}),
    (11, "setup/first_round", 7.0, 10.5, None, {"bytes_in_use": 8192}),
    # the plain reference, after the window: under no span, uncached
    (20, "compile/backend", 30.0, 3.0, None, {"program": "jit(reference)",
                                              "cache": "uncached"}),
]
WANT = {"dataset_s.setup": 2.0, "build_s.setup": 2.0, "stage_s.setup": 1.75,
        "compile_s.setup": 0.25 + 0.125 + 1.0 + 0.5 + 8.0,
        "compile_misses.setup": 2}


def _record(rows=RECORDED):
    from heterofl_tpu.obs import spans

    rec = spans.SpanRecord()
    rec.spans = [spans.Span(*r) for r in rows]
    rec.counters.update(compile_requests=6, compile_hits=2, compile_misses=3)
    rec.by_parent["dispatch"] = {"compile_requests": 2, "compile_hits": 0,
                                 "compile_misses": 2}
    return rec


def test_the_programs_reduction_of_a_recorded_record():
    """The ONE reduction, the program's (`obs.spans.summarize`), holds what
    the five files read."""
    found = _record().summary()
    assert {k: v for k, v in found["seconds"].items() if "compile/" not in k} == {
        "setup/dataset": 2.0, "setup/model": 0.5, "setup/engine": 1.0,
        "setup/experiment": 4.0, "setup/split": 0.25, "setup/stage/train": 1.0,
        "setup/stage/eval": 0.5, "setup/stage": 1.5, "stage": 0.25,
        "dispatch": 9.75, "setup/first_round": 10.5}
    assert found["self_s"]["setup/experiment"] == 0.5
    assert found["self_s"]["setup/first_round"] == 0.5
    # a trace inside a trace is part of it; `outside` is the benchmark's own
    assert found["compile_s"] == {"setup/engine": 0.25, "outside": 3.5,
                                  "stage": 0.125, "dispatch": 9.5}
    assert found["own_compile_s"] == WANT["compile_s.setup"]
    # ... but a miss inside a trace is a miss of the span around it
    assert found["misses"] == {"outside": 1, "dispatch": 2}
    assert found["own_misses"] == 2
    assert found["cache"] == {"hit": 2, "miss": 3, "uncached": 1}
    assert found["programs"][0] == ["jit(body)", "compile/backend", 8.0, "miss",
                                    "dispatch"]
    assert found["bytes_in_use"]["setup/stage"] == 4096
    assert found["bytes_in_use"]["setup/first_round"] == 8192


def test_the_line_names_every_part(recorded):
    setup_reduce.table()
    text = recorded.readouterr().out
    for part in ("setup/experiment 4.000s (self 0.500s)", "setup/split 0.250s",
                 "setup/engine 1.000s (self 0.750s, compile 0.250s) 512 B in use",
                 "setup/stage/train 1.000s", "setup/first_round 10.500s (self 0.500s) "
                 "8192 B in use", "compile under dispatch 9.500s",
                 "compile under the program's spans 9.875s",
                 "compile under outside 3.500s", "compile/lower 0.500s",
                 "compile/backend jit(body) 8.000s miss under dispatch",
                 "hit 2, miss 3, uncached 1", "misses under dispatch 2, outside 1",
                 "requests/hits/misses: 6/2/3; by span: dispatch 2/0/2"):
        assert part in text, part
    assert text.count("\n") == 1 and text.startswith("benchmark: set-up seconds by span")


@pytest.fixture
def recorded(monkeypatch, capsys):
    from heterofl_tpu.obs import spans

    monkeypatch.setattr(setup_reduce, "_memo", {})
    monkeypatch.setattr(spans, "RECORD", _record())
    return capsys


@pytest.mark.parametrize("metric", METRICS)
def test_metric_files_read_the_record(metric, recorded):
    compute = harness.load_module("layer_metrics", metric).compute
    # the harness hands a metric (reduction, phases, info); none holds set-up
    assert compute(None, [], {}) == WANT[metric]
    assert compute({"idle_share": 0.0}, [{"stage": 1.0}], {"chips": 1}) == WANT[metric]
    out = recorded.readouterr().out
    assert out.count("benchmark: set-up seconds by span") == 1  # ONE line a run
    assert len(out.strip().splitlines()) == 1


@pytest.mark.parametrize("metric", METRICS)
def test_metric_files_return_none_without_the_module(metric, monkeypatch, capsys):
    """The parent commit under these files: `heterofl_tpu.obs` has no `spans`."""
    import heterofl_tpu.obs as obs

    monkeypatch.setattr(setup_reduce, "_memo", {})
    if hasattr(obs, "spans"):
        monkeypatch.delattr(obs, "spans")
    monkeypatch.setitem(sys.modules, "heterofl_tpu.obs.spans", None)
    assert setup_reduce.table() is None
    assert harness.load_module("layer_metrics", metric).compute(None, [], {}) is None
    assert "set-up seconds" not in capsys.readouterr().out


def test_a_span_the_metric_needs_but_the_record_lacks_gives_none(monkeypatch):
    from heterofl_tpu.obs import spans

    monkeypatch.setattr(setup_reduce, "_memo", {})
    monkeypatch.setattr(spans, "RECORD", _record(RECORDED[5:]))  # no `setup/experiment`
    get = lambda n: harness.load_module("layer_metrics", n).compute(None, [], {})
    assert get("build_s.setup") is None and get("dataset_s.setup") is None
    assert get("stage_s.setup") == 1.75 and get("compile_misses.setup") == 2


def test_every_new_metric_moves_setup_s_in_every_cell():
    manifest = harness.load_json("..", "BENCHMARK.json")
    mine = [m for m in manifest["per_layer"] if m["name"] in METRICS]
    assert [m["name"] for m in mine] == list(METRICS)
    assert manifest["per_layer"][-len(METRICS):] == mine  # appended, in order
    for m in mine:
        assert m["moves"] == "setup_s" and m["source"] == "program_span"
        assert m["better"] == "lower" and "workloads" not in m
    assert [m["layer"] for m in mine] == ["entry / driver", "entry / driver", "staging",
                                          "round program", "round program"]
    for cell in manifest["workloads"]:
        names = [m["name"] for m in harness.cell_metrics(cell["name"])[1]]
        assert set(METRICS) <= set(names)


def test_the_record_of_a_program_this_process_ran(monkeypatch, capsys, tmp_path):
    """A tiny experiment as the harness builds it, its first round, and the
    metric files on the process's own record."""
    import json

    import jax

    from heterofl_tpu.obs import spans

    monkeypatch.setattr(setup_reduce, "_memo", {})
    monkeypatch.setattr(spans.RECORD, "spans", [])
    spans.install()
    exp, _, _ = harness.build_experiment([
        "--control_name", "1_8_0.5_iid_fix_a1-b1-c1-d1-e1_bn_1_1",
        "--data_name", "MNIST", "--model_name", "conv", "--synthetic", "1",
        "--synthetic_sizes", json.dumps({"train": 200, "test": 80}),
        "--output_dir", str(tmp_path), "--override", json.dumps({
            "num_epochs": {"global": 2, "local": 1},
            "conv": {"hidden_size": [8, 16]}})])
    log = harness.make_round_log(str(tmp_path / "log"))
    params = exp.model.init(jax.random.key(0))
    jax.block_until_ready(exp.train_round(params, 1, exp.scheduler(1), log))
    get = lambda n: harness.load_module("layer_metrics", n).compute(None, [], {})
    values = {n: get(n) for n in METRICS}
    assert all(v is not None and v >= 0 for v in values.values()), values
    found = setup_reduce.table()
    assert values["compile_s.setup"] > 0.0
    assert found["compile_s"].get("dispatch", 0.0) > 0.0  # the round program
    assert values["stage_s.setup"] == pytest.approx(
        found["seconds"]["setup/split"] + found["seconds"]["setup/stage"])
    assert values["build_s.setup"] + values["dataset_s.setup"] == pytest.approx(
        found["seconds"]["setup/experiment"])
    assert "setup/first_round" in capsys.readouterr().out
