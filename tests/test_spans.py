"""Set-up and compile spans (ISSUE 38, heterofl_tpu/obs/spans.py).

Contracts:

* the vocabulary is closed (an unknown name raises, as ``scope()`` does);
* a tiny experiment's set-up spans nest as the driver nests them, and a
  span's self time plus its children's is its length;
* the compile-bearing first round files ``compile/*`` spans under
  ``dispatch`` under ``setup/first_round`` with the program's name, the
  second round files none;
* cold then warm on a temporary cache directory reads misses then hits;
* a jax span, converted from the wall clock, lies inside the phase that
  caused it;
* ``enable_persistent_cache()`` registers one set of listeners however
  often it is called;
* past the cap only the counters move;
* the round programs lower to the same text with the record's listeners
  installed and with them patched away;
* a ``PhaseTimer`` hook receives set-up and compile spans with their parent;
* a driver run's ``trace.json`` begins with ``setup/experiment`` and
  ``obs.report`` prints the set-up table from its ``events.jsonl``;
* the recorder belongs to ``run()``: an experiment that is built and never
  run (an evaluation, a refused configuration) writes no trace file and
  leaves a training run's alone, and ``run()`` closes it on every exit.
"""

import json
import os

import jax
import jax._src.monitoring as monitoring
import jax.numpy as jnp
import numpy as np
import pytest

from heterofl_tpu import config as C
from heterofl_tpu.data import (fetch_dataset, label_split_masks, split_dataset,
                               stack_client_shards)
from heterofl_tpu.models import make_model
from heterofl_tpu.obs import spans
from heterofl_tpu.parallel import PhaseTimer, RoundEngine, make_mesh
from heterofl_tpu.utils.compile_cache import (enable_persistent_cache,
                                              no_persistent_cache)
from heterofl_tpu.utils.logger import Logger

from test_models import small_cfg
from test_round import _lm_setup


@pytest.fixture
def record(monkeypatch):
    """The process's record, emptied for the test: the worker's earlier
    tests may have filled it to the cap."""
    monkeypatch.setattr(spans.RECORD, "spans", [])
    monkeypatch.setattr(spans.RECORD, "dropped", 0)
    spans.install()
    return spans.RECORD


def _driver_cfg(out_dir, model="conv", **over):
    cfg = C.default_cfg()
    lm = model == "transformer"
    cfg["control"] = C.parse_control_name(
        "1_4_0.5_iid_fix_a1-b1_bn_1_1" if lm
        else "1_8_0.5_iid_fix_a1-b1-c1-d1-e1_bn_1_1")
    cfg["data_name"] = "WikiText2" if lm else "MNIST"
    cfg["model_name"] = model
    cfg["synthetic"] = True
    cfg["synthetic_sizes"] = {"train": 200, "test": 80}
    cfg["output_dir"] = str(out_dir)
    cfg["override"] = {
        "num_epochs": {"global": 2, "local": 1},
        "conv": {"hidden_size": [8, 16]},
        "transformer": {"embedding_size": 32, "num_heads": 4, "hidden_size": 64,
                        "num_layers": 2, "dropout": 0.0},
        "bptt": 16,
        "batch_size": {"train": 4, "test": 2} if lm else {"train": 10, "test": 20},
        **over}
    return C.process_control(cfg)


def _staged(tmp_path, model="conv", **over):
    from heterofl_tpu.entry.common import FedExperiment

    exp = FedExperiment(_driver_cfg(tmp_path, model, **over), 0)
    data_split, label_split = exp.make_splits()
    exp.stage(data_split, label_split)
    return exp


def _names(record, parent_name):
    """Names of the spans directly inside the (one) span ``parent_name``."""
    (up,) = [s for s in record.spans if s.name == parent_name]
    return [s.name for s in sorted(record.spans, key=lambda s: s.t0)
            if s.parent == up.id and not s.name.startswith("compile/")]


# ---------------------------------------------------------------------------
# the vocabulary
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("name", ["setup/unknown", "dispatch", "compile/backend",
                                  "setup", ""])
def test_the_vocabulary_is_closed(name):
    with pytest.raises(ValueError, match="Not valid span"):
        with spans.span(name):
            pass


def test_the_vocabularies_do_not_overlap():
    assert len(set(spans.SETUP_SPANS)) == len(spans.SETUP_SPANS)
    assert not set(spans.SETUP_SPANS) & set(spans.COMPILE_SPANS)
    assert all(n.startswith("setup/") for n in spans.SETUP_SPANS)
    assert all(n.startswith("compile/") for n in spans.COMPILE_SPANS)


# ---------------------------------------------------------------------------
# nesting and self time
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("model", ["conv", "transformer"])
def test_setup_spans_nest_as_the_driver_nests_them(tmp_path, record, model):
    exp = _staged(tmp_path, model)
    with spans.span("setup/init", exp.phase_timer):
        exp.model.init(jax.random.key(0))
    assert _names(record, "setup/experiment") == [
        "setup/dataset", "setup/model", "setup/engine"]
    assert _names(record, "setup/stage") == ["setup/stage/train",
                                             "setup/stage/eval"]
    top = [s.name for s in sorted(record.spans, key=lambda s: s.t0)
           if s.parent is None and s.name.startswith("setup/")]
    assert top == ["setup/experiment", "setup/split", "setup/stage",
                   "setup/init"]
    # a span's self time plus its children's is its length, span by span
    by_id = {s.id: s for s in record.spans}
    for s in record.spans:
        inside = sum(c.dt for c in record.spans if c.parent == s.id)
        assert -1e-6 <= s.dt - inside <= s.dt + 1e-6, s
        if s.parent is not None:
            up = by_id[s.parent]
            assert up.t0 - 5e-3 <= s.t0 and s.t0 + s.dt <= up.t0 + up.dt + 5e-3
    # ... and name by name, in the summary the readers print
    found = record.summary()
    total = sum(s.dt for s in record.spans if s.parent is None)
    assert sum(found["self_s"].values()) == pytest.approx(total, rel=1e-9)
    assert found["seconds"]["setup/experiment"] >= \
        found["seconds"]["setup/dataset"] + found["seconds"]["setup/engine"]


def test_setup_spans_stay_out_of_the_phase_totals(tmp_path, record):
    exp = _staged(tmp_path)
    assert not any(k.startswith(("setup/", "compile/"))
                   for k in exp.phase_timer.totals)


# ---------------------------------------------------------------------------
# the compile-bearing first round
# ---------------------------------------------------------------------------

def test_first_round_files_its_compilations_and_the_second_none(tmp_path, record):
    exp = _staged(tmp_path)
    params = exp.model.init(jax.random.key(0))
    logger = Logger(str(tmp_path / "log"))
    logger.safe(True)
    with no_persistent_cache():
        params = exp.train_round(params, 1, 0.1, logger)
        jax.block_until_ready(params)
        by_id = {s.id: s for s in record.spans}
        (first,) = [s for s in record.spans if s.name == "setup/first_round"]
        (dispatch,) = [s for s in record.spans
                       if s.name == "dispatch" and s.parent == first.id]
        under = [s for s in record.spans if s.parent == dispatch.id]
        assert {s.name for s in under} == set(spans.COMPILE_SPANS)
        (backend,) = [s for s in under if s.name == "compile/backend"
                      and s.dt == max(c.dt for c in under
                                      if c.name == "compile/backend")]
        # the round program, by jax's own name for it, compiled fresh
        assert backend.args["program"].startswith("jit(")
        assert backend.args["cache"] == "uncached"
        assert "bytes_in_use" in first.args or jax.default_backend() == "cpu"
        assert spans.program_parent(backend, by_id) == "dispatch"
        n = len(record.spans)
        params = exp.train_round(params, 2, 0.1, logger)
        jax.block_until_ready(params)
    logger.safe(False)
    assert len(record.spans) == n  # no phase, no compilation, no set-up span
    assert sum(s.name == "setup/first_round" for s in record.spans) == 1
    # the per-round phase table reads what it read: the first round's phases
    assert exp.phase_timer.calls["dispatch"] == 2


# ---------------------------------------------------------------------------
# the persistent cache: cold, then warm
# ---------------------------------------------------------------------------

def _recompile(fn, x):
    jax.clear_caches()
    return jax.jit(fn)(x).block_until_ready()


def test_cold_then_warm_reads_misses_then_hits(tmp_path, record):
    from jax.experimental.compilation_cache.compilation_cache import reset_cache

    keep = {k: getattr(jax.config, k) for k in (
        "jax_compilation_cache_dir", "jax_persistent_cache_min_compile_time_secs",
        "jax_persistent_cache_min_entry_size_bytes")}
    before = dict(record.counters)
    timer = PhaseTimer()
    try:
        jax.config.update("jax_compilation_cache_dir", str(tmp_path / "cache"))
        jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
        jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)
        reset_cache()

        def fn(x):  # a program no other test compiles
            return jnp.tanh(x * 38.0 + 3.8).sum()

        for _ in range(2):
            with timer.phase("dispatch"):
                _recompile(fn, np.arange(383.0))
    finally:
        for k, v in keep.items():
            jax.config.update(k, v)
        reset_cache()
    mine = [s for s in record.spans if s.name == "compile/backend"
            and s.args["program"] == "jit(fn)"]
    assert [s.args["cache"] for s in mine] == ["miss", "hit"]
    assert mine[0].args.get("written") is True and "written" not in mine[1].args
    assert mine[1].args["load_s"] >= 0.0 and "load_s" not in mine[0].args
    moved = {k: record.counters[k] - before[k] for k in before}
    assert moved["compile_misses"] >= 1 and moved["compile_hits"] >= 1
    assert moved["compile_requests"] == (moved["compile_hits"]
                                         + moved["compile_misses"])
    assert record.by_parent["dispatch"]["compile_hits"] >= 1
    assert record.summary()["misses"].get("dispatch", 0) >= 1


# ---------------------------------------------------------------------------
# the two clocks
# ---------------------------------------------------------------------------

def test_a_jax_span_lies_inside_the_phase_that_caused_it(record):
    timer = PhaseTimer()
    with no_persistent_cache():
        with timer.phase("dispatch"):
            _recompile(lambda x: jnp.cos(x * 38.5).sum(), np.arange(385.0))
    (phase,) = [s for s in record.spans if s.name == "dispatch"]
    inside = [s for s in record.spans if s.parent == phase.id]
    assert {s.name for s in inside} == set(spans.COMPILE_SPANS)
    for s in inside:
        # ONE paired reading of the clocks: off by the clocks' drift since
        # the record was made, far under the 5 ms allowed here
        assert phase.t0 - 5e-3 <= s.t0
        assert s.t0 + s.dt <= phase.t0 + phase.dt + 5e-3
    assert record.to_perf(record._wall0) == record._perf0


# ---------------------------------------------------------------------------
# one observer
# ---------------------------------------------------------------------------

def _mine(listeners):
    return [f for f in listeners if getattr(f, "__self__", None) is spans.RECORD]


def test_enable_persistent_cache_twice_registers_one_set_of_listeners():
    enable_persistent_cache()
    enable_persistent_cache()
    assert spans.install() is spans.RECORD
    assert len(_mine(monitoring.get_event_listeners())) == 1
    assert len(_mine(monitoring.get_event_duration_listeners())) == 1
    assert len(_mine(monitoring.get_event_time_span_listeners())) == 1
    assert len(_mine(monitoring.get_scalar_listeners())) == 1


def test_compile_cache_still_loads_alone_by_file_path():
    """ROADMAP's tier-1 line asks ``default_cache_dir`` of the file loaded
    by path, outside the package and without jax: the record is imported
    where it is installed, not at the file's top."""
    import subprocess
    import sys

    import heterofl_tpu.utils.compile_cache as cc

    code = ("import importlib.util as u, sys; "
            f"s = u.spec_from_file_location('cc', {cc.__file__!r}); "
            "m = u.module_from_spec(s); s.loader.exec_module(m); "
            "print(m.default_cache_dir('/x')); "
            "assert 'jax' not in sys.modules and 'heterofl_tpu' not in sys.modules")
    out = subprocess.run([sys.executable, "-c", code], capture_output=True,
                         text=True, timeout=60)
    assert out.returncode == 0, out.stderr
    assert out.stdout.strip() == cc.default_cache_dir("/x")


# ---------------------------------------------------------------------------
# the cap
# ---------------------------------------------------------------------------

def test_past_the_cap_only_the_counters_move():
    rec = spans.SpanRecord(cap=3)
    for i in range(5):
        rec.on_scalar("/jax/core/compile/backend_compile_duration", 10.0 + i,
                      fun_name=f"jit(p{i})")
        rec.on_event("/jax/compilation_cache/compile_requests_use_cache")
        rec.on_time_span("/jax/core/compile/backend_compile_duration",
                         10.0 + i, 10.5 + i, fun_name=f"jit(p{i})")
    assert [s.args["program"] for s in rec.spans] == ["jit(p0)", "jit(p1)", "jit(p2)"]
    assert rec.dropped == 2 and not rec.stack()
    assert rec.counters == {"compile_requests": 5, "compile_hits": 0,
                            "compile_misses": 5}
    assert rec.by_parent == {spans.OUTSIDE: rec.counters}
    assert rec.summary()["compile_s"] == {spans.OUTSIDE: pytest.approx(1.5)}
    # what an operator reads once the spans stop: the counters, by span
    assert spans.table(rec.summary())[-1] == (
        "compile cache requests/hits/misses: 5/0/5; by span: outside 5/0/5; "
        "2 spans past the cap")
    assert spans.CAP == 4096


def test_a_trace_nested_in_a_trace_is_part_of_it():
    rec = spans.SpanRecord()
    trace = "/jax/core/compile/jaxpr_trace_duration"
    rec.on_scalar(trace, 1.0, fun_name="outer")
    rec.on_scalar(trace, 1.1, fun_name="where")      # a jnp function's own jit
    rec.on_time_span(trace, 1.1, 1.2, fun_name="where")
    rec.on_scalar(trace, 1.3, fun_name="eager")      # something compiles inside
    rec.on_scalar("/jax/core/compile/backend_compile_duration", 1.35,
                  fun_name="jit(eager)")
    rec.on_time_span("/jax/core/compile/backend_compile_duration", 1.35, 1.4,
                     fun_name="jit(eager)")
    rec.on_time_span(trace, 1.3, 1.45, fun_name="eager")
    rec.on_time_span(trace, 1.0, 2.0, fun_name="outer")
    assert [(s.args["program"], s.parent) for s in rec.spans] == [
        ("jit(eager)", 1), ("eager", 0), ("outer", None)]
    found = rec.summary()
    assert found["seconds"] == {"compile/trace": pytest.approx(1.0)}
    assert found["self_s"]["compile/trace"] == pytest.approx(0.95)
    assert found["cache"] == {"uncached": 1}


# ---------------------------------------------------------------------------
# the round programs do not know the record
# ---------------------------------------------------------------------------

def _round_program(kind):
    """(jitted K = 1 round program, its arguments) at a tiny size."""
    if kind == "lm":
        cfg, data = _lm_setup()
        users = np.arange(4, dtype=np.int32)
    else:
        cfg = small_cfg("resnet18", data_name="CIFAR10",
                        control="1_8_0.5_iid_fix_a1-b1-c1-d1-e1_bn_1_1")
        ds = fetch_dataset("CIFAR10", synthetic=True, seed=0,
                           synthetic_sizes={"train": 80, "test": 40})
        split, lsplit = split_dataset(ds, 8, cfg["data_split_mode"],
                                      np.random.default_rng(0), classes_size=10)
        x, y, m = stack_client_shards(ds["train"].data, ds["train"].target,
                                      split["train"], list(range(8)))
        data = tuple(jnp.asarray(a) for a in
                     (x, y, m, label_split_masks(lsplit, 8, 10)))
        users = np.array([0, 2, 4, 6], np.int32)
    model = make_model(cfg)
    eng = RoundEngine(model, cfg, make_mesh(2, 1))
    fix = (eng.fix_rates,) if eng.fix_rates is not None else ()
    return eng._build_train(), (model.init(jax.random.key(0)), jax.random.key(0),
                                np.float32(0.1), users, users, *data, *fix)


@pytest.mark.parametrize("kind", ["resnet", "lm"])
def test_round_program_lowers_to_the_same_text_without_the_listeners(
        kind, record, monkeypatch):
    texts, counts = [], []
    for patched_away in (False, True):  # one call site: it is in the debug info
        if patched_away:
            for group in ("_event_listeners", "_event_duration_secs_listeners",
                          "_event_time_span_listeners", "_scalar_listeners"):
                monkeypatch.setattr(monitoring, group, [
                    f for f in getattr(monitoring, group) if not _mine([f])])
        prog, args = _round_program(kind)
        n = len(record.spans)
        texts.append(prog.lower(*args).as_text(debug_info=True))
        counts.append(sum(s.name == "compile/lower" for s in record.spans[n:]))
    assert texts[0] == texts[1] and "round/local_train" in texts[0]
    assert counts == [1, 0]  # recorded, then patched away


# ---------------------------------------------------------------------------
# the hook
# ---------------------------------------------------------------------------

class _Hook:
    """A ``PhaseTimer.trace`` hook that takes ``args``, as TraceRecorder."""

    def __init__(self):
        self.events = []

    def complete(self, name, t0, dt, cat="phase", args=None):
        self.events.append((name, cat, dict(args or {})))


class _BareHook:
    """... and one that takes none, as the benchmark's ``PhaseSpans``."""

    def __init__(self):
        self.events = []

    def complete(self, name, t0, dt, cat=None):
        self.events.append((name, cat, {}))


@pytest.mark.parametrize("hook_type", [_Hook, _BareHook])
def test_a_timer_hook_receives_setup_and_compile_spans(hook_type, record):
    timer = PhaseTimer()
    hook = timer.trace = hook_type()
    with no_persistent_cache():
        with spans.span("setup/first_round", timer):
            with timer.phase("stage"):
                pass
            with timer.phase("dispatch"):
                _recompile(lambda x: jnp.sin(x * 38.7).sum(), np.arange(387.0))
    names = [e[0] for e in hook.events]
    assert names[0] == "stage" and names[-2:] == ["dispatch", "setup/first_round"]
    assert set(names[1:-2]) == set(spans.COMPILE_SPANS)
    cats = {e[0]: e[1] for e in hook.events}
    assert cats["setup/first_round"] == "setup" and cats["dispatch"] == "phase"
    assert cats["compile/backend"] == "compile"
    assert timer.totals.keys() == {"stage", "dispatch"}
    if hook_type is _Hook:  # the bare one got the same spans, names alone
        args = {e[0]: e[2] for e in hook.events}
        assert args["stage"] == {}  # nothing compiled in it: a plain phase
        assert args["setup/first_round"]["parent"] is None
        assert args["dispatch"]["parent"] == args["setup/first_round"]["id"]
        assert args["compile/backend"]["parent"] == args["dispatch"]["id"]
        assert args["compile/backend"]["cache"] == "uncached"
        assert args["compile/backend"]["program"].startswith("jit(")


# ---------------------------------------------------------------------------
# the run's own timeline, and its reader
# ---------------------------------------------------------------------------

def test_trace_json_of_a_driver_run_begins_with_setup(tmp_path, record, capsys):
    from heterofl_tpu.entry.common import FedExperiment
    from heterofl_tpu.obs import report
    from heterofl_tpu.obs.trace import validate_event

    cfg = _driver_cfg(tmp_path, trace_dir=str(tmp_path / "trace"))
    exp = FedExperiment(cfg, 0)
    tdir = tmp_path / "trace" / exp.tag
    # the recorder is the run's: built, the experiment has written nothing
    assert exp.tracer is None and exp.phase_timer.trace is None
    assert not tdir.exists()
    exp.run("Global-Accuracy")
    assert exp.tracer.closed and exp.phase_timer.trace is None
    events = json.load(open(tdir / "trace.json"))["traceEvents"]
    first = min(events, key=lambda e: e["ts"])
    assert first["name"] == "setup/experiment" and first["ts"] >= 0.0
    names = {e["name"] for e in events}
    assert set(spans.SETUP_SPANS) | set(spans.COMPILE_SPANS) <= names
    lines = [validate_event(json.loads(l)) for l in open(tdir / "events.jsonl")]
    by_id = {e["args"]["id"]: e for e in lines if "id" in e["args"]}
    # how to read a compilation off events.jsonl: name, program, cache, and
    # the chain of parents up to the set-up span
    backend = max((e for e in lines if e["name"] == "compile/backend"),
                  key=lambda e: e["dur_s"])
    assert backend["args"]["cache"] in ("hit", "miss", "uncached")
    assert backend["args"]["program"]
    chain = []
    e = backend
    while e["args"].get("parent") is not None:
        e = by_id[e["args"]["parent"]]
        chain.append(e["name"])
    assert chain and chain[-1].startswith("setup/")
    # the documented reader prints the set-up table, ledger or none
    assert report.main([str(tdir)]) == 0
    out = capsys.readouterr().out
    assert "set-up (seconds by span" in out and "setup/experiment" in out
    assert "compile/backend by cache" in out
    found = report.summarize_events(str(tdir / "events.jsonl"))["setup"]
    assert found["seconds"]["setup/first_round"] > 0.0
    assert sum(found["cache"].values()) >= 1


def test_a_refused_configuration_writes_no_trace(tmp_path):
    from heterofl_tpu.entry.common import FedExperiment

    cfg = _driver_cfg(tmp_path, trace_dir=str(tmp_path / "trace"),
                      mesh={"clients": 64, "data": 1})
    with pytest.raises(ValueError):  # a mesh the devices cannot honour
        FedExperiment(cfg, 0)
    assert not (tmp_path / "trace").exists()


def test_an_evaluation_leaves_the_training_runs_trace_alone(tmp_path, record):
    from heterofl_tpu.entry.common import FedExperiment
    from heterofl_tpu.entry.evaluate import evaluate_experiment

    cfg = _driver_cfg(tmp_path, trace_dir=str(tmp_path / "trace"))
    exp = FedExperiment(cfg, 0)
    exp.run("Global-Accuracy")
    tdir = tmp_path / "trace" / exp.tag
    before = {f: (tdir / f).read_bytes() for f in ("events.jsonl", "trace.json")}
    assert b"setup/first_round" in before["events.jsonl"]
    # the same cfg, trace_dir and all, under the same tag
    evaluate_experiment(_driver_cfg(tmp_path, trace_dir=str(tmp_path / "trace")), 0)
    assert {f: (tdir / f).read_bytes() for f in before} == before
    assert sorted(os.listdir(tmp_path / "trace")) == [exp.tag]


def test_a_run_that_fails_before_its_first_round_closes_its_recorder(
        tmp_path, record, monkeypatch):
    from heterofl_tpu.entry.common import FedExperiment

    exp = FedExperiment(_driver_cfg(tmp_path, trace_dir=str(tmp_path / "trace")), 0)

    def no_stage(*a):
        raise RuntimeError("staging failed")

    monkeypatch.setattr(exp, "stage", no_stage)
    with pytest.raises(RuntimeError, match="staging failed"):
        exp.run("Global-Accuracy")
    assert exp.tracer.closed and exp.phase_timer.trace is None
    # closed: trace.json is written on close, and holds the set-up so far
    tdir = tmp_path / "trace" / exp.tag
    names = [e["name"] for e in json.load(open(tdir / "trace.json"))["traceEvents"]]
    assert {"setup/dataset", "setup/engine", "setup/experiment", "setup/split"} <= \
        set(names)
    assert "setup/stage" not in names
