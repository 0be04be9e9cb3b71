"""Runtime telemetry (ISSUE 10): in-program health probes, run tracing,
and the non-finite watchdog.

Contracts under test:

* ``telemetry='off'`` (the default) changes NOTHING: engines build the
  same outputs and ``telemetry='on'`` runs produce BIT-IDENTICAL params
  and train metrics to off runs across masked x {replicated, sharded} /
  grouped x {span, slices} x K in {1, 8} -- the probes are pure
  observers of the round, never participants.
* probe values equal host-recomputed references on a small program
  (update norm vs the sequential param trajectory, per-level
  participation vs the rate table, grad == update under dense sync).
* the watchdog trips on an injected NaN (and on loss spikes vs the
  rolling median), warn and abort modes both.
* the trace recorder's ``trace.json`` is a loadable Chrome trace and
  every ``events.jsonl`` line round-trips through the schema validator.
"""

import json
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from heterofl_tpu import config as C
from heterofl_tpu.fed.core import (round_users, superstep_rate_schedule,
                                   superstep_user_schedule)
from heterofl_tpu.models import make_model
from heterofl_tpu.obs import (TelemetrySpec, resolve_telemetry_cfg,
                              split_probes)
from heterofl_tpu.obs.trace import TraceRecorder, validate_event
from heterofl_tpu.obs.watchdog import Watchdog, WatchdogError
from heterofl_tpu.parallel import (GroupedRoundEngine, RoundEngine,
                                   make_mesh, shard_client_data)
from heterofl_tpu.utils.logger import Logger

from test_round import _vision_setup

HOST_KEY = jax.random.key(0)


def _params_equal(a, b):
    for k in a:
        np.testing.assert_array_equal(np.asarray(a[k]), np.asarray(b[k]),
                                      err_msg=k)


def _train_rounds(out):
    return out["train"] if isinstance(out, dict) else out


def _metrics_equal(off_out, on_out, k):
    off_r, on_r = _train_rounds(off_out), _train_rounds(on_out)
    for r in range(k):
        for name in ("loss_sum", "score_sum", "n", "rate"):
            np.testing.assert_array_equal(np.asarray(off_r[r][name]),
                                          np.asarray(on_r[r][name]),
                                          err_msg=f"round {r} {name}")


# ---------------------------------------------------------------------------
# telemetry-off bit-identity: on-vs-off params + metrics, probe presence
# ---------------------------------------------------------------------------

def test_masked_replicated_k1_on_off_bit_identical():
    cfg, ds, data = _vision_setup()
    model = make_model(cfg)
    mesh = make_mesh(4, 1)
    uidx = np.array([0, 2, 4, 6])
    results = {}
    for tel in ("off", "on"):
        eng = RoundEngine(model, dict(cfg, telemetry=tel), mesh)
        p = model.init(jax.random.key(0))
        p, ms = eng.train_round(p, jax.random.key(1), 0.05, uidx, data)
        results[tel] = (p, {k: np.asarray(v) for k, v in ms.items()})
    p_off, ms_off = results["off"]
    p_on, ms_on = results["on"]
    assert not any(k.startswith("obs_") for k in ms_off)
    _params_equal(p_off, p_on)
    clean, probes = split_probes(ms_on, 4)
    assert len(probes) == 1 and set(clean) == set(ms_off)
    for name in ms_off:
        np.testing.assert_array_equal(ms_off[name], clean[name], err_msg=name)
    rec = probes[0]
    assert rec["nonfinite"] == 0 and np.isfinite(rec["update_norm"])


@pytest.mark.parametrize("k", [1, 8])
def test_masked_replicated_superstep_on_off_bit_identical(k):
    cfg, ds, data = _vision_setup()
    model = make_model(cfg)
    mesh = make_mesh(4, 1)
    outs = {}
    for tel in ("off", "on"):
        eng = RoundEngine(model, dict(cfg, telemetry=tel), mesh)
        p = model.init(jax.random.key(0))
        p, pending = eng.train_superstep(p, HOST_KEY, 1, k, data, num_active=4)
        outs[tel] = (p, pending.fetch())
    _params_equal(outs["off"][0], outs["on"][0])
    _metrics_equal(outs["off"][1], outs["on"][1], k)
    assert isinstance(outs["off"][1], list)
    probes = outs["on"][1]["obs"]
    assert len(probes) == k
    for rec in probes:
        assert rec["nonfinite"] == 0
        assert sum(rec["participation"]) == 4.0  # the active cohort


def test_masked_sharded_superstep_on_off_bit_identical():
    cfg, ds, data = _vision_setup()
    model = make_model(cfg)
    mesh = make_mesh(4, 1)
    k = 8
    sched = superstep_user_schedule(HOST_KEY, 1, k, cfg["num_users"], 4)
    outs = {}
    for tel in ("off", "on"):
        eng = RoundEngine(model, dict(cfg, data_placement="sharded",
                                      telemetry=tel), mesh)
        data_sh = shard_client_data(mesh, tuple(np.asarray(a) for a in data))
        p = model.init(jax.random.key(0))
        p, pending = eng.train_superstep(p, HOST_KEY, 1, k, data_sh,
                                         user_schedule=sched)
        outs[tel] = (p, pending.fetch())
    _params_equal(outs["off"][0], outs["on"][0])
    _metrics_equal(outs["off"][1], outs["on"][1], k)
    assert len(outs["on"][1]["obs"]) == k


@pytest.mark.parametrize("placement,k", [("span", 1), ("span", 8),
                                         ("slices", 8)])
def test_grouped_superstep_on_off_bit_identical(placement, k):
    cfg, ds, data = _vision_setup()
    mesh = make_mesh(8, 1)  # slices needs >= 5 device rows (one per level)
    model = make_model(cfg)
    users = cfg["num_users"]
    sched = superstep_user_schedule(HOST_KEY, 1, k, users, users)
    rates = superstep_rate_schedule(HOST_KEY, 1, k, cfg, sched)
    outs = {}
    for tel in ("off", "on"):
        grp = GroupedRoundEngine(dict(cfg, level_placement=placement,
                                      telemetry=tel), mesh)
        p = model.init(jax.random.key(0))
        p, pending = grp.train_superstep(p, HOST_KEY, 1, k, sched, rates, data)
        outs[tel] = (p, pending.fetch())
    _params_equal(outs["off"][0], outs["on"][0])
    _metrics_equal(outs["off"][1], outs["on"][1], k)
    probes = outs["on"][1]["obs"]
    assert len(probes) == k
    for rec in probes:
        assert rec["nonfinite"] == 0
        assert sum(rec["participation"]) == users  # all users active


def test_grouped_k1_host_path_refuses_telemetry():
    cfg, ds, data = _vision_setup()
    mesh = make_mesh(4, 1)
    grp = GroupedRoundEngine(dict(cfg, telemetry="on"), mesh)
    rates = np.asarray(cfg["model_rate"], np.float32)
    uidx = np.array([0, 1, 2, 3])
    p = make_model(cfg).init(jax.random.key(0))
    with pytest.raises(ValueError, match="telemetry"):
        grp.train_round(p, uidx, rates[uidx], data, 0.05, jax.random.key(1))


# ---------------------------------------------------------------------------
# probe values vs host-recomputed references
# ---------------------------------------------------------------------------

def test_probe_values_match_host_reference():
    """update_norm matches the sequential param trajectory, participation
    matches the drawn cohort's rate table, grad == update under dense sync
    (the stale rule zeroes both where no client contributed)."""
    cfg, ds, data = _vision_setup()
    model = make_model(cfg)
    mesh = make_mesh(4, 1)
    k, A = 2, 4
    # sequential reference: train_round consuming the same streams is
    # bit-identical to the superstep (the PR 2 contract), so its param
    # trajectory IS the reference for the in-program update norm
    eng_ref = RoundEngine(model, cfg, mesh)
    p = model.init(jax.random.key(0))
    ref_norm, ref_part = [], []
    rates_vec = np.asarray(cfg["model_rate"], np.float32)
    levels = sorted({float(r) for r in rates_vec}, reverse=True)
    from heterofl_tpu.utils.optim import make_traced_lr_fn

    lr_fn = make_traced_lr_fn(cfg)
    for r in range(k):
        key = jax.random.fold_in(HOST_KEY, 1 + r)
        uidx = np.asarray(round_users(key, cfg["num_users"], A))
        lr = float(np.asarray(lr_fn(jnp.int32(1 + r))))
        # host snapshot BEFORE the dispatch: train_round donates the carry
        p_host = {n: np.asarray(v, np.float64) for n, v in p.items()}
        p, _ = eng_ref.train_round(p, key, lr, uidx, data)
        delta_sq = sum(np.sum((np.asarray(p[n], np.float64)
                               - p_host[n]) ** 2) for n in p)
        ref_norm.append(float(np.sqrt(delta_sq)))
        ref_part.append([float((rates_vec[uidx] == lvl).sum())
                         for lvl in levels])

    eng = RoundEngine(model, dict(cfg, telemetry="on"), mesh)
    p0 = model.init(jax.random.key(0))
    _, pending = eng.train_superstep(p0, HOST_KEY, 1, k, data, num_active=A)
    probes = pending.fetch()["obs"]
    for r in range(k):
        np.testing.assert_allclose(probes[r]["update_norm"], ref_norm[r],
                                   rtol=1e-4, err_msg=f"round {r}")
        assert probes[r]["participation"] == ref_part[r], f"round {r}"
        # dense sync: the pseudo-gradient IS the applied update
        np.testing.assert_allclose(probes[r]["grad_norm"],
                                   probes[r]["update_norm"], rtol=1e-6)
        assert probes[r]["resid_norm"] == 0.0
        assert probes[r]["stale_norm"] == 0.0
        assert probes[r]["nonfinite"] == 0


def test_probe_resid_norm_under_wire_codec():
    """A lossy codec's error-feedback residual shows up in the probes (and
    the codec program still runs telemetry without new carries)."""
    cfg, ds, data = _vision_setup()
    model = make_model(cfg)
    mesh = make_mesh(4, 1)
    eng = RoundEngine(model, dict(cfg, telemetry="on", wire_codec="int8"),
                      mesh)
    p = model.init(jax.random.key(0))
    _, pending = eng.train_superstep(p, HOST_KEY, 1, 2, data, num_active=4)
    probes = pending.fetch()["obs"]
    assert probes[-1]["resid_norm"] > 0.0  # stochastic rounding left error
    assert np.isfinite(probes[-1]["resid_norm"])


def test_probe_stale_mass_under_buffered_aggregation():
    cfg, ds, data = _vision_setup()
    model = make_model(cfg)
    mesh = make_mesh(4, 1)
    eng = RoundEngine(model, dict(cfg, telemetry="on",
                                  schedule={"aggregation": "buffered"}), mesh)
    p = model.init(jax.random.key(0))
    _, pending = eng.train_superstep(p, HOST_KEY, 1, 2, data, num_active=4)
    probes = pending.fetch()["obs"]
    # every round buffers its fresh reduction: the pending mass is nonzero
    assert probes[0]["stale_norm"] > 0.0
    assert probes[1]["stale_norm"] > 0.0


# ---------------------------------------------------------------------------
# watchdog
# ---------------------------------------------------------------------------

def test_watchdog_trips_on_injected_nan():
    """A NaN planted in the params carry reaches the in-program non-finite
    counter, and the watchdog trips on it at the fetch boundary."""
    cfg, ds, data = _vision_setup()
    model = make_model(cfg)
    mesh = make_mesh(4, 1)
    eng = RoundEngine(model, dict(cfg, telemetry="on"), mesh)
    p = model.init(jax.random.key(0))
    name = next(iter(p))
    bad = np.asarray(p[name]).copy()
    bad.flat[0] = np.nan
    p[name] = jnp.asarray(bad)
    _, ms = eng.train_round(p, jax.random.key(1), 0.05,
                            np.array([0, 2, 4, 6]), data)
    _, probes = split_probes({k: np.asarray(v) for k, v in ms.items()}, 4)
    assert probes[0]["nonfinite"] >= 1
    spec = resolve_telemetry_cfg({"telemetry": "on"}).watchdog
    wd = Watchdog(spec)
    with pytest.warns(UserWarning, match="nonfinite"):
        events = wd.check(1, probes=probes[0], loss=2.0)
    assert events and wd.fired and events[0]["kind"] == "nonfinite"
    spec_abort = resolve_telemetry_cfg(
        {"telemetry": "on", "watchdog": {"action": "abort"}}).watchdog
    wd2 = Watchdog(spec_abort)
    with pytest.warns(UserWarning):
        with pytest.raises(WatchdogError, match="nonfinite"):
            wd2.check(1, probes=probes[0], loss=2.0)


def test_watchdog_loss_spike_rolling_median():
    spec = resolve_telemetry_cfg(
        {"telemetry": "on",
         "watchdog": {"spike_factor": 3.0, "window": 4}}).watchdog
    wd = Watchdog(spec)
    for e, loss in enumerate([1.0, 1.1, 0.9, 1.0], start=1):
        assert wd.check(e, probes={"nonfinite": 0}, loss=loss) == []
    with pytest.warns(UserWarning, match="loss-spike"):
        events = wd.check(5, probes={"nonfinite": 0}, loss=10.0)
    assert events[0]["kind"] == "loss-spike"
    # a non-finite loss trips its own kind without median history
    with pytest.warns(UserWarning, match="loss-nonfinite"):
        wd.check(6, probes={"nonfinite": 0}, loss=float("nan"))
    assert len(wd.fired) == 2


def test_telemetry_config_validation():
    with pytest.raises(ValueError, match="telemetry"):
        resolve_telemetry_cfg({"telemetry": "sometimes"})
    with pytest.raises(ValueError, match="watchdog"):
        resolve_telemetry_cfg({"watchdog": {"action": "warn"}})  # off mode
    with pytest.raises(ValueError, match="spike_factor"):
        resolve_telemetry_cfg({"telemetry": "on",
                               "watchdog": {"spike_factor": 0.5}})
    with pytest.raises(ValueError, match="watchdog keys"):
        resolve_telemetry_cfg({"telemetry": "on", "watchdog": {"limit": 1}})
    spec = resolve_telemetry_cfg({"telemetry": "on",
                                  "watchdog": {"action": "off"}})
    assert isinstance(spec, TelemetrySpec)
    assert spec.probes and spec.watchdog is None
    assert resolve_telemetry_cfg({}).probes is False


# ---------------------------------------------------------------------------
# trace recorder: Chrome trace + events.jsonl schema round-trip
# ---------------------------------------------------------------------------

def test_trace_events_schema_roundtrip(tmp_path):
    from heterofl_tpu.parallel import PhaseTimer

    rec = TraceRecorder(str(tmp_path / "t"))
    timer = PhaseTimer()
    timer.trace = rec  # the PhaseTimer hook files phases on the timeline
    with timer.phase("dispatch"):
        pass
    with rec.span("superstep", args={"epoch0": 1, "k": 8}):
        rec.instant("probes", cat="obs", args={"epoch": 1, "nonfinite": 0})
    path = rec.close()
    assert rec.close() == path  # idempotent
    trace = json.load(open(path))
    names = [e["name"] for e in trace["traceEvents"]]
    assert {"dispatch", "superstep", "probes"} <= set(names)
    for ev in trace["traceEvents"]:
        assert {"name", "cat", "ph", "ts", "pid", "tid"} <= set(ev)
        if ev["ph"] == "X":
            assert "dur" in ev
    lines = [json.loads(l) for l in open(rec.events_path)]
    assert len(lines) == len(trace["traceEvents"])
    for line in lines:
        # schema round-trip: validate -> serialize -> parse -> validate
        again = json.loads(json.dumps(validate_event(line)))
        assert validate_event(again) == line
    # the X events carry durations, the instants do not
    sup = next(l for l in lines if l["name"] == "superstep")
    assert sup["ph"] == "X" and sup["dur_s"] >= 0
    assert sup["args"] == {"epoch0": 1, "k": 8}


def test_validate_event_rejects_malformed():
    good = {"v": 1, "t": 0.0, "name": "x", "cat": "driver", "ph": "i",
            "args": {}}
    validate_event(good)
    with pytest.raises(ValueError, match="version"):
        validate_event({**good, "v": 2})
    with pytest.raises(ValueError, match="required"):
        validate_event({k: v for k, v in good.items() if k != "name"})
    with pytest.raises(ValueError, match="dur_s"):
        validate_event({**good, "ph": "X"})
    with pytest.raises(ValueError, match="unknown"):
        validate_event({**good, "extra": 1})


# ---------------------------------------------------------------------------
# Logger satellites: structured emit + the un-swallowed tensorboard failure
# ---------------------------------------------------------------------------

def test_logger_emit_structured_obs_event(tmp_path):
    logger = Logger(str(tmp_path / "runs"))
    logger.emit({"event": "probes", "epoch": 1})  # closed writer: no-op
    logger.safe(True)
    logger.emit({"event": "probes", "epoch": 2, "update_norm": 1.5})
    logger.safe(False)
    recs = [json.loads(l) for l in open(tmp_path / "runs" / "log.jsonl")]
    obs = [r for r in recs if r.get("tag") == "obs"]
    assert len(obs) == 1
    assert obs[0]["event"] == "probes" and obs[0]["epoch"] == 2
    assert obs[0]["update_norm"] == 1.5 and "t" in obs[0]


def test_logger_warns_on_tensorboard_import_failure(tmp_path, monkeypatch):
    # poison the import: a None sys.modules entry raises ImportError
    monkeypatch.setitem(sys.modules, "torch.utils.tensorboard", None)
    logger = Logger(str(tmp_path / "runs"), use_tensorboard=True)
    with pytest.warns(UserWarning, match="tensorboard"):
        logger.safe(True)
    assert logger.writer is None
    logger.safe(False)
    logger.safe(True)  # warned ONCE per Logger, degraded mode proceeds
    logger.safe(False)


# ---------------------------------------------------------------------------
# driver integration: end-to-end telemetry + tracing, and loud conflicts
# ---------------------------------------------------------------------------

def _driver_cfg(out_dir, **over):
    cfg = C.default_cfg()
    cfg["control"] = C.parse_control_name("1_8_0.5_iid_fix_a1-b1-c1-d1-e1_bn_1_1")
    cfg["data_name"] = "MNIST"
    cfg["model_name"] = "conv"
    cfg["synthetic"] = True
    cfg["synthetic_sizes"] = {"train": 400, "test": 100}
    cfg["output_dir"] = str(out_dir)
    cfg["override"] = {"num_epochs": {"global": 4, "local": 2},
                       "conv": {"hidden_size": [8, 16]},
                       "superstep_rounds": 2, "eval_interval": 2, **over}
    return C.process_control(cfg)


def test_driver_run_with_telemetry_and_trace(tmp_path):
    from heterofl_tpu.entry.common import FedExperiment

    cfg = _driver_cfg(tmp_path, telemetry="on",
                      trace_dir=str(tmp_path / "trace"))
    exp = FedExperiment(cfg, 0)
    exp.run("Global-Accuracy")
    tdir = tmp_path / "trace" / exp.tag
    trace = json.load(open(tdir / "trace.json"))
    names = {e["name"] for e in trace["traceEvents"]}
    assert {"superstep", "checkpoint", "probes", "dispatch"} <= names
    for line in open(tdir / "events.jsonl"):
        validate_event(json.loads(line))
    log = tmp_path / "runs" / f"train_{exp.tag}" / "log.jsonl"
    obs = [json.loads(l) for l in open(log)
           if json.loads(l).get("tag") == "obs"]
    assert len(obs) == 4  # one probe record per round
    assert [o["epoch"] for o in obs] == [1, 2, 3, 4]
    assert all(o["nonfinite"] == 0 for o in obs)


def test_driver_telemetry_conflicts_fail_loudly(tmp_path):
    from heterofl_tpu.entry.common import FedExperiment

    with pytest.raises(ValueError, match="mesh-native"):
        FedExperiment(_driver_cfg(tmp_path, telemetry="on",
                                  strategy="sliced", superstep_rounds=1), 0)
    with pytest.raises(ValueError, match="fused superstep"):
        FedExperiment(_driver_cfg(tmp_path, telemetry="on",
                                  strategy="grouped", superstep_rounds=1), 0)


# ---------------------------------------------------------------------------
# a model's own counters: finished by the fold the model declares (ISSUE 45)
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("fold, rows, want, over_rounds", [
    # the per-device sums as they are, summed over a run's rounds
    ("sum", [[1.0, 2.0, 3.0], [10.0, 20.0, 30.0]], [11.0, 22.0, 33.0], [22.0, 44.0, 66.0]),
    # a (numerator, denominator) pair divided, averaged over the rounds
    ("ratio", [[3.0, 4.0], [1.0, 4.0]], 0.5, 0.5),
    # sums with their count last, each over the count, averaged over the rounds
    ("mean", [[2.0, 4.0, 2.0], [4.0, 8.0, 2.0]], [1.5, 3.0], [1.5, 3.0]),
])
def test_a_declared_counter_reaches_the_record_by_its_fold(fold, rows, want, over_rounds,
                                                           tmp_path):
    """`split_probes` finishes a counter the model declares (`meta['counters']`:
    name -> (shape, fold)) from its per-device sums, in both layouts and for
    every round of a superstep; a zero count gives 0 and not a division; a fold
    nobody knows is refused; and `obs.report` sums or averages the rounds'
    records by the folds a run's `run-start` event carries."""
    from heterofl_tpu.obs import report

    declared = {"fam_thing": ((len(rows[0]),), fold)}
    flat = np.asarray([np.concatenate(rows), np.concatenate(rows)])  # [k=2, n_dev * X]
    clean, rounds = split_probes({"n": np.ones(2), "obs_fam_thing": flat}, 2, counters=declared)
    assert list(clean) == ["n"] and [r["fam_thing"] for r in rounds] == [want, want]
    span = np.moveaxis(np.asarray([rows, rows]), 1, -1)  # [k, X, n_dev]
    _, rounds = split_probes({"obs_fam_thing": span}, 2, layout="span", counters=declared)
    assert [r["fam_thing"] for r in rounds] == [want, want]
    if fold != "sum":
        _, (rec,) = split_probes({"obs_fam_thing": np.zeros(2 * len(rows[0]))}, 2,
                                 counters=declared)
        assert rec["fam_thing"] == (0.0 if fold == "ratio" else [0.0] * (len(rows[0]) - 1))
    with pytest.raises(ValueError, match="Not valid fold of the counter 'fam_thing'"):
        split_probes({"obs_fam_thing": flat}, 2, counters={"fam_thing": ((3,), "median")})
    events = tmp_path / "events.jsonl"
    events.write_text("".join(json.dumps(e) + "\n" for e in [
        {"v": 1, "t": 0.0, "name": "run-start", "ph": "i",
         "args": {"counters": {"fam_thing": fold}}}]
        + [{"v": 1, "t": 0.0, "name": "probes", "cat": "obs", "ph": "i", "args": r}
           for r in rounds]))
    assert report.summarize_events(str(events))["fam"] == {"rounds": 2, "thing": over_rounds}
