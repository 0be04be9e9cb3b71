"""Population observatory (ISSUE 12): cohort histograms, the client
ledger, the report surface, and abort-evidence durability.

Contracts under test:

* ``telemetry='hist'`` changes NOTHING observable but the metrics tree:
  params and train metrics stay BIT-IDENTICAL to ``'off'`` across masked
  (replicated, streaming, deadline, buffered, int8-codec) and grouped
  (span, slices) paths, and the ``hist_*`` records appear only on 'hist';
* hist bucket counts equal host-recomputed references EXACTLY (the same
  float32 ops + ``searchsorted`` rule on the fetched per-slot metrics;
  deadline budgets re-derived from the pure ``(key, uid)`` stream);
* the :class:`~heterofl_tpu.obs.ledger.ClientLedger` updates O(active),
  its loss EMA matches a host reference, its state round-trips through
  ``state_dict``/``ledger.npz`` bitwise, and a checkpoint-resumed driver
  run CONTINUES the ledger bit-identically to an uninterrupted one;
* ``python -m heterofl_tpu.obs.report`` renders a snapshot from
  ``ledger.npz`` (+ events.jsonl);
* a watchdog ABORT leaves its evidence on disk: the last events.jsonl
  record is the watchdog instant, the Chrome trace is closed/fsync'd and
  the ledger snapshot is written BEFORE the error propagates.
"""

import json
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from heterofl_tpu import config as C
from heterofl_tpu.fed.core import (superstep_rate_schedule,
                                   superstep_user_schedule)
from heterofl_tpu.models import make_model
from heterofl_tpu.obs import (HIST_FIELDS, resolve_ledger_cfg,
                              resolve_telemetry_cfg, split_probes)
from heterofl_tpu.obs.hist import (LOSS_EDGES, STALE_EDGES, STEP_EDGES,
                                   bucket_counts)
from heterofl_tpu.obs.ledger import (LEDGER_FIELDS, LOSS_EMA_DECAY,
                                     ClientLedger, gini)
from heterofl_tpu.obs.watchdog import WatchdogError
from heterofl_tpu.parallel import (ClientStore, GroupedRoundEngine,
                                   RoundEngine, make_mesh)
from heterofl_tpu.utils.logger import Logger

from test_round import _vision_setup

HOST_KEY = jax.random.key(0)


def _params_equal(a, b):
    for k in a:
        np.testing.assert_array_equal(np.asarray(a[k]), np.asarray(b[k]),
                                      err_msg=k)


def _np_hist(values, weights, edges):
    """The host twin of obs.hist.bucket_counts: float32 values, same
    searchsorted(side='left') rule -- EXACT equality is the contract."""
    e = np.asarray(edges, np.float32)
    idx = np.searchsorted(e, np.asarray(values, np.float32), side="left")
    out = np.zeros(len(e) + 1, np.float64)
    np.add.at(out, idx, np.asarray(weights, np.float64))
    return out


# ---------------------------------------------------------------------------
# hist mode: bit identity + presence
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("k", [1, 8])
def test_masked_hist_superstep_bit_identical(k):
    cfg, ds, data = _vision_setup()
    model = make_model(cfg)
    mesh = make_mesh(4, 1)
    outs = {}
    for tel in ("off", "hist"):
        eng = RoundEngine(model, dict(cfg, telemetry=tel), mesh)
        p = model.init(jax.random.key(0))
        p, pending = eng.train_superstep(p, HOST_KEY, 1, k, data, num_active=4)
        outs[tel] = (p, pending.fetch())
    _params_equal(outs["off"][0], outs["hist"][0])
    off_rounds = outs["off"][1]
    hist_rounds = outs["hist"][1]["train"]
    for r in range(k):
        for name in ("loss_sum", "score_sum", "n", "rate"):
            np.testing.assert_array_equal(np.asarray(off_rounds[r][name]),
                                          np.asarray(hist_rounds[r][name]))
    probes = outs["hist"][1]["obs"]
    assert len(probes) == k
    for rec in probes:
        assert set(HIST_FIELDS) <= set(rec)
        # the membership histogram IS the participation probe
        assert rec["hist_level"] == rec["participation"]
        assert sum(rec["hist_loss"]) == 4.0  # every active client has loss
        # no deadline: every valid client sits in the full-budget bucket
        full = list(STEP_EDGES).index(1.0)
        assert rec["hist_steps"][full] == 4.0
        assert sum(rec["hist_steps"]) == 4.0
        assert rec["hist_stale"] == [0.0] * (len(STALE_EDGES) + 1)


def test_masked_stream_hist_bit_identical():
    """Streaming cohort path (AC: streaming included): hist vs off."""
    cfg, ds, data = _vision_setup()
    model = make_model(cfg)
    mesh = make_mesh(4, 1)
    rng = np.random.default_rng(0)
    from heterofl_tpu.data import label_split_masks, split_dataset
    split, lsplit = split_dataset(ds, 8, "iid", rng, classes_size=10)
    store = ClientStore.from_split(ds["train"].data, ds["train"].target,
                                   split["train"], lsplit, 10)
    sched = superstep_user_schedule(HOST_KEY, 1, 2, 8, 4)
    outs = {}
    for tel in ("off", "hist"):
        eng = RoundEngine(model, dict(cfg, telemetry=tel,
                                      client_store="stream"), mesh)
        coh = eng.stage_cohort(store, sched)
        p = model.init(jax.random.key(0))
        p, pending = eng.train_superstep(p, HOST_KEY, 1, 2, cohort=coh)
        outs[tel] = (p, pending.fetch())
    _params_equal(outs["off"][0], outs["hist"][0])
    probes = outs["hist"][1]["obs"]
    assert len(probes) == 2 and sum(probes[0]["hist_loss"]) == 4.0


@pytest.mark.parametrize("placement,k", [("span", 8), ("slices", 2)])
def test_grouped_hist_superstep_bit_identical(placement, k):
    cfg, ds, data = _vision_setup()
    mesh = make_mesh(8, 1)  # slices needs >= 5 device rows
    model = make_model(cfg)
    users = cfg["num_users"]
    sched = superstep_user_schedule(HOST_KEY, 1, k, users, users)
    rates = superstep_rate_schedule(HOST_KEY, 1, k, cfg, sched)
    outs = {}
    for tel in ("off", "hist"):
        grp = GroupedRoundEngine(dict(cfg, level_placement=placement,
                                      telemetry=tel), mesh)
        p = model.init(jax.random.key(0))
        p, pending = grp.train_superstep(p, HOST_KEY, 1, k, sched, rates, data)
        outs[tel] = (p, pending.fetch())
    _params_equal(outs["off"][0], outs["hist"][0])
    probes = outs["hist"][1]["obs"]
    assert len(probes) == k
    for rec in probes:
        assert rec["hist_level"] == rec["participation"]
        assert sum(rec["hist_loss"]) == users


# ---------------------------------------------------------------------------
# hist counts vs host-recomputed references (exact)
# ---------------------------------------------------------------------------

def test_hist_loss_counts_match_host_reference_exactly():
    cfg, ds, data = _vision_setup()
    model = make_model(cfg)
    mesh = make_mesh(4, 1)
    k = 2
    eng = RoundEngine(model, dict(cfg, telemetry="hist"), mesh)
    p = model.init(jax.random.key(0))
    _, pending = eng.train_superstep(p, HOST_KEY, 1, k, data, num_active=4)
    out = pending.fetch()
    for r in range(k):
        ms = out["train"][r]
        rate = np.asarray(ms["rate"], np.float32)
        n = np.asarray(ms["n"], np.float32)
        loss_sum = np.asarray(ms["loss_sum"], np.float32)
        # the engine's own f32 ops, replayed in numpy: exact equality
        vals = loss_sum / np.maximum(n, np.float32(1.0))
        w = ((rate > 0) & (n > 0)).astype(np.float32)
        expect = _np_hist(vals, w, LOSS_EDGES)
        np.testing.assert_array_equal(out["obs"][r]["hist_loss"], expect)


def test_hist_deadline_steps_match_host_reference_exactly():
    """Deadline scenario (AC: scenario paths included): the step-fraction
    buckets equal a host re-derivation of the pure (key, uid) budget
    stream, and hist mode stays bit-identical to off under the scenario."""
    from heterofl_tpu.sched.deadline import deadline_steps

    cfg, ds, data = _vision_setup()
    model = make_model(cfg)
    mesh = make_mesh(4, 1)
    k, A, min_frac = 2, 4, 0.4
    dcfg = dict(cfg, schedule={"deadline": {"min_frac": min_frac}})
    outs = {}
    for tel in ("off", "hist"):
        eng = RoundEngine(model, dict(dcfg, telemetry=tel), mesh)
        p = model.init(jax.random.key(0))
        p, pending = eng.train_superstep(p, HOST_KEY, 1, k, data,
                                         num_active=A)
        outs[tel] = (p, pending.fetch())
    _params_equal(outs["off"][0], outs["hist"][0])
    out = outs["hist"][1]
    sched = superstep_user_schedule(HOST_KEY, 1, k, cfg["num_users"], A)
    shard_n = int(np.asarray(data[0]).shape[1])
    total = cfg["num_epochs"]["local"] * -(-shard_n
                                           // cfg["batch_size"]["train"])
    for r in range(k):
        key_r = jax.random.fold_in(HOST_KEY, 1 + r)
        budgets = np.asarray(deadline_steps(key_r, jnp.asarray(sched[r]),
                                            total, min_frac))
        frac = budgets.astype(np.float32) / np.float32(total)
        rate = np.asarray(out["train"][r]["rate"], np.float32)[:A]
        expect = _np_hist(frac, (rate > 0).astype(np.float32), STEP_EDGES)
        np.testing.assert_array_equal(out["obs"][r]["hist_steps"], expect)
        assert sum(out["obs"][r]["hist_steps"]) == A


def test_hist_stale_under_buffered_counts_whole_carry():
    from heterofl_tpu.ops.flatspec import FlatSpec

    cfg, ds, data = _vision_setup()
    model = make_model(cfg)
    mesh = make_mesh(4, 1)
    eng = RoundEngine(model, dict(cfg, telemetry="hist",
                                  schedule={"aggregation": "buffered"}), mesh)
    p = model.init(jax.random.key(0))
    total = FlatSpec.of(p).total
    _, pending = eng.train_superstep(p, HOST_KEY, 1, 2, data, num_active=4)
    probes = pending.fetch()["obs"]
    for rec in probes:
        # every entry of the [2, total] carry lands in exactly one bucket
        assert sum(rec["hist_stale"]) == 2 * total
    # after a buffered round the pending mass is nonzero: some entries
    # leave the exact-zero bucket
    assert sum(probes[-1]["hist_stale"][1:]) > 0.0


def test_hist_rides_int8_codec_path():
    cfg, ds, data = _vision_setup()
    model = make_model(cfg)
    mesh = make_mesh(4, 1)
    outs = {}
    for tel in ("off", "hist"):
        eng = RoundEngine(model, dict(cfg, telemetry=tel, wire_codec="int8"),
                          mesh)
        p = model.init(jax.random.key(0))
        p, pending = eng.train_superstep(p, HOST_KEY, 1, 2, data,
                                         num_active=4)
        outs[tel] = (p, pending.fetch())
    _params_equal(outs["off"][0], outs["hist"][0])
    rec = outs["hist"][1]["obs"][-1]
    assert rec["resid_norm"] > 0.0 and sum(rec["hist_loss"]) == 4.0


def test_bucket_counts_edge_semantics():
    """Bucket i covers (edges[i-1], edges[i]]; overflow is the last bin --
    shared by the jax half and the numpy reference."""
    vals = jnp.asarray([0.0, 0.05, 0.0501, 200.0])
    w = jnp.ones(4)
    h = np.asarray(bucket_counts(vals, w, LOSS_EDGES))
    assert h[0] == 2.0      # 0.0 and the 0.05 edge itself
    assert h[1] == 1.0      # just past the first edge
    assert h[-1] == 1.0     # overflow
    np.testing.assert_array_equal(h, _np_hist(np.asarray(vals), np.ones(4),
                                              LOSS_EDGES))


def test_telemetry_hist_config():
    spec = resolve_telemetry_cfg({"telemetry": "hist"})
    assert spec.probes and spec.hist and spec.watchdog is not None
    assert not resolve_telemetry_cfg({"telemetry": "on"}).hist
    with pytest.raises(ValueError, match="telemetry"):
        resolve_telemetry_cfg({"telemetry": "histogram"})


# ---------------------------------------------------------------------------
# ClientLedger: O(active) semantics, EMA reference, persistence
# ---------------------------------------------------------------------------

def test_ledger_update_semantics_and_reference_ema():
    U, levels = 50, [1.0, 0.5, 0.25]
    led = ClientLedger(U, levels)
    rng = np.random.default_rng(0)
    ref_count = np.zeros(U)
    ref_ema = np.zeros(U)
    ref_last = np.zeros(U, int)
    ref_stale = np.zeros(U, int)
    for epoch in range(1, 9):
        uids = rng.choice(U, size=6, replace=False)
        rates = rng.choice(levels, size=6).astype(np.float32)
        losses = rng.uniform(0.5, 4.0, size=6).astype(np.float32)
        ns = np.full(6, 10.0, np.float32)
        led.update(epoch, uids, rates, losses * ns, ns)
        for u, loss in zip(uids, losses):
            if ref_last[u] > 0:
                ref_stale[u] += epoch - ref_last[u]
            ref_ema[u] = loss if ref_count[u] == 0 else \
                (1 - LOSS_EMA_DECAY) * ref_ema[u] + LOSS_EMA_DECAY * loss
            ref_count[u] += 1
            ref_last[u] = epoch
    np.testing.assert_array_equal(led.count, ref_count.astype(np.uint32))
    np.testing.assert_array_equal(led.last_seen, ref_last.astype(np.int32))
    np.testing.assert_array_equal(led.stale_sum, ref_stale.astype(np.uint32))
    # the satellite's EMA tolerance (the arrays are f32; the reference f64)
    np.testing.assert_allclose(led.loss_ema, ref_ema, atol=1e-4)
    assert led.seen == int((ref_count > 0).sum())
    assert int(led.level_counts.sum()) == 8 * 6
    # resident budget: ~27 B/user at 3 levels is well under the 32 B line
    assert led.nbytes / U <= 32


def test_ledger_ignores_padding_and_failed_slots():
    led = ClientLedger(10, [1.0, 0.5])
    s = led.update(1, [3, -1, 7], [1.0, 0.0, 0.0], [2.0, 9.0, 9.0],
                   [1.0, 1.0, 1.0])
    assert s["active"] == 1 and led.count[3] == 1 and led.count[7] == 0
    # participation without samples (n=0): counted, loss EMA untouched
    s = led.update(2, [3], [0.5], [0.0], [0.0])
    assert led.count[3] == 2 and led.loss_ema[3] == np.float32(2.0)
    assert s["loss_ema_mean"] is None
    with pytest.raises(ValueError, match="aligned"):
        led.update(3, [1, 2], [1.0], [1.0], [1.0])
    with pytest.raises(ValueError, match="num_users"):
        led.update(3, [11], [1.0], [1.0], [1.0])


def test_ledger_persistence_roundtrips(tmp_path):
    led = ClientLedger(20, [1.0, 0.5])
    led.update(1, [0, 5], [1.0, 0.5], [3.0, 4.0], [1.0, 2.0])
    led.update(4, [5, 6], [0.5, 1.0], [1.0, 2.0], [1.0, 1.0])
    # state_dict round-trip
    led2 = ClientLedger(20, [1.0, 0.5])
    led2.load_state_dict(led.state_dict())
    for f in LEDGER_FIELDS:
        np.testing.assert_array_equal(getattr(led, f), getattr(led2, f))
    assert (led2.round, led2.updates, led2.seen) == (4, 2, 3)
    # npz round-trip
    path = led.save(str(tmp_path / "obs" / "ledger.npz"))
    led3 = ClientLedger.load(path)
    for f in LEDGER_FIELDS:
        np.testing.assert_array_equal(getattr(led, f), getattr(led3, f))
    # mismatched geometry refuses loudly
    with pytest.raises(ValueError, match="mismatch"):
        ClientLedger(21, [1.0, 0.5]).load_state_dict(led.state_dict())
    with pytest.raises(ValueError, match="ledger"):
        resolve_ledger_cfg({"ledger": "maybe"})
    assert not resolve_ledger_cfg({}).enabled
    assert resolve_ledger_cfg({"ledger": "on"}).enabled


def test_gini_bounds():
    assert gini(np.zeros(10)) == 0.0
    assert gini(np.ones(10)) == pytest.approx(0.0, abs=1e-12)
    one_hot = np.zeros(10)
    one_hot[0] = 5
    assert gini(one_hot) == pytest.approx(0.9)


# ---------------------------------------------------------------------------
# driver integration: fold, resume, report, durability
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


def test_driver_ledger_run_emits_and_snapshots(tmp_path):
    from heterofl_tpu.entry.common import FedExperiment

    cfg = _driver_cfg(tmp_path, ledger="on")
    exp = FedExperiment(cfg, 0)
    exp.run("Global-Accuracy")
    log = tmp_path / "runs" / f"train_{exp.tag}" / "log.jsonl"
    led_lines = [json.loads(l) for l in open(log)
                 if json.loads(l).get("tag") == "ledger"]
    assert len(led_lines) == 2  # one per superstep fetch
    assert led_lines[-1]["coverage"] > 0
    assert sum(l["active"] for l in led_lines) == 4 * exp.num_active
    path = exp._ledger_path()
    assert os.path.exists(path)
    led = ClientLedger.load(path)
    assert int(led.count.sum()) == 4 * exp.num_active
    assert led.round == 4


def test_driver_ledger_checkpoint_resume_bit_identical(tmp_path):
    """The acceptance resume contract: counts/EMAs CONTINUE, not reset --
    a 2-round + resumed-2-round run ends with the exact ledger arrays of
    an uninterrupted 4-round run."""
    from heterofl_tpu.entry.common import FedExperiment

    full_exp = FedExperiment(_driver_cfg(tmp_path / "full", ledger="on"), 0)
    full_exp.run("Global-Accuracy")

    part_dir = tmp_path / "part"
    cfg_p = _driver_cfg(part_dir, ledger="on")
    cfg_short = dict(cfg_p)
    cfg_short["num_epochs"] = dict(cfg_p["num_epochs"], **{"global": 2})
    FedExperiment(cfg_short, 0).run("Global-Accuracy")
    cfg_res = dict(cfg_p)
    cfg_res["resume_mode"] = 1
    res_exp = FedExperiment(cfg_res, 0)
    res_exp.run("Global-Accuracy")
    full = ClientLedger.load(full_exp._ledger_path())
    resumed = ClientLedger.load(res_exp._ledger_path())
    for f in LEDGER_FIELDS:
        np.testing.assert_array_equal(getattr(full, f), getattr(resumed, f),
                                      err_msg=f)
    assert (full.round, full.updates) == (resumed.round, resumed.updates)


def test_driver_ledger_conflicts_fail_loudly(tmp_path):
    from heterofl_tpu.entry.common import FedExperiment

    with pytest.raises(ValueError, match="mesh-native"):
        FedExperiment(_driver_cfg(tmp_path, ledger="on", strategy="sliced",
                                  superstep_rounds=1), 0)
    with pytest.raises(ValueError, match="replicated"):
        FedExperiment(_driver_cfg(tmp_path, ledger="on",
                                  data_placement="sharded"), 0)


def test_report_renders_snapshot(tmp_path, capsys):
    from heterofl_tpu.obs import report as R

    led = ClientLedger(100, [1.0, 0.5])
    rng = np.random.default_rng(1)
    for epoch in range(1, 13):
        uids = rng.choice(100, size=8, replace=False)
        rates = rng.choice([1.0, 0.5], size=8).astype(np.float32)
        ns = np.full(8, 4.0, np.float32)
        led.update(epoch, uids, rates,
                   rng.uniform(0.5, 3.0, 8).astype(np.float32) * ns, ns)
    run_dir = tmp_path / "trace" / "run0"
    led.save(str(run_dir / "ledger.npz"))
    assert R.main([str(tmp_path), "--json"]) == 0
    rep = json.loads(capsys.readouterr().out)
    assert rep["num_users"] == 100 and rep["round"] == 12
    assert 0 < rep["participation"]["coverage"] <= 1
    assert 0 <= rep["participation"]["gini"] < 1
    classes = {c["class"] for c in rep["staleness"]["by_class"]}
    assert "never-seen" in classes and "frequent" in classes
    assert len(rep["per_level"]) == 2
    # the human-readable table renders too
    assert R.main([str(run_dir)]) == 0
    text = capsys.readouterr().out
    assert "participation" in text and "per-level loss EMA" in text
    with pytest.raises(FileNotFoundError, match="ledger.npz"):
        R.find_ledger(str(tmp_path / "empty"))


def test_report_prints_the_expert_layers_counters(tmp_path, capsys):
    """`probes` events that carry an expert layer's counters (ISSUE 28) sum
    over the run's rounds into one line of the report, dropped pairs
    included (always 0); the run's `run-start` event says that they are
    sums (ISSUE 45)."""
    from heterofl_tpu.obs import report as R
    from heterofl_tpu.obs.trace import TraceRecorder

    led = ClientLedger(10, [1.0])
    led.update(1, np.arange(4), np.ones(4, np.float32), np.ones(4, np.float32),
               np.ones(4, np.float32))
    run_dir = tmp_path / "trace" / "run0"
    led.save(str(run_dir / "ledger.npz"))
    rec = TraceRecorder(str(run_dir))
    rec.instant("run-start", args={"tag": "run0", "epoch0": 0, "rounds": 2,
                                   "counters": {"moe_tokens": "sum", "moe_assign": "sum"}})
    for epoch in (1, 2):
        rec.instant("probes", cat="obs", args={
            "epoch": epoch, "moe_tokens": [10.0, 20.0, 30.0, 36.0],
            "moe_assign": [1536.0, 96.0, 0.0], "moe_held_share": 0.0625,
            "moe_dropped": 0})
    rec.close()
    assert R.main([str(run_dir)]) == 0
    text = capsys.readouterr().out
    assert "pairs on held experts 192 of 3072 (6.25 %), dropped 0" in text
    assert "tokens per held expert: 20 40 60 72" in text
    assert R.main([str(run_dir), "--json"]) == 0
    assert json.loads(capsys.readouterr().out)["events"]["moe"]["rounds"] == 2


def test_watchdog_abort_preserves_evidence_on_disk(tmp_path):
    """The durability satellite: after an induced abort the LAST events
    record is the watchdog instant, the Chrome trace is written, and the
    ledger snapshot exists -- all before WatchdogError reaches the
    caller."""
    from heterofl_tpu.entry.common import FedExperiment
    from heterofl_tpu.obs.trace import TraceRecorder

    cfg = _driver_cfg(tmp_path, telemetry="on", ledger="on",
                      watchdog={"action": "abort"},
                      trace_dir=str(tmp_path / "trace"))
    exp = FedExperiment(cfg, 0)
    exp.tracer = TraceRecorder(str(tmp_path / "trace" / exp.tag))
    logger = Logger(str(tmp_path / "runs" / "x"))
    logger.safe(True)
    ms = {"n": np.ones(2, np.float32), "loss_sum": np.ones(2, np.float32)}
    with pytest.warns(UserWarning, match="nonfinite"):
        with pytest.raises(WatchdogError, match="nonfinite"):
            exp._observe(logger, 3, {"nonfinite": 2}, ms)
    assert exp.tracer.closed
    lines = [json.loads(l) for l in open(exp.tracer.events_path)]
    assert lines[-1]["name"] == "watchdog"
    assert lines[-1]["args"]["kind"] == "nonfinite"
    trace = json.load(open(exp.tracer.trace_path))
    assert any(e["name"] == "watchdog" for e in trace["traceEvents"])
    assert os.path.exists(exp._ledger_path())
    logger.safe(False)


def test_split_probes_passthrough_without_hist():
    """A telemetry='on' (scalar-probe) metrics tree has no hist keys; the
    split must not invent them."""
    cfg, ds, data = _vision_setup()
    model = make_model(cfg)
    mesh = make_mesh(4, 1)
    eng = RoundEngine(model, dict(cfg, telemetry="on"), mesh)
    p = model.init(jax.random.key(0))
    _, ms = eng.train_round(p, jax.random.key(1), 0.05,
                            np.array([0, 2, 4, 6]), data)
    _, probes = split_probes({k: np.asarray(v) for k, v in ms.items()}, 4)
    assert probes and not any(k.startswith("hist_") for k in probes[0])


@pytest.mark.parametrize("compact, share", [([[3.0, 6.0], [6.0, 6.0]], 0.75),
                                            ([[0.0, 6.0], [0.0, 6.0]], 0.0)])
def test_split_probes_folds_the_expert_layers_counters(compact, share):
    """An expert layer's counters, a partial sum a device: `moe_assign`
    finishes as the share of the routed pairs on held experts and the dropped
    pairs, `moe_compact` as the share of the layer applications whose dispatch
    was the compact one (0 where no compact branch is built)."""
    ms = {"obs_moe_assign": np.array([[768.0, 40.0, 0.0], [768.0, 56.0, 0.0]], np.float32).ravel(),
          "obs_moe_compact": np.array(compact, np.float32).ravel()}
    clean, rounds = split_probes(ms, 2, counters={"moe_assign": ((3,), "sum"),
                                                  "moe_compact": ((2,), "sum")})
    assert not clean and len(rounds) == 1
    rec = rounds[0]
    assert rec["moe_assign"] == [1536.0, 96.0, 0.0]
    assert rec["moe_held_share"] == 0.0625 and rec["moe_dropped"] == 0
    assert rec["moe_compact"] == [sum(c[0] for c in compact), 12.0]
    assert rec["moe_compact_share"] == share
