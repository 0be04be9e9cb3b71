"""The reduction from a trace to busy time, idle share, per-operation time
and attributed gaps, on a small hand-made trace and (where present) on the
stretch recorded on the chip."""

import os

import pytest

from benchmark import trace_reduce

HERE = os.path.dirname(os.path.abspath(__file__))


def test_union_and_self_times():
    assert trace_reduce.union([[5, 7], [0, 2], [1, 3]]) == [[0, 3], [5, 7]]
    own = trace_reduce.self_times(
        [["outer", 0, 100], ["a", 10, 20], ["b", 50, 60]], 0, 100)
    # b runs to 110, past its parent: only the part inside it is the parent's
    assert own == {"outer": 30.0, "a": 20.0, "b": 50.0}


def test_short_name_keeps_result_operation_and_shape():
    long = ('%reshape.3001 = f32[10,87283,128]{2,1,0:T(8,128)} reshape(f32[10,11172224]'
            '{1,0:T(8,128)} %dynamic-update-slice.365)')
    assert trace_reduce.short_name(long) == "%reshape.3001 reshape f32[10,87283,128]"
    call = ('%closed_call.30 = (f32[10,87283,128]{2,1,0:T(8,128)}, f32[10,87283,128]{2,1,0}) '
            'custom-call(f32[10,87283,128]{2,1,0} %reshape.3001), custom_call_target="tpu_custom_call"')
    assert trace_reduce.short_name(call).startswith("%closed_call.30 custom-call (f32[10,87283,128]")
    assert trace_reduce.short_name("fusion.1") == "fusion.1"


def test_reduce_synthetic_trace():
    r = trace_reduce.reduce(trace_reduce.load_json(
        os.path.join(HERE, "synthetic_trace.json")))
    assert r["rounds"] == 2
    assert r["window_s"] == pytest.approx(2000e-9)
    d0, d1 = r["devices"]["/device:TPU:0"], r["devices"]["/device:TPU:1"]
    assert d0["busy_s"] == pytest.approx(1100e-9)      # [100,800] + [1100,1500]
    assert d1["busy_s"] == pytest.approx(1000e-9)      # late.1 lies outside
    assert d0["idle_share"] == pytest.approx(0.45)
    assert r["busy_s"] == pytest.approx(1050e-9)
    assert r["idle_share"] == pytest.approx(0.475)
    assert "/device:TPU:0 SparseCore" not in r["devices"]
    # self time: the while's own 100 ns, not its 700
    assert r["ops_s"]["while.1"] == pytest.approx(50e-9)   # mean over 2 devices
    assert r["ops_s"]["fusion.1"] == pytest.approx((300 + 500) / 2 * 1e-9)
    # what a collective's reader would sum: all-reduce.1 is 200 ns on device 0,
    # where copy.1 overlaps its tail (150 ns of its own), and 500 ns on device 1
    assert r["ops_s"]["all-reduce.1"] == pytest.approx((150 + 500) / 2 * 1e-9)
    assert r["device_ops"][0][0] == "fusion.1"
    # gaps: device 0 idles [0,100] [800,1100] [1500,2000]; device 1 [500,1000]
    # [1500,2000].  [800,1100] has its middle in the span between the rounds.
    names = dict((g[0], g[1]) for g in r["idle_gaps"])
    assert names["bench.between@/device:TPU:0"] == pytest.approx(300e-9)
    assert r["idle_gaps"][0][1] == pytest.approx(500e-9)
    assert r["idle_by_span_s"]["bench.round"] == pytest.approx(
        (100 + 500 + 500 + 500) / 2 * 1e-9)


def test_phase_spans_name_the_gaps_by_what_the_host_was_doing():
    trace = trace_reduce.load_json(os.path.join(HERE, "synthetic_trace.json"))
    spans = trace_reduce.PhaseSpans()
    # perf_counter clock: the first round span was opened at 7.0 s
    spans.complete("stage", 7.0 + 10e-9, 80e-9)      # covers the gap [0,100]
    spans.complete("fetch", 7.0 + 1500e-9, 400e-9)   # covers the gap [1500,2000]
    r = trace_reduce.reduce(trace_reduce.add_phase_spans(trace, spans.events, 7.0))
    names = [g[0] for g in r["idle_gaps"]]
    assert "bench.phase.fetch@/device:TPU:0" in names
    assert r["idle_by_span_s"]["bench.phase.stage"] == pytest.approx(100 / 2 * 1e-9)
    assert r["rounds"] == 2  # the phase spans are no rounds


def test_gaps_of_one_length_with_and_without_a_span_sort():
    """Two devices idle for the same 10 ns, one under a span and one under
    none (the chip gave such a pair: my chip run 8, PR 25)."""
    ops = lambda events: {"name": "XLA Ops", "events": events}
    trace = {"planes": [
        {"name": "/host:CPU", "lines": [{"name": "python", "events": [
            ["bench.round", 0, 40], ["bench.round", 60, 40], ["bench.between", 40, 10]]}]},
        {"name": "/device:TPU:0", "lines": [ops([["a", 0, 40], ["b", 50, 50]])]},
        {"name": "/device:TPU:1", "lines": [ops([["a", 0, 50], ["b", 60, 40]])]}]}
    r = trace_reduce.reduce(trace)
    assert sorted(g[0] for g in r["idle_gaps"]) == [
        "bench.between@/device:TPU:0", "outside_spans@/device:TPU:1"]
    assert [g[1] for g in r["idle_gaps"]] == [pytest.approx(10e-9)] * 2


def test_reduce_refuses_a_trace_without_device_work():
    trace = {"planes": [{"name": "/host:CPU", "lines": [
        {"name": "python", "events": [["bench.round", 0, 10]]}]}]}
    with pytest.raises(ValueError):
        trace_reduce.reduce(trace)
    with pytest.raises(ValueError):
        trace_reduce.reduce({"planes": []})


def test_layer_metrics_read_the_reduction():
    from benchmark import harness

    red = {"busy_s": 8.0, "rounds": 2, "idle_share": 0.2}
    phases = [{"sample": 0.001, "dispatch": 0.002, "stage": 0.004, "fetch": 4.0}] * 2
    cell = {"chips": 4, "steps_per_round": 250, "model_flops_per_round": 4e12,
            "peak_flops_per_s": 1e12}
    get = lambda n: harness.load_module("layer_metrics", n).compute(red, phases, cell)
    assert get("host_ms.round") == pytest.approx(3.0)
    assert get("stage_ms.round") == pytest.approx(4.0)
    assert get("device_ms.round") == pytest.approx(4000.0)
    assert get("step_ms") == pytest.approx(16.0)
    assert get("mfu_pct") == pytest.approx(100 * 8e12 / 32.0 / 1e12)
    assert get("device_idle_pct") == pytest.approx(20.0)
    for n in ("device_ms.round", "step_ms", "mfu_pct", "device_idle_pct"):
        assert harness.load_module("layer_metrics", n).compute(None, phases, cell) is None


def test_reduce_recorded_chip_stretch():
    path = os.path.join(HERE, "recorded_trace.json.gz")
    r = trace_reduce.reduce(trace_reduce.load_json(path))
    assert r["rounds"] == 1 and r["window_s"] == pytest.approx(0.2)
    # 2.6 local steps of 78.6 ms: the device is busy but for the gaps between
    # the loop's iterations and the 1.8 ms before the first operation
    assert r["busy_s"] == pytest.approx(0.194727805, rel=1e-6)
    assert r["idle_share"] == pytest.approx(0.02636, rel=1e-3)
    # self times add up to the busy union: nothing is counted twice
    assert sum(r["ops_s"].values()) == pytest.approx(r["busy_s"], rel=1e-6)
    assert r["device_ops"][0][0] == "%reshape.3001 reshape f32[10,87283,128]"
    assert r["idle_gaps"][0] == ["bench.round@/device:TPU:0", pytest.approx(0.001825209)]
