"""Device time by scope: the path of an instruction, the self time of a small
hand-made trace (a `while` round scoped, wrapped and unscoped events on two
devices), and the second reading of a trace file this process made."""

import os
import tempfile

import pytest

from benchmark import harness, scope_reduce
from benchmark.scope_reduce import has, kernel_call, reduce_scopes, scope_of, seconds

STEP = "jit(body)/shard_map/round/local_train/vmap()/while/body/closed_call/"

#: device event -> op_name, as the profiler stores it (`<op_name>:`)
NAMES = {
    "%while.1 while (f32[4])": "jit(body)/shard_map/round/local_train/vmap()/while:",
    "%fusion.1 fusion f32[4]": STEP + "jvp(step/model)/conv/conv_general_dilated:",
    "%fusion.2 fusion f32[4]": STEP + "transpose(jvp(step/model))/conv/conv_general_dilated:",
    "%fusion.3 fusion f32[4]": STEP + "transpose(jvp(step/model))/norm/mul;"
                               + STEP + "jvp(step/model)/norm/sub:",
    "%gather.1 gather f32[4]": STEP + "step/batch/RoundEngine._local_train_vision"
                               ".<locals>.step/jit(_take)/gather:",
    "%call.1 custom-call (f32[4],": STEP + "step/update/update/kernel/fused_sgd/pallas_call:",
    "%pad.1 pad f32[4]": STEP + "step/update/update/kernel/fused_sgd/pad:",
    "%reshape.1 reshape f32[4]": STEP + "step/update/update/pack/reshape:",
    "%slice.1 slice f32[4]": STEP + "step/unflatten/slice:",
    "%all-reduce.1 all-reduce f32[4]": "jit(body)/shard_map/round/aggregate/psum/psum:",
    "%divide.1 divide f32[4]": "jit(body)/shard_map/round/aggregate/div:",
    "%copy.7 copy f32[4]": "",                                   # the compiler's own
    "%iota.1 iota s32[4]": "jit(body)/jit(_threefry_fold_in)/client_stream_keys/iota:",
}
#: ... -> [op_name, whether it is the instruction's own], as `read_op_names`
#: gives it; copy.8 is the compiler's, under the name of what reads it
OP_NAMES = {**{k: [v, True] for k, v in NAMES.items()},
            "%copy.8 copy f32[4]": [STEP + "step/update/update/unpack/reshape", False]}

TRACE = {"planes": [
    {"name": "/host:CPU", "lines": [{"name": "python", "events": [
        ["bench.round", 0, 1000], ["bench.round", 1000, 1000], ["other", 0, 9000]]}]},
    {"name": "/device:TPU:0", "lines": [
        {"name": "XLA Modules", "events": [["jit_body", 0, 2000]]},
        {"name": "XLA Ops", "events": [
            ["%while.1 while (f32[4])", 100, 1500],
            ["%gather.1 gather f32[4]", 100, 100],
            ["%slice.1 slice f32[4]", 200, 100],
            ["%fusion.1 fusion f32[4]", 300, 200],
            ["%fusion.2 fusion f32[4]", 500, 300],
            ["%fusion.3 fusion f32[4]", 800, 100],
            ["%copy.7 copy f32[4]", 900, 100],
            ["%reshape.1 reshape f32[4]", 1000, 100],
            ["%pad.1 pad f32[4]", 1100, 100],
            ["%call.1 custom-call (f32[4],", 1200, 300],
            ["%all-reduce.1 all-reduce f32[4]", 1600, 100],
            ["%divide.1 divide f32[4]", 1700, 100],
            ["%iota.1 iota s32[4]", 1800, 100],
            ["%copy.8 copy f32[4]", 1900, 100],
            ["%fusion.1 fusion f32[4]", 2500, 100]]}]},         # outside the stretch
    {"name": "/device:TPU:1", "lines": [{"name": "XLA Ops", "events": [
        ["%fusion.1 fusion f32[4]", 0, 400],
        ["%call.1 custom-call (f32[4],", 400, 100]]}]},
    {"name": "/device:TPU:0 SparseCore", "lines": [{"name": "XLA Ops", "events": [
        ["%fusion.1 fusion f32[4]", 0, 2000]]}]},
]}


@pytest.mark.parametrize("op_name, want", [
    (STEP + "jvp(step/model)/conv/conv_general_dilated:",
     ("round/local_train/step/model/conv", "fwd")),
    (STEP + "transpose(jvp(step/model))/norm/mul", ("round/local_train/step/model/norm", "bwd")),
    # a scope entered inside the vmapped function is wrapped whole
    ("jit(f)/vmap(round/local_train)/while/body/closed_call/step/update/update/kernel/"
     "fused_sgd/pallas_call", ("round/local_train/step/update/update/kernel/fused_sgd", "")),
    # a partial path, as the compiler leaves on some fusions
    ("transpose(jvp(step/model))/embed/scatter-add", ("step/model/embed", "bwd")),
    # the primitive at the end is no scope, whatever its name
    ("jit(body)/shard_map/round/gather/gather", ("round/gather", "")),
    ("jit(body)/shard_map/round/aggregate/psum/psum:", ("round/aggregate/psum", "")),
    ("jit(body)/shard_map/psum", ("", "")),
    ("jit(body)/jit(_threefry_fold_in)/client_stream_keys/while/body/closed_call/add", ("", "")),
    ("jit(body)/shard_map/round/local_train/vmap()/vmap(vmap(jit(argsort)))", ("round/local_train", "")),
    ("jit(body)/jit(shmap_body)/eval/sbn/while/body/norm/reduce_sum", ("eval/sbn/norm", "")),
    ("", ("", "")), (None, ("", "")),
])
def test_scope_of_strips_wrappers_and_flags_direction(op_name, want):
    assert scope_of(op_name) == want


def test_vocabulary_is_the_programs():
    from heterofl_tpu.obs import trace

    assert scope_reduce.SCOPES == trace.SCOPES
    assert scope_reduce.KERNELS == trace.KERNELS


def test_reduce_scopes_synthetic_trace():
    t = reduce_scopes(TRACE, OP_NAMES)
    assert t["rounds"] == 2
    ns = 1e-9 / 2                                  # mean over two devices

    def s(pred):
        return seconds(t, pred)

    # the while's own time: its 1500 less the 1400 of what it encloses
    assert s(lambda r: r[2] == "while") == pytest.approx(100 * ns)
    # forward and backward apart; device 1's fusion.1 counts, the SparseCore's
    # and the one after the last round do not
    assert s(lambda r: has("step/model", "conv")(r) and r[1] == "fwd") == pytest.approx(600 * ns)
    assert s(lambda r: has("step/model", "conv")(r) and r[1] == "bwd") == pytest.approx(300 * ns)
    assert s(has("step/model")) == pytest.approx((200 + 300 + 100 + 400) * ns)
    assert s(has("step/model", "norm")) == pytest.approx(100 * ns)
    assert s(has("step/batch")) == pytest.approx(100 * ns)
    # the kernel's custom-call alone; the pad under the same scope is carry
    assert s(kernel_call) == pytest.approx((300 + 100) * ns)
    carry = s(lambda r: (has("step/unflatten")(r) or has("step/update")(r))
              and not kernel_call(r))
    assert carry == pytest.approx((100 + 100 + 100 + 100) * ns)   # with the lent copy.8
    assert s(lambda r: has("round/gather")(r) or has("round/aggregate")(r)) \
        == pytest.approx(200 * ns)
    assert s(has("round/aggregate", "psum")) == pytest.approx(100 * ns)
    # no op_name, or one with no scope in it
    assert s(lambda r: r[0] == scope_reduce.UNSCOPED) == pytest.approx(200 * ns)
    # a scope the cell does not have
    assert s(has("step/model", "embed")) is None
    assert s(has("eval/sbn")) is None
    assert t["total_s"] == pytest.approx((1900 + 500) * ns)  # busy [100,2000] and [0,500]
    # a scope of its own: all but the two unscoped and the lent one
    assert s(scope_reduce.own_scope) == pytest.approx((2400 - 200 - 100) * ns)
    assert sum(r[3] for r in t["rows"]) == pytest.approx(t["total_s"])


def test_equal_times_do_not_compare_their_names():
    """Rows of one length, one with a direction and one without (the kind of
    tie PR 25's chip run found in `trace_reduce.reduce`), sort by time alone."""
    t = reduce_scopes(TRACE, OP_NAMES)
    hundred = [r for r in t["rows"] if r[3] == pytest.approx(50e-9)]
    assert len(hundred) >= 5 and {r[1] for r in hundred} >= {"", "bwd"}
    assert {r[4] for r in hundred} == {True, False}
    top = scope_reduce.by_scope(t, top=3)
    assert [round(x[2] * 2e9) for x in top] == [600, 500, 300]   # kernel + its pad
    assert top[0][:2] == ["round/local_train/step/model/conv", "fwd"]


def test_has_matches_whole_scopes_only():
    row = ["round/local_train/step/update/update/kernel/fused_sgd", "", "custom-call", 1.0, True]
    assert has("step/update")(row) and has("update/kernel", "fused_sgd")(row)
    assert not has("kernel/fused")(row) and not has("step/model")(row)
    assert not has("norm")(["round/local_train/step/model/normal", "", "x", 1.0, True])


def _varint(n):
    out = b""
    while n > 0x7F:
        out += bytes([n & 0x7F | 0x80])
        n >>= 7
    return out + bytes([n])


def _msg(*fields):
    """A protobuf message of (number, int | bytes | str) fields."""
    out = b""
    for number, value in fields:
        if isinstance(value, int):
            out += _varint(number << 3) + _varint(value)
        else:
            value = value.encode() if isinstance(value, str) else value
            out += _varint(number << 3 | 2) + _varint(len(value)) + value
    return out


def _instr(iid, name, op_name="", operands=(), called=()):
    fields = [(1, name), (35, iid)]
    if op_name:
        fields.append((7, _msg((2, op_name))))
    if operands:
        fields.append((36, b"".join(_varint(o) for o in operands)))   # packed
    fields += [(38, c) for c in called]                                # not packed
    return (2, _msg(*fields))


CONV = STEP + "jvp(step/model)/conv/conv_general_dilated"
KERNEL = STEP + "step/update/update/kernel/fused_sgd/pallas_call"
#: the program: a while whose body holds a named fusion, a relayout copy the
#: compiler made for the kernel (no metadata), the kernel, a pad of its
#: result that nothing named reads, a lone constant, and a loop the compiler
#: made of a copy, nameless itself and in its body
HLO = _msg((1, _msg((1, "jit_body"), (3, _msg(
    (1, "body"), (5, 7),
    _instr(1, "fusion.1", CONV),
    _instr(2, "copy.7", operands=[1]),
    _instr(3, "call.1", KERNEL, operands=[2]),
    _instr(4, "pad.3", operands=[3]),
    _instr(5, "tuple.1", operands=[4]),
    _instr(6, "constant.9"),
    _instr(7, "while.5", operands=[1], called=[9]))), (3, _msg(
        (1, "chunked_copy"), (5, 9), _instr(20, "dynamic-update-slice.4"))), (3, _msg(
        (1, "main"), (5, 8),
        _instr(10, "while.1", "jit(body)/shard_map/round/local_train/vmap()/while",
               called=[7]))))))


def _text_bytes(blob):
    return "".join("\\%03o" % b for b in blob)


XSPACE = """
planes {
  name: "/device:TPU:0"
  lines { name: "XLA Ops" timestamp_ns: 1000
    events { metadata_id: 1 offset_ps: 0 duration_ps: 400000 }
    events { metadata_id: 2 offset_ps: 400000 duration_ps: 100000 }
    events { metadata_id: 3 offset_ps: 500000 duration_ps: 50000 }
    events { metadata_id: 4 offset_ps: 550000 duration_ps: 50000 } }
  event_metadata { key: 1 value { id: 1
    name: "%%fusion.1 = f32[4]{0} fusion(f32[4]{0} %%p), kind=kLoop" display_name: "fusion.1"
    stats { metadata_id: 7 str_value: "conv" }
    stats { metadata_id: 9 str_value: "%(conv)s:" } } }
  event_metadata { key: 2 value { id: 2
    name: "%%call.1 = (f32[4]{0}, f32[4]{0}) custom-call(f32[4]{0} %%copy.7)"
    stats { metadata_id: 9 ref_value: 11 } } }
  event_metadata { key: 3 value { id: 3 name: "%%copy.7 = f32[4]{0} copy(f32[4]{0} %%fusion.1)"
    stats { metadata_id: 7 str_value: "copy" }
    stats { metadata_id: 9 str_value: "jit(body)/shard_map/round/local_train/vmap()/while:" } } }
  event_metadata { key: 4 value { id: 4 name: "%%pad.3 = f32[4]{0} pad(f32[4]{0} %%call.1)" } }
  stat_metadata { key: 7 value { id: 7 name: "hlo_category" } }
  stat_metadata { key: 9 value { id: 9 name: "tf_op" } }
  stat_metadata { key: 11 value { id: 11 name: "%(kernel)s:" } }
}
planes {
  name: "/host:CPU"
  lines { name: "python" timestamp_ns: 1000
    events { metadata_id: 1 offset_ps: 0 duration_ps: 600000 } }
  event_metadata { key: 1 value { id: 1 name: "bench.round"
    stats { metadata_id: 9 str_value: "not a device plane" } } }
  stat_metadata { key: 9 value { id: 9 name: "tf_op" } }
}
%(programs)s
"""
PROGRAMS = """
planes {
  name: "/host:metadata"
  event_metadata { key: 1 value { id: 1 name: "jit_body(123)"
    stats { metadata_id: 2 bytes_value: "%s" } } }
  stat_metadata { key: 2 value { id: 2 name: "Hlo Proto" } }
}
""" % _text_bytes(HLO)


def test_an_instruction_without_metadata_takes_a_neighbours_name():
    hlo = scope_reduce.read_hlo(memoryview(HLO))
    assert hlo["%fusion.1"] == [CONV, ""]
    assert hlo["%copy.7"] == ["", KERNEL]          # what reads it, before what it reads
    assert hlo["%pad.3"] == ["", KERNEL]           # nothing named reads it: what it reads
    assert hlo["%constant.9"] == ["", "jit(body)/shard_map/round/local_train/vmap()/while"]
    assert hlo["%while.1"][0].endswith("/while") and hlo["%tuple.1"] == ["", KERNEL]
    # a nameless loop goes by what it reads, and its body goes by the loop
    assert hlo["%while.5"] == ["", CONV] and hlo["%dynamic-update-slice.4"] == ["", CONV]
    assert scope_reduce.read_hlo(memoryview(b"")) == {}


@pytest.fixture(params=["with the programs", "without the programs"])
def traced_run(request, monkeypatch):
    """What `run.py` leaves while its metrics are computed: a live
    `heterofl_bench_*` directory with one trace, beside the newer trace of a
    killed run that no object of this process holds."""
    import jax

    programs = PROGRAMS if request.param == "with the programs" else ""
    blob = jax.profiler.ProfileData.text_proto_to_serialized_xspace(
        XSPACE % {"conv": CONV, "kernel": KERNEL, "programs": programs})

    def write(root):
        d = os.path.join(root, "trace", "plugins", "profile", "2026_09_27")
        os.makedirs(d)
        with open(os.path.join(d, "host.xplane.pb"), "wb") as f:
            f.write(blob)
        return os.path.join(d, "host.xplane.pb")

    monkeypatch.setattr(scope_reduce, "_memo", {})
    with tempfile.TemporaryDirectory(prefix="heterofl_bench_") as work, \
            tempfile.TemporaryDirectory(prefix="stale_") as other:
        mine = write(work)
        stale = os.path.join(other, "heterofl_bench_killed")
        os.makedirs(stale)
        write(stale)
        yield mine, bool(programs)


def test_second_reading_of_the_trace_file(traced_run, capsys):
    path, programs = traced_run
    assert scope_reduce.find_xplane() == path
    names = scope_reduce.read_op_names(path)
    want = {"%fusion.1 fusion f32[4]": [CONV + ":" * (not programs), True],
            "%call.1 custom-call (f32[4],": [KERNEL + ":" * (not programs), True]}
    if programs:   # the compiler's copy and pad go with the kernel they serve
        want["%copy.7 copy f32[4]"] = [KERNEL, False]
        want["%pad.3 pad f32[4]"] = [KERNEL, False]
    else:          # the profiler lent the copy its loop's name; the pad has none
        want["%copy.7 copy f32[4]"] = [
            "jit(body)/shard_map/round/local_train/vmap()/while:", True]
    assert names == want
    reduction = {"rounds": 1}                       # what run.py hands a metric
    cell = {"steps_per_round": 4}

    def metric(name):
        return harness.load_module("layer_metrics", name).compute(reduction, [], cell)

    # the pad has a scope only where the programs lend it one
    assert metric("scoped_share_pct") == pytest.approx(100.0 if programs else 100.0 * 550 / 600)
    assert metric("model_ms.step") == pytest.approx(400e-6 / 4)
    assert metric("conv_ms.step") == pytest.approx(400e-6 / 4)
    assert metric("update_kernel_ms.step") == pytest.approx(100e-6 / 4)
    assert metric("carry_ms.step") == (pytest.approx(100e-6 / 4) if programs else None)
    for absent in ("norm_ms.step", "embed_ms.step", "batch_ms.step", "aggregate_ms.round"):
        assert metric(absent) is None
    assert harness.load_module("layer_metrics", "model_ms.step").compute(None, [], cell) is None
    out = capsys.readouterr().out
    assert out.count("benchmark: device ms a round by scope") == 1   # read once
    assert "round/local_train/step/model/conv fwd 0.000" in out
    assert ("a neighbour's 16.67 %, none 0.00 %" if programs
            else "a neighbour's 0.00 %, none 8.33 %") in out


def test_no_trace_or_no_scopes_reports_nothing(monkeypatch):
    monkeypatch.setattr(scope_reduce, "_memo", {})
    assert scope_reduce.find_xplane() is None
    assert scope_reduce.table() is None
    compute = harness.load_module("layer_metrics", "scoped_share_pct").compute
    assert compute({"rounds": 2}, [], {"steps_per_round": 4}) is None
    # a program without scopes (the parent): a table with no scoped row
    t = reduce_scopes(TRACE, {})
    assert {r[0] for r in t["rows"]} == {scope_reduce.UNSCOPED}
