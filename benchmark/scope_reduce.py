"""Device time by the part of the program that spends it.

The program enters `jax.named_scope`s (`heterofl_tpu/obs/trace.py` lists them),
so every instruction's `op_name` carries the path of the part it belongs to,
with autodiff's wrappers on it:

    jit(body)/shard_map/round/local_train/vmap()/while/body/closed_call/
        transpose(jvp(step/model))/conv/conv_general_dilated

`trace_reduce.load_xplane` keeps of a device event only its short name, and
jax's `ProfileData` does not show what the profiler stored beside it, so this
module reads the trace file a second time:

- the file: `run.py` gives `compute(reduction, phases, cell)` no path, so
  `find_xplane` takes the `heterofl_bench_*` `TemporaryDirectory` this very
  process holds (a stale directory of a killed run belongs to no live object);
- the names: each device plane's event metadata holds the instruction's
  `op_name` under the stat `tf_op`, and the `/host:metadata` plane holds every
  program as an `HloProto`.  `read_op_names` decodes both from the protobuf
  wire format (field numbers of tsl's `xplane.proto` and xla's `hlo.proto`)
  and skips the events, which `load_xplane` reads.  About a quarter of a
  ResNet round and two thirds of a transformer round run in instructions the
  compiler made itself and gave no metadata (relayout `copy`s, `pad`s to a
  tile, the loops and `dynamic-update-slice`s a `concatenate` or a `reshape`
  becomes); the profiler files them all under their loop's name.  `read_hlo`
  instead lends each the name of the nearest named instruction that reads its
  result, and a row says whether its scope is its own;
- the path: `scope_of` unwraps `vmap(...)`, `jvp(...)`, `transpose(...)`,
  drops the primitive at the end and every component that is no scope of the
  vocabulary below (`jit(...)`, `while`, `body`, `closed_call`, function
  names), and keeps `jvp(` / `transpose(` as a forward / backward flag;
- the time: self time as `trace_reduce.self_times` computes it, inside the
  stretch `trace_reduce.reduce` uses (first `bench.round` start to last end),
  mean over the devices.

A program without scopes (the parent of the PR that added them) gives a table
with no scoped row: every metric that reads it then returns None.
"""

import gc
import glob
import os
import tempfile
import time

from benchmark import trace_reduce

#: the scope names this yardstick reads, as the program enters them; a
#: two-word name is two path components.  benchmark/tests/test_scope_reduce.py
#: holds them against `heterofl_tpu.obs.trace.SCOPES`.
SCOPES = (
    "round/gather", "round/local_train", "round/aggregate", "psum",
    "step/batch", "augment", "step/unflatten", "step/model", "step/update",
    "conv", "linear", "embed", "norm", "attn", "loss",
    "update/flatten", "update/pack", "update/kernel", "update/unpack",
    "eval/sbn", "eval/users", "eval/global",
)
#: `name=` of the program's `pallas_call`s: kept in a path like a scope
KERNELS = ("fused_sgd", "masked_bn_fwd", "masked_bn_bwd", "int8_pack")
UNSCOPED = "unscoped"

_PAIRS = {tuple(s.split("/")) for s in SCOPES if "/" in s}
_SINGLES = {s for s in SCOPES if "/" not in s} | set(KERNELS)


# ---- the path of one instruction -------------------------------------------

def _split(path):
    """Components of ``path`` at the slashes outside any parenthesis."""
    out, depth, start = [], 0, 0
    for i, c in enumerate(path):
        if c == "(":
            depth += 1
        elif c == ")":
            depth -= 1
        elif c == "/" and depth == 0:
            out.append(path[start:i])
            start = i + 1
    out.append(path[start:])
    return out


def _words(component, flags):
    """Plain words of one component, transform wrappers taken off: `jvp(` and
    `transpose(` are noted in ``flags``; what a `jit(` holds is a function's
    name, not a scope."""
    while component.endswith(")") and "(" in component:
        head, inner = component.split("(", 1)
        if head == "jit":
            return []
        if head in ("jvp", "transpose"):
            flags.add(head)
        component = inner[:-1]
        if "/" in component:
            return [w for c in _split(component) for w in _words(c, flags)]
    return [component] if component else []


def scope_of(op_name):
    """(scope path, direction) of an instruction's ``op_name``: the scopes of
    the vocabulary in the order they were entered, joined by "/", "" if none;
    direction "fwd" under `jvp(`, "bwd" under `transpose(`, else ""."""
    name = (op_name or "").split(";")[0].rsplit(":", 1)[0]
    flags, words = set(), []
    for component in _split(name)[:-1]:  # the last one is the primitive
        words += _words(component, flags)
    scopes, i = [], 0
    while i < len(words):
        if tuple(words[i:i + 2]) in _PAIRS:
            scopes.append(words[i] + "/" + words[i + 1])
            i += 2
        else:
            if words[i] in _SINGLES:
                scopes.append(words[i])
            i += 1
    direction = "bwd" if "transpose" in flags else "fwd" if "jvp" in flags else ""
    return "/".join(scopes), direction


# ---- the trace file ----------------------------------------------------------

def find_xplane():
    """The `.xplane.pb` of the traced run this process made, or None."""
    found = []
    for obj in gc.get_objects():
        if isinstance(obj, tempfile.TemporaryDirectory) and \
                os.path.basename(obj.name).startswith("heterofl_bench_"):
            found += glob.glob(os.path.join(obj.name, "trace", "plugins",
                                            "profile", "*", "*.xplane.pb"))
    return max(found, key=os.path.getmtime) if found else None


def _varint(buf, i):
    value = shift = 0
    while True:
        b = buf[i]
        i += 1
        value |= (b & 0x7F) << shift
        if b < 0x80:
            return value, i
        shift += 7


def _fields(buf):
    """(field number, value) of a protobuf message: an int for a varint, a
    memoryview for a length-delimited or fixed field."""
    i, n = 0, len(buf)
    while i < n:
        key, i = _varint(buf, i)
        wire = key & 7
        if wire == 0:
            value, i = _varint(buf, i)
        else:
            if wire == 2:
                size, i = _varint(buf, i)
            elif wire in (1, 5):
                size = 8 if wire == 1 else 4
            else:
                raise ValueError(f"wire type {wire} in an xplane.pb")
            value = buf[i:i + size]
            i += size
        yield key >> 3, value


def _map_value(entry):
    return next((v for f, v in _fields(entry) if f == 2), None)


def _planes(path):
    """(name, {event metadata id: message}, {stat metadata id: name}) of each
    plane of an `.xplane.pb` (XSpace.planes = 1; XPlane: name = 2,
    event_metadata = 4, stat_metadata = 5; the lines, field 3, are skipped)."""
    with open(path, "rb") as f:
        space = memoryview(f.read())
    for field, plane in _fields(space):
        if field != 1:
            continue
        name, events, stats = "", [], {}
        for f, v in _fields(plane):
            if f == 2:
                name = bytes(v).decode()
            elif f == 4:
                events.append(_map_value(v))
            elif f == 5:
                meta = dict(_fields(_map_value(v)))
                stats[meta.get(1, 0)] = bytes(meta.get(2, b"")).decode()
        yield name, events, stats


def _event_stats(event, stats):
    """(name, {stat name: value}) of one XEventMetadata (name = 2, stats = 5;
    XStat: metadata_id = 1, str_value = 5, bytes_value = 6, ref_value = 7)."""
    name, out = "", {}
    for f, v in _fields(event):
        if f == 2:
            name = bytes(v).decode()
        elif f == 5:
            stat = dict(_fields(v))
            key = stats.get(stat.get(1))
            if 5 in stat:
                out[key] = bytes(stat[5]).decode()
            elif 6 in stat:
                out[key] = stat[6]
            elif 7 in stat:
                out[key] = stats.get(stat[7], "")
    return name, out


def _ids(values):
    """Repeated int64, packed or not."""
    out = []
    for v in values:
        if isinstance(v, int):
            out.append(v)
        else:
            i = 0
            while i < len(v):
                x, i = _varint(v, i)
                out.append(x)
    return out


def read_hlo(proto):
    """{"%name": [own op_name or "", op_name lent by a neighbour or ""]} of
    one program's instructions, from its serialized `HloProto` (hlo_module =
    1; HloModuleProto.computations = 3; HloComputationProto: instructions = 2,
    id = 5; HloInstructionProto: name = 1, metadata = 7 with op_name = 2, id =
    35, operand_ids = 36, called_computation_ids = 38).

    The compiler makes instructions of its own (relayout `copy`s, `pad`s to a
    tile, the `dynamic-update-slice`s a `concatenate` becomes) and gives many
    of them no metadata.  Such a one is lent the `op_name` of the nearest
    named instruction that needs its result (users first: a relayout belongs
    to what reads it), else of the nearest named one it reads, else what the
    instruction that calls its computation goes by (a `while` for its body)."""
    module = next((v for f, v in _fields(proto) if f == 1), None)
    if module is None:
        return {}
    comps = []
    for f, comp in _fields(module):
        if f != 3:
            continue
        comp_id, instrs = None, []
        for cf, cv in _fields(comp):
            if cf == 5:
                comp_id = cv
            elif cf == 2:
                name, op_name, iid, operands, called = "", "", None, [], []
                for g, v in _fields(cv):
                    if g == 1:
                        name = bytes(v).decode()
                    elif g == 7:
                        op_name = next((bytes(x).decode() for h, x in _fields(v)
                                        if h == 2), "")
                    elif g == 35:
                        iid = v
                    elif g == 36:
                        operands.append(v)
                    elif g == 38:
                        called.append(v)
                instrs.append([name, op_name, iid, _ids(operands), _ids(called)])
        comps.append((comp_id, instrs))
    out, inside = {}, {}  # inside: "%name" of an instruction -> the "%name" that calls its computation
    for comp_id, instrs in comps:
        by_id = {i[2]: i for i in instrs}
        users = {}
        for i in instrs:
            for o in i[3]:
                users.setdefault(o, []).append(i[2])

        def nearest(start, edges):
            seen, frontier = {start}, [start]
            for _ in range(12):  # hops
                nxt = []
                for node in frontier:
                    for other in edges(node):
                        if other in seen or other not in by_id:
                            continue
                        seen.add(other)
                        if by_id[other][1]:
                            return by_id[other][1]
                        nxt.append(other)
                frontier = nxt
            return ""

        for name, op_name, iid, _, _ in instrs:
            lent = ""
            if not op_name:
                lent = nearest(iid, lambda n: users.get(n, ())) \
                    or nearest(iid, lambda n: by_id[n][3])
            out["%" + name] = [op_name, lent]
    by_comp = dict(comps)
    for _, instrs in comps:
        for name, _, _, _, called in instrs:
            for c in called:
                for inner in by_comp.get(c, ()):
                    inside.setdefault("%" + inner[0], "%" + name)
    for name, names in out.items():  # nothing named near it: its caller's name
        at = name
        for _ in range(8):  # nesting
            if names[0] or names[1] or at not in inside:
                break
            at = inside[at]
            names[1] = out[at][0] or out[at][1]
    return out


def read_op_names(path):
    """{short name of a device event: [op_name, own]} of a trace file.  The
    profiler stores each instruction's `op_name` as the stat `tf_op` of the
    event's metadata on the `/device:TPU:<n>` planes, and lends an
    instruction that has none its loop's.  Where the trace also holds the
    programs (`/host:metadata`, stat `Hlo Proto`), an instruction without
    metadata of its own takes a neighbour's name instead (`read_hlo`) and
    ``own`` is False for it; the larger program wins a name two programs
    share."""
    planes = list(_planes(path))
    hlo = {}
    protos = [st["Hlo Proto"] for name, events, stats in planes
              if name == "/host:metadata"
              for st in (_event_stats(e, stats)[1] for e in events)
              if isinstance(st.get("Hlo Proto"), memoryview)]
    for proto in sorted(protos, key=len):
        hlo.update(read_hlo(proto))
    out = {}
    for name, events, stats in planes:
        if not trace_reduce.DEVICE_PLANE.match(name):
            continue
        for event in events:
            ev_name, st = _event_stats(event, stats)
            short = trace_reduce.short_name(ev_name)
            own, lent = hlo.get(short.split(" ")[0], [None, ""])
            op = own or lent or st.get("tf_op")
            if op:
                out[short] = [op, own is None or bool(own)]
    return out


# ---- the reduction -------------------------------------------------------------

def opcode(short):
    """The operation of an event's short name (`%result op shape`)."""
    parts = short.split(" ")
    return parts[1] if len(parts) > 1 else ""


def reduce_scopes(trace, op_names):
    """Self time by (scope path, direction, operation, own) over the traced
    stretch, mean over the devices: {"rows": [[path, direction, operation,
    seconds, own], ...] longest first, "total_s": their sum, "rounds": n}.
    ``trace`` as `trace_reduce.load_xplane` gives it; ``op_names`` maps a
    device event's name to [its `op_name`, whether that is its own]
    (`read_op_names`); an event with none, or with no scope of the vocabulary
    in it, is filed under `unscoped`."""
    rounds = [s for s in trace_reduce.host_spans(trace)
              if s[0] == trace_reduce.ROUND_SPAN]
    if not rounds:
        raise ValueError(f"trace holds no {trace_reduce.ROUND_SPAN} span")
    lo, hi = rounds[0][1], max(s + d for _, s, d in rounds)
    keys, totals, devices = {}, {}, 0
    for plane in trace["planes"]:
        if not trace_reduce.DEVICE_PLANE.match(plane["name"]):
            continue
        devices += 1
        events = []
        for line in plane["lines"]:
            if line["name"] != trace_reduce.OPS_LINE:
                continue
            for name, start, dur in line["events"]:
                if name not in keys:
                    op_name, own = op_names.get(name, (None, True))
                    path, direction = scope_of(op_name)
                    keys[name] = (path or UNSCOPED, direction, opcode(name),
                                  bool(own))
                events.append([keys[name], start, dur])
        for key, ns in trace_reduce.self_times(events, lo, hi).items():
            totals[key] = totals.get(key, 0.0) + ns
    if not devices:
        raise ValueError("trace holds no device plane")
    rows = sorted(([p, d, op, ns / 1e9 / devices, own]
                   for (p, d, op, own), ns in totals.items()),
                  key=lambda r: -r[3])  # by time alone: ties keep their order
    return {"rows": rows, "total_s": sum(r[3] for r in rows), "rounds": len(rounds)}


def own_scope(row):
    """The row's scope is the instruction's own, not a neighbour's."""
    return row[0] != UNSCOPED and row[4]


def has(*scopes):
    """A row filter: every one of ``scopes`` is on the row's path."""
    def pred(row):
        on_path = "/" + row[0] + "/"
        return all("/" + s + "/" in on_path for s in scopes)
    return pred


def kernel_call(row):
    """The named Pallas update kernel alone: the `custom-call` under
    `update/kernel` (the `pad`s a `vmap` adds under the same scope are not)."""
    return row[2] == "custom-call" and has("update/kernel")(row)


def seconds(table, pred):
    """Summed seconds of the rows ``pred`` accepts; None if it accepts none."""
    picked = [r[3] for r in table["rows"] if pred(r)]
    return sum(picked) if picked else None


def by_scope(table, top=20):
    """[[path, direction, seconds], ...]: the rows summed over operations,
    longest first, forward and backward apart."""
    out = {}
    for path, direction, _, s, _ in table["rows"]:
        out[(path, direction)] = out.get((path, direction), 0.0) + s
    return [[p, d, s] for (p, d), s in
            sorted(out.items(), key=lambda kv: -kv[1])[:top]]


# ---- what the metric files call -------------------------------------------------

_memo = {}


def table():
    """The reduction of this process's traced run, read once for all metric
    files; None where there is no trace to read or the program has no scope
    in it.  Prints the split by scope as one `benchmark:` line."""
    if "table" not in _memo:
        _memo["table"] = None
        try:
            path = find_xplane()
            if path is not None:
                t = time.perf_counter()
                result = reduce_scopes(trace_reduce.load_xplane(path),
                                       read_op_names(path))
                if any(r[0] != UNSCOPED for r in result["rows"]):
                    _memo["table"] = result
                per_round = 1e3 / result["rounds"]
                share = 100.0 / max(result["total_s"], 1e-30)
                own, lent, none = (
                    share * (seconds(result, pred) or 0.0) for pred in (
                        own_scope, lambda r: r[0] != UNSCOPED and not r[4],
                        lambda r: r[0] == UNSCOPED))
                print("benchmark: device ms a round by scope (self time; "
                      f"scope its own {own:.2f} %, a neighbour's {lent:.2f} %, "
                      f"none {none:.2f} %; "
                      f"read in {time.perf_counter() - t:.1f}s): " + "; ".join(
                          f"{p}{' ' + d if d else ''} {s * per_round:.3f}"
                          for p, d, s in by_scope(result)), flush=True)
        except Exception as e:  # a new metric's reader reports nothing, never fails the run
            print(f"benchmark: scope_reduce read nothing: {type(e).__name__}: {e}",
                  flush=True)
    return _memo["table"]


def ms(reduction, pred, per=1.0):
    """Milliseconds a traced round of the rows ``pred`` accepts, over ``per``
    (local steps a round, for a per-step metric); None without a traced run,
    without scopes, or where ``pred`` finds nothing."""
    if not reduction:
        return None
    found = table()
    s = seconds(found, pred) if found else None
    return None if s is None else 1e3 * s / found["rounds"] / per
