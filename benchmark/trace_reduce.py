"""From a profiler trace to the numbers the per-layer metrics read.

`load_xplane` turns the profiler's `.xplane.pb` into plain lists (planes ->
lines -> [name, start_ns, duration_ns]); `reduce` works on those lists alone,
so that a small recorded trace kept as JSON beside the tests checks it.

Device planes are the planes named `/device:TPU:<n>`; their `XLA Ops` line
holds one event per executed operation (nested where an operation such as a
`while` encloses others).  Host spans are the harness's own
`jax.profiler.TraceAnnotation`s, named `bench.*`, on the host plane.

- traced stretch: from the start of the first `bench.round` span to the end of
  the last one;
- busy: per device the union of its operations' intervals, cut to the stretch;
- per-operation time: self time (an enclosing operation does not count its
  children's time), summed by name;
- gaps: the stretches inside the traced stretch in which no operation ran on a
  device, each named by the innermost `bench.*` span that covers its middle.
"""

import gzip
import json
import re

DEVICE_PLANE = re.compile(r"^/device:TPU:\d+$")
OPS_LINE = "XLA Ops"
SPAN_PREFIX = "bench."
ROUND_SPAN = "bench.round"


def short_name(name, limit=96):
    """A device event is named by its whole HLO instruction; keep the result's
    name, the operation and the result's shape."""
    m = re.match(r"^(%?[\w.\-]+) = (.*)$", name)
    if not m:
        return name[:limit]
    rest = m.group(2)
    shape = re.sub(r"\{[^}]*\}", "", rest.split(" ")[0])
    op = re.search(r"(?:^|[\s)}])([a-z][\w\-]*)\(", rest)
    return f"{m.group(1)} {op.group(1) if op else '?'} {shape}"[:limit]


def load_xplane(path):
    """The trace as plain lists.  Of the host plane only the harness's spans
    are kept; of device planes every line."""
    import jax

    data = jax.profiler.ProfileData.from_file(path)
    planes = []
    for plane in data.planes:
        device = bool(DEVICE_PLANE.match(plane.name))
        if not device and not plane.name.startswith("/host:CPU"):
            continue
        lines = []
        for line in plane.lines:
            if device and line.name != OPS_LINE:
                continue
            events = [[short_name(e.name) if device else e.name,
                       float(e.start_ns), float(e.duration_ns)]
                      for e in line.events if device or e.name.startswith(SPAN_PREFIX)]
            if events:
                lines.append({"name": line.name, "events": events})
        planes.append({"name": plane.name, "lines": lines})
    return {"planes": planes}


class PhaseSpans:
    """Collects the program's `PhaseTimer` phases (attach as its `trace`):
    name, start and length on the host's `perf_counter` clock."""

    def __init__(self):
        self.events = []

    def complete(self, name, t0, dt, cat=None):
        self.events.append((name, t0, dt))


def add_phase_spans(trace, phases, round_t0):
    """File the program's phases into the trace as `bench.phase.<name>` spans.
    ``round_t0``: the `perf_counter` reading taken just before the first
    `bench.round` span was opened, which ties the two clocks together."""
    rounds = [s for s in host_spans(trace) if s[0] == ROUND_SPAN]
    if not rounds or not phases:
        return trace
    offset = rounds[0][1] - round_t0 * 1e9
    events = [[SPAN_PREFIX + "phase." + name, t0 * 1e9 + offset, dt * 1e9]
              for name, t0, dt in phases]
    trace["planes"].append({"name": "/host:CPU phases",
                            "lines": [{"name": "PhaseTimer", "events": events}]})
    return trace


def load_json(path):
    opener = gzip.open if path.endswith(".gz") else open
    with opener(path, "rt", encoding="utf-8") as f:
        return json.load(f)


def union(intervals):
    """Sorted, merged [start, end) intervals."""
    out = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return out


def self_times(events, lo, hi):
    """{name: ns} of each operation's own time inside [lo, hi): its interval
    less the intervals of the operations nested in it."""
    totals = {}
    stack = []  # [name, end, own]

    def close(until):
        while stack and stack[-1][1] <= until:
            name, _, own = stack.pop()
            totals[name] = totals.get(name, 0.0) + own

    for name, start, dur in sorted(events, key=lambda e: (e[1], -e[2])):
        s, e = max(start, lo), min(start + dur, hi)
        if e <= s:
            continue
        close(s)
        if stack:
            stack[-1][2] -= min(e, stack[-1][1]) - s
        stack.append([name, e, e - s])
    close(float("inf"))
    return totals


def host_spans(trace):
    spans = []
    for plane in trace["planes"]:
        if DEVICE_PLANE.match(plane["name"]):
            continue
        for line in plane["lines"]:
            spans += [e for e in line["events"] if e[0].startswith(SPAN_PREFIX)]
    return sorted(spans, key=lambda e: e[1])


def covering_span(spans, t):
    """Innermost harness span that covers time ``t`` (None outside all)."""
    best = None
    for name, start, dur in spans:
        if start <= t < start + dur and (best is None or dur < best[2]):
            best = (name, start, dur)
    return best[0] if best else None


def reduce(trace, top=10, gaps=5):
    """The reduction; times in seconds.  Raises if the trace holds no device
    operation or no round span."""
    spans = host_spans(trace)
    rounds = [s for s in spans if s[0] == ROUND_SPAN]
    if not rounds:
        raise ValueError(f"trace holds no {ROUND_SPAN} span")
    lo = rounds[0][1]
    hi = max(s + d for _, s, d in rounds)
    devices = {}
    ops_total = {}
    all_gaps = []
    for plane in trace["planes"]:
        if not DEVICE_PLANE.match(plane["name"]):
            continue
        events = [e for line in plane["lines"] if line["name"] == OPS_LINE
                  for e in line["events"]]
        busy = union([max(s, lo), min(s + d, hi)] for _, s, d in events
                     if min(s + d, hi) > max(s, lo))
        busy_ns = sum(e - s for s, e in busy)
        own = self_times(events, lo, hi)
        for name, ns in own.items():
            ops_total[name] = ops_total.get(name, 0.0) + ns
        edges = [lo] + [t for iv in busy for t in iv] + [hi]
        for a, b in zip(edges[0::2], edges[1::2]):
            if b > a:
                all_gaps.append((b - a, covering_span(spans, (a + b) / 2.0),
                                 plane["name"]))
        devices[plane["name"]] = {"busy_s": busy_ns / 1e9,
                                  "idle_share": 1.0 - busy_ns / (hi - lo)}
    if not devices or not any(d["busy_s"] > 0 for d in devices.values()):
        raise ValueError("trace holds no device operation inside the rounds")
    n = len(devices)
    gap_by_span = {}
    for ns, span, _ in all_gaps:
        key = span or "outside_spans"
        gap_by_span[key] = gap_by_span.get(key, 0.0) + ns / n
    all_gaps.sort(key=lambda g: -g[0])  # by length alone: a gap's span may be None
    return {
        "rounds": len(rounds),
        "window_s": (hi - lo) / 1e9,
        "busy_s": sum(d["busy_s"] for d in devices.values()) / n,
        "idle_share": sum(d["idle_share"] for d in devices.values()) / n,
        "devices": devices,
        "ops_s": {k: v / 1e9 / n for k, v in ops_total.items()},
        "device_ops": [[k, v / 1e9 / n] for k, v in
                       sorted(ops_total.items(), key=lambda kv: -kv[1])[:top]],
        "idle_gaps": [[f"{span or 'outside_spans'}@{dev}", ns / 1e9]
                      for ns, span, dev in all_gaps[:gaps]],
        "idle_by_span_s": {k: v / 1e9 for k, v in gap_by_span.items()},
    }
