"""Set-up and compilation spans (ISSUE 38): ONE process-wide, in-memory
record of what happens O(programs) times a process, never O(rounds).

The round is measured from inside on both sides already -- the host by
``PhaseTimer`` (``sample`` / ``stage`` / ``dispatch`` / ``fetch``), the
device by the scope vocabulary of :mod:`.trace` -- but what precedes the
first steady round was not: building the experiment, staging the
population, the parameters' init, and every compilation.  This module is
the host-side twin of :data:`~.trace.SCOPES` for that stretch:

* **set-up spans** (:data:`SETUP_SPANS`, entered with :func:`span`; a name
  outside the vocabulary raises, as :func:`~.trace.scope` does):
  ``setup/experiment`` is all of ``FedExperiment.__init__`` and encloses
  ``setup/dataset``, ``setup/model`` and ``setup/engine``; ``setup/split``,
  ``setup/stage`` (with ``setup/stage/train`` and ``setup/stage/eval``
  inside it), ``setup/init`` and ``setup/first_round`` (the compile-bearing
  first ``train_round`` / ``train_superstep`` of an experiment) follow.
  Each carries ``bytes_in_use``, the fullest local device's live bytes at
  its close (one ``memory_stats()`` call a device and span, never in a
  round);
* **compile spans** (:data:`COMPILE_SPANS`), from jax's own monitoring
  events: ``compile/trace``, ``compile/lower`` and ``compile/backend``,
  each with ``program`` = the event's ``fun_name``; ``compile/backend``
  also says whether the persistent cache served it (``cache`` = ``hit`` |
  ``miss`` | ``uncached``), whether an entry was ``written`` (jax's
  ``cache_misses`` event fires only when one is, so it is not the count of
  misses) and how long a hit took to load (``load_s``);
* **counters** (:data:`COUNTERS`), whole and by the program span they fell
  under (:data:`OUTSIDE` where they fell under none): they go on counting
  past the cap, and :func:`table` prints both.

A span is ``(id, name, t0, dt, parent, args)`` on ``perf_counter``; the
parent is the span that was open, on the same thread, when it began, so a
span's self time is its length less its children's.  ``PhaseTimer.phase``
keeps that stack of open spans (two list operations a phase: no clock
read, no system call and no device call is added to the round path) and a
phase becomes a span of the record only when something of the record --
a compilation -- happens inside it.  A compilation under no program span
(a caller's own programs: the benchmark's weights and plain reference)
has no parent and is summed under :data:`OUTSIDE`.

Where the ``PhaseTimer`` of the enclosing span has a ``trace`` hook, each
span is also filed to it through ``complete(name, t0, dt, cat=...)``, with
its ``id`` and ``parent`` in ``args`` (:func:`file_to`): the run's
``trace.json`` / ``events.jsonl`` and the benchmark's device trace hold the
same spans.  A run's recorder attaches when the run begins
(``FedExperiment.run``) and is handed the spans of the experiment's
construction from the record then, so its timeline begins with
``setup/experiment`` and an experiment that is built and never run (an
evaluation, a benchmark) writes nothing.

Clocks: jax's events arrive on ``time.time()``; the record reads the two
clocks ONCE, side by side, when it is made (as ``TraceRecorder`` pairs
its own) and converts with that offset, so a wall-clock step in between
(NTP) shows as skew of the compile spans against the phases.

Always on, like the timer and the scopes: no config key, no environment
variable, no switch.  The record is capped (:data:`CAP` spans, then
counters only).  Host-side only; jax is imported where a listener or a
device is needed, never at import.
"""

from __future__ import annotations

import itertools
import threading
import time
from collections import namedtuple
from contextlib import contextmanager
from typing import Any, Dict, List, Optional

#: every name :func:`span` enters, nested as the driver nests them
SETUP_SPANS = (
    "setup/experiment", "setup/dataset", "setup/model", "setup/engine",
    "setup/split", "setup/stage", "setup/stage/train", "setup/stage/eval",
    "setup/init", "setup/first_round",
)

#: jax's monitoring event -> the compile span it becomes
#: (``jax/_src/dispatch.py``: each event is sent as a scalar, its start on
#: ``time.time()``, when the stage begins and as a time span when it ends)
_JAX_SPANS = {
    "/jax/core/compile/jaxpr_trace_duration": "compile/trace",
    "/jax/core/compile/jaxpr_to_mlir_module_duration": "compile/lower",
    "/jax/core/compile/backend_compile_duration": "compile/backend",
}
COMPILE_SPANS = tuple(_JAX_SPANS.values())

#: the persistent cache's events, all sent inside ``compile/backend``
_REQUEST = "/jax/compilation_cache/compile_requests_use_cache"
_HIT = "/jax/compilation_cache/cache_hits"
_WRITTEN = "/jax/compilation_cache/cache_misses"
_LOAD = "/jax/compilation_cache/cache_retrieval_time_sec"
#: ... -> what each notes on the open ``compile/backend`` span
_FLAGS = {_REQUEST: "_asked", _HIT: "_hit", _WRITTEN: "written"}

COUNTERS = ("compile_requests", "compile_hits", "compile_misses")

#: where a compilation under no program span is summed
OUTSIDE = "outside"

#: spans the record keeps; past it only the counters move
CAP = 4096

Span = namedtuple("Span", "id name t0 dt parent args")


class Open:
    """An open span on a thread's stack.  ``id`` is None for a
    ``PhaseTimer`` phase until something of the record happens inside it;
    ``timer`` is the ``PhaseTimer`` whose ``trace`` hook the span and what
    it encloses are filed to."""

    __slots__ = ("name", "t0", "id", "timer", "args")

    def __init__(self, name: str, t0: float, timer=None, args=None):
        self.name, self.t0, self.id = name, t0, None
        self.timer, self.args = timer, args


def _is_compile(name: str) -> bool:
    return name.startswith("compile/")


def _cat(name: str) -> str:
    """The category a span is filed under: ``setup``, ``compile``, or
    ``phase`` for a ``PhaseTimer`` phase in which something compiled."""
    head = name.split("/", 1)[0]
    return head if head in ("setup", "compile") else "phase"


def file_to(hook, s: Span) -> None:
    """File a span of the record to a ``PhaseTimer.trace`` hook, with its
    ``id`` and ``parent`` in ``args`` where the hook's ``complete`` takes
    them (``TraceRecorder``'s does), else in the four-argument form the
    timer files its phases in (the benchmark's ``PhaseSpans`` keeps name,
    start and length)."""
    cat = _cat(s.name)
    try:
        hook.complete(s.name, s.t0, s.dt, cat=cat,
                      args={"id": s.id, "parent": s.parent, **s.args})
    except TypeError:
        hook.complete(s.name, s.t0, s.dt, cat=cat)


class SpanRecord:
    """The record: closed spans in closing order, the counters, and each
    thread's stack of open spans."""

    def __init__(self, cap: int = CAP):
        self.cap = cap
        self.spans: List[Span] = []
        self.dropped = 0  # spans past the cap
        self.counters: Dict[str, int] = dict.fromkeys(COUNTERS, 0)
        #: program span name (or OUTSIDE) -> its share of the counters
        self.by_parent: Dict[str, Dict[str, int]] = {}
        self.installed = False  # subscribed to jax's events (install())
        self._ids = itertools.count()
        self._tls = threading.local()
        # the ONE paired reading of the two clocks (module docstring)
        self._perf0, self._wall0 = time.perf_counter(), time.time()

    # -- the stack of open spans -----------------------------------------

    def stack(self) -> List[Open]:
        """This thread's open spans, outermost first."""
        try:
            return self._tls.stack
        except AttributeError:
            self._tls.stack = []
            return self._tls.stack

    def to_perf(self, wall: float) -> float:
        """A ``time.time()`` reading on the ``perf_counter`` clock."""
        return self._perf0 + (wall - self._wall0)

    def open(self, frame: Open) -> Open:
        """Push a span of the record; every span open around it (a phase
        that had no id yet) becomes one of the record's."""
        stack = self.stack()
        stack.append(frame)
        for f in stack:
            if f.id is None:
                f.id = next(self._ids)
        return frame

    def close(self, frame: Open, dt: float) -> None:
        """Pop ``frame`` (and anything left open above it), record it and
        file it to the nearest enclosing timer's hook."""
        stack = self.stack()
        if frame in stack:
            del stack[stack.index(frame):]
        self.closed(frame, dt, stack)

    def closed(self, frame: Open, dt: float, stack: List[Open]) -> None:
        """Record ``frame``, already popped off ``stack``."""
        s = Span(frame.id, frame.name, frame.t0, dt,
                 stack[-1].id if stack else None, dict(frame.args or {}))
        if len(self.spans) < self.cap:
            self.spans.append(s)
        else:
            self.dropped += 1
        for f in [frame] + stack[::-1]:
            hook = getattr(f.timer, "trace", None)
            if hook is not None:
                file_to(hook, s)
                break

    def _count(self, counter: str, stack: List[Open]) -> None:
        self.counters[counter] += 1
        under = next((f.name for f in reversed(stack)
                      if not _is_compile(f.name)), OUTSIDE)
        mine = self.by_parent.setdefault(under, dict.fromkeys(COUNTERS, 0))
        mine[counter] += 1

    # -- jax's monitoring events -----------------------------------------

    def _backend(self) -> Optional[Open]:
        """The ``compile/backend`` span the cache's events belong to: they
        are sent from inside it, so it is the innermost open span."""
        stack = self.stack()
        if stack and stack[-1].name == "compile/backend":
            return stack[-1]
        return None

    def on_scalar(self, event: str, value: float, **kw) -> None:
        name = _JAX_SPANS.get(event)
        if name is None:
            return
        # a stage begins; ``value`` is its start
        frame = Open(name, self.to_perf(value),
                     args={"program": str(kw.get("fun_name", "?"))})
        stack = self.stack()
        if name == "compile/trace" and stack and _is_compile(stack[-1].name):
            # a jitted function traced inside another's trace (every
            # ``jnp`` function is one): part of that trace, and a span of
            # its own only if something compiles inside it
            stack.append(frame)
        else:
            self.open(frame)

    def on_time_span(self, event: str, start: float, end: float, **kw) -> None:
        name = _JAX_SPANS.get(event)
        if name is None:
            return
        stack = self.stack()
        frame = next((f for f in reversed(stack) if f.name == name), None)
        if frame is None:  # the listeners came between its two events
            frame = self.open(Open(name, self.to_perf(start), args={
                "program": str(kw.get("fun_name", "?"))}))
        if frame.id is None:  # a nested trace in which nothing compiled
            del stack[stack.index(frame):]
            return
        if name == "compile/backend":
            flags = frame.args
            hit, asked = flags.pop("_hit", False), flags.pop("_asked", False)
            flags["cache"] = "hit" if hit else "miss" if asked else "uncached"
            if asked and not hit:
                self._count("compile_misses", stack[:stack.index(frame)])
        self.close(frame, end - start)

    def on_event(self, event: str, **kw) -> None:
        flag = _FLAGS.get(event)
        if flag is None:
            return
        if event == _REQUEST:
            self._count("compile_requests", self.stack())
        elif event == _HIT:
            self._count("compile_hits", self.stack())
        frame = self._backend()
        if frame is not None:
            frame.args[flag] = True

    def on_duration(self, event: str, duration: float, **kw) -> None:
        if event == _LOAD:
            frame = self._backend()
            if frame is not None:
                frame.args["load_s"] = round(float(duration), 6)

    # -- reading -----------------------------------------------------------

    def summary(self) -> Dict[str, Any]:
        """:func:`summarize` of the record's spans, with its counters
        (which go on counting past the cap) beside them."""
        out = summarize(self.spans)
        out.update(counters=dict(self.counters), dropped=self.dropped,
                   by_parent={k: dict(v) for k, v in self.by_parent.items()})
        return out


def summarize(spans: List[Span]) -> Dict[str, Any]:
    """What the readers print and the metrics read, from a list of spans
    (the record's, or those read back from an ``events.jsonl``):

    * ``seconds``: span name -> summed length of the spans of that name
      (of compile spans only those directly under a program span or under
      none: a trace nested in a trace counts once);
    * ``self_s``: span name -> summed self time;
    * ``compile_s``: program span name (or ``outside``) -> seconds of the
      compile spans directly under it, and ``own_compile_s``: their sum
      without ``outside``, the program's own compilations;
    * ``programs``: ``[program, stage, seconds, cache, under]`` of every
      such compile span, longest first;
    * ``cache``: ``hit`` / ``miss`` / ``uncached`` -> ``compile/backend``
      spans that read so, ``misses``: those that read ``miss``, by the
      program span around them, and ``own_misses``: their count without
      ``outside``;
    * ``bytes_in_use``: set-up span name -> the reading at its (last) close.
    """
    by_id = {s.id: s for s in spans}
    seconds: Dict[str, float] = {}
    self_s: Dict[str, float] = {}
    compile_s: Dict[str, float] = {}
    cache: Dict[str, int] = {}
    misses: Dict[str, int] = {}
    bytes_in_use: Dict[str, int] = {}
    programs = []
    for s in spans:
        self_s[s.name] = self_s.get(s.name, 0.0) + s.dt
        up = by_id.get(s.parent)
        if up is not None:
            self_s[up.name] = self_s.get(up.name, 0.0) - s.dt
        if s.name == "compile/backend" and "cache" in s.args:
            cache[s.args["cache"]] = cache.get(s.args["cache"], 0) + 1
            if s.args["cache"] == "miss":
                under = program_parent(s, by_id)
                misses[under] = misses.get(under, 0) + 1
        if _is_compile(s.name):
            if up is not None and _is_compile(up.name):
                continue  # inside another compilation: its time is there
            under = up.name if up is not None else OUTSIDE
            compile_s[under] = compile_s.get(under, 0.0) + s.dt
            programs.append([s.args.get("program", "?"), s.name, s.dt,
                             s.args.get("cache"), under])
        elif "bytes_in_use" in s.args:
            bytes_in_use[s.name] = s.args["bytes_in_use"]
        seconds[s.name] = seconds.get(s.name, 0.0) + s.dt
    programs.sort(key=lambda p: -p[2])
    def own(by_span):
        return sum(v for k, v in by_span.items() if k != OUTSIDE)

    return {"seconds": seconds, "self_s": self_s, "compile_s": compile_s,
            "own_compile_s": own(compile_s), "programs": programs,
            "cache": cache, "misses": misses, "own_misses": own(misses),
            "bytes_in_use": bytes_in_use}


def program_parent(s: Span, by_id: Dict[int, Span]) -> str:
    """Name of the nearest span around ``s`` that is no compile span
    (:data:`OUTSIDE` for a compilation under no program span)."""
    while s is not None and s.parent is not None:
        s = by_id.get(s.parent)
        if s is not None and not _is_compile(s.name):
            return s.name
    return OUTSIDE


#: the process's record
RECORD = SpanRecord()


def install() -> SpanRecord:
    """Subscribe :data:`RECORD` to jax's monitoring events, once a process
    (a second call registers nothing), and return it."""
    if not RECORD.installed:
        import jax.monitoring as m

        RECORD.installed = True
        m.register_scalar_listener(RECORD.on_scalar)
        m.register_event_time_span_listener(RECORD.on_time_span)
        m.register_event_listener(RECORD.on_event)
        m.register_event_duration_secs_listener(RECORD.on_duration)
    return RECORD


def _bytes_in_use() -> Optional[int]:
    """Live bytes of the fullest local device (None where the backend
    reports none: the CPU)."""
    import jax

    best = None
    for d in jax.local_devices():
        stats = d.memory_stats() or {}
        if "bytes_in_use" in stats:
            best = max(best or 0, int(stats["bytes_in_use"]))
    return best


@contextmanager
def span(name: str, timer=None):
    """A set-up span of :data:`SETUP_SPANS` around a block.  ``timer``: the
    experiment's ``PhaseTimer``; the span and the compilations inside it
    go to its ``trace`` hook too, never into its ``totals`` (the per-round
    phase tables read what they read: ``setup/first_round`` encloses that
    round's phases and would count them twice)."""
    if name not in SETUP_SPANS:
        raise ValueError(f"Not valid span: {name!r} (obs.spans.SETUP_SPANS)")
    t0 = time.perf_counter()
    frame = RECORD.open(Open(name, t0, timer, {}))
    try:
        yield
    finally:
        dt = time.perf_counter() - t0
        used = _bytes_in_use()
        if used is not None:
            frame.args["bytes_in_use"] = used
        RECORD.close(frame, dt)


def table(summary: Dict[str, Any], top: int = 8) -> List[str]:
    """The set-up table's lines, for a log or a report: each set-up span
    with its seconds, its self time, the compilations directly under it and
    the bytes it left; the compilations under the phases and under no span;
    the longest compilations; the cache's answers and, of a live record
    (:meth:`SpanRecord.summary`), the counters, whole and by span."""
    sec, comp = summary["seconds"], summary["compile_s"]
    lines = []
    for name in SETUP_SPANS:
        if name in sec:
            used = summary["bytes_in_use"].get(name)
            lines.append(f"{name} {sec[name]:.3f}s (self "
                         f"{summary['self_s'][name]:.3f}s"
                         + (f", compile {comp[name]:.3f}s" if name in comp else "")
                         + ")" + (f" {used} B in use" if used is not None else ""))
    for name in sorted(set(comp) - set(SETUP_SPANS)):
        lines.append(f"compile under {name} {comp[name]:.3f}s")
    lines.append(f"compile under the program's spans {summary['own_compile_s']:.3f}s; "
                 "all, by stage: " + (", ".join(
                     f"{n} {sec[n]:.3f}s" for n in COMPILE_SPANS if n in sec)
                     or "none"))
    for program, stage, s, cache, under in summary["programs"][:top]:
        lines.append(f"{stage} {program} {s:.3f}s"
                     + (f" {cache}" if cache else "") + f" under {under}")
    lines.append("compile/backend by cache: " + (", ".join(
        f"{k} {v}" for k, v in sorted(summary["cache"].items())) or "none")
        + "; misses under " + (", ".join(
            f"{k} {v}" for k, v in sorted(summary["misses"].items())) or "none"))
    c = summary.get("counters")
    if c is not None:
        lines.append("compile cache requests/hits/misses: "
                     + "/".join(str(c[k]) for k in COUNTERS) + "; by span: "
                     + (", ".join(f"{name} " + "/".join(str(v[k]) for k in COUNTERS)
                                  for name, v in sorted(summary["by_parent"].items()))
                        or "none")
                     + (f"; {summary['dropped']} spans past the cap"
                        if summary["dropped"] else ""))
    return lines
