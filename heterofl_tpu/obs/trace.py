"""Run tracing (ISSUE 10): one Chrome-trace + events-JSONL recorder per run.

The driver already times its phases (``PhaseTimer``: sample / stage /
dispatch / fetch) and jax can annotate device traces
(``jax.profiler``), but the three clocks never met in one artifact: a
stall was a number in a phase table, not a visible gap on a timeline.
:class:`TraceRecorder` unifies them:

* every ``PhaseTimer`` phase becomes a complete ("X") trace event (the
  timer calls :meth:`TraceRecorder.complete` when a recorder is attached
  to its ``trace`` attribute);
* driver events -- superstep boundaries, checkpoint writes, eval windows,
  cohort prefetch -- are recorded via :meth:`span` / :meth:`instant`, and
  ``span`` additionally enters a ``jax.profiler.TraceAnnotation`` so a
  simultaneously-captured device profile (``cfg['profile_dir']``) carries
  the same labels;
* ``close()`` writes ``trace.json`` in the Chrome trace-event format
  (open in Perfetto or ``chrome://tracing``) and every event ALSO streams
  to ``events.jsonl`` as it happens -- one schema'd JSON object per line
  (:data:`EVENT_FIELDS`, checked by :func:`validate_event`), so a killed
  run still leaves its timeline on disk.

Host-side only (stdlib + lazy jax import for the annotation); the traced
programs are never touched -- recording is pure driver bookkeeping.

The DEVICE side of the same picture is the scope vocabulary below
(ISSUE 26): :func:`scope` enters ``jax.named_scope`` with one of
:data:`SCOPES`, so every instruction of the round program carries the path
of the part it belongs to in its ``op_name`` metadata
(``.../round/local_train/.../jvp(step/model)/conv/conv_general_dilated``)
and a ``cfg['profile_dir']`` device trace can be split by part.  A scope is
trace-time metadata: no run-time cost, no switch.
"""

from __future__ import annotations

import functools
import json
import os
import time
from contextlib import contextmanager, nullcontext
from typing import Any, Dict, Optional

#: Device-side scope vocabulary: every name the programs enter with
#: :func:`scope`.  Names are ``op_name`` path components and nest as the
#: program nests them: ``round/local_train`` encloses the scan of ``step/*``,
#: ``step/model`` (entered INSIDE the differentiated function, so autodiff
#: marks its forward ``jvp(step/model)`` and its backward
#: ``transpose(jvp(step/model))``) encloses the layer leaves,
#: ``round/aggregate`` encloses ``psum``.
#: benchmark/scope_reduce.py reads device time by these names (PERF.md
#: section 3 lists which metric reads which).
#: Unentered since PR 30: ``step/unflatten``, ``update/flatten``,
#: ``update/pack``, ``update/kernel``, ``update/unpack`` and the kernel name
#: ``fused_sgd`` below belonged to the flat carry and its fused update, which
#: are deleted; no program enters them.  They stay letter for letter because
#: benchmark/tests/test_scope_reduce.py holds benchmark/scope_reduce.py's
#: ``SCOPES`` / ``KERNELS`` equal to these: the ``benchmark`` PR of PERF.md
#: section 7 drops them on both sides (and bumps :data:`SCOPE_VERSION`; this
#: PR moved no scope a program enters, so it owed no bump).
SCOPES = (
    "round/gather", "round/local_train", "round/aggregate", "psum",
    "step/batch", "augment", "step/unflatten", "step/model", "step/update",
    "conv", "linear", "embed", "norm", "attn", "loss",
    "update/flatten", "update/pack", "update/kernel", "update/unpack",
    "eval/sbn", "eval/users", "eval/global",
)

#: What ISSUE 28 added to the vocabulary: latent attention and expert layers
#: (models/kanana2.py) and the chunked cohort.  ``mla`` = the projections and
#: the latent norm around ``attn`` (which stays the score / softmax / value
#: part); ``moe/dispatch`` = grouping the (token, expert) pairs, the gathers
#: and the weighted combine around ``moe/experts``; ``round/chunk`` = one
#: chunk of the cohort under ``cfg['round_chunk']``.  A tuple of its own
#: because the accepted benchmark mirrors :data:`SCOPES` name for name
#: (benchmark/scope_reduce.py, which a PR that adds a cell may not edit) and
#: reads these through benchmark/scope_reduce_moe.py; :func:`scope` takes
#: both.
EXTRA_SCOPES = (
    "mla", "rope", "moe/router", "moe/dispatch", "moe/experts", "moe/shared",
    "round/chunk",
)

#: What ISSUE 32 added: the two mixers of models/lfm2.py.  ``shortconv`` = a
#: conv mixer whole (its two projections file under ``shortconv/linear``),
#: ``shortconv/gate`` = the two elementwise gates and the depthwise taps
#: between the projections (``ops.layers.short_conv``); ``gqa`` = grouped-query
#: attention's projections and per-head norms around ``rope`` and ``attn``,
#: as ``mla`` is for latent attention.  A third tuple because
#: benchmark/scope_reduce_moe.py mirrors :data:`EXTRA_SCOPES` name for name
#: as benchmark/scope_reduce.py mirrors :data:`SCOPES`; read through
#: benchmark/scope_reduce_lfm2.py.
MIXER_SCOPES = ("shortconv", "shortconv/gate", "gqa")

#: What ISSUE 35 added: the learned sparse-attention indexer of
#: models/keye.py.  ``sparse/index`` = the indexer's three projections, its
#: key head's LayerNorm and turn, and the per-block ``relu``-weighted score;
#: ``sparse/select`` = the exact top-k of a score block and the mask built
#: from it (``ops.layers.select_keys``).  The attention they select for stays
#: under ``gqa`` / ``rope`` / ``attn``.  A fourth tuple for the reason
#: :data:`MIXER_SCOPES` is one; read through benchmark/scope_reduce_keye.py.
SPARSE_SCOPES = ("sparse/index", "sparse/select")

#: What ISSUE 40 added: the loop of models/ouro.py, whose layer stack runs
#: ``total_ut_steps`` times on shared weights.  ``loop/pass`` = one pass of
#: the stack (its attention stays under ``gqa`` / ``rope`` / ``attn`` inside
#: it, its norms and products under ``norm`` / ``linear``); ``loop/head`` =
#: the head and the cross entropy of every pass's read-out, block by block
#: (``ops.layers.pass_token_nll``); ``loop/exit`` = the final norm after a
#: pass, the exit gate, the exit distribution, the mixing of the passes'
#: losses under it and its entropy.  A fifth tuple for the reason
#: :data:`MIXER_SCOPES` is one; read through benchmark/scope_reduce_ouro.py.
LOOP_SCOPES = ("loop/pass", "loop/head", "loop/exit")

#: What ISSUE 42 added: the sliding-window layers of models/laguna.py.  ``swa``
#: = a sliding layer's score / softmax / value part, kernel pair or block loop
#: (``ops.layers.sliding_gq_attention``), as ``attn`` stays the full layers';
#: the projections, the output gate and both rotary turns stay under ``gqa`` /
#: ``rope``, the experts under ``moe/*``.  A sixth tuple for the reason
#: :data:`MIXER_SCOPES` is one; read through benchmark/scope_reduce_laguna.py.
WINDOW_SCOPES = ("swa",)

#: What ISSUE 46 added: the state-space mixer of models/nemotron_h.py.  ``ssm``
#: = a Mamba-2 mixer whole (its projections file under ``ssm/linear``),
#: ``ssm/conv`` = the depthwise causal convolution, its bias and ``silu`` over
#: the ``x``, ``B`` and ``C`` channels, ``ssm/scan`` = the chunked selective
#: scan (``ops.layers.ssm_chunked_scan``: the products inside a chunk, the
#: chunks' states and the scan that carries them), ``ssm/norm`` = the gated
#: group RMSNorm before the output projection.  The family's attention layer
#: stays under ``gqa`` / ``attn``, its experts under ``moe/*``.  A seventh
#: tuple for the reason :data:`MIXER_SCOPES` is one; read through
#: benchmark/scope_reduce_nemotron_h.py.  (No bump of :data:`SCOPE_VERSION`:
#: no program that a cache can hold entered or left a scope, and the new
#: family's was never cached.)
SSM_SCOPES = ("ssm", "ssm/conv", "ssm/scan", "ssm/norm")

#: What ISSUE 50 added: the two mixers of models/phi4flash.py that no older
#: scope names.  ``gmu`` = a gated memory unit whole (``(m * silu(h W1)) W2``
#: on another layer's scan output; its products file under ``gmu/linear``),
#: ``diff`` = differential attention's combine (the two softmaxes'
#: difference, the RMSNorm over a pair's value dims and the constant).  The
#: family's Mamba-1 mixer enters ``ssm``, ``ssm/conv`` and ``ssm/scan`` (it has
#: no ``ssm/norm``), its attention's projections ``gqa`` and its softmaxes
#: ``attn`` (``swa`` under a window).  An eighth tuple for the reason
#: :data:`MIXER_SCOPES` is one; read through
#: benchmark/scope_reduce_phi4flash.py.  (No bump of :data:`SCOPE_VERSION`, for
#: PR 46's reason: no program that a cache can hold entered or left a scope --
#: the eight accepted cells' lowered round programs hash equal to the parent's
#: -- and the new family's was never cached; a bump would compile every
#: program of every cell and of the test gate cold once for nothing.)
SAMBAY_SCOPES = ("gmu", "diff")

#: Version of the vocabulary AND of where it is entered.  jax keeps metadata
#: out of the persistent compile cache's key, so a program whose only change
#: is a scope would load the executable cached before the change, without
#: the new names, silently; ``utils.compile_cache`` folds this number into
#: the key.  Bump it with every change to :data:`SCOPES` or to where a scope
#: is entered (one cold compile per program, once).
SCOPE_VERSION = 7

#: ``name=`` of every ``pallas_call`` (the kernel's device events carry it)
KERNELS = ("fused_sgd", "masked_bn_fwd", "masked_bn_bwd", "int8_pack")

#: The kernels ISSUES 29, 34, 36 and 42 added (ops/pallas_attention.py, under
#: ``attn``, the band pair under ``swa`` too): a tuple of its own as :data:`EXTRA_SCOPES` is one (the benchmark
#: mirrors :data:`KERNELS`); its reader files their time under ``attn``.
EXTRA_KERNELS = ("latent_attn_fwd", "latent_attn_bwd", "gq_attn_fwd", "gq_attn_bwd",
                 "sel_attn_fwd", "sel_attn_bwd", "band_attn_fwd", "band_attn_bwd")


def _known(name: str) -> str:
    if name not in SCOPES + EXTRA_SCOPES + MIXER_SCOPES + SPARSE_SCOPES + LOOP_SCOPES \
            + WINDOW_SCOPES + SSM_SCOPES + SAMBAY_SCOPES:
        raise ValueError(f"Not valid scope: {name!r} (obs.trace.SCOPES)")
    return name


def scope(name: str):
    """``jax.named_scope(name)`` for a name of :data:`SCOPES` (a typo
    raises at trace time instead of filing device time under a name no
    metric reads)."""
    import jax

    return jax.named_scope(_known(name))


def scoped(name: str):
    """Decorator form of :func:`scope`: the whole function runs under the
    scope, entered anew at each call (each trace)."""
    _known(name)

    def deco(fn):
        @functools.wraps(fn)
        def wrapped(*args, **kwargs):
            with scope(name):
                return fn(*args, **kwargs)
        return wrapped
    return deco


#: The kernels ISSUE 47 added (ops/pallas_ssm.py, under ``ssm/scan``; the
#: readers file their time under the scope).  A tuple of its own, and down
#: here, because :func:`scoped` above sits on every Mosaic kernel's call stack,
#: which is part of its compile-cache key: a line added above it would compile
#: every kernel cell cold once.
SSM_KERNELS = ("ssm_scan_fwd", "ssm_scan_bwd")


#: events.jsonl schema, version 1: required fields -> type.  ``dur_s`` is
#: present exactly on complete ("X") events; ``args`` is a flat JSON
#: object of event-specific facts.
EVENT_VERSION = 1
EVENT_FIELDS = {"v": int, "t": float, "name": str, "cat": str, "ph": str,
                "args": dict}
EVENT_PHASES = ("i", "X")


def validate_event(rec: Dict[str, Any]) -> Dict[str, Any]:
    """Validate one events.jsonl record against the schema; returns the
    record (so loaders can ``[validate_event(json.loads(l)) ...]``) or
    raises ``ValueError`` naming the violation."""
    if not isinstance(rec, dict):
        raise ValueError(f"event record must be an object, got {type(rec)}")
    for field, typ in EVENT_FIELDS.items():
        if field not in rec:
            raise ValueError(f"event record misses required field {field!r}: "
                             f"{rec}")
        if typ is float:
            if not isinstance(rec[field], (int, float)) \
                    or isinstance(rec[field], bool):
                raise ValueError(f"event field {field!r} must be a number, "
                                 f"got {rec[field]!r}")
        elif not isinstance(rec[field], typ):
            raise ValueError(f"event field {field!r} must be {typ.__name__}, "
                             f"got {rec[field]!r}")
    if rec["v"] != EVENT_VERSION:
        raise ValueError(f"event version {rec['v']} != {EVENT_VERSION}")
    if rec["ph"] not in EVENT_PHASES:
        raise ValueError(f"event ph {rec['ph']!r} not in {EVENT_PHASES}")
    if rec["ph"] == "X":
        dur = rec.get("dur_s")
        if not isinstance(dur, (int, float)) or isinstance(dur, bool):
            raise ValueError(f"complete event needs a numeric dur_s: {rec}")
    extra = set(rec) - set(EVENT_FIELDS) - {"dur_s"}
    if extra:
        raise ValueError(f"unknown event fields {sorted(extra)}: {rec}")
    return rec


def _jax_annotation(name: str):
    """A ``jax.profiler.TraceAnnotation`` when jax is importable (it always
    is in the driver), else a no-op -- the recorder itself must work in
    jax-free host tooling/tests."""
    try:
        from jax.profiler import TraceAnnotation

        return TraceAnnotation(name)
    except Exception:  # pragma: no cover - jax is present everywhere we run
        return nullcontext()


class TraceRecorder:
    """One run's trace: collects events in memory for ``trace.json`` and
    streams them to ``events.jsonl`` as they happen.

    Timestamps: the Chrome ``ts``/``dur`` fields are microseconds on the
    ``time.perf_counter`` clock relative to recorder construction (the
    same clock ``PhaseTimer`` uses, so attached phases line up exactly);
    the JSONL ``t`` field is absolute wall-clock seconds for cross-run
    correlation."""

    def __init__(self, out_dir: str):
        os.makedirs(out_dir, exist_ok=True)
        self.out_dir = out_dir
        self.trace_path = os.path.join(out_dir, "trace.json")
        self.events_path = os.path.join(out_dir, "events.jsonl")
        self._events = []
        self._jsonl = open(self.events_path, "w")
        self._t0 = time.perf_counter()
        self._t0_wall = time.time()
        self.closed = False

    # -- recording -----------------------------------------------------

    def _push(self, name: str, cat: str, ph: str, t_perf: float,
              dur: Optional[float], args: Optional[Dict[str, Any]]) -> None:
        if self.closed:
            return
        args = dict(args or {})
        ev = {"name": name, "cat": cat, "ph": ph, "pid": 0, "tid": 0,
              "ts": round((t_perf - self._t0) * 1e6, 1), "args": args}
        if ph == "X":
            ev["dur"] = round((dur or 0.0) * 1e6, 1)
        self._events.append(ev)
        rec = {"v": EVENT_VERSION,
               "t": self._t0_wall + (t_perf - self._t0),
               "name": name, "cat": cat, "ph": ph, "args": args}
        if ph == "X":
            rec["dur_s"] = round(dur or 0.0, 6)
        self._jsonl.write(json.dumps(validate_event(rec)) + "\n")
        self._jsonl.flush()

    def instant(self, name: str, cat: str = "driver",
                args: Optional[Dict[str, Any]] = None) -> None:
        """A point event (watchdog trips, probe snapshots, run markers)."""
        self._push(name, cat, "i", time.perf_counter(), None, args)

    def complete(self, name: str, t0: float, dur: float, cat: str = "phase",
                 args: Optional[Dict[str, Any]] = None) -> None:
        """A finished interval with an explicit ``perf_counter`` start --
        the ``PhaseTimer`` hook (the timer already measured the phase, the
        recorder just files it)."""
        self._push(name, cat, "X", t0, dur, args)

    @contextmanager
    def span(self, name: str, cat: str = "driver",
             args: Optional[Dict[str, Any]] = None):
        """Record an interval around a block AND enter the matching
        ``jax.profiler.TraceAnnotation`` so device-side profiles captured
        in parallel carry the same label."""
        t0 = time.perf_counter()
        try:
            with _jax_annotation(name):
                yield
        finally:
            self.complete(name, t0, time.perf_counter() - t0, cat=cat,
                          args=args)

    # -- finish --------------------------------------------------------

    def _write_trace(self) -> None:
        """Flush + fsync the JSONL stream and write ``trace.json`` (fsync'd)
        from the events recorded so far."""
        self._jsonl.flush()
        os.fsync(self._jsonl.fileno())
        with open(self.trace_path, "w") as f:
            json.dump({"traceEvents": self._events,
                       "displayTimeUnit": "ms",
                       "metadata": {"clock": "perf_counter",
                                    "t0_wall": self._t0_wall}}, f)
            f.write("\n")
            f.flush()
            os.fsync(f.fileno())

    def sync(self) -> str:
        """Flush + fsync the artifacts WITHOUT closing the recorder: the
        rollback path's durability twin of :meth:`close` (ISSUE 15
        satellite -- the abort path closes, but a rollback continues the
        run, and each recovery attempt must still leave the trip evidence
        on disk: events.jsonl fsync'd with the trip instant as its last
        line, trace.json a point-in-time snapshot).  Returns the trace
        path; no-op after close."""
        if not self.closed:
            self._write_trace()
        return self.trace_path

    def close(self) -> str:
        """Write ``trace.json`` and close the JSONL stream; returns the
        trace path.  Idempotent (a driver finally-block and an explicit
        close may both run).

        Durability (ISSUE 12 satellite): both artifacts are fsync'd --
        close() runs on the abort path BEFORE a ``WatchdogError``
        propagates, and the buffered tail it would otherwise lose IS the
        abort evidence (the watchdog instant must be the last event on
        disk after a crash)."""
        if self.closed:
            return self.trace_path
        self.closed = True
        self._write_trace()
        self._jsonl.close()
        return self.trace_path


def recorder_from(out_dir: str, t_perf: float) -> TraceRecorder:
    """A recorder whose timeline begins at ``t_perf``, a ``perf_counter``
    reading taken before it was made: a run's recorder attaches when the
    run begins and is then handed the set-up spans of the experiment's
    construction (ISSUE 38, :mod:`.spans`), which must not land left of
    ``ts`` 0 in ``trace.json``.  The paired wall reading moves with it, so
    ``events.jsonl``'s ``t`` is unchanged."""
    rec = TraceRecorder(out_dir)
    rec._t0_wall += t_perf - rec._t0
    rec._t0 = t_perf
    return rec
