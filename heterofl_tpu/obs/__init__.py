"""Runtime telemetry (ISSUE 10 tentpole): in-program health probes, run
tracing, and a non-finite watchdog.

Once ``superstep_rounds=K`` fuses K federated rounds into one donated XLA
program (PR 2/4), the running system is a black box between fetches: grad
and update norms, per-level participation, the wire-codec residual
magnitude and the buffered-async staleness mass are all computed (or
cheaply derivable) inside the program, yet nothing surfaced them --
``grep isfinite`` over the package returned nothing, and the Round 12/13
instabilities (signsgd long-horizon divergence, the buffered staleness
tax) had to be diagnosed by hand from accuracy trajectories.  This package
makes per-round health statistics first-class (1610.05492 and 2405.20431
treat them as the tuning signal for codec/schedule choices):

* **In-program health probes** (:mod:`.probes`, the jax half): per-round
  scalars -- global grad/update norm, per-level participation, wire-codec
  residual norm, buffered-carry staleness mass, a non-finite leaf counter
  -- computed INSIDE the fused superstep from quantities the scan already
  holds (the post-psum aggregates and the new params carry).  ZERO new
  collectives: every probe is either derived from already-reduced values
  or emitted as a per-device partial that the host finishes at fetch time
  (the probes ride the existing metrics pytree through
  ``PendingMetrics``).  ``telemetry='off'`` (default) builds bit-identical
  programs to the pre-obs engines -- no new outputs, no new arguments.
* **Run tracing** (:mod:`.trace`): a :class:`~.trace.TraceRecorder`
  unifying ``PhaseTimer`` phases, driver events (superstep boundaries,
  checkpoint, eval, prefetch overlap) and ``jax.profiler`` annotations
  into a Chrome-trace-event ``trace.json`` (load it in Perfetto /
  ``chrome://tracing``) plus a schema'd ``events.jsonl`` per run, wired
  through ``entry/common.py`` and ``Logger.emit``.
* **Watchdog** (:mod:`.watchdog`): non-finite counts and a loss-spike
  detector (vs a rolling median) surfaced at fetch boundaries -- loud
  warning by default, configurable abort.
* **Set-up and compile spans** (:mod:`.spans`, ISSUE 38): one process-wide
  record of what happens once a program -- building, staging, the first
  round, every compilation with its program's name and whether the
  persistent cache served it -- always on, filed onto the same timelines.

This module is import-light (numpy only): config validation and the
host-side probe assembly live here; :mod:`.probes` is hot-path jax code
(it joins the staticcheck kernel lint scope), :mod:`.trace` and
:mod:`.watchdog` are host-side like ``sched/__init__``.
"""

from __future__ import annotations

from typing import Any, Dict, List, Optional, Tuple

import numpy as np

#: cfg['telemetry'] values: 'off' (default) keeps every engine program
#: bit-identical to the pre-obs tree; 'on' folds the health probes into
#: the metrics pytree of every fused round; 'hist' (ISSUE 12) additionally
#: folds the fixed-bucket cohort histograms (:mod:`.hist`) in -- still
#: zero new collectives, still the same one-psum/wire budgets
TELEMETRY_MODES = ("off", "on", "hist")

#: watchdog reactions (cfg['watchdog']['action']): 'warn' (default) emits
#: a loud warning + structured obs event, 'abort' raises WatchdogError at
#: the fetch boundary, 'rollback' (ISSUE 15) raises WatchdogRollback --
#: the driver restores the newest verifying checkpoint generation, salts
#: the round key stream and retries with bounded attempts + backoff,
#: escalating to abort when the budget is spent -- 'off' disables the
#: watchdog while keeping probes
WATCHDOG_ACTIONS = ("warn", "abort", "rollback", "off")

#: rollback budget defaults (cfg['watchdog']['max_retries'/'backoff']):
#: attempts before escalating to abort, and the base of the exponential
#: backoff in seconds (attempt n sleeps backoff * 2**(n-1))
DEFAULT_MAX_RETRIES = 3
DEFAULT_BACKOFF = 0.5

#: default loss-spike threshold: loss > factor x rolling median trips
DEFAULT_SPIKE_FACTOR = 3.0

#: default rolling-median window (rounds) of the loss-spike detector
DEFAULT_SPIKE_WINDOW = 8

#: key prefix of probe leaves inside the engines' metrics pytree -- the
#: fetch-side split (``split_probes``) and every assemble path key on it
PROBE_PREFIX = "obs_"

#: the finished per-round probe record's fields (the order is the schema).
#: ``quarantined`` (ISSUE 15) is present exactly when quarantine is on --
#: the count of clients whose update the in-program gate zeroed out.
PROBE_FIELDS = ("update_norm", "grad_norm", "participation", "resid_norm",
                "stale_norm", "nonfinite", "quarantined")

#: the finished cohort-histogram fields of a telemetry='hist' record
#: (ISSUE 12; each a list of bucket counts -- see obs/hist.py for edges)
HIST_FIELDS = ("hist_loss", "hist_steps", "hist_level", "hist_stale")

#: hist leaves derived from REPLICATED values: the host takes device 0's
#: row instead of summing the per-device partials (obs/hist.py emits the
#: staleness-carry histogram identically on every device)
HIST_REPLICATED = ("hist_stale",)

#: cfg['ledger'] values: 'on' maintains the host-side ClientLedger
#: (:mod:`.ledger`) -- O(active) per fetch, never a program change
LEDGER_MODES = ("off", "on")


class WatchdogSpec:
    """Resolved watchdog knobs (one immutable object, the ScheduleSpec
    convention).  ``spike_factor=None`` disables the loss-spike detector
    while keeping the non-finite check.  ``max_retries``/``backoff`` only
    matter under ``action='rollback'`` (ISSUE 15): the recovery budget and
    the exponential-backoff base in seconds."""

    def __init__(self, action: str = "warn",
                 spike_factor: Optional[float] = DEFAULT_SPIKE_FACTOR,
                 window: int = DEFAULT_SPIKE_WINDOW,
                 max_retries: int = DEFAULT_MAX_RETRIES,
                 backoff: float = DEFAULT_BACKOFF):
        self.action = action
        self.spike_factor = spike_factor
        self.window = window
        self.max_retries = max_retries
        self.backoff = backoff


class QuarantineSpec:
    """The resolved client-update quarantine configuration (ISSUE 15):
    engines read ``enabled``/``max_norm`` at construction.  Built by
    :func:`resolve_quarantine_cfg` -- there is no second parser."""

    def __init__(self, enabled: bool = False,
                 max_norm: Optional[float] = None):
        self.enabled = enabled
        self.max_norm = max_norm


def resolve_quarantine_cfg(cfg: Dict[str, Any]) -> QuarantineSpec:
    """Validate ``cfg['quarantine']`` and return the :class:`QuarantineSpec`.

    THE one validator (the PR 6/8/9 convention): an unknown mode or a
    malformed ``max_norm`` fails loudly at config time, never as a silent
    quarantine-off fallback mid-run.  ``'off'``/None = disabled (every
    program bit-identical to pre-quarantine); ``'on'`` = finiteness gate
    only; ``{'max_norm': R}`` additionally quarantines updates whose
    masked L2 norm exceeds ``R`` (R > 0)."""
    raw = cfg.get("quarantine", "off")
    if raw is None or raw == "off":
        return QuarantineSpec()
    if raw == "on":
        spec = QuarantineSpec(enabled=True)
    elif isinstance(raw, dict):
        unknown = set(raw) - {"max_norm"}
        if unknown:
            raise ValueError(f"Not valid quarantine keys: {sorted(unknown)} "
                             f"(max_norm)")
        mn = raw.get("max_norm")
        if mn is not None and (not isinstance(mn, (int, float))
                               or isinstance(mn, bool) or float(mn) <= 0.0):
            raise ValueError(f"Not valid quarantine max_norm: {mn!r} (a "
                             f"positive update-norm bound, or None for the "
                             f"finiteness-only gate)")
        spec = QuarantineSpec(enabled=True,
                              max_norm=None if mn is None else float(mn))
    else:
        raise ValueError(f"Not valid quarantine: {raw!r} ('off', 'on' or a "
                         f"{{'max_norm': R}} dict)")
    # quarantine x engine cross-check (ISSUE 18): promoted from the driver.
    # This validator OWNS the quarantine axis in the staticcheck lattice.
    if (cfg.get("strategy", "masked") or "masked") == "sliced":
        raise ValueError(
            "Not valid quarantine with strategy='sliced': the gate lives "
            "in the mesh-native engines' round cores ('masked' or "
            "'grouped'); the sliced debug twin replays the reference host "
            "loop and has no in-program round core to gate")
    return spec


class TelemetrySpec:
    """The resolved telemetry configuration: engines read ``probes`` /
    ``hist``, the driver reads ``watchdog``/``trace_dir``.  Built by
    :func:`resolve_telemetry_cfg` -- there is no second parser."""

    def __init__(self, probes: bool = False,
                 watchdog: Optional[WatchdogSpec] = None,
                 trace_dir: Optional[str] = None, hist: bool = False):
        self.probes = probes
        self.watchdog = watchdog
        self.trace_dir = trace_dir
        self.hist = hist


class LedgerSpec:
    """The resolved ledger configuration (ISSUE 12): ``enabled`` turns the
    driver's per-fetch :class:`~.ledger.ClientLedger` fold on.  Built by
    :func:`resolve_ledger_cfg` -- there is no second parser."""

    def __init__(self, enabled: bool = False):
        self.enabled = enabled


def resolve_ledger_cfg(cfg: Dict[str, Any]) -> LedgerSpec:
    """Validate ``cfg['ledger']`` and return the :class:`LedgerSpec`.

    THE one validator (the PR 6/8/9 convention): an unknown mode fails
    loudly at config time, never as a silent ledger-off fallback mid-run.
    The strategy/placement cross-checks are promoted from the driver
    (ISSUE 18) -- this validator OWNS the ledger axis in the staticcheck
    lattice."""
    mode = cfg.get("ledger", "off") or "off"
    if mode not in LEDGER_MODES:
        raise ValueError(f"Not valid ledger: {mode!r} "
                         f"(one of {LEDGER_MODES})")
    if mode == "on":
        if (cfg.get("strategy", "masked") or "masked") == "sliced":
            raise ValueError(
                "Not valid ledger='on' with strategy='sliced': the sliced "
                "debug twin replays the reference host loop, whose metrics "
                "never ride the fetch path the ledger folds from -- use a "
                "mesh-native strategy ('masked' or 'grouped')")
        if cfg.get("data_placement") == "sharded":
            raise ValueError(
                "Not valid ledger='on' with data_placement='sharded': the "
                "sharded slot packing re-orders metric rows by owning "
                "device, dropping the schedule-order uid alignment the "
                "O(active) fold consumes -- use replicated (or streaming) "
                "placement")
    return LedgerSpec(enabled=mode == "on")


def resolve_telemetry_cfg(cfg: Dict[str, Any]) -> TelemetrySpec:
    """Validate ``cfg['telemetry']`` / ``cfg['watchdog']`` /
    ``cfg['trace_dir']`` and return the :class:`TelemetrySpec`.

    THE one validator (the PR 6/8/9 convention): unknown modes, keys or
    malformed values fail loudly at config time, never as a silent
    telemetry-off fallback mid-run.  ``telemetry='on'`` enables the
    watchdog at warn defaults; ``cfg['watchdog']`` refines it (or turns it
    off with ``{'action': 'off'}``).  ``trace_dir`` is independent of the
    probes -- run tracing is pure host-side bookkeeping."""
    mode = cfg.get("telemetry", "off") or "off"
    if mode not in TELEMETRY_MODES:
        raise ValueError(f"Not valid telemetry: {mode!r} "
                         f"(one of {TELEMETRY_MODES})")
    raw_wd = cfg.get("watchdog")
    if raw_wd is not None and mode == "off":
        raise ValueError("cfg['watchdog'] needs telemetry='on'/'hist': the "
                         "watchdog feeds on the in-program probes (the "
                         "non-finite counter), which telemetry='off' does "
                         "not compute")
    watchdog: Optional[WatchdogSpec] = None
    if mode != "off":
        wd = dict(raw_wd or {})
        unknown = set(wd) - {"action", "spike_factor", "window",
                             "max_retries", "backoff"}
        if unknown:
            raise ValueError(f"Not valid watchdog keys: {sorted(unknown)} "
                             f"(action/spike_factor/window/max_retries/"
                             f"backoff)")
        action = wd.get("action", "warn") or "warn"
        if action not in WATCHDOG_ACTIONS:
            raise ValueError(f"Not valid watchdog action: {action!r} "
                             f"(one of {WATCHDOG_ACTIONS})")
        sf = wd.get("spike_factor", DEFAULT_SPIKE_FACTOR)
        if sf is not None and (not isinstance(sf, (int, float))
                               or isinstance(sf, bool) or float(sf) <= 1.0):
            raise ValueError(f"Not valid watchdog spike_factor: {sf!r} "
                             f"(a factor > 1 over the rolling median loss, "
                             f"or None to disable the spike detector)")
        window = wd.get("window", DEFAULT_SPIKE_WINDOW)
        if not isinstance(window, int) or isinstance(window, bool) \
                or window < 2:
            raise ValueError(f"Not valid watchdog window: {window!r} "
                             f"(an int >= 2, the rolling-median horizon in "
                             f"rounds)")
        retries = wd.get("max_retries", DEFAULT_MAX_RETRIES)
        if not isinstance(retries, int) or isinstance(retries, bool) \
                or retries < 1:
            raise ValueError(f"Not valid watchdog max_retries: {retries!r} "
                             f"(an int >= 1 rollback attempts before "
                             f"escalating to abort)")
        backoff = wd.get("backoff", DEFAULT_BACKOFF)
        if not isinstance(backoff, (int, float)) or isinstance(backoff, bool) \
                or float(backoff) < 0.0:
            raise ValueError(f"Not valid watchdog backoff: {backoff!r} (a "
                             f"non-negative exponential-backoff base in "
                             f"seconds)")
        if action != "off":
            watchdog = WatchdogSpec(action=action,
                                    spike_factor=None if sf is None
                                    else float(sf),
                                    window=window,
                                    max_retries=retries,
                                    backoff=float(backoff))
    trace_dir = cfg.get("trace_dir")
    if trace_dir is not None and not isinstance(trace_dir, str):
        raise ValueError(f"Not valid trace_dir: {trace_dir!r} (a directory "
                         f"path for trace.json + events.jsonl, or None)")
    # telemetry x engine cross-checks (ISSUE 18): promoted from the driver
    # so an unprobeable telemetry config refuses at config resolution.
    # This validator OWNS the telemetry axis in the staticcheck lattice.
    if mode != "off":
        strategy = cfg.get("strategy", "masked") or "masked"
        if strategy == "sliced":
            raise ValueError(
                f"Not valid telemetry={mode!r} with strategy='sliced': the "
                f"sliced debug twin replays the reference host loop and "
                f"has no in-program round core to probe -- use a "
                f"mesh-native strategy ('masked' or 'grouped')")
        if strategy == "grouped" \
                and int(cfg.get("superstep_rounds", 1) or 1) <= 1 \
                and (cfg.get("client_store", "eager") or "eager") != "stream":
            raise ValueError(
                f"Not valid telemetry={mode!r} with strategy='grouped' at "
                f"superstep_rounds<=1 and client_store='eager': the K=1 "
                f"path splits the round across L+1 host-orchestrated "
                f"programs with no shared round core to probe -- telemetry "
                f"needs the fused superstep path (superstep_rounds>1) or "
                f"client_store='stream'")
    return TelemetrySpec(probes=mode != "off", watchdog=watchdog,
                         trace_dir=trace_dir, hist=mode == "hist")


def split_probes(ms: Dict[str, Any], n_dev: int, layout: str = "flat",
                 counters: Optional[Dict[str, Any]] = None,
                 ) -> Tuple[Dict[str, Any], Optional[List[Dict[str, Any]]]]:
    """Pop the ``obs_*`` probe leaves out of a FETCHED metrics dict and
    finish them into per-round probe records.

    The engines emit every probe as a small per-device row that the
    shard_map out-spec concatenates over the clients axis; this host half
    undoes the concat and applies each probe's finishing rule -- replicated
    scalars (update/grad/stale norms, the non-finite counter) take device
    0's copy, per-device PARTIALS (per-level participation counts, the
    residual sum-of-squares) sum over devices, and the ``_sq`` leaves take
    the final sqrt.  ``layout``: ``'flat'`` = device-major concat on the
    last axis (masked engine, grouped slices); ``'span'`` = device axis
    LAST (grouped span, whose metric leaves are ``[k, L, slots]``).
    ``counters``: the model's own counters as it declares them
    (``meta['counters']``: name -> (shape, fold)), each per-device sums over
    that device's valid slots, steps and layers, finished by its fold:
    ``sum`` = the sums as they are; ``ratio`` = a (numerator, denominator)
    pair divided; ``mean`` = sums with their count last, each over the count.
    Returns ``(metrics-without-probes, [per-round records] or None)``."""
    keys = [k for k in ms if k.startswith(PROBE_PREFIX)]
    if not keys:
        return ms, None
    clean = {k: v for k, v in ms.items() if not k.startswith(PROBE_PREFIX)}
    canon: Dict[str, np.ndarray] = {}
    for name in keys:
        v = np.asarray(ms[name])
        if layout == "span":
            # [k, X, n_dev] -> [k, n_dev, X]
            canon[name] = np.moveaxis(v, -1, 1)
        else:
            if v.ndim == 1:  # the K=1 train_round path: one implicit round
                v = v[None]
            canon[name] = v.reshape(v.shape[0], n_dev, -1)
    k_rounds = next(iter(canon.values())).shape[0]
    folds = {name: fold for name, (_, fold) in (counters or {}).items()}
    rounds: List[Dict[str, Any]] = []
    for r in range(k_rounds):
        rec: Dict[str, Any] = {}
        for name, c in canon.items():
            x = c[r]  # [n_dev, X]
            base = name[len(PROBE_PREFIX):]
            if base == "part":
                rec["participation"] = [float(p) for p in x.sum(axis=0)]
            elif base.startswith("hist_"):
                # cohort histograms (ISSUE 12): per-device bucket-count
                # partials sum across devices; the replicated ones take
                # device 0's row (obs/hist.py emits them identically)
                row = x[0] if base in HIST_REPLICATED else x.sum(axis=0)
                rec[base] = [float(c) for c in row]
            elif base == "resid_sq":
                rec["resid_norm"] = float(np.sqrt(x.sum()))
            elif base == "quarantine":
                # quarantined-client count (ISSUE 15): per-device partials
                # (each device counts its own gated slots) sum across
                # devices -- and across levels on the grouped span layout
                rec["quarantined"] = int(round(float(x.sum())))
            elif base in folds:
                *nums, den = total = [float(c) for c in x.sum(axis=0)]
                if folds[base] == "sum":
                    rec[base] = total
                elif folds[base] == "ratio":
                    rec[base] = nums[0] / den if den else 0.0
                elif folds[base] == "mean":
                    rec[base] = [n / den if den else 0.0 for n in nums]
                else:
                    raise ValueError(f"Not valid fold of the counter {base!r}: "
                                     f"{folds[base]!r} ('sum' | 'ratio' | 'mean')")
            elif base == "nonfinite":
                rec["nonfinite"] = int(x[0, 0])
            elif base.endswith("_sq"):
                rec[base[:-3] + "_norm"] = float(np.sqrt(x[0, 0]))
            else:  # pragma: no cover - future probes default to replicated
                rec[base] = float(x[0, 0])
        if "moe_assign" in rec:
            # (pairs routed, pairs on held experts, held pairs not computed)
            routed, held, lost = rec["moe_assign"]
            rec["moe_held_share"] = held / routed if routed else 0.0
            rec["moe_dropped"] = int(round(lost))
        if "moe_compact" in rec:
            # (layer applications that took the compact dispatch, applications)
            took, layers = rec["moe_compact"]
            rec["moe_compact_share"] = took / layers if layers else 0.0
        rounds.append(rec)
    return clean, rounds
