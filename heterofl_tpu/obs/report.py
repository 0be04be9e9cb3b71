"""Population-observatory report surface (ISSUE 12).

``python -m heterofl_tpu.obs.report <run-dir-or-ledger.npz>`` renders a
population snapshot from the artifacts a ledger-enabled run leaves behind:

* ``ledger.npz`` (:class:`~.ledger.ClientLedger`): participation coverage
  and Gini, current-staleness quantiles and mass by availability class
  (participation-count quartiles of the seen population -- the honest
  proxy for the availability rate when no trace is on disk), per-level
  loss-EMA quantiles;
* ``events.jsonl`` (optional, the PR 10 trace stream next to it): event
  counts by name plus the watchdog trips, so an aborted run's report leads
  with the evidence, and the set-up table (ISSUE 38): the run's set-up and
  compile spans (:mod:`.spans`) with their seconds, the compilations under
  each and whether the cache served them.  A run traced without a ledger
  (``trace_dir`` alone) gets the events part alone.

``--json`` prints the machine-readable snapshot instead of the table.
Host-side and numpy-only, like the rest of the obs host half -- the
report never imports jax.
"""

from __future__ import annotations

import argparse
import json
import os
import sys
from typing import Any, Dict, List, Optional

from . import spans
from .ledger import ClientLedger


def find_ledger(path: str) -> str:
    """Resolve a run directory (searched recursively for the newest
    ``ledger.npz``) or a direct ``.npz`` path."""
    if os.path.isfile(path):
        return path
    hits = []
    for root, _dirs, files in os.walk(path):
        if "ledger.npz" in files:
            p = os.path.join(root, "ledger.npz")
            hits.append((os.path.getmtime(p), p))
    if not hits:
        raise FileNotFoundError(f"no ledger.npz under {path!r}: run with "
                                f"cfg['ledger']='on' (or point at the file)")
    return max(hits)[1]


def summarize_events(events_path: str) -> Dict[str, Any]:
    """Count events.jsonl records by name; surface the watchdog trips."""
    counts: Dict[str, int] = {}
    watchdog: List[Dict[str, Any]] = []
    folds: Dict[str, str] = {}  # the model's counters: name -> fold (`run-start`)
    seen: Dict[str, List[Any]] = {}  # a declared counter's value, a round each
    setup: List[spans.Span] = []  # every span filed with its id
    with open(events_path) as f:
        for line in f:
            line = line.strip()
            if not line:
                continue
            rec = json.loads(line)
            name = rec.get("name", "?")
            counts[name] = counts.get(name, 0) + 1
            if name == "watchdog":
                watchdog.append(rec.get("args", {}))
            args = rec.get("args", {})
            if rec.get("ph") == "X" and "id" in args:
                setup.append(spans.Span(args["id"], name, rec["t"],
                                        rec["dur_s"], args.get("parent"), args))
            if name == "run-start":
                folds.update(args.get("counters", {}))
            if name == "probes":
                for key in folds:
                    if key in args:
                        seen.setdefault(key, []).append(args[key])
    out = {"path": events_path, "events_by_name": counts,
           "watchdog_trips": watchdog[:16]}
    for key, rounds in seen.items():
        # a model's counters (obs.split_probes), by family (the name's first
        # word): a `sum` summed over the run's rounds, a `ratio` or a `mean`
        # averaged over them
        family, _, short = key.partition("_")
        over = 1 if folds[key] == "sum" else len(rounds)
        out.setdefault(family, {"rounds": len(rounds)})[short] = \
            [sum(col) / over for col in zip(*rounds)] if isinstance(rounds[0], list) \
            else sum(rounds) / over
    if setup:
        out["setup"] = spans.summarize(setup)
    return out


def build_report(ledger_path: str,
                 events_path: Optional[str] = None) -> Dict[str, Any]:
    led = ClientLedger.load(ledger_path)
    rep = {"ledger": ledger_path, **led.snapshot()}
    if events_path is None:
        cand = os.path.join(os.path.dirname(ledger_path), "events.jsonl")
        events_path = cand if os.path.exists(cand) else None
    if events_path is not None:
        rep["events"] = summarize_events(events_path)
    return rep


def _fmt_q(q: Dict[str, float]) -> str:
    return "  ".join(f"{k}={v:g}" for k, v in q.items())


def render_text(rep: Dict[str, Any]) -> str:
    """The human-readable table."""
    p = rep["participation"]
    s = rep["staleness"]
    lines = [
        f"population observatory -- {rep['ledger']}",
        f"  users {rep['num_users']}  levels {rep['levels']}  "
        f"round {rep['round']}  updates {rep['updates']}  "
        f"resident {rep['bytes']} B ({rep['bytes_per_user']} B/user)",
        "participation",
        f"  coverage {p['coverage']:.4f}  gini {p['gini']:.4f}  "
        f"total {p['total']}  max {p['count_max']}  "
        f"{_fmt_q(p['count_quantiles'])}",
        "staleness (rounds since last seen)",
        f"  {_fmt_q(s['now_quantiles'])}  cumulative "
        f"{s['cumulative_total']}",
    ]
    for c in s["by_class"]:
        extra = "" if c.get("stale_mean") is None \
            else f"  mean {c['stale_mean']:g}"
        lines.append(f"    class {c['class']:<10} users {c['users']:<8} "
                     f"stale mass {c['stale_mass']:g}{extra}")
    lines.append("per-level loss EMA")
    for lv in rep["per_level"]:
        q = ("(no observations)" if lv["loss_ema_quantiles"] is None
             else _fmt_q(lv["loss_ema_quantiles"]))
        lines.append(f"    level {lv['level']:<8g} users {lv['users_last']:<8}"
                     f" participations {lv['participations']:<8} {q}")
    lines += render_events(rep.get("events"))
    return "\n".join(lines)


def render_events(ev: Optional[Dict[str, Any]]) -> List[str]:
    """The events part of the table (all of it for a run without a
    ledger)."""
    lines: List[str] = []
    if ev:
        lines.append(f"events -- {ev['path']}")
        lines.append("  " + "  ".join(f"{k}:{v}" for k, v in
                                      sorted(ev["events_by_name"].items())))
        moe = ev.get("moe")
        if moe:
            # (pairs routed, pairs on held experts, held pairs not computed)
            routed, held, dropped = moe["assign"]
            share = held / routed if routed else 0.0
            lines.append(
                f"  expert layers over {moe['rounds']} rounds: (token, expert) "
                f"pairs on held experts {held:g} of {routed:g} "
                f"({100.0 * share:.2f} %), dropped {round(dropped)}")
            lines.append("    tokens per held expert: " + " ".join(
                f"{t:g}" for t in moe["tokens"]))
        loop = ev.get("loop")
        if loop:
            lines.append(
                f"  loop over {loop['rounds']} rounds: expected pass {loop['passes']:.3f}; "
                "exit share a pass " + " ".join(f"{p:.4f}" for p in loop["exit_share"])
                + "; mean negative log-likelihood a pass "
                + " ".join(f"{v:.4f}" for v in loop["pass_nll"]))
        swa = ev.get("swa")
        if swa:
            lines.append(
                f"  sliding layers over {swa['rounds']} rounds: band over causal pairs "
                f"{swa['pairs']:.4f}; key tiles visited of those on or under the diagonal "
                f"{swa['tiles']:.4f}; query tiles on the band kernels {swa['fused']:.2f}")
        if ev["watchdog_trips"]:
            lines.append(f"  WATCHDOG TRIPPED {len(ev['watchdog_trips'])}x: "
                         f"{ev['watchdog_trips'][0]}")
        if ev.get("setup"):
            lines.append("  set-up (seconds by span, the compilations under "
                         "each, the longest compilations)")
            lines += ["    " + l for l in spans.table(ev["setup"])]
    return lines


def main(argv: Optional[List[str]] = None) -> int:
    ap = argparse.ArgumentParser(
        prog="python -m heterofl_tpu.obs.report",
        description="Render a population snapshot from ledger.npz "
                    "(+ events.jsonl)")
    ap.add_argument("path", help="run/trace directory or a ledger.npz path")
    ap.add_argument("--events", default=None,
                    help="events.jsonl path (default: next to the ledger)")
    ap.add_argument("--json", action="store_true",
                    help="print the machine-readable snapshot")
    args = ap.parse_args(argv)
    try:
        ledger = find_ledger(args.path)
    except FileNotFoundError:
        # a run traced without a ledger: its events alone
        events = args.events or os.path.join(args.path, "events.jsonl")
        if not os.path.isfile(events):
            raise
        rep = {"events": summarize_events(events)}
        print(json.dumps(rep) if args.json
              else "\n".join(render_events(rep["events"])))
        return 0
    rep = build_report(ledger, events_path=args.events)
    if args.json:
        print(json.dumps(rep))
    else:
        print(render_text(rep))
    return 0


if __name__ == "__main__":  # pragma: no cover - exercised via main() tests
    sys.exit(main())
