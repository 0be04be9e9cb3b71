"""Baseline ratchet (ISSUE 7 tentpole): every audited metric becomes
diffable -- and non-regressable -- against a committed baseline.

The analytic budgets in :mod:`.wire` and :mod:`.memory` are ceilings; the
ratchet is the tight line.  ``STATICCHECK_BASELINE.json`` (repo root,
committed) pins the per-program metric view of a known-good audit:
collective counts and wire bytes (exact -- they are pure functions of
shapes), donation coverage (exact), scan-body fusion/instruction counts
and memory bytes and FLOPs (small relative headroom for compiler/platform
variance).  ``python -m heterofl_tpu.staticcheck --diff-baseline``
structurally diffs a fresh audit against it and exits 2 on any regression
(1 stays the audit/lint failure code); ``--update-baseline`` re-pins after
an intentional change.

jax-free: the diff works on report dicts, so CI and tests can exercise it
without lowering anything.
"""

from __future__ import annotations

import json
from typing import Any, Dict, List, Optional, Sequence, Tuple

BASELINE_BASENAME = "STATICCHECK_BASELINE.json"

#: per-program metric table: (label, path into the serialised
#: ProgramReport, relative headroom, mode).  ``up_bad``: growth beyond the
#: headroom is a regression, shrinkage an improvement; ``change_bad``: any
#: drift regresses (donation coverage has one right answer).  Exact (0.0)
#: headroom for everything that is a pure function of program shapes;
#: small headroom where codegen/platform variance moves the number.
PROGRAM_METRICS: Tuple[Tuple[str, Tuple[str, ...], float, str], ...] = (
    ("psum_clients", ("psum_clients",), 0.0, "up_bad"),
    ("psum_eval", ("psum_eval",), 0.0, "up_bad"),
    ("all_gather", ("all_gather",), 0.0, "up_bad"),
    ("donated", ("donated",), 0.0, "change_bad"),
    ("aliased", ("aliased",), 0.0, "change_bad"),
    ("wire.train_bytes_per_round",
     ("wire", "train_bytes_per_round"), 0.0, "up_bad"),
    ("wire.eval_bytes_total", ("wire", "eval_bytes_total"), 0.0, "up_bad"),
    ("wire.other_bytes", ("wire", "other_bytes"), 0.0, "up_bad"),
    ("wire.dcn_bytes", ("wire", "dcn_bytes"), 0.0, "up_bad"),
    ("reshards.total", ("reshards", "total"), 0.0, "up_bad"),
    ("step_body.fusions", ("step_body", "fusions"), 0.15, "up_bad"),
    ("step_body.instructions", ("step_body", "instructions"), 0.15, "up_bad"),
    ("memory.temp_size_in_bytes",
     ("memory", "temp_size_in_bytes"), 0.25, "up_bad"),
    ("memory.argument_size_in_bytes",
     ("memory", "argument_size_in_bytes"), 0.10, "up_bad"),
    ("memory.output_size_in_bytes",
     ("memory", "output_size_in_bytes"), 0.25, "up_bad"),
    ("flops", ("flops",), 0.10, "up_bad"),
)

#: audit-config keys that must match for a diff to be meaningful at all
CONFIG_KEYS = ("flagship", "data_name", "model_name", "num_users", "levels",
               "mesh")

#: cross-program coverage counters pinned by the baseline (ISSUE 18):
#: the declared config lattice and key-stream provenance graph must
#: never silently SHRINK -- dropping an axis value, a registry row, or
#: a declared fold_in site without re-pinning is a ratchet regression
#: (growth is recorded as an improvement).  Finding-grade properties
#: (unreached points, salt collisions) fail the audit itself and need
#: no headroom here.
COVERAGE_METRICS: Tuple[Tuple[str, Tuple[str, ...]], ...] = (
    ("lattice.points", ("lattice", "points")),
    ("lattice.refusal_rules", ("lattice", "refusal_rules")),
    ("key_streams.fold_in_sites", ("key_streams", "fold_in_sites")),
    ("key_streams.registry_rows", ("key_streams", "registry_rows")),
)


def _get(d: Optional[Dict[str, Any]], path: Sequence[str]):
    for k in path:
        if not isinstance(d, dict) or k not in d:
            return None
        d = d[k]
    return d


def baseline_view(report_dict: Dict[str, Any]) -> Dict[str, Any]:
    """The committed shape: config subset + per-program metric values.
    Stored instead of the full report so baseline diffs in review stay
    readable (one line per metric, no HLO body names or provenance)."""
    programs = {}
    for name, prog in sorted((report_dict.get("programs") or {}).items()):
        programs[name] = {label: _get(prog, path)
                          for label, path, _tol, _mode in PROGRAM_METRICS}
    coverage = {}
    for label, path in COVERAGE_METRICS:
        v = _get(report_dict, path)
        coverage[label] = len(v) if isinstance(v, list) else v
    return {
        "version": 2,
        "generated_at": report_dict.get("generated_at"),
        "config": {k: (report_dict.get("config") or {}).get(k)
                   for k in CONFIG_KEYS},
        "programs": programs,
        "coverage": coverage,
    }


def diff_reports(current_dict: Dict[str, Any],
                 baseline: Dict[str, Any]) -> Dict[str, Any]:
    """Structural diff of a fresh report against a committed baseline view.

    Returns the ``ratchet`` section: ``ok`` is False on any regression --
    a metric past its headroom, a metric that went dark (None where the
    baseline had a number), a baseline program missing from the fresh
    audit, or an incomparable audit config.  Improvements (metrics that
    shrank) and brand-new programs are recorded, never failed: the ratchet
    only tightens."""
    out: Dict[str, Any] = {"checked": True, "ok": True,
                           "baseline_generated_at": baseline.get("generated_at"),
                           "regressions": [], "improvements": [],
                           "new_programs": [], "missing_programs": []}

    def regress(program, metric, base, cur, tol, msg):
        out["ok"] = False
        out["regressions"].append({
            "program": program, "metric": metric, "baseline": base,
            "current": cur, "tolerance": tol, "message": msg})

    cur_cfg = {k: (current_dict.get("config") or {}).get(k)
               for k in CONFIG_KEYS}
    base_cfg = baseline.get("config") or {}
    if cur_cfg != base_cfg:
        regress("<config>", "config", base_cfg, cur_cfg, 0.0,
                "audit config differs from the baseline's; the diff is "
                "apples-to-oranges -- re-pin with --update-baseline if the "
                "config change is intentional")
        return out

    cur_full = baseline_view(current_dict)
    base_cov = baseline.get("coverage") or {}
    for label, _path in COVERAGE_METRICS:
        base, cur = base_cov.get(label), cur_full["coverage"].get(label)
        if base is None:
            continue  # counter not pinned by this baseline
        if cur is None:
            regress("<coverage>", label, base, None, 0.0,
                    "coverage counter recorded in the baseline is absent "
                    "from the fresh audit (the measurement went dark)")
        elif cur < base:
            regress("<coverage>", label, base, cur, 0.0,
                    "declared coverage shrank below the pinned baseline -- "
                    "re-pin with --update-baseline if the removal is "
                    "intentional")
        elif cur > base:
            out["improvements"].append(
                {"program": "<coverage>", "metric": label,
                 "baseline": base, "current": cur})

    cur_view = cur_full["programs"]
    base_progs = baseline.get("programs") or {}
    for name in sorted(set(base_progs) - set(cur_view)):
        out["ok"] = False
        out["missing_programs"].append(name)
        regress(name, "<program>", "audited", "absent", 0.0,
                "program audited in the baseline is missing from the fresh "
                "audit: the matrix shrank")
    out["new_programs"] = sorted(set(cur_view) - set(base_progs))

    for name in sorted(set(base_progs) & set(cur_view)):
        base_m, cur_m = base_progs[name], cur_view[name]
        for label, _path, tol, mode in PROGRAM_METRICS:
            base, cur = base_m.get(label), cur_m.get(label)
            if base is None:
                continue  # metric not pinned by this baseline
            if cur is None:
                regress(name, label, base, None, tol,
                        "metric recorded in the baseline is absent from the "
                        "fresh audit (the measurement went dark)")
                continue
            if mode == "change_bad":
                if cur != base:
                    regress(name, label, base, cur, 0.0,
                            "exact metric drifted")
                continue
            limit = base * (1.0 + tol)
            if cur > limit:
                regress(name, label, base, cur, tol,
                        f"grew past the baseline by more than "
                        f"{tol:.0%} headroom" if tol else
                        "grew past the exact baseline")
            elif cur < base:
                out["improvements"].append(
                    {"program": name, "metric": label, "baseline": base,
                     "current": cur})
    return out


def load_baseline(path: str) -> Dict[str, Any]:
    with open(path) as f:
        return json.load(f)


def write_baseline(path: str, report_dict: Dict[str, Any]) -> None:
    with open(path, "w") as f:
        json.dump(baseline_view(report_dict), f, indent=2, sort_keys=True)
        f.write("\n")
