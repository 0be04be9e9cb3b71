"""CLI: ``python -m heterofl_tpu.staticcheck [--json] [...]``.

Runs the AST lint (jax-free, milliseconds) and then the program audit
(lowers/compiles the flagship program matrix on a CPU mesh).  Exits 0 only
when both fronts are clean; writes the ``STATICCHECK.json`` artifact (CI
keeps it; nothing else reads it).

The CPU pin below MUST run before jax initialises: the audit never claims
an accelerator (on a machine with a chip, one process holds it and this is
not that process), and it needs an 8-device virtual CPU platform for the
slices placement.  The ``--aot`` child inherits the pin.
``heterofl_tpu.staticcheck`` itself stays jax-free so the lint front (and
``--skip-audit``) never boots a backend at all.
"""

from __future__ import annotations

import argparse

import os
import sys
from datetime import datetime, timezone

_REPO = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def _pin_cpu_for_audit() -> None:
    os.environ["JAX_PLATFORMS"] = "cpu"
    flags = os.environ.get("XLA_FLAGS", "")
    if "xla_force_host_platform_device_count" not in flags:
        os.environ["XLA_FLAGS"] = \
            (flags + " --xla_force_host_platform_device_count=8").strip()
    os.environ.setdefault("JAX_ENABLE_X64", "0")


def main(argv=None) -> int:
    parser = argparse.ArgumentParser(
        prog="python -m heterofl_tpu.staticcheck",
        description="jaxpr/HLO program auditor + hot-path lint gate")
    parser.add_argument("--json", action="store_true",
                        help="print the full report as JSON (default: "
                             "findings + one summary line)")
    parser.add_argument("--flagship", action="store_true",
                        help="audit at full CIFAR-10 ResNet-18 widths "
                             "(slower; tightens the FLOP-share tolerance "
                             "to 2%%)")
    parser.add_argument("--skip-audit", action="store_true",
                        help="lint only (never imports jax)")
    parser.add_argument("--list", action="store_true",
                        help="print the program/check matrix (names only, "
                             "nothing is audited) and exit")
    parser.add_argument("--only", metavar="GLOB", default=None,
                        help="audit only programs matching this fnmatch "
                             "glob; cross-program checks (flop budget, "
                             "lattice, key streams, ...) are skipped -- "
                             "incompatible with --diff-baseline/"
                             "--update-baseline")
    parser.add_argument("--lattice-md", action="store_true",
                        help="print the compatibility-lattice markdown "
                             "(the README section is generated from this; "
                             "jax-free) and exit")
    parser.add_argument("--aot-v4128", action="store_true",
                        help="also run the subprocess v4-128 AOT multi-"
                             "host check (ISSUE 17); records into "
                             "config.aot_v4128, tries the TPU topology "
                             "then falls back to a 64-device CPU mesh")
    parser.add_argument("--skip-lint", action="store_true",
                        help="program audit only")
    parser.add_argument("--flop-tol", type=float, default=None,
                        help="override the FLOP-share tolerance")
    parser.add_argument("--seed", type=int, default=0)
    parser.add_argument("--lint-root", default=_REPO,
                        help="tree to lint (default: this repo)")
    parser.add_argument("--out", default=os.path.join(_REPO, "STATICCHECK.json"),
                        help="artifact path (default: <repo>/STATICCHECK.json)")
    parser.add_argument("--no-artifact", action="store_true",
                        help="do not write the artifact file")
    parser.add_argument("--baseline", default=None,
                        help="ratchet baseline path (default: "
                             "<repo>/STATICCHECK_BASELINE.json)")
    parser.add_argument("--diff-baseline", action="store_true",
                        help="diff the fresh audit against the committed "
                             "baseline; exit 2 on any ratchet regression "
                             "(audit/lint failures still exit 1)")
    parser.add_argument("--update-baseline", action="store_true",
                        help="re-pin the baseline from this (green) audit "
                             "after an intentional metric change")
    args = parser.parse_args(argv)
    if args.baseline is None:
        from .ratchet import BASELINE_BASENAME

        args.baseline = os.path.join(_REPO, BASELINE_BASENAME)
    if (args.diff_baseline or args.update_baseline) and args.skip_audit:
        parser.error("--diff-baseline/--update-baseline need the program "
                     "audit (drop --skip-audit)")
    if args.only and (args.diff_baseline or args.update_baseline):
        parser.error("--only audits a subset -- the ratchet baseline "
                     "covers the full matrix (drop --only)")

    if args.lattice_md:
        # jax-free: the lattice replays the validator chain, nothing is
        # traced.  ``--lattice-md > section.md`` regenerates the README's
        # "Compatibility lattice" section.
        from .lattice import lattice_markdown

        print(lattice_markdown())
        return 0

    if args.list:
        _pin_cpu_for_audit()
        from .audit import CROSS_CHECKS, list_targets

        names = list_targets(flagship=args.flagship, seed=args.seed)
        print(f"# {len(names)} programs (audit matrix)")
        for n in names:
            print(f"program {n}")
        print(f"# {len(CROSS_CHECKS)} cross-program checks "
              f"(skipped under --only)")
        for c in CROSS_CHECKS:
            print(f"check   {c}")
        print("check   lint")
        return 0

    from .report import AuditReport
    from .rules import lint_tree, pragma_sweep

    lint_findings = []
    if not args.skip_lint:
        subdirs = ["heterofl_tpu"] if args.lint_root == _REPO else None
        lint_findings = lint_tree(args.lint_root, subdirs=subdirs)
        if subdirs:
            # ISSUE 18 satellite: pragma liveness sweeps the WHOLE repo
            # (tests/, scripts/, ...), not just the scoped package tree
            lint_findings += pragma_sweep(args.lint_root,
                                          exclude=tuple(subdirs))

    if args.skip_audit:
        report = AuditReport()
    else:
        _pin_cpu_for_audit()
        from ..utils.compile_cache import enable_persistent_cache

        enable_persistent_cache()  # amortise the program-matrix compiles
        from .audit import run_audit

        report = run_audit(flagship=args.flagship, flop_tol=args.flop_tol,
                           seed=args.seed, with_aot=args.aot_v4128,
                           only=args.only)
    report.add_lint(lint_findings)
    report.generated_at = datetime.now(timezone.utc).isoformat()
    report.config["argv"] = list(argv) if argv is not None else sys.argv[1:]
    report.config["skipped"] = {"audit": args.skip_audit,
                                "lint": args.skip_lint}

    # baseline ratchet (ISSUE 7): the analytic budgets are ceilings, the
    # committed baseline is the tight line -- diff before the artifact is
    # written so STATICCHECK.json carries the ratchet section
    from .ratchet import diff_reports, load_baseline, write_baseline

    if args.diff_baseline:
        try:
            baseline = load_baseline(args.baseline)
        except (OSError, ValueError) as e:
            report.ratchet = {
                "checked": True, "ok": False,
                "regressions": [{"program": "<baseline>", "metric": "load",
                                 "baseline": None, "current": None,
                                 "tolerance": 0.0,
                                 "message": f"cannot load baseline "
                                            f"{args.baseline}: {e} -- run "
                                            f"--update-baseline on a green "
                                            f"tree and commit the file"}],
                "improvements": [], "new_programs": [],
                "missing_programs": []}
        else:
            report.ratchet = diff_reports(report.to_dict(), baseline)
    if args.update_baseline:
        if not report.ok:
            # refuse the pin but fall through: the failing artifact still
            # gets written and the findings still print, exactly like a
            # plain failing run
            print("staticcheck: refusing to pin a baseline from a FAILING "
                  "audit -- fix the findings first", file=sys.stderr)
        else:
            write_baseline(args.baseline, report.to_dict())

    if not args.no_artifact:
        with open(args.out, "w") as f:
            f.write(report.to_json())
            f.write("\n")

    ratchet_regressed = report.ratchet.get("checked") \
        and not report.ratchet.get("ok")
    if args.json:
        print(report.to_json())
    else:
        for f in report.all_findings():
            print(f)
        for reg in report.ratchet.get("regressions", []):
            print(f"{reg['program']}: [ratchet:{reg['metric']}] "
                  f"{reg['baseline']} -> {reg['current']}: {reg['message']}")
        n_prog = len(report.programs)
        verdict = "OK" if report.ok else "FAILED"
        if report.ok and ratchet_regressed:
            verdict = "RATCHET REGRESSED"
        print(f"staticcheck: {verdict} -- "
              f"{n_prog} programs audited, "
              f"{len(report.all_findings())} finding(s), "
              f"{len(report.ratchet.get('regressions', []))} ratchet "
              f"regression(s)"
              + ("" if args.no_artifact else f"; artifact: {args.out}"))
    if not report.ok:
        return 1
    return 2 if ratchet_regressed else 0


if __name__ == "__main__":
    sys.exit(main())
