"""Finding/report containers shared by the lint and audit fronts.

Kept jax-free: the lint front and the CLI's report plumbing must import
without booting a JAX backend (the CLI pins the platform to cpu before
jax loads).
"""

from __future__ import annotations

import json
from dataclasses import asdict, dataclass, field
from typing import Any, Dict, List, Optional


@dataclass
class Finding:
    """One violation.  ``where`` is ``path:line`` for lint findings and the
    program name (plus op provenance when known) for audit findings."""

    rule: str
    where: str
    message: str

    def __str__(self) -> str:  # `path:line: [rule] message` -- grep-friendly
        return f"{self.where}: [{self.rule}] {self.message}"


@dataclass
class ProgramReport:
    """Audit result for one lowered/compiled program."""

    name: str
    ok: bool = True
    findings: List[Finding] = field(default_factory=list)
    #: psum binds over the ``clients`` axis alone (the per-training-round
    #: global-collective budget; eval-phase joint reductions are separate)
    psum_clients: int = 0
    #: psum binds over ``(clients, data)`` jointly -- the eval-fused
    #: superstep's sBN + Global reductions, audited as their own budget
    psum_eval: int = 0
    all_gather: int = 0
    #: collective axis names seen in the program
    collective_axes: List[str] = field(default_factory=list)
    #: donation: leaves marked for donation at lowering / consumed by
    #: input-output aliasing in the optimized HLO / expected count
    donated: int = 0
    aliased: int = 0
    donation_expected: int = 0
    flops: Optional[float] = None
    memory: Optional[Dict[str, int]] = None
    #: analytic HBM bound the memory fields were held to, plus the
    #: donation-savings accounting (ISSUE 7: staticcheck/memory.py)
    memory_budget: Optional[Dict[str, Any]] = None
    #: per-collective bytes-on-the-wire table + train/eval/DCN totals
    #: (ISSUE 7: staticcheck/wire.py)
    wire: Optional[Dict[str, Any]] = None
    #: explicit (jaxpr) + GSPMD-introduced (optimized HLO) reshard op
    #: counts; zero allowed (ISSUE 7 reshard detector)
    reshards: Optional[Dict[str, Any]] = None
    #: optimized-HLO kernel stats of the program's scan body (the local-step
    #: loop): fusion launches + instruction count per iteration, and the
    #: budget enforced against it (None = recorded, not budgeted)
    step_body: Optional[Dict[str, Any]] = None
    step_body_budget: Optional[int] = None

    def fail(self, rule: str, message: str) -> None:
        self.ok = False
        self.findings.append(Finding(rule, self.name, message))


@dataclass
class AuditReport:
    """The whole staticcheck run: lint findings + per-program audits +
    cross-program checks, serialisable to STATICCHECK.json."""

    ok: bool = True
    config: Dict[str, Any] = field(default_factory=dict)
    programs: Dict[str, ProgramReport] = field(default_factory=dict)
    flop_budget: Dict[str, Any] = field(default_factory=dict)
    recompile: Dict[str, Any] = field(default_factory=dict)
    #: analytic flagship compression frontier (ISSUE 8:
    #: audit.codec_frontier_check) -- per-codec payload bytes vs dense,
    #: with the int8 <= 25%-of-dense acceptance line enforced
    wire_frontier: Dict[str, Any] = field(default_factory=dict)
    #: sampling-stream consistency (ISSUE 11: audit.sampler_stream_check)
    #: -- in-jit == host draw bitwise for both sampler kinds, all-ones
    #: availability == uniform cohort, PRP exact bijection
    sampler: Dict[str, Any] = field(default_factory=dict)
    #: arms-axis FLOP linearity (ISSUE 14: audit.arms_flop_check) -- an
    #: E-arm program's compiled FLOPs == E x its unbatched twin's
    arms: Dict[str, Any] = field(default_factory=dict)
    #: config-lattice exhaustiveness (ISSUE 18: lattice.lattice_check) --
    #: every point of the declared feature lattice classified SUPPORTED
    #: (audited anchor / equivalence contract) or REFUSED (typed
    #: ValueError from exactly one resolve_* validator); UNREACHED
    #: points are findings
    lattice: Dict[str, Any] = field(default_factory=dict)
    #: RNG-stream provenance (ISSUE 18: keys.key_streams_check) -- the
    #: salt/fold_in graph: interval disjointness per root, pinned salt
    #: constants, declared fold sites, raw-key reuse, jaxpr bind roots
    key_streams: Dict[str, Any] = field(default_factory=dict)
    lint: List[Finding] = field(default_factory=list)
    #: baseline-ratchet diff (ISSUE 7: staticcheck/ratchet.py).  ``checked``
    #: is False unless the CLI ran ``--diff-baseline``; a regressed ratchet
    #: keeps ``ok`` True (the audit itself is green) but exits 2.
    ratchet: Dict[str, Any] = field(default_factory=lambda: {"checked": False})
    generated_at: Optional[str] = None

    def add_program(self, prog: ProgramReport) -> None:
        self.programs[prog.name] = prog
        self.ok = self.ok and prog.ok

    def add_lint(self, findings: List[Finding]) -> None:
        self.lint.extend(findings)
        self.ok = self.ok and not findings

    def fail(self, section: Dict[str, Any], rule: str, message: str) -> None:
        """Record a cross-program failure in ``section`` (flop_budget /
        recompile) and flip the report."""
        self.ok = False
        section.setdefault("findings", []).append(
            asdict(Finding(rule, "audit", message)))
        section["ok"] = False

    def all_findings(self) -> List[Finding]:
        out = list(self.lint)
        for p in self.programs.values():
            out.extend(p.findings)
        for sec in (self.flop_budget, self.recompile, self.wire_frontier,
                    self.sampler, self.arms, self.lattice, self.key_streams):
            out.extend(Finding(**f) for f in sec.get("findings", []))
        return out

    def to_dict(self) -> Dict[str, Any]:
        return {
            "version": 2,  # 2: + per-program wire/memory/reshards, ratchet
            "ok": self.ok,
            "generated_at": self.generated_at,
            "config": self.config,
            "programs": {k: asdict(v) for k, v in self.programs.items()},
            "flop_budget": self.flop_budget,
            "recompile": self.recompile,
            "wire_frontier": self.wire_frontier,
            "sampler": self.sampler,
            "arms": self.arms,
            "lattice": self.lattice,
            "key_streams": self.key_streams,
            "ratchet": self.ratchet,
            "lint": [asdict(f) for f in self.lint],
        }

    def to_json(self, indent: Optional[int] = 2) -> str:
        return json.dumps(self.to_dict(), indent=indent, sort_keys=True)
