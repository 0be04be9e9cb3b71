"""Static analysis for the round engines: AST lint + compiled-program audit.

Two fronts, one gate (ISSUE 3):

* :mod:`.rules` -- path-scoped banned-call lint over the package source
  (``jnp.asarray`` wraps, ``float()`` coercions, undonated ``jax.jit``,
  wall-clock/fresh-RNG calls in steady-state code), suppressible per line
  with ``# staticcheck: allow(<rule-id>)`` pragmas.  Pure-AST, jax-free,
  runs in milliseconds.
* :mod:`.audit` -- lowers the flagship round programs (masked + grouped
  engines x span/slices placements x ``superstep_rounds`` in {1, 8}) on a
  CPU mesh and walks the jaxpr/StableHLO/optimized-HLO to enforce: no host
  callbacks or f64 in any round program, full donation coverage (every
  donated leaf consumed by input-output aliasing, donation warnings
  promoted to failures), the collectives budget (exactly ONE global psum
  per fused round, axes resolvable in the mesh), recompile-hazard freedom
  (fresh-but-identical host inputs leave the program cache untouched), the
  FLOP budget (``cost_analysis()`` per level vs the analytic shares from
  :func:`~..fed.core.level_flop_shares`), and the ISSUE 7 passes: the
  bytes-on-the-wire budget (:mod:`.wire`, enforced by equality against
  ``fed.core.level_byte_table``), the HBM footprint budget
  (:mod:`.memory`), and the reshard detector (zero data-movement
  collectives, jaxpr and optimized-HLO halves).
* :mod:`.ratchet` -- every audited metric diffed against the committed
  ``STATICCHECK_BASELINE.json`` with per-metric tolerances
  (``--diff-baseline`` exits 2 on regression; ``--update-baseline``
  re-pins after an intentional change).

CLI: ``python -m heterofl_tpu.staticcheck --json`` (exits non-zero on any
finding; writes the ``STATICCHECK.json`` artifact, which CI keeps).

This module stays import-light (no jax): the CLI must pin the platform to
cpu before any backend initialises, and the lint front must be usable
without booting a platform.
"""

from .report import AuditReport, Finding, ProgramReport  # noqa: F401
from .rules import DEFAULT_RULES, lint_paths, lint_tree  # noqa: F401

__all__ = [
    "AuditReport", "Finding", "ProgramReport",
    "DEFAULT_RULES", "lint_paths", "lint_tree",
    "run_audit",
]


def run_audit(*args, **kwargs):
    """Lazy forwarder to :func:`.audit.run_audit` (imports jax)."""
    from .audit import run_audit as _run

    return _run(*args, **kwargs)
