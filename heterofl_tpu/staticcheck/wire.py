"""Wire model (ISSUE 7 tentpole): static bytes-on-the-wire accounting for
every collective in an audited program.

HeteroFL's headline claim is *communication* efficiency, but until this
module the auditor only counted psum binds -- it never measured the bytes
they move.  Here every collective bind in a traced program is priced from
its operand avals (shape x dtype x participating mesh-axis size) and
classified by link class:

* **payload_bytes** -- the per-participant logical reduction payload (the
  sum of operand aval bytes at the bind; under ``shard_map`` the operands
  are per-device values, so this is exactly what each participant
  contributes).
* **ring_bytes_per_device** -- the per-participant wire traffic of a
  bidirectional-ring all-reduce, ``2 (p-1)/p x payload`` (reduce-scatter +
  all-gather phases): the standard lower bound, and the number the
  compression PR will shrink.
* **scope** -- ``ici`` (intra-slice interconnect) vs ``dcn`` (data-center
  network): a collective is DCN-eligible when any of its mesh axes crosses
  a process boundary (:func:`dcn_axes_of`).  On the single-process audit
  mesh everything is ICI; the multi-host slices work must keep the DCN
  budget at exactly the one global reduction per round.

The enforced budget (``wire-budget``): the single-axis ``clients`` psums of
a fused training round must move EXACTLY ``sum(param_bytes) + count_bytes``
-- one dense global reduction of the program's level footprint, both trees
f32 (:func:`~..fed.core.level_byte_table` supplies the analytic number,
which matches the traced operand avals bit-for-bit).  The eval phase's
joint (clients, data) reductions are budgeted separately
(``wire-eval-budget``): every traced eval point must move the identical
payload set.  ``wire-dcn`` holds cross-slice bytes to the per-program DCN
budget (zero today).

Import-light on purpose (no jax at module level): the report plumbing uses
the analytic half without booting a backend.
"""

from __future__ import annotations

from collections import Counter
from typing import Any, Dict, Optional, Sequence, Tuple

#: the training-round reduction axis and the eval phase's joint axes --
#: must match the audit's psum budget split (audit.py counts them the same
#: way)
TRAIN_AXIS = "clients"
EVAL_AXES = ("clients", "data")


def dcn_axes_of(mesh) -> Tuple[str, ...]:
    """Mesh axes whose traversal crosses a process boundary: collectives
    binding such an axis are DCN-eligible (their reduction cannot complete
    on intra-slice links alone).  Derived from the device array's
    ``process_index`` grid, so a multi-host mesh classifies itself --
    nothing to configure when the pod-scale slices placement lands."""
    import numpy as np

    devs = np.asarray(mesh.devices)
    names = tuple(mesh.axis_names)
    out = []
    for i in range(devs.ndim):
        moved = np.moveaxis(devs, i, 0).reshape(devs.shape[i], -1)
        for col in range(moved.shape[1]):
            procs = {getattr(d, "process_index", 0) for d in moved[:, col]}
            if len(procs) > 1:
                out.append(names[i])
                break
    return tuple(out)


def classify(axes: Sequence[str], dcn_axes: Sequence[str]) -> str:
    """Link class of a collective binding ``axes``: ``dcn`` when any bound
    axis crosses a slice boundary, else ``ici``."""
    return "dcn" if any(a in dcn_axes for a in axes) else "ici"


def participants_of(axes: Sequence[str], mesh) -> int:
    """Number of devices participating in a collective over ``axes``."""
    shape = dict(zip(mesh.axis_names, mesh.devices.shape))
    n = 1
    for a in axes:
        n *= int(shape.get(a, 1))
    return n


def ring_allreduce_bytes(payload_bytes: int, participants: int) -> int:
    """Per-participant wire traffic of a bidirectional-ring all-reduce:
    ``2 (p-1)/p x payload`` (reduce-scatter then all-gather).  Zero for a
    single participant (the reduction is local)."""
    if participants <= 1:
        return 0
    return int(round(2.0 * (participants - 1) / participants * payload_bytes))


def program_wire(jaxpr, mesh, dcn_axes: Optional[Sequence[str]] = None
                 ) -> Dict[str, Any]:
    """The per-program wire table: one priced row per collective bind plus
    the totals the budget checks and the ratchet consume.

    ``train_bytes_per_round`` sums the single-axis psums binding
    :data:`TRAIN_AXIS` (one bind per fused round -- scan bodies execute it
    once per round, so the bind payload IS the per-round wire cost);
    ``eval_bytes_total`` sums the joint ``(clients, data)`` psums (the eval
    phase's sBN-moment + Global-metric reductions, one pair per traced
    eval point); everything else lands in ``other_bytes`` (zero in every
    green program)."""
    from .jaxpr_walk import collective_payload_rows

    if dcn_axes is None:
        dcn_axes = dcn_axes_of(mesh)
    rows = []
    train = eval_total = other = dcn_total = 0
    eval_payloads = []
    for r in collective_payload_rows(jaxpr):
        axes = tuple(r["axes"])
        p = participants_of(axes, mesh)
        scope = classify(axes, dcn_axes)
        rows.append({**r, "participants": p, "scope": scope,
                     "ring_bytes_per_device":
                         ring_allreduce_bytes(r["payload_bytes"], p)})
        if r["primitive"] == "psum" and all(a in axes for a in EVAL_AXES):
            eval_total += r["payload_bytes"]
            eval_payloads.append(r["payload_bytes"])
        elif r["primitive"] == "psum" and TRAIN_AXIS in axes:
            train += r["payload_bytes"]
        else:
            other += r["payload_bytes"]
        if scope == "dcn":
            dcn_total += r["payload_bytes"]
    return {
        "collectives": rows,
        "train_bytes_per_round": train,
        "train_ring_bytes_per_device":
            ring_allreduce_bytes(train, participants_of((TRAIN_AXIS,), mesh)),
        "eval_bytes_total": eval_total,
        "eval_payloads": sorted(eval_payloads),
        "other_bytes": other,
        "dcn_bytes": dcn_total,
        "dcn_axes": list(dcn_axes),
    }


def check_wire(rep, wire: Dict[str, Any], expected_train_bytes: int,
               n_eval_points: int, dcn_budget_bytes: int = 0,
               dcn_exact: bool = False) -> None:
    """Enforce the wire budgets on one program report (``rep`` is a
    :class:`~.report.ProgramReport`).

    * ``wire-budget``: the training reduction moves exactly
      ``expected_train_bytes`` per round (today: one dense global psum of
      the level's ``sum(param_bytes) + count_bytes``).  An extra psum, a
      widened operand or a smuggled dtype all land here with the measured
      vs budgeted bytes.
    * ``wire-eval-budget``: each of the ``n_eval_points`` traced eval
      points moves the identical payload multiset (the sBN + Global pair);
      a lopsided point means an eval reduction forked.
    * ``wire-dcn``: cross-slice bytes within ``dcn_budget_bytes`` (zero on
      the single-slice audit mesh).  ``dcn_exact=True`` -- the multi-host
      variants (ISSUE 17) -- tightens the bound to EQUALITY: DCN must
      carry exactly one dense level-a reduction per training round,
      nothing more (a smuggled reshard) and nothing less (the reduction
      silently left the cross-host axis).
    * ``wire-unbudgeted``: collectives outside the train/eval buckets
      (``pmax``/``pmin``/``reduce_scatter``/``all_gather`` binds, psums
      over other axis sets) move ZERO bytes -- a reduction smuggled past
      the psum bind count still shows up here by its payload."""
    got = wire["train_bytes_per_round"]
    if got != expected_train_bytes:
        rep.fail("wire-budget",
                 f"training-round collective payload is {got} bytes/round, "
                 f"budget is exactly {expected_train_bytes} (one dense "
                 f"global reduction of sum(param_bytes) + count_bytes at "
                 f"this program's level)")
    if n_eval_points > 0:
        per_payload = Counter(wire["eval_payloads"])
        bad = {pay: n for pay, n in per_payload.items()
               if n % n_eval_points != 0}
        if bad or not per_payload:
            rep.fail("wire-eval-budget",
                     f"eval payloads {dict(per_payload)} do not divide into "
                     f"{n_eval_points} identical eval points (sBN + Global "
                     f"pair per point)")
        wire["eval_bytes_per_point"] = wire["eval_bytes_total"] // n_eval_points
    elif wire["eval_bytes_total"]:
        rep.fail("wire-eval-budget",
                 f"{wire['eval_bytes_total']} joint (clients, data) psum "
                 f"bytes in a program with no eval points")
    if wire["other_bytes"]:
        others = [r for r in wire["collectives"]
                  if not (r["primitive"] == "psum"
                          and (all(a in r["axes"] for a in EVAL_AXES)
                               or TRAIN_AXIS in r["axes"]))]
        rep.fail("wire-unbudgeted",
                 f"{wire['other_bytes']} collective bytes outside the "
                 f"train/eval budgets "
                 f"({[(r['primitive'], r['axes']) for r in others]}): every "
                 f"byte on the wire must ride the budgeted reductions")
    if dcn_exact and wire["dcn_bytes"] != dcn_budget_bytes:
        rep.fail("wire-dcn",
                 f"{wire['dcn_bytes']} cross-slice (DCN) collective bytes, "
                 f"budget is EXACTLY {dcn_budget_bytes} (one dense level-a "
                 f"reduction per training round on a multi-process mesh): "
                 f"either a second cross-host transfer crept in or the "
                 f"training reduction left the cross-host axis (axes "
                 f"{wire['dcn_axes']})")
    elif wire["dcn_bytes"] > dcn_budget_bytes:
        rep.fail("wire-dcn",
                 f"{wire['dcn_bytes']} cross-slice (DCN) collective bytes, "
                 f"budget is {dcn_budget_bytes}: a reshard or a second "
                 f"cross-slice reduction crept in (axes {wire['dcn_axes']})")


def link_split(payload_bytes: int, participants: int,
               processes: int = 1) -> Dict[str, int]:
    """Analytic per-link ICI-vs-DCN byte split of one bidirectional-ring
    all-reduce (ISSUE 17 satellite).

    A ring over ``p`` participants has ``p`` links, each carrying the same
    ``2 (p-1)/p x payload`` bytes (reduce-scatter + all-gather, the
    :func:`ring_allreduce_bytes` number).  With the participants laid out
    as ``h`` contiguous per-process blocks (the host-aligned slices
    placement), exactly ``h`` of those links cross a process boundary --
    the scarce DCN links (PAPERS.md 2405.20431); the remaining ``p - h``
    stay on intra-host ICI.  ``processes <= 1`` puts every byte on ICI.
    Import-light like the rest of the analytic half (no jax)."""
    p = max(1, int(participants))
    h = max(1, int(processes))
    per_link = ring_allreduce_bytes(payload_bytes, p)
    dcn_links = h if (h > 1 and p > 1) else 0
    ici_links = (p if p > 1 else 0) - dcn_links
    return {
        "participants": p,
        "processes": h,
        "bytes_per_link": per_link,
        "dcn_links": dcn_links,
        "ici_links": ici_links,
        "dcn_bytes_total": dcn_links * per_link,
        "ici_bytes_total": ici_links * per_link,
    }
