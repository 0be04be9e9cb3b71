"""HBM footprint auditor (ISSUE 7 tentpole): the ``memory_analysis()``
fields stop being decoration and become budgeted findings.

Until this module the audit recorded per-program temp/argument/output bytes
into STATICCHECK.json and enforced nothing -- a silent memory doubling
(an un-donated carry, a duplicated staging commit, a forgotten eval
operand) would fail on the TPU at 1e6-user scale instead of failing the
audit.  Three layers now:

* **required fields** (``memory-analysis-missing``): a compiled flagship
  program whose ``memory_analysis()`` lacks temp/argument/output bytes is
  a loud finding, not an empty record (the old ``getattr``-skip silently
  produced exactly that).
* **analytic bounds** (``hbm-budget``): each field is held to a bound
  derived from the analytic byte tables
  (:func:`~..fed.core.level_byte_table` activations + params, the flat
  scan carry, the staged operand bytes).  The bounds are deliberately
  generous ceilings (the audit widths leave the compiler room); they catch
  order-of-magnitude blowups outright, while the **ratchet**
  (:mod:`.ratchet`) pins the exact measured bytes against the committed
  baseline at tight tolerances -- that is where a 2x doubling fails.
* **donation savings** (``hbm-donation-savings``): the bytes input-output
  aliasing ACTUALLY saved, accounted from the donated argument footprint x
  the consumed-alias fraction.  An un-donated leaf shows up here as lost
  bytes, not just as a count mismatch.

Import-light (no jax at module level), like the rest of the package.
"""

from __future__ import annotations

from typing import Any, Dict, List, Optional, Tuple

from .report import Finding

#: ``memory_analysis()`` fields a compiled flagship program MUST expose --
#: their absence means the audit can no longer see the program's HBM
#: footprint and must say so loudly (ISSUE 7 satellite: audit.py used to
#: ``getattr``-skip these into an empty record)
REQUIRED_MEMORY_FIELDS = ("temp_size_in_bytes", "argument_size_in_bytes",
                          "output_size_in_bytes")

#: recorded when present, never required (backend-dependent)
OPTIONAL_MEMORY_FIELDS = ("generated_code_size_in_bytes",
                          "alias_size_in_bytes", "peak_memory_in_bytes",
                          "host_temp_size_in_bytes")

#: HBM temp budget = TEMP_FACTOR x (per-device analytic working set) +
#: SLACK.  The working set: ACT_WORKING_SET live activation copies per
#: concurrent client (forward outputs + backward-saved + grad workspace),
#: CARRY_COPIES param-shaped carry buffers (params, momentum, update sums,
#: count masks, double-buffered across the donation boundary), the psum
#: payload, and one materialised copy of the staged operands.  Sized so the
#: green matrix sits well inside the budget and a 10x blowup trips
#: unconditionally; the ratchet holds the tight line.  (Re-pinned 2.5 ->
#: 3.5 with PR 23: XLA:CPU of jaxlib 0.9.0 assigns the two k8 eval-fused
#: programs 14.7 MB of temp against the old 12.85 MB bound -- the same
#: programs, a different buffer assignment.)
TEMP_FACTOR = 3.5
ACT_WORKING_SET = 3
CARRY_COPIES = 8
TEMP_SLACK = 1 << 20

#: argument budget: the per-device argument bytes can never exceed the
#: whole staged operand footprint (sharded placements hold a 1/n_dev
#: shard); the margin absorbs XLA's tupling/padding
ARG_MARGIN = 1.02
ARG_SLACK = 64 << 10

#: output budget: fresh params (aliased over the donated ones) + stacked
#: per-round metrics
OUT_SLACK = 1 << 20


def collect_memory(ma, name: str) -> Tuple[Optional[Dict[str, int]],
                                           List[Finding]]:
    """Extract the memory fields of one ``memory_analysis()`` result.

    Returns ``(fields, findings)``: every :data:`REQUIRED_MEMORY_FIELDS`
    member that is absent (or the whole analysis being unavailable) is a
    ``memory-analysis-missing`` finding -- the audit's view of the
    program's HBM footprint just went dark, which is itself a regression.
    ``peak_bytes`` is derived (argument + temp + output; XLA:CPU exposes
    no direct peak) so the ratchet has one headline number per program."""
    findings: List[Finding] = []
    if ma is None:
        findings.append(Finding(
            "memory-analysis-missing", name,
            "memory_analysis() returned None for a compiled flagship "
            "program: the HBM footprint audit is blind here"))
        return None, findings
    out: Dict[str, int] = {}
    for k in REQUIRED_MEMORY_FIELDS:
        if not hasattr(ma, k):
            findings.append(Finding(
                "memory-analysis-missing", name,
                f"memory_analysis() lacks required field `{k}`: the HBM "
                f"budget for this program can no longer be audited"))
            continue
        out[k] = int(getattr(ma, k))
    for k in OPTIONAL_MEMORY_FIELDS:
        if hasattr(ma, k):
            out[k] = int(getattr(ma, k))
    if all(k in out for k in REQUIRED_MEMORY_FIELDS):
        out["peak_bytes"] = (out["temp_size_in_bytes"]
                             + out["argument_size_in_bytes"]
                             + out["output_size_in_bytes"])
    return out, findings


def analytic_budget(param_bytes: int, activation_bytes: int,
                    clients_per_device: int, staged_arg_bytes: int,
                    train_payload_bytes: int) -> Dict[str, int]:
    """The per-program analytic HBM bound (see module docstring for the
    model).  All inputs are analytic or example-arg derived -- nothing is
    fitted to measured values, so the bound holds at flagship widths by
    construction."""
    working = (clients_per_device * ACT_WORKING_SET * activation_bytes
               + CARRY_COPIES * param_bytes
               + train_payload_bytes
               + staged_arg_bytes)
    return {
        "temp_budget": int(TEMP_FACTOR * working) + TEMP_SLACK,
        "argument_budget": int(ARG_MARGIN * staged_arg_bytes) + ARG_SLACK,
        "output_budget": int(param_bytes) + OUT_SLACK,
        "inputs": {
            "param_bytes": int(param_bytes),
            "activation_bytes": int(activation_bytes),
            "clients_per_device": int(clients_per_device),
            "staged_arg_bytes": int(staged_arg_bytes),
            "train_payload_bytes": int(train_payload_bytes),
        },
    }


#: measured field -> budget key
_BUDGETED = (("temp_size_in_bytes", "temp_budget"),
             ("argument_size_in_bytes", "argument_budget"),
             ("output_size_in_bytes", "output_budget"))


def check_memory(rep, mem: Optional[Dict[str, int]],
                 budget: Dict[str, int]) -> None:
    """Hold one program's measured memory fields to the analytic bound
    (``rep`` is a :class:`~.report.ProgramReport`; ``hbm-budget``
    findings name the field and both numbers)."""
    if mem is None:
        return  # collect_memory already failed memory-analysis-missing
    for field, bkey in _BUDGETED:
        if field not in mem:
            continue  # absence already reported by collect_memory
        if mem[field] > budget[bkey]:
            rep.fail("hbm-budget",
                     f"{field} = {mem[field]} bytes exceeds the analytic "
                     f"bound {budget[bkey]} ({bkey}; inputs "
                     f"{budget['inputs']}): the program's HBM footprint "
                     f"blew past what its shapes justify")


def donation_accounting(rep, donated_arg_bytes: int) -> Dict[str, int]:
    """Bytes input-output aliasing actually saved vs what full donation
    coverage would save.  ``donated_arg_bytes`` is the footprint of the
    donation-expected argument leaves (the params carry); the consumed
    fraction comes from the compiled alias count already parsed by the
    audit.  Shortfall -> ``hbm-donation-savings`` with the lost bytes (the
    buffers XLA will double)."""
    expected = int(donated_arg_bytes) if rep.donation_expected else 0
    if rep.donation_expected:
        saved = expected * rep.aliased // rep.donation_expected
    else:
        saved = 0
    acct = {"expected_saved_bytes": expected, "saved_bytes": saved}
    if saved < expected:
        rep.fail("hbm-donation-savings",
                 f"input-output aliasing saved {saved} of the "
                 f"{expected} donated-carry bytes ({rep.aliased}/"
                 f"{rep.donation_expected} leaves consumed): the "
                 f"difference is silently double-buffered every dispatch")
    return acct
