"""Jaxpr / lowered-IR walking utilities for the program auditor.

Everything here is *static*: programs are traced/lowered/compiled but never
executed.  The walkers recurse through every sub-jaxpr (scan/while bodies,
cond/switch branches, shard_map and custom-derivative bodies), so an op
smuggled inside a ``lax.scan`` round body is found exactly like a top-level
one.
"""

from __future__ import annotations

import re
from collections import Counter
from typing import Any, Iterable, List, Set, Tuple

import jax
from jax.extend import core as jex_core

#: primitive names that call back into the host (banned in round programs:
#: one callback serialises the whole fused round on the host boundary)
CALLBACK_PRIMITIVES = ("pure_callback", "io_callback", "debug_callback")

#: collective primitives whose axis names must resolve in the mesh
COLLECTIVE_PRIMITIVES = ("psum", "all_gather", "all_to_all", "ppermute",
                        "pmax", "pmin", "reduce_scatter")


def _sub_jaxprs(params: dict) -> Iterable[Any]:
    for v in params.values():
        vs = v if isinstance(v, (tuple, list)) else (v,)
        for item in vs:
            if isinstance(item, jex_core.ClosedJaxpr):
                yield item.jaxpr
            elif isinstance(item, jex_core.Jaxpr):
                yield item


def iter_eqns(jaxpr) -> Iterable[Any]:
    """Yield every eqn of ``jaxpr`` (a ``Jaxpr`` or ``ClosedJaxpr``),
    recursing into all sub-jaxprs."""
    if isinstance(jaxpr, jex_core.ClosedJaxpr):
        jaxpr = jaxpr.jaxpr
    for eqn in jaxpr.eqns:
        yield eqn
        for sub in _sub_jaxprs(eqn.params):
            yield from iter_eqns(sub)


def _is_collective(name: str) -> bool:
    return any(name == p or name.startswith(p + "_")
               for p in COLLECTIVE_PRIMITIVES)


def iter_collective_launches(jaxpr) -> Iterable[Tuple[Any, List[Any]]]:
    """Yield ``(first eqn, operand vars)`` per collective LAUNCH, recursing
    into all sub-jaxprs.

    The installed jax binds a pytree collective -- ``lax.psum((sums,
    counts), axis)`` -- as one eqn PER LEAF, back to back, and leaves the
    joining to XLA's all-reduce combiner.  A launch is therefore a maximal
    run of adjacent eqns of one jaxpr body with the same primitive and the
    same params, none of which reads another member's output: what the
    program asks to be reduced together.  A reduction that depends on an
    earlier one, or is separated from it by any other op, is a second
    launch."""
    if isinstance(jaxpr, jex_core.ClosedJaxpr):
        jaxpr = jaxpr.jaxpr
    head, operands, outs = None, [], set()
    for eqn in jaxpr.eqns:
        joins = (head is not None and eqn.primitive is head.primitive
                 and eqn.params == head.params
                 and not any(isinstance(v, jex_core.Var) and v in outs
                             for v in eqn.invars))
        if head is not None and not joins:
            yield head, operands
            head, operands, outs = None, [], set()
        if _is_collective(eqn.primitive.name):
            if head is None:
                head = eqn
            operands.extend(eqn.invars)
            outs.update(eqn.outvars)
        else:
            for sub in _sub_jaxprs(eqn.params):
                yield from iter_collective_launches(sub)
    if head is not None:
        yield head, operands


def provenance(eqn) -> str:
    """``file:line (fn)`` of the python frame that bound the op, best
    effort -- the loud half of a callback/f64 finding."""
    try:
        from jax._src import source_info_util

        return source_info_util.summarize(eqn.source_info)
    except Exception:
        return "<unknown provenance>"


def primitive_counts(jaxpr) -> Counter:
    return Counter(eqn.primitive.name for eqn in iter_eqns(jaxpr))


#: primitives that derive or consume PRNG state in a traced program --
#: every one of these binds must descend from a declared (salt, purpose)
#: root (ISSUE 18: staticcheck/keys.py)
RANDOM_PRIMITIVE_PREFIXES = ("random_", "threefry")


def random_bind_files(jaxpr, package_root: str) -> Set[str]:
    """Package-relative source files of every PRNG bind in ``jaxpr``.

    Walks all ``random_*``/``threefry*`` eqns (recursing into sub-jaxprs)
    and maps each bind's user frame back to the file that bound it; files
    outside ``package_root`` (jax internals, test harnesses) are dropped.
    The key-stream audit cross-checks the result against the modules its
    SALT_REGISTRY models -- randomness appearing in an unmodeled package
    file has no declared provenance."""
    import os
    import re

    root = os.path.abspath(package_root)
    files: Set[str] = set()
    for eqn in iter_eqns(jaxpr):
        name = eqn.primitive.name
        if not any(name.startswith(p) for p in RANDOM_PRIMITIVE_PREFIXES):
            continue
        prov = provenance(eqn)  # "path:line:col (fn)"
        path = os.path.abspath(re.sub(r"(:\d+)+( \(.*\))?$", "", prov))
        if path.startswith(root + os.sep):
            files.add(os.path.relpath(path, root).replace(os.sep, "/"))
    return files


def find_callbacks(jaxpr) -> List[Tuple[str, str]]:
    """(primitive name, provenance) of every host-callback op."""
    out = []
    for eqn in iter_eqns(jaxpr):
        if any(eqn.primitive.name.startswith(p) for p in CALLBACK_PRIMITIVES):
            out.append((eqn.primitive.name, provenance(eqn)))
    return out


def find_f64(jaxpr) -> List[Tuple[str, str]]:
    """(description, provenance) of every float64 value or convert: a silent
    f64 in a round program doubles its bandwidth/footprint (and on TPU
    deoptimises to software emulation)."""
    import numpy as np

    out = []
    for eqn in iter_eqns(jaxpr):
        nd = eqn.params.get("new_dtype")
        if eqn.primitive.name == "convert_element_type" and nd == np.float64:
            out.append((f"convert_element_type -> float64", provenance(eqn)))
            continue
        for var in eqn.outvars:
            aval = getattr(var, "aval", None)
            if getattr(aval, "dtype", None) == np.float64:
                out.append((f"{eqn.primitive.name} produces float64 "
                            f"{getattr(aval, 'shape', ())}", provenance(eqn)))
                break
    return out


def collective_axes(eqn) -> Tuple[str, ...]:
    """Flattened axis names a collective eqn operates over."""
    axes = eqn.params.get("axes", eqn.params.get("axis_name", ()))
    if not isinstance(axes, (tuple, list)):
        axes = (axes,)
    flat = []
    for a in axes:
        if isinstance(a, (tuple, list)):
            flat.extend(a)
        else:
            flat.append(a)
    return tuple(str(a) for a in flat if isinstance(a, (str,)) or a is not None)


def count_collectives(jaxpr) -> Tuple[Counter, Set[str]]:
    """(per-primitive launch counts, all axis names seen).  A ``psum`` over
    ``(sums, counts)`` is ONE launch (:func:`iter_collective_launches`) --
    the budget the engines are audited against counts launches, not
    leaves."""
    counts: Counter = Counter()
    axes: Set[str] = set()
    for eqn, _ in iter_collective_launches(jaxpr):
        counts[eqn.primitive.name] += 1
        axes.update(collective_axes(eqn))
    return counts, axes


def count_psum_over(jaxpr, axis: str = "clients") -> int:
    """psum launches whose axes include ``axis`` (the global-collective
    budget; a data-axis psum inside intra-client DP is not a global one)."""
    return sum(1 for eqn, _ in iter_collective_launches(jaxpr)
               if eqn.primitive.name == "psum"
               and axis in collective_axes(eqn))


def collective_payload_rows(jaxpr) -> List[dict]:
    """One priced row per collective launch: primitive, sorted axis names,
    per-participant payload bytes (sum of operand aval bytes -- under
    ``shard_map`` the operands are per-device values, so this is exactly
    what each participant contributes to the wire), operand shapes/dtypes,
    and provenance.  The wire model (:mod:`.wire`) turns these into
    ICI/DCN-classified budgets."""
    import numpy as np

    rows = []
    for eqn, invars in iter_collective_launches(jaxpr):
        name = eqn.primitive.name
        payload = 0
        operands = []
        for v in invars:
            aval = getattr(v, "aval", None)
            dt = getattr(aval, "dtype", None)
            if dt is None:
                continue
            try:
                nbytes = int(np.prod(aval.shape)) * np.dtype(dt).itemsize
            except TypeError:  # extended dtypes (PRNG keys) have no itemsize
                continue
            payload += nbytes
            operands.append([list(map(int, aval.shape)), str(dt)])
        rows.append({"primitive": name, "axes": sorted(collective_axes(eqn)),
                     "payload_bytes": payload, "operands": operands,
                     "provenance": provenance(eqn)})
    return rows


#: jaxpr-level primitives that MOVE data between devices without reducing
#: it -- explicit reshards; zero are allowed in any round program
RESHARD_PRIMITIVES = ("all_to_all", "ppermute")

#: optimized-HLO instruction ops GSPMD inserts to fix up sharding
#: mismatches -- implicit reshards the jaxpr never shows; zero allowed
RESHARD_HLO_OPS = ("all-to-all", "collective-permute")


def find_reshards(jaxpr) -> List[Tuple[str, str]]:
    """(primitive, provenance) of every explicit data-movement collective
    bound in the program (jaxpr level)."""
    out = []
    for eqn in iter_eqns(jaxpr):
        name = eqn.primitive.name
        if any(name == p or name.startswith(p + "_")
               for p in RESHARD_PRIMITIVES):
            out.append((name, provenance(eqn)))
    return out


def reshard_ops(compiled_text: str) -> dict:
    """Counts of GSPMD-introduced data-movement instructions in an
    optimized-HLO dump: ``all-to-all`` and ``collective-permute`` (their
    async ``-start`` forms count once; ``-done`` halves are skipped).
    These appear when sharding propagation decides operands live on the
    wrong devices -- data movement the jaxpr walk cannot see, and exactly
    what the multi-host slices work must keep at zero."""
    out = {}
    for op in RESHARD_HLO_OPS:
        # `= <shape> op(`: the shape may be a tuple (async -start forms), so
        # allow anything shape-like between `=` and the op name; `[^=]`
        # keeps the match from crossing into metadata/attribute text
        out[op] = len(re.findall(
            rf"=[ ]*[^=\n]*?\b{re.escape(op)}(?:-start)?\(", compiled_text))
    out["total"] = sum(out.values())
    return out


def count_psum_joint(jaxpr, axes: Tuple[str, ...] = ("clients", "data")) -> int:
    """psum launches whose axis set includes ALL of ``axes`` -- the eval
    phase's whole-mesh reductions (sBN moments, Global metric sums) reduce
    over ``(clients, data)`` jointly, while every training-round psum binds
    a single axis, so this cleanly separates the eval-fused superstep's
    collective budget from the one-global-psum-per-training-round
    invariant."""
    return sum(1 for eqn, _ in iter_collective_launches(jaxpr)
               if eqn.primitive.name == "psum"
               and all(a in collective_axes(eqn) for a in axes))


# ---------------------------------------------------------------------------
# optimized-HLO computation parsing: the step-body kernel count
# ---------------------------------------------------------------------------

def hlo_computations(compiled_text: str) -> dict:
    """``{computation_name: block_text}`` of an optimized HLO module dump.

    Computations start at column 0 (``%name (params) -> type {`` or
    ``ENTRY ...``) and end at a column-0 ``}``."""
    blocks, name, buf = {}, None, []
    for line in compiled_text.splitlines():
        if not line.startswith(" ") and "{" in line and name is None:
            m = re.search(r"%?([\w\.\-]+)\s*\(", line)
            if m:
                name = m.group(1)
                buf = [line]
        elif name is not None:
            buf.append(line)
            if line.startswith("}"):
                blocks[name] = "\n".join(buf)
                name = None
    return blocks


def while_body_stats(compiled_text: str) -> dict:
    """Per-while-loop-body kernel stats of an optimized HLO module:
    ``{body_name: {"fusions": n, "instructions": m}}``.

    ``fusions`` counts fusion-instruction launches inside the body -- the
    CPU/TPU proxy for per-iteration kernel count; ``instructions`` is the
    body's total op count.  Scans lower to whiles, so the LOCAL-STEP body
    of a round program is one of these (in practice the largest)."""
    blocks = hlo_computations(compiled_text)
    out = {}
    for body in set(re.findall(r"body=%?([\w\.\-]+)", compiled_text)):
        blk = blocks.get(body)
        if blk is None:
            continue
        out[body] = {
            "fusions": len(re.findall(r"= \S+ fusion\(", blk)),
            "instructions": len(re.findall(r"^\s+\S+ = ", blk, re.M)),
        }
    return out


def scan_body_kernel_count(compiled_text: str) -> dict:
    """Kernel stats of THE scan body -- the largest while body by
    instruction count (the local-step loop dominates every round program;
    smaller whiles are bookkeeping).  ``{"fusions": n, "instructions": m,
    "body": name}``; zeros when the program has no loop."""
    stats = while_body_stats(compiled_text)
    if not stats:
        return {"fusions": 0, "instructions": 0, "body": None}
    body = max(stats, key=lambda b: stats[b]["instructions"])
    return {**stats[body], "body": body}


# ---------------------------------------------------------------------------
# donation / aliasing, from the lowered & compiled IR text
# ---------------------------------------------------------------------------

def donation_marks(lowered_text: str) -> int:
    """Donated input tensors at lowering: ``jax.buffer_donor`` (donation
    deferred to XLA) + ``tf.aliasing_output`` (aliasing already pinned)."""
    return lowered_text.count("jax.buffer_donor") + \
        lowered_text.count("tf.aliasing_output")


def aliased_outputs(compiled_text: str) -> int:
    """Input-output alias pairs the compiled executable actually
    established -- donation that CONSUMED a buffer, not just permission.

    Parsed from the optimized ``HloModule`` header, which lists one
    ``{out_index}: (param, {}, may-alias)`` entry per aliased tensor inside
    ``input_output_alias={ ... }`` (brace-balanced scan: the entries
    themselves contain ``{}`` sub-indices)."""
    start = compiled_text.find("input_output_alias={")
    if start < 0:
        return 0
    i = compiled_text.index("{", start)
    depth, j = 0, i
    for j in range(i, min(len(compiled_text), i + 1_000_000)):
        c = compiled_text[j]
        if c == "{":
            depth += 1
        elif c == "}":
            depth -= 1
            if depth == 0:
                break
    block = compiled_text[i:j + 1]
    return block.count("may-alias") + block.count("must-alias")
