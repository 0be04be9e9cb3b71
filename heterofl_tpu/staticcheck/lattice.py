"""Config-lattice exhaustiveness pass (ISSUE 18 tentpole).

The repo's feature axes (engine x placement x codec x scheduler x
telemetry x ledger x arms x quarantine x sampler x store x pod x
eval-cohort x chunk) multiply into a lattice of ~10^5 nominally-expressible
configs.  Before this pass, the only exhaustiveness statement was
social: each subsystem promised its validator refused "the bad combos"
and the audit compiled "the good ones".  This module makes the
statement mechanical -- it enumerates EVERY point of the declared
lattice (one machine-readable axis table, :data:`AXES`) and proves each
point is exactly one of:

* **SUPPORTED** -- its structural core maps to an audited-green anchor
  program (:data:`ANCHORS`, names cross-checked against the live audit
  report) and every riding axis value is covered by a *named
  equivalence contract* (:data:`CONTRACTS`, each carrying its audited
  program evidence);
* **REFUSED** -- replaying :func:`heterofl_tpu.config.validator_chain`
  on the point's cfg raises a typed ``ValueError`` from exactly one
  ``resolve_*`` validator, the refusal matches a *declared* refusal
  rule (:data:`REFUSAL_RULES`: same owner validator, message naming the
  offending cfg keys), and the rule actually fires somewhere (a
  declared rule that never fires is a silent-fallback finding);
* **UNREACHED** -- anything else, which is a finding: an unclassified
  combo, a refusal with undeclared provenance, or a declared refusal
  the validators no longer deliver (the silent fallback).

Deliberately jax-free (the report.py convention): classification only
replays the config validators, so ``--lattice-md`` and the regression
tests run without booting a backend.  The audit front passes its
compiled-program report in via ``audited=`` to also prove every piece
of program evidence is audited green (``lattice-evidence-missing``).

Every table is injectable (``lattice_check(axes=..., rules=...,
anchors=..., contracts=...)``) so the regression tests can seed an
unclassified combo, a silently-falling-back rule, or rotted evidence
and watch the named finding trip.
"""

from __future__ import annotations

import itertools
from typing import Any, Dict, Iterable, List, Optional, Sequence, Tuple

from .. import config as C

#: How many example points a single finding rule reports before
#: summarising -- the full list of a rotted axis can be ~10^4 points.
MAX_FINDING_SAMPLES = 12

# ---------------------------------------------------------------------------
# the declared feature lattice
# ---------------------------------------------------------------------------

#: THE machine-readable axis table: every (axis, value-domain) the repo
#: declares.  The first value of each axis is its default; the product
#: of all domains is the lattice this pass enumerates exhaustively.
#: Domains mirror the config registries (config.STRATEGIES & friends)
#: -- test_lattice.py pins that correspondence so the table cannot rot.
AXES: Tuple[Tuple[str, Tuple[str, ...]], ...] = (
    ("engine", ("masked", "grouped", "sliced")),
    ("placement", ("replicated", "sharded")),
    ("levels", ("span", "slices")),
    ("store", ("eager", "stream")),
    ("codec", ("dense", "int8", "signsgd", "topk")),
    ("scheduler", ("k1", "k8", "k1-deadline", "k8-deadline",
                   "k1-buffered", "k8-buffered")),
    ("telemetry", ("off", "on", "hist")),
    ("ledger", ("off", "on")),
    ("arms", ("off", "e2")),
    ("quarantine", ("off", "on")),
    ("sampler", ("prp", "perm")),
    ("eval_cohort", ("off", "c8")),
    ("pod", ("local", "pod")),
    ("chunk", ("all", "c1")),
)

#: cfg skeleton every lattice point is written over: the non-axis keys
#: the validators consult (num_users for eval cohorts, the vision model
#: for the eval-cohort x LM refusal, lockstep fetch cadence).
BASE_CFG: Dict[str, Any] = {
    "num_users": 100,
    "model_name": "conv",
    "metrics_fetch_every": 1,
    "eval_interval": 1,
    "scheduler_name": "MultiStepLR",
}


def point_cfg(point: Dict[str, str]) -> Dict[str, Any]:
    """Materialise one lattice point as the cfg dict the validator chain
    consumes -- THE single mapping from axis values to cfg keys."""
    cfg = dict(BASE_CFG)
    cfg["strategy"] = point["engine"]
    cfg["data_placement"] = point["placement"]
    cfg["level_placement"] = point["levels"]
    cfg["client_store"] = point["store"]
    cfg["wire_codec"] = point["codec"]
    sched = point["scheduler"]
    cfg["superstep_rounds"] = 1 if sched.startswith("k1") else 8
    if sched.endswith("-deadline"):
        cfg["schedule"] = {"deadline": {"min_frac": 0.5}}
    elif sched.endswith("-buffered"):
        cfg["schedule"] = {"aggregation": "buffered"}
    else:
        cfg["schedule"] = None
    cfg["telemetry"] = point["telemetry"]
    cfg["ledger"] = point["ledger"]
    cfg["arms"] = None if point["arms"] == "off" else 2
    cfg["quarantine"] = point["quarantine"]
    cfg["sampler"] = point["sampler"]
    cfg["eval_cohort"] = None if point["eval_cohort"] == "off" else 8
    cfg["strict_placement"] = point["pod"] == "pod"
    cfg["round_chunk"] = None if point["chunk"] == "all" else 1
    return cfg


#: cfg key(s) each axis writes -- the provenance test asserts a REFUSED
#: point's message names the keys its matching rule declares, and those
#: keys must come from this map.
AXIS_CFG_KEYS: Dict[str, Tuple[str, ...]] = {
    "engine": ("strategy",),
    "placement": ("data_placement",),
    "levels": ("level_placement",),
    "store": ("client_store",),
    "codec": ("wire_codec",),
    "scheduler": ("superstep_rounds", "schedule"),
    "telemetry": ("telemetry",),
    "ledger": ("ledger",),
    "arms": ("arms",),
    "quarantine": ("quarantine",),
    "sampler": ("sampler",),
    "eval_cohort": ("eval_cohort",),
    "pod": ("strict_placement",),
    "chunk": ("round_chunk",),
}

# ---------------------------------------------------------------------------
# declared refusals: the provenance table
# ---------------------------------------------------------------------------

#: Every cross-axis refusal the lattice can reach, declared: ``when``
#: matches axis values (a string or a tuple of alternatives), ``owner``
#: is the ONE validator that must raise first in the chain, ``keys``
#: the cfg keys its message must name.  A REFUSED point with no
#: validating rule is an undeclared refusal (lattice-unreached); a rule
#: that validates zero points is a silent fallback
#: (lattice-silent-fallback).  Ordering does not matter: any validating
#: rule clears a point.
REFUSAL_RULES: Tuple[Dict[str, Any], ...] = (
    {"id": "chunk-needs-masked",
     "when": {"engine": ("grouped", "sliced"), "chunk": "c1"},
     "owner": "resolve_chunk_cfg", "keys": ("round_chunk", "strategy")},
    {"id": "grouped-sharded",
     "when": {"engine": "grouped", "placement": "sharded"},
     "owner": "resolve_placement_cfg", "keys": ("data_placement", "strategy")},
    {"id": "slices-needs-grouped",
     "when": {"engine": ("masked", "sliced"), "levels": "slices"},
     "owner": "resolve_placement_cfg",
     "keys": ("level_placement", "strategy")},
    {"id": "sliced-sharded-noop",
     "when": {"engine": "sliced", "placement": "sharded"},
     "owner": "resolve_placement_cfg", "keys": ("data_placement", "strategy")},
    {"id": "stream-needs-mesh-native",
     "when": {"engine": "sliced", "store": "stream"},
     "owner": "resolve_store_cfg", "keys": ("client_store", "strategy")},
    {"id": "stream-sharded-noop",
     "when": {"engine": ("masked", "grouped"), "store": "stream",
              "placement": "sharded"},
     "owner": "resolve_store_cfg", "keys": ("data_placement", "client_store")},
    {"id": "sliced-superstep",
     "when": {"engine": "sliced",
              "scheduler": ("k8", "k8-deadline", "k8-buffered")},
     "owner": "resolve_superstep_cfg",
     "keys": ("superstep_rounds", "strategy")},
    {"id": "sliced-codec",
     "when": {"engine": "sliced", "codec": ("int8", "signsgd", "topk"),
              "scheduler": ("k1", "k1-deadline", "k1-buffered")},
     "owner": "resolve_codec_cfg", "keys": ("wire_codec", "strategy")},
    {"id": "grouped-k1-codec",
     "when": {"engine": "grouped", "codec": ("int8", "signsgd", "topk"),
              "scheduler": ("k1", "k1-deadline", "k1-buffered"),
              "store": "eager", "placement": "replicated"},
     "owner": "resolve_codec_cfg",
     "keys": ("wire_codec", "strategy", "superstep_rounds", "client_store")},
    {"id": "sliced-schedule",
     "when": {"engine": "sliced",
              "scheduler": ("k1-deadline", "k1-buffered"),
              "codec": "dense"},
     "owner": "resolve_schedule_cfg", "keys": ("schedule", "strategy")},
    {"id": "buffered-lossy-codec",
     "when": {"engine": ("masked", "grouped"),
              "scheduler": ("k1-buffered", "k8-buffered"),
              "codec": ("int8", "signsgd", "topk")},
     "owner": "resolve_schedule_cfg", "keys": ("schedule", "wire_codec")},
    {"id": "grouped-k1-buffered",
     "when": {"engine": "grouped", "scheduler": "k1-buffered",
              "codec": "dense", "store": "eager", "placement": "replicated"},
     "owner": "resolve_schedule_cfg",
     "keys": ("schedule", "strategy", "superstep_rounds", "client_store")},
    {"id": "eval-cohort-needs-stream",
     "when": {"eval_cohort": "c8", "store": "eager"},
     "owner": "resolve_eval_cohort", "keys": ("eval_cohort", "client_store")},
    {"id": "sliced-telemetry",
     "when": {"engine": "sliced", "telemetry": ("on", "hist"),
              "store": "eager", "eval_cohort": "off"},
     "owner": "resolve_telemetry_cfg", "keys": ("telemetry", "strategy")},
    {"id": "grouped-k1-telemetry",
     "when": {"engine": "grouped", "telemetry": ("on", "hist"),
              "scheduler": ("k1", "k1-deadline"), "store": "eager",
              "codec": "dense", "placement": "replicated",
              "eval_cohort": "off"},
     "owner": "resolve_telemetry_cfg",
     "keys": ("telemetry", "strategy", "superstep_rounds", "client_store")},
    {"id": "sliced-ledger",
     "when": {"engine": "sliced", "ledger": "on"},
     "owner": "resolve_ledger_cfg", "keys": ("ledger", "strategy")},
    {"id": "sharded-ledger",
     "when": {"engine": "masked", "placement": "sharded", "ledger": "on",
              "store": "eager"},
     "owner": "resolve_ledger_cfg", "keys": ("ledger", "data_placement")},
    {"id": "sliced-quarantine",
     "when": {"engine": "sliced", "quarantine": "on"},
     "owner": "resolve_quarantine_cfg", "keys": ("quarantine", "strategy")},
    {"id": "sliced-arms",
     "when": {"engine": "sliced", "arms": "e2"},
     "owner": "resolve_arms_cfg", "keys": ("arms", "strategy")},
    {"id": "arms-ledger",
     "when": {"engine": ("masked", "grouped"), "arms": "e2", "ledger": "on"},
     "owner": "resolve_arms_cfg", "keys": ("arms", "ledger")},
    {"id": "arms-buffered",
     "when": {"engine": ("masked", "grouped"), "arms": "e2",
              "scheduler": ("k1-buffered", "k8-buffered"), "codec": "dense",
              "ledger": "off"},
     "owner": "resolve_arms_cfg", "keys": ("arms", "schedule")},
    {"id": "arms-stream",
     "when": {"engine": ("masked", "grouped"), "arms": "e2",
              "store": "stream", "ledger": "off",
              "scheduler": ("k1", "k8", "k1-deadline", "k8-deadline")},
     "owner": "resolve_arms_cfg", "keys": ("arms", "client_store")},
    {"id": "grouped-arms-codec",
     "when": {"engine": "grouped", "arms": "e2",
              "codec": ("int8", "signsgd", "topk"),
              "scheduler": ("k8", "k8-deadline"), "store": "eager",
              "ledger": "off"},
     "owner": "resolve_arms_cfg", "keys": ("arms", "wire_codec", "strategy")},
    {"id": "grouped-arms-telemetry",
     "when": {"engine": "grouped", "arms": "e2", "telemetry": ("on", "hist"),
              "codec": "dense", "scheduler": ("k8", "k8-deadline"),
              "store": "eager", "ledger": "off"},
     "owner": "resolve_arms_cfg", "keys": ("arms", "telemetry", "strategy")},
    {"id": "grouped-arms-quarantine",
     "when": {"engine": "grouped", "arms": "e2", "quarantine": "on",
              "telemetry": "off", "codec": "dense",
              "scheduler": ("k1", "k8", "k1-deadline", "k8-deadline"),
              "store": "eager", "ledger": "off"},
     "owner": "resolve_arms_cfg", "keys": ("arms", "quarantine", "strategy")},
    {"id": "grouped-arms-slices",
     "when": {"engine": "grouped", "arms": "e2", "levels": "slices",
              "quarantine": "off", "telemetry": "off", "codec": "dense",
              "scheduler": ("k1", "k8", "k1-deadline", "k8-deadline"),
              "store": "eager", "ledger": "off"},
     "owner": "resolve_arms_cfg", "keys": ("arms", "level_placement")},
)

# ---------------------------------------------------------------------------
# declared support: anchors + contracts
# ---------------------------------------------------------------------------

#: Structural-core anchors: (engine, placement, levels, store) -> the
#: audited program (``program:<name>``) or named contract
#: (``contract:<name>``) that proves the core lowers, per K class.
#: A surviving point whose core has no anchor is UNREACHED -- this map
#: is where the exhaustiveness proof has teeth.
ANCHORS: Dict[Tuple[str, str, str, str], Dict[str, str]] = {
    ("masked", "replicated", "span", "eager"): {
        "k1": "program:masked/replicated/k1",
        "k8": "program:masked/replicated/k8"},
    ("masked", "replicated", "span", "stream"): {
        "k1": "contract:stream-k1-superstep",
        "k8": "program:masked/stream/k8"},
    ("masked", "sharded", "span", "eager"): {
        "k1": "program:masked/sharded/k1",
        "k8": "program:masked/sharded/k8"},
    ("grouped", "replicated", "span", "eager"): {
        "k1": "contract:grouped-k1-host-orchestrated",
        "k8": "program:grouped/span/k8-fused"},
    ("grouped", "replicated", "span", "stream"): {
        "k1": "contract:stream-k1-superstep",
        "k8": "program:grouped/stream/span/k8"},
    ("grouped", "replicated", "slices", "eager"): {
        "k1": "contract:grouped-k1-host-orchestrated",
        "k8": "program:grouped/slices/k8-fused"},
    ("grouped", "replicated", "slices", "stream"): {
        "k1": "contract:stream-k1-superstep",
        "k8": "program:grouped/stream/slices/k8"},
    ("sliced", "replicated", "span", "eager"): {
        "k1": "contract:sliced-reference-twin"},
}

#: Named equivalence contracts: each covers one riding axis value (or a
#: k1 anchor) on every surviving point, with the audited programs that
#: evidence it.  ``evidence`` entries are ``program:<audited name>``
#: (checked against the live audit report), ``check:<cross-check
#: section>`` or ``test:<pytest node>`` (documentary).
CONTRACTS: Dict[str, Dict[str, Any]] = {
    "stream-k1-superstep": {
        "note": "the driver routes client_store='stream' at "
                "superstep_rounds=1 through the k=1 superstep program "
                "(the fused path with a length-1 scan), never the legacy "
                "round path",
        "evidence": ("program:masked/stream/k8",
                     "program:grouped/stream/span/k8",
                     "test:tests/test_streaming.py")},
    "grouped-k1-host-orchestrated": {
        "note": "grouped at K=1 runs L per-level programs + one combine "
                "program; audited per level and as the combine",
        "evidence": ("program:grouped/span/level-1/k1",
                     "program:grouped/span/combine",
                     "test:tests/test_grouped.py")},
    "sliced-reference-twin": {
        "note": "the sliced engine is the host-orchestrated debug twin: "
                "bitwise-equivalent to the masked engine per round "
                "(shared client_stream_keys derivation), never compiled "
                "as one program",
        "evidence": ("program:masked/replicated/k1",
                     "test:tests/test_sliced.py")},
    "codec-wire-frontier": {
        "note": "a lossy codec wraps THE one global psum (the wire "
                "frontier); data placement and client store only change "
                "staging, audited by the codec variants per engine",
        "evidence": ("program:masked/replicated/k8-int8",
                     "program:masked/sharded/k8-int8",
                     "program:grouped/span/k8-fused-int8",
                     "program:grouped/slices/k8-fused-int8",
                     "check:wire_frontier")},
    "deadline-budget-draw": {
        "note": "deadline budgets are per-client draws folded into the "
                "round core; engine-invariant by the shared "
                "deadline_steps derivation",
        "evidence": ("program:masked/replicated/k8-deadline",
                     "program:grouped/span/k8-fused-deadline",
                     "test:tests/test_sched.py")},
    "buffered-staleness-carry": {
        "note": "buffered aggregation adds one replicated [2, total] "
                "carry to the superstep scan; K=1 is the length-1 scan "
                "of the same program",
        "evidence": ("program:masked/replicated/k8-buffered",
                     "program:grouped/span/k8-fused-buffered",
                     "test:tests/test_sched.py")},
    "telemetry-probe-rows": {
        "note": "probes ride the round core as extra metric rows "
                "(split_probes); store/placement only change staging",
        "evidence": ("program:masked/replicated/k1-telemetry",
                     "program:masked/replicated/k8-telemetry",
                     "program:grouped/span/k8-fused-telemetry",
                     "test:tests/test_obs.py")},
    "telemetry-hist-rows": {
        "note": "hist mode widens the probe rows with bucket counts; "
                "same carriage as telemetry='on'",
        "evidence": ("program:masked/replicated/k1-hist",
                     "program:masked/replicated/k8-hist",
                     "program:grouped/span/k8-fused-hist",
                     "test:tests/test_obs.py")},
    "ledger-host-fold": {
        "note": "the ledger is a host-side O(active) fold over fetched "
                "metric rows -- NEVER a program change; the compiled "
                "program set is identical with it on",
        "evidence": ("test:tests/test_obs.py",)},
    "arms-batched-superstep": {
        "note": "arms vmap the superstep scan over a leading [E] axis; "
                "E=1 is bit-identical to the unbatched program and the "
                "tail dispatch covers k=1",
        "evidence": ("program:masked/replicated/k8-arms2",
                     "program:grouped/span/k8-fused-arms2",
                     "check:arms",
                     "test:tests/test_arms.py")},
    "quarantine-gate": {
        "note": "the quarantine gate folds into each round/level core "
                "before aggregation; engine-invariant counter rows",
        "evidence": ("program:masked/replicated/k1-quarantine",
                     "program:masked/replicated/k8-quarantine",
                     "program:grouped/span/k8-fused-quarantine",
                     "test:tests/test_chaos.py")},
    "sampler-stream-commitment": {
        "note": "both sampler kinds draw the identical cohort in-jit and "
                "on the host (sampler_stream_check: bitwise), so the "
                "sampler axis never changes program structure",
        "evidence": ("program:masked/replicated/k8-perm",
                     "check:sampler",
                     "test:tests/test_sampling.py")},
    "eval-cohort-sampled-local": {
        "note": "eval_cohort subsamples the streaming store's Local eval "
                "operand staging; the eval-fused program family is the "
                "same (cohort size is a staging shape)",
        "evidence": ("program:masked/stream/k8-eval1",
                     "test:tests/test_sched.py")},
    "chunked-cohort-scan": {
        "note": "round_chunk trains the cohort c slots at a time inside the "
                "masked engine's round core: a scan over the chunks carries "
                "the aggregate's sums, the counts follow in a loop of their "
                "own, the per-slot streams and the one psum are the "
                "unchunked round's; every rider acts on the sums and "
                "counts after the scan (the superstep scans the same core)",
        "evidence": ("test:tests/test_round.py",)},
    "pod-placement-pinned": {
        "note": "strict_placement pins the pod layout: multi-process "
                "slices refuse instead of silently falling back to span; "
                "single-process meshes are unaffected",
        "evidence": ("program:grouped/slices/k8-fused/mh",
                     "program:grouped/stream/slices/k8/mh",
                     "test:tests/test_grouped.py")},
}

#: riding-axis value -> contract that covers it on surviving points.
#: Axes absent here (engine/placement/levels/store) are anchor
#: coordinates; default values ride the anchor itself.
RIDER_CONTRACTS: Dict[Tuple[str, str], str] = {
    ("codec", "int8"): "codec-wire-frontier",
    ("codec", "signsgd"): "codec-wire-frontier",
    ("codec", "topk"): "codec-wire-frontier",
    ("scheduler", "k1-deadline"): "deadline-budget-draw",
    ("scheduler", "k8-deadline"): "deadline-budget-draw",
    ("scheduler", "k1-buffered"): "buffered-staleness-carry",
    ("scheduler", "k8-buffered"): "buffered-staleness-carry",
    ("telemetry", "on"): "telemetry-probe-rows",
    ("telemetry", "hist"): "telemetry-hist-rows",
    ("ledger", "on"): "ledger-host-fold",
    ("arms", "e2"): "arms-batched-superstep",
    ("quarantine", "on"): "quarantine-gate",
    ("sampler", "perm"): "sampler-stream-commitment",
    ("eval_cohort", "c8"): "eval-cohort-sampled-local",
    ("pod", "pod"): "pod-placement-pinned",
    ("chunk", "c1"): "chunked-cohort-scan",
}

# ---------------------------------------------------------------------------
# classification
# ---------------------------------------------------------------------------


def iter_points(axes: Sequence[Tuple[str, Tuple[str, ...]]] = AXES
                ) -> Iterable[Dict[str, str]]:
    """Every point of the declared lattice, as axis -> value dicts."""
    names = [a for a, _ in axes]
    for combo in itertools.product(*(vals for _, vals in axes)):
        yield dict(zip(names, combo))


def _rule_matches(rule: Dict[str, Any], point: Dict[str, str]) -> bool:
    for axis, want in rule["when"].items():
        have = point.get(axis)
        if isinstance(want, tuple):
            if have not in want:
                return False
        elif have != want:
            return False
    return True


def classify_point(point: Dict[str, str],
                   chain: Optional[Sequence[Tuple[str, Any]]] = None
                   ) -> Dict[str, Any]:
    """Replay the validator chain on one point: REFUSED with the owning
    validator + message, or SUPPORTED-candidate (evidence resolved by
    the caller)."""
    cfg = point_cfg(point)
    for name, fn in (chain if chain is not None else C.validator_chain()):
        try:
            fn(cfg)
        except ValueError as e:
            return {"class": "REFUSED", "owner": name, "message": str(e)}
    return {"class": "SUPPORTED"}


def support_evidence(point: Dict[str, str],
                     anchors: Dict[Tuple[str, str, str, str],
                                   Dict[str, str]] = ANCHORS,
                     riders: Dict[Tuple[str, str], str] = RIDER_CONTRACTS,
                     contracts: Dict[str, Dict[str, Any]] = CONTRACTS,
                     axes: Sequence[Tuple[str, Tuple[str, ...]]] = AXES,
                     ) -> Optional[List[str]]:
    """Evidence refs proving a surviving point is supported, or ``None``
    when the declared tables leave it uncovered (an UNREACHED hole)."""
    core = (point["engine"], point["placement"], point["levels"],
            point["store"])
    k_class = "k1" if point["scheduler"].startswith("k1") else "k8"
    anchor = anchors.get(core, {}).get(k_class)
    if anchor is None:
        return None
    evidence = [anchor]
    defaults = {axis: vals[0] for axis, vals in axes}
    for axis, value in point.items():
        if axis in ("engine", "placement", "levels", "store"):
            continue
        if axis == "scheduler" and value in ("k1", "k8"):
            continue
        if value == defaults.get(axis):
            continue
        name = riders.get((axis, value))
        if name is None or name not in contracts:
            return None
        evidence.append(f"contract:{name}")
    return evidence


def lattice_check(chain: Optional[Sequence[Tuple[str, Any]]] = None,
                  axes: Sequence[Tuple[str, Tuple[str, ...]]] = AXES,
                  rules: Sequence[Dict[str, Any]] = REFUSAL_RULES,
                  anchors: Dict[Tuple[str, str, str, str],
                                Dict[str, str]] = ANCHORS,
                  riders: Dict[Tuple[str, str], str] = RIDER_CONTRACTS,
                  contracts: Dict[str, Dict[str, Any]] = CONTRACTS,
                  audited: Optional[Iterable[str]] = None,
                  ) -> Dict[str, Any]:
    """Run the exhaustiveness pass; returns the ``lattice`` section dict
    for STATICCHECK.json (``ok``/counts/per-rule fire counts/findings).

    ``audited``: the live audit report's program names; when given,
    every ``program:`` evidence ref must be in it (and green is the
    caller's concern -- run_audit only passes names of green programs).
    """
    chain = list(chain) if chain is not None else C.validator_chain()
    owners = {name for name, _ in chain}
    fired: Dict[str, int] = {r["id"]: 0 for r in rules}
    counts = {"SUPPORTED": 0, "REFUSED": 0, "UNREACHED": 0}
    findings: List[Dict[str, str]] = []
    samples: Dict[str, int] = {}
    evidence_used: Dict[str, int] = {}
    owner_counts: Dict[str, int] = {}

    def fail(rule: str, point: Optional[Dict[str, str]], message: str):
        samples[rule] = samples.get(rule, 0) + 1
        if samples[rule] > MAX_FINDING_SAMPLES:
            return
        where = "lattice" if point is None else \
            "lattice:" + "/".join(point[a] for a, _ in axes)
        findings.append({"rule": rule, "where": where, "message": message})

    for r in rules:
        if r["owner"] not in owners:
            fail("lattice-silent-fallback", None,
                 f"refusal rule {r['id']!r} names owner {r['owner']!r}, "
                 f"which is not in the validator chain")

    n_points = 0
    for point in iter_points(axes):
        n_points += 1
        res = classify_point(point, chain)
        if res["class"] == "REFUSED":
            owner, message = res["owner"], res["message"]
            owner_counts[owner] = owner_counts.get(owner, 0) + 1
            validated = False
            for r in rules:
                if not _rule_matches(r, point):
                    continue
                if r["owner"] != owner:
                    continue
                if all(k in message for k in r["keys"]):
                    fired[r["id"]] += 1
                    validated = True
                    break
            if validated:
                counts["REFUSED"] += 1
            else:
                counts["UNREACHED"] += 1
                fail("lattice-unreached", point,
                     f"refusal with undeclared provenance: {owner} raised "
                     f"{message!r} but no declared rule matches "
                     f"(owner + offending-key naming)")
            continue
        # validators passed: a declared refusal that did NOT fire here is
        # a silent fallback -- the combo would run and quietly degrade.
        silent = [r["id"] for r in rules if _rule_matches(r, point)]
        if silent:
            counts["UNREACHED"] += 1
            fail("lattice-silent-fallback", point,
                 f"declared refusal rule(s) {silent} match this point but "
                 f"no validator refused it -- the combo silently falls "
                 f"back / degrades mid-run")
            continue
        evidence = support_evidence(point, anchors, riders, contracts, axes)
        if evidence is None:
            counts["UNREACHED"] += 1
            fail("lattice-unreached", point,
                 "unclassified combo: no validator refuses it and no "
                 "anchor/contract covers it")
            continue
        counts["SUPPORTED"] += 1
        for ref in evidence:
            evidence_used[ref] = evidence_used.get(ref, 0) + 1

    for r in rules:
        if r["owner"] in owners and fired[r["id"]] == 0:
            fail("lattice-silent-fallback", None,
                 f"declared refusal rule {r['id']!r} (owner {r['owner']}) "
                 f"validated zero lattice points -- either the combo "
                 f"silently falls back or the rule rotted")

    # evidence liveness: every program ref used by a supported point (or
    # named by a live contract) must be in the audited-green program set
    if audited is not None:
        audited = set(audited)
        program_refs = {ref for ref in evidence_used if
                        ref.startswith("program:")}
        for name, c in contracts.items():
            if f"contract:{name}" in evidence_used or name in {
                    v.split(":", 1)[1] for a in anchors.values()
                    for v in a.values() if v.startswith("contract:")}:
                program_refs.update(e for e in c.get("evidence", ())
                                    if e.startswith("program:"))
        for ref in sorted(program_refs):
            if ref.split(":", 1)[1] not in audited:
                fail("lattice-evidence-missing", None,
                     f"evidence {ref} backs supported lattice points but "
                     f"is not in the audited program set")

    ok = not findings
    return {
        "ok": ok,
        "points": n_points,
        "supported": counts["SUPPORTED"],
        "refused": counts["REFUSED"],
        "unreached": counts["UNREACHED"],
        "axes": {a: list(v) for a, v in axes},
        "refusal_rules": [{"id": r["id"], "owner": r["owner"],
                           "points": fired[r["id"]]} for r in rules],
        "refusal_owners": owner_counts,
        "contracts": [{"name": n,
                       "points": evidence_used.get(f"contract:{n}", 0),
                       "evidence": list(c.get("evidence", ()))}
                      for n, c in sorted(contracts.items())],
        "evidence_checked": audited is not None,
        "findings": findings,
    }


# ---------------------------------------------------------------------------
# human-readable rendering (README's Compatibility-lattice section)
# ---------------------------------------------------------------------------


def lattice_markdown(section: Optional[Dict[str, Any]] = None) -> str:
    """Render the lattice summary as the README's auto-generated
    "Compatibility lattice" block (jax-free; classification only)."""
    if section is None:
        section = lattice_check()
    lines = [
        "<!-- generated by: python -m heterofl_tpu.staticcheck "
        "--lattice-md (do not edit by hand) -->",
        "",
        f"The declared feature lattice has **{section['points']}** points "
        f"({' x '.join(str(len(v)) for v in section['axes'].values())} "
        f"over {len(section['axes'])} axes): "
        f"**{section['supported']} supported** (audited anchor + named "
        f"contracts), **{section['refused']} refused** (typed ValueError "
        f"at config resolution), **{section['unreached']} unreached**.",
        "",
        "| axis | values |",
        "|---|---|",
    ]
    for axis, vals in section["axes"].items():
        pretty = [f"`{v}`" + (" (default)" if i == 0 else "")
                  for i, v in enumerate(vals)]
        lines.append(f"| {axis} | {', '.join(pretty)} |")
    lines += [
        "",
        "Refusal provenance (one owning validator per axis; points each "
        "rule refuses):",
        "",
        "| rule | owner | points |",
        "|---|---|---|",
    ]
    for r in section["refusal_rules"]:
        lines.append(f"| `{r['id']}` | `{r['owner']}` | {r['points']} |")
    lines += [
        "",
        "Equivalence contracts carrying the riding axes (points each "
        "covers; program evidence is audited green):",
        "",
        "| contract | points | evidence |",
        "|---|---|---|",
    ]
    for c in section["contracts"]:
        ev = ", ".join(f"`{e}`" for e in c["evidence"])
        lines.append(f"| `{c['name']}` | {c['points']} | {ev} |")
    if section["findings"]:
        lines += ["", "**FINDINGS:**", ""]
        lines += [f"- `{f['rule']}` at `{f['where']}`: {f['message']}"
                  for f in section["findings"]]
    return "\n".join(lines) + "\n"
