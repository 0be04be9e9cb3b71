"""Wire codecs (ISSUE 8 tentpole): in-program compressed aggregation.

HeteroFL's pitch is *communication*-efficient federated learning, yet the
wire format was dense f32 until this package: every fused round moved ONE
global reduction of ``sum(param_bytes) + count_bytes`` (89.4 MB for the
flagship ResNet-18 round, MEASUREMENTS.md Round 11).  The codecs here
compress each device's partial ``(update sums, count masks)`` contribution
INSIDE the scanned superstep program -- quantise -> ONE global psum ->
dequantise -- preserving the one-global-psum invariant the staticcheck
auditor enforces, with error-feedback residuals carried as a new flat
entry in the scan state so compression error is re-injected next round
instead of lost (PAPERS.md: Konecny et al. 1610.05492; EF-signSGD;
Dynamic Sampling and Selective Masking 2003.09603).

Codecs (``cfg['wire_codec']``):

* ``dense`` (default) -- today's program, bit for bit: no payload
  transform, no residual carry, no new program arguments.  Every
  pre-existing equivalence contract is untouched by construction.
* ``int8`` -- per-leaf stochastic-rounding quantisation with int32 psum
  accumulation: each device's contribution is rounded onto a shared
  per-leaf grid (scale derived from the replicated params carry, so no
  scale exchange is needed), packed 4 values per int32 in 8-bit lanes
  sized so the cross-device lane sums cannot carry, and summed in ONE
  integer psum.  Counts ride the same bind in exact 8-bit integer lanes
  (counts are small integers -- lossless).  Wire: 2 bytes/element = 25%
  of dense.
* ``signsgd`` -- 1-bit sign per element (4-bit lanes, 8 per int32) with a
  per-leaf per-device scale vector summed in the SAME bind (the decoder
  applies the mean scale); counts exact as in ``int8``.  Wire: ~1.5
  bytes/element = ~19% of dense.
* ``topk`` -- block sparsification riding the flat width-mask layout: each
  round transmits one of ``TOPK_BLOCKS`` contiguous blocks of the flat
  update (the block index drawn from the round key, identical on every
  device), with BOTH the value and count residuals accumulated so unsent
  coordinates keep a consistent sum/count ratio when they finally ship.
  Wire: 2 bytes/element = 25% of dense.

This module is import-light (no jax): the analytic byte accounting below
is THE single source of truth consumed by ``fed.core.level_codec_byte_table``
and the staticcheck wire budget (equality against traced psum operand
avals) -- there is no second bytes formula.
The jax codec implementations live in :mod:`.codecs`.
"""

from __future__ import annotations

from typing import Any, Dict, Tuple

#: the codec registry; ``dense`` is the default and the only lossless one
CODEC_NAMES = ("dense", "int8", "signsgd", "topk")

#: lossy codecs carry an error-feedback residual in the scan state
LOSSY_CODECS = ("int8", "signsgd", "topk")

#: blocks of the ``topk`` rotation: one block of ``ceil(N / TOPK_BLOCKS)``
#: flat coordinates ships per round
TOPK_BLOCKS = 4

#: lane widths (bits) of the packed integer payloads
VALUE_LANE_BITS = 8   # int8 codec: quantised values
SIGN_LANE_BITS = 4    # signsgd codec: sign bits with cross-device headroom
COUNT_LANE_BITS = 8   # both: exact integer count masks


def lane_words(n_elems: int, lane_bits: int) -> int:
    """int32 words needed to pack ``n_elems`` lanes of ``lane_bits`` bits."""
    per = 32 // lane_bits
    return -(-n_elems // per)


def resid_slots(name: str) -> int:
    """Flat error-feedback buffers the codec carries per device: ``topk``
    accumulates value AND count residuals (so a block that ships after m
    rounds carries m rounds of counts alongside m rounds of sums -- the
    sum/count ratio stays a mean); the quantising codecs carry one."""
    return 2 if name == "topk" else (0 if name == "dense" else 1)


def codec_payload_bytes(name: str, n_elems: int, n_leaves: int = 0,
                        blocks: int = TOPK_BLOCKS) -> int:
    """Per-participant psum payload bytes of one compressed training round:
    a pure function of the flat element count (and leaf count for the
    signsgd scale vector), exactly matching the traced psum operand avals
    -- which is what lets staticcheck enforce the compressed wire budget
    by EQUALITY, like the dense one."""
    if name == "dense":
        return 2 * 4 * n_elems  # f32 sums + f32 counts
    if name == "int8":
        return 4 * lane_words(n_elems, VALUE_LANE_BITS) \
            + 4 * lane_words(n_elems, COUNT_LANE_BITS)
    if name == "signsgd":
        return 4 * lane_words(n_elems, SIGN_LANE_BITS) \
            + 4 * lane_words(n_elems, COUNT_LANE_BITS) \
            + 4 * n_leaves
    if name == "topk":
        return 2 * 4 * (-(-n_elems // blocks))  # f32 value + count block
    raise ValueError(f"Not valid wire_codec: {name!r} (one of {CODEC_NAMES})")


def normalize_codec_map(raw: Dict[Any, Any]) -> Dict[float, str]:
    """Normalize a per-level codec map (ISSUE 9 satellite): keys are rate
    levels (floats, or their string forms -- JSON objects key by string),
    values codec names.  An all-dense map collapses to the plain ``dense``
    path at the engines; key COVERAGE of the engine's level table is the
    engine's check (it owns the table)."""
    out: Dict[float, str] = {}
    for k, v in raw.items():
        try:
            rate = float(k)  # staticcheck: allow(no-float-coercion): host config-key parse
        except (TypeError, ValueError):
            raise ValueError(f"Not valid wire_codec level key: {k!r} (a rate "
                             f"level, e.g. 1.0 or '0.0625')")
        if v not in CODEC_NAMES:
            raise ValueError(f"Not valid wire_codec for level {rate:g}: "
                             f"{v!r} (one of {CODEC_NAMES})")
        if rate in out:
            # two string keys coercing to one rate ("1" and "1.0") would
            # otherwise silently last-win -- the loud-validation convention
            # says a config collision fails, never resolves arbitrarily
            raise ValueError(f"Not valid wire_codec map: level {rate:g} "
                             f"assigned twice (duplicate keys coerce to "
                             f"the same rate)")
        out[rate] = v
    if not out:
        raise ValueError("Not valid wire_codec: an empty per-level map")
    return out


def resolve_codec_cfg(cfg: Dict[str, Any], engine_strategy: str = None):
    """Validate ``cfg['wire_codec']`` / ``cfg['error_feedback']`` and return
    ``(codec, error_feedback)`` -- ``codec`` is a name, or a normalized
    ``{rate: name}`` per-level map (ISSUE 9 satellite; grouped engine's
    fused superstep only -- the engines enforce that placement).

    Loud ``ValueError`` on unknown values (the PR 6 convention: stale or
    typo'd config keys fail at validation, never as silent defaults
    mid-run).  ``error_feedback`` defaults True and only matters for lossy
    codecs.

    ``engine_strategy`` is the engine-direct re-validation hook: an engine
    constructor passes its own identity and gets codec-local validation
    only (names, map shape, error_feedback).  The strategy-coupled
    cross-checks below belong to the config-RESOLUTION path alone: the
    caller of an engine class picked the strategy (whatever
    ``cfg['strategy']`` says), drives ``k`` per ``train_superstep`` call
    (``cfg['superstep_rounds']`` binds only the driver's schedule), and
    the engines keep their own placement refusals -- the masked engine
    refuses a per-level map at dispatch, the grouped engine checks map
    keys against its level table."""
    name = cfg.get("wire_codec", "dense") or "dense"
    if isinstance(name, dict):
        name = normalize_codec_map(name)
        if all(v == "dense" for v in name.values()):
            name = "dense"
    elif name not in CODEC_NAMES:
        raise ValueError(f"Not valid wire_codec: {name!r} "
                         f"(one of {CODEC_NAMES})")
    ef = cfg.get("error_feedback", True)
    if not isinstance(ef, bool):
        raise ValueError(f"Not valid error_feedback: {ef!r} (must be a bool; "
                         f"it gates the residual re-injection of lossy wire "
                         f"codecs)")
    if engine_strategy is not None:
        return name, ef
    # codec x engine cross-checks (ISSUE 18): promoted from the driver so
    # a codec the engines cannot lower refuses at config resolution, not
    # at experiment construction.  This validator OWNS the codec axis in
    # the staticcheck config lattice.
    strategy = cfg.get("strategy", "masked") or "masked"
    if isinstance(name, dict) and strategy != "grouped":
        raise ValueError(
            f"Not valid wire_codec: a per-level map needs strategy="
            f"'grouped' (its fused superstep compresses each level's "
            f"sliced payload under that level's codec), got strategy="
            f"{strategy!r}")
    if name != "dense":
        if strategy == "sliced":
            raise ValueError(
                f"Not valid wire_codec={name!r} with strategy='sliced': "
                f"the sliced debug twin aggregates on the host, there is "
                f"no psum to compress -- use a mesh-native strategy "
                f"('masked' or 'grouped')")
        if strategy == "grouped" \
                and int(cfg.get("superstep_rounds", 1) or 1) <= 1 \
                and (cfg.get("client_store", "eager") or "eager") != "stream":
            raise ValueError(
                f"Not valid wire_codec={name!r} with strategy='grouped' at "
                f"superstep_rounds<=1 and client_store='eager': the K=1 "
                f"host-orchestrated path reduces per level and has no "
                f"single global psum to compress (set superstep_rounds>1 "
                f"or client_store='stream')")
    return name, ef


def make_codec(name: str, spec, participants: int, error_feedback: bool = True,
               axis: str = "clients"):
    """Build the jax codec object (None for ``dense``); lazy import so the
    analytic half of this package stays jax-free."""
    if name == "dense":
        return None
    from .codecs import Int8Codec, SignSGDCodec, TopKCodec

    cls = {"int8": Int8Codec, "signsgd": SignSGDCodec, "topk": TopKCodec}
    if name not in cls:
        raise ValueError(f"Not valid wire_codec: {name!r} "
                         f"(one of {CODEC_NAMES})")
    return cls[name](spec, participants, error_feedback=error_feedback,
                     axis=axis)
