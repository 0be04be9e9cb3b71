"""Device time by scope for the scopes ISSUE 32 added
(`heterofl_tpu.obs.trace.MIXER_SCOPES`: `shortconv`, `shortconv/gate`, `gqa`)
beside the expert layer's (`scope_reduce_moe.EXTRA_SCOPES`), which the LFM2
cell enters too.

`scope_reduce_moe.table()` reads this process's traced run under ITS widened
vocabulary, which is a constant of that module; this one lends it the longer
list for the length of one read and puts list and memo back, so the accepted
metrics read what they read before.  A program without these scopes (a parent
commit) gives no table, and each metric returns None.  The `benchmark` PR of
PERF.md section 7 (1) folds both modules into `scope_reduce.SCOPES`.
"""

import math

from benchmark import harness, scope_reduce, scope_reduce_moe

MIXER_SCOPES = ("shortconv", "shortconv/gate", "gqa")

_memo = {}


def table():
    """`scope_reduce.reduce_scopes` of this process's traced run with the
    mixers' and the expert layer's scopes kept, read once; None without a
    trace or where no row carries one of them."""
    if "table" not in _memo:
        moe = scope_reduce_moe
        kept_scopes, kept_memo = moe.EXTRA_SCOPES, dict(moe._memo)
        moe.EXTRA_SCOPES = kept_scopes + MIXER_SCOPES
        moe._memo.clear()
        try:
            _memo["table"] = moe.table()
        finally:
            moe.EXTRA_SCOPES = kept_scopes
            moe._memo.clear()
            moe._memo.update(kept_memo)
    return _memo["table"]


#: a row filter: under `step/model` and under at least one of the scopes
any_of = scope_reduce_moe.any_of


def ms(reduction, pred, per=1.0):
    """Milliseconds a traced round of the rows ``pred`` accepts, over ``per``;
    None without a traced run, without these scopes, or where ``pred`` finds
    nothing."""
    if not reduction:
        return None
    found = table()
    s = scope_reduce.seconds(found, pred) if found else None
    return None if s is None else 1e3 * s / found["rounds"] / per


def roofline_pct(reduction, cell, scopes, flops_fn):
    """The FLOPs ``flops_fn`` (a name in the cell's `benchmark/flops/` file: a
    client step's, forward once and backward twice, at full width) of every
    active client, over the device time a step spends under ``scopes``,
    against the chip's bf16 peak; None where either is missing."""
    per_step = ms(reduction, any_of(*scopes), cell["steps_per_round"])
    if not per_step:
        return None
    _, config = harness.load_cell(cell["name"])
    flops = harness.load_module("flops", config["flops"])
    if not hasattr(flops, flops_fn):
        return None
    fed = config["federation"]
    active = int(math.ceil(fed["frac"] * fed["num_users"]))
    need = active * getattr(flops, flops_fn)(config)
    return 100.0 * need / cell["peak_flops_per_s"] / (per_step / 1e3)
