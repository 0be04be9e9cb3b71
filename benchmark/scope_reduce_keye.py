"""Device time by scope for the scopes ISSUE 35 added
(`heterofl_tpu.obs.trace.SPARSE_SCOPES`: `sparse/index`, `sparse/select`)
beside grouped-query attention's and the expert layer's, which the Keye cell
enters too.

No third copy of the reader: `scope_reduce_lfm2` reads this process's traced
run under ITS list of scopes (which it lends `scope_reduce_moe` in turn) and
keeps the table in ITS memo; for the length of a call this module lends it the
longer list and a memo of its own and puts both back, so the accepted metrics
read what they read before.  A program without these scopes (a parent commit)
gives no table, and each metric returns None.  The `benchmark` PR of PERF.md
section 7 (1) folds the three modules into `scope_reduce.SCOPES`.
"""

import contextlib

from benchmark import scope_reduce, scope_reduce_lfm2

SPARSE_SCOPES = ("sparse/index", "sparse/select")

_memo = {}


@contextlib.contextmanager
def _lent():
    lfm2 = scope_reduce_lfm2
    kept = lfm2.MIXER_SCOPES, lfm2._memo
    lfm2.MIXER_SCOPES, lfm2._memo = kept[0] + SPARSE_SCOPES, _memo
    try:
        yield
    finally:
        lfm2.MIXER_SCOPES, lfm2._memo = kept


def table():
    """`scope_reduce_lfm2.table()` with the indexer's scopes kept, read once."""
    with _lent():
        return scope_reduce_lfm2.table()


any_of = scope_reduce_lfm2.any_of


def outside(pred, *scopes):
    """``pred`` on the rows that lie under none of ``scopes``."""
    inside = [scope_reduce.has(s) for s in scopes]
    return lambda row: pred(row) and not any(p(row) for p in inside)


def ms(reduction, pred, per=1.0):
    """`scope_reduce_lfm2.ms` on this module's table."""
    with _lent():
        return scope_reduce_lfm2.ms(reduction, pred, per)


def roofline_pct(reduction, cell, scopes, flops_fn):
    """`scope_reduce_lfm2.roofline_pct` on this module's table."""
    with _lent():
        return scope_reduce_lfm2.roofline_pct(reduction, cell, scopes, flops_fn)
