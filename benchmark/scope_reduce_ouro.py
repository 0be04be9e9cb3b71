"""Device time by scope for the scopes ISSUE 40 added
(`heterofl_tpu.obs.trace.LOOP_SCOPES`: `loop/pass`, `loop/head`, `loop/exit`)
beside grouped-query attention's (`gqa`, `rope`, `attn`), which the Ouro cell
enters inside `loop/pass`.

No fourth copy of the reader: as `scope_reduce_keye` does, this module lends
`scope_reduce_lfm2` the longer list and a memo of its own for the length of a
call and puts both back, so the accepted metrics read what they read before.
A program without these scopes (a parent commit) gives no table, and each
metric returns None.  The `benchmark` PR of PERF.md section 7 (1) folds the
four modules into `scope_reduce.SCOPES`.
"""

import contextlib

from benchmark import scope_reduce_lfm2

LOOP_SCOPES = ("loop/pass", "loop/head", "loop/exit")

_memo = {}


@contextlib.contextmanager
def _lent():
    lfm2 = scope_reduce_lfm2
    kept = lfm2.MIXER_SCOPES, lfm2._memo
    lfm2.MIXER_SCOPES, lfm2._memo = kept[0] + LOOP_SCOPES, _memo
    try:
        yield
    finally:
        lfm2.MIXER_SCOPES, lfm2._memo = kept


def table():
    """`scope_reduce_lfm2.table()` with the loop's scopes kept, read once."""
    with _lent():
        return scope_reduce_lfm2.table()


any_of = scope_reduce_lfm2.any_of


def ms(reduction, pred, per=1.0):
    """`scope_reduce_lfm2.ms` on this module's table."""
    with _lent():
        return scope_reduce_lfm2.ms(reduction, pred, per)


def roofline_pct(reduction, cell, scopes, flops_fn):
    """`scope_reduce_lfm2.roofline_pct` on this module's table."""
    with _lent():
        return scope_reduce_lfm2.roofline_pct(reduction, cell, scopes, flops_fn)
