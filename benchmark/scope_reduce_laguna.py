"""Device time by scope for the scope ISSUE 42 added
(`heterofl_tpu.obs.trace.WINDOW_SCOPES`: `swa`, a sliding layer's score /
softmax / value part) beside grouped-query attention's (`gqa`, `rope`, `attn`)
and the expert layer's (`moe/*`), which the Laguna cell enters too.

No fifth copy of the reader: as `scope_reduce_ouro` does, this module lends
`scope_reduce_lfm2` the longer list and a memo of its own for the length of a
call and puts both back, so the accepted metrics read what they read before.
A program without this scope (a parent commit) gives no row under `swa`, and
the metrics that read it return None.  The `benchmark` PR of PERF.md section 7
(1) folds the five modules into `scope_reduce.SCOPES`.
"""

import contextlib

from benchmark import scope_reduce_lfm2

WINDOW_SCOPES = ("swa",)

_memo = {}


@contextlib.contextmanager
def _lent():
    lfm2 = scope_reduce_lfm2
    kept = lfm2.MIXER_SCOPES, lfm2._memo
    lfm2.MIXER_SCOPES, lfm2._memo = kept[0] + WINDOW_SCOPES, _memo
    try:
        yield
    finally:
        lfm2.MIXER_SCOPES, lfm2._memo = kept


def table():
    """`scope_reduce_lfm2.table()` with the window's scope kept, read once."""
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
