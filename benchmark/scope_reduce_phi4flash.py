"""Device time by scope for the scopes ISSUE 50 added
(`heterofl_tpu.obs.trace.SAMBAY_SCOPES`: `gmu`, a gated memory unit whole, and
`diff`, differential attention's combine) beside the state-space mixer's
(`ssm`, `ssm/conv`, `ssm/scan`), grouped-query attention's (`gqa`, `attn`) and
a sliding layer's (`swa`), which the Phi-4-mini-flash family enters too.

No seventh copy of the reader: as `scope_reduce_nemotron_h` lends
`scope_reduce_lfm2` its longer list, this module lends `scope_reduce_nemotron_h`
a longer one still and a memo of its own for the length of a call and puts
both back, so the accepted metrics read what they read before.  A program
without these scopes (a parent commit) gives no row under them, and the
metrics that read them return None.  The `benchmark` PR of PERF.md section 7
(1) folds the seven modules into `scope_reduce.SCOPES`.
"""

import contextlib

from benchmark import scope_reduce, scope_reduce_nemotron_h

SAMBAY_SCOPES = ("gmu", "diff")
WINDOW_SCOPES = ("swa",)
#: what files a row of the family's layers under a mixer, a norm, the
#: embedding or the loss: a row under `step/model` and under none of these is
#: a feed-forward's (or the residual stream's)
NOT_FFN = ("ssm", "gmu", "gqa", "attn", "swa", "diff", "norm", "embed", "loss")

_memo = {}


@contextlib.contextmanager
def _lent():
    nh = scope_reduce_nemotron_h
    kept = nh.SSM_SCOPES, nh._memo
    nh.SSM_SCOPES, nh._memo = kept[0] + SAMBAY_SCOPES + WINDOW_SCOPES, _memo
    try:
        yield
    finally:
        nh.SSM_SCOPES, nh._memo = kept


any_of = scope_reduce_nemotron_h.any_of


def feed_forward(row):
    """A row filter: under `step/model` and under none of :data:`NOT_FFN`."""
    return scope_reduce.has("step/model")(row) \
        and not any(scope_reduce.has(s)(row) for s in NOT_FFN)


def ms(reduction, pred, per=1.0):
    """`scope_reduce_nemotron_h.ms` on this module's table."""
    with _lent():
        return scope_reduce_nemotron_h.ms(reduction, pred, per)


def roofline_pct(reduction, cell, scopes, flops_fn):
    """`scope_reduce_nemotron_h.roofline_pct` on this module's table."""
    with _lent():
        return scope_reduce_nemotron_h.roofline_pct(reduction, cell, scopes, flops_fn)


def two_sided_roofline_pct(reduction, cell, scopes, flops_fn, bytes_fn):
    """`scope_reduce_nemotron_h.two_sided_roofline_pct` on this module's table."""
    with _lent():
        return scope_reduce_nemotron_h.two_sided_roofline_pct(reduction, cell, scopes, flops_fn,
                                                              bytes_fn)
