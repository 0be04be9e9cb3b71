"""Device time by scope for the scopes the accepted yardstick's vocabulary
(`benchmark/scope_reduce.SCOPES`) does not hold: latent attention's
projections (`mla`), `rope`, the expert layer's `moe/router`, `moe/dispatch`,
`moe/experts`, `moe/shared`, and `round/chunk`
(`heterofl_tpu.obs.trace.EXTRA_SCOPES`).

`scope_reduce.scope_of` drops a path component it does not know, so the
accepted metrics file these instructions under what encloses them
(`step/model`, `linear`, `norm`).  The metric files of this module's scopes
need the components kept: `table()` reads this process's traced run a second
time through `scope_reduce`'s own functions, with the vocabulary widened for
the length of that read and put back after it, so every accepted metric reads
what it read before.  A program without these scopes (a parent commit) gives
a table in which no row carries them, and each metric returns None.
"""

import time

from benchmark import scope_reduce, trace_reduce

EXTRA_SCOPES = ("mla", "rope", "moe/router", "moe/dispatch", "moe/experts",
                "moe/shared", "round/chunk")

_memo = {}


def _widened():
    pairs = {tuple(s.split("/")) for s in EXTRA_SCOPES if "/" in s}
    singles = {s for s in EXTRA_SCOPES if "/" not in s}
    return scope_reduce._PAIRS | pairs, scope_reduce._SINGLES | singles


def table():
    """`scope_reduce.reduce_scopes` of this process's traced run under the
    widened vocabulary, read once; None without a trace or where no row
    carries one of :data:`EXTRA_SCOPES`.  Prints the split as one
    `benchmark:` line."""
    if "table" in _memo:
        return _memo["table"]
    _memo["table"] = None
    kept = scope_reduce._PAIRS, scope_reduce._SINGLES
    try:
        path = scope_reduce.find_xplane()
        if path is None:
            return None
        t = time.perf_counter()
        scope_reduce._PAIRS, scope_reduce._SINGLES = _widened()
        result = scope_reduce.reduce_scopes(trace_reduce.load_xplane(path),
                                            scope_reduce.read_op_names(path))
        if any(_has_extra(r) for r in result["rows"]):
            _memo["table"] = result
        per_round = 1e3 / result["rounds"]
        scoped = scope_reduce.seconds(
            result, lambda r: r[0] != scope_reduce.UNSCOPED) or 0.0
        print("benchmark: device ms a round by scope, expert-layer vocabulary "
              f"(self time; under a scope {100.0 * scoped / max(result['total_s'], 1e-30):.2f} %; "
              f"read in {time.perf_counter() - t:.1f}s): " + "; ".join(
                  f"{p}{' ' + d if d else ''} {s * per_round:.3f}"
                  for p, d, s in scope_reduce.by_scope(result, top=40)), flush=True)
    except Exception as e:  # a new metric's reader reports nothing, never fails the run
        print(f"benchmark: scope_reduce_moe read nothing: {type(e).__name__}: {e}",
              flush=True)
    finally:
        scope_reduce._PAIRS, scope_reduce._SINGLES = kept
    return _memo["table"]


def _has_extra(row):
    on_path = "/" + row[0] + "/"
    return any("/" + s + "/" in on_path for s in EXTRA_SCOPES)


def any_of(*scopes):
    """A row filter: the row lies under `step/model` and under at least one
    of ``scopes``."""
    preds = [scope_reduce.has("step/model", s) for s in scopes]
    return lambda row: any(p(row) for p in preds)


def ms(reduction, pred, per=1.0):
    """Milliseconds a traced round of the rows ``pred`` accepts, over ``per``;
    None without a traced run, without these scopes, or where ``pred`` finds
    nothing."""
    if not reduction:
        return None
    found = table()
    s = scope_reduce.seconds(found, pred) if found else None
    return None if s is None else 1e3 * s / found["rounds"] / per
