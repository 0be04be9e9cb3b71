"""Set-up seconds by span: the reader of the program's own span record
(`heterofl_tpu.obs.spans`, ISSUE 38) for the per-layer metrics that move
`setup_s`.

`benchmark/run.py` hands a metric file `(reduction, phases, info)` and none
of them holds set-up, so the five `*.setup` files read this process's record
through `value(...)` below.  The record's spans, `(id, name, t0, dt, parent,
args)` on the host's `perf_counter`, and the ONE reduction of them are the
program's (`obs.spans.summarize`; the same one `obs.report` and
`chip_smoke.py` print): a metric is defined there, in one place.

- set-up spans `setup/experiment` (with `setup/dataset`, `setup/model`,
  `setup/engine` inside), `setup/split`, `setup/stage` (`.../train`,
  `.../eval`), `setup/init`, `setup/first_round`;
- the `PhaseTimer` phases (`sample`, `stage`, `dispatch`, `fetch`) in which
  something compiled;
- compile spans `compile/trace`, `compile/lower`, `compile/backend`, each with
  `args["program"]`; `compile/backend` with `args["cache"]` = `hit`, `miss` or
  `uncached`.

A compile span counts as the program's own where the chain of its parents
reaches a span that is no compile span; the benchmark's own programs (the
weights, the plain reference) compile under no span and are summed as
`outside`.  A compile span inside another compile span (an operation run
while a function is traced) is part of that one's time.

A program without `obs.spans` (a parent commit) gives no record and every
metric returns None.  Prints ONE line a run, `benchmark: set-up seconds by
span ...`: the program's set-up table (`obs.spans.table`).
"""

_memo = {}


def table():
    """The summary of this process's span record, read once for all metric
    files and printed as one `benchmark:` line; None where the program has
    no record."""
    if "table" not in _memo:
        _memo["table"] = None
        try:
            from heterofl_tpu.obs import spans

            rec = spans.RECORD
            _memo["table"] = found = rec.summary()
            print(f"benchmark: set-up seconds by span (host clock; {len(rec.spans)} "
                  "spans): " + "; ".join(spans.table(found)), flush=True)
        except ImportError:
            pass
        except Exception as e:  # a new metric's reader reports nothing, never fails the run
            _memo["table"] = None
            print(f"benchmark: setup_reduce read nothing: {type(e).__name__}: {e}",
                  flush=True)
    return _memo["table"]


def value(fn):
    """``fn(table)`` where there is a record; None without one, or where a
    span the metric needs is not in it (``fn`` raises KeyError)."""
    found = table()
    if found is None:
        return None
    try:
        return fn(found)
    except KeyError:
        return None
