"""What decides `correct`, outside the measured window.

Three parts, each through the cell's own compiled round program (the learning
rate is a runtime scalar, the cohort a runtime array of ids, so these rounds
compile nothing new):

1. Identity round (exact).  A round at lr = 0: every client returns the
   parameters it was given, so the counted average must give the global
   parameters back.  In float32 the sum of k equal values x is off by at most
   2^-24 |x| k^2/2 (each of k-1 additions rounds a partial sum of at most
   k|x|), the division by k brings that to k/2 * 2^-24 |x| and adds one
   rounding of its own: at most k/2 + 1 spacings of x, whatever the order of
   the additions (so across chips too).  The limit is k, the cohort's size, in
   units of `numpy.spacing(x)`.  Any pass of the parameters, the update or the
   aggregate through bfloat16 is off by up to 2^15 spacings.
2. HeteroFL invariants (exact).  A round whose cohort is all smallest-level
   clients leaves every entry outside their slice bit for bit unchanged and
   moves entries inside it; every slot of every check round trained local
   steps x samples a step; every loss is finite; the window's last round's
   mean client loss is below that of the first round from the seeded weights.
3. Plain reference (semantics of the compute path).  One round at lr = 1e-3
   from the seeded weights on the window's first cohort: the program's mean
   client loss per level against the reference's, and the norm of the change
   of every leaf of the global parameters against the reference's (the gap
   between the two norms, over the reference's norm of that leaf or of the
   median leaf, whichever is larger).  Augmentation, dropout and corruption
   are drawn inside the program's jit, so the reference draws its own and the
   two agree in distribution, not step by step: the limits are set from
   measured runs (PERF.md section 2) and live in the configuration's file.
"""

import math

import numpy as np

CHECK_LR = 1e-3


def to_host(tree):
    return {k: np.asarray(v) for k, v in tree.items()}


def to_device(tree):
    import jax.numpy as jnp

    return {k: jnp.asarray(v) for k, v in tree.items()}


def level_means(ms):
    """{rate: mean client loss} of the slots that trained."""
    n, loss, rate = (np.asarray(ms[k], np.float64) for k in ("n", "loss_sum", "rate"))
    out = {}
    for r in sorted(set(rate[n > 0].tolist())):
        sel = (rate == r) & (n > 0)
        out[float(r)] = float(loss[sel].sum() / n[sel].sum())
    return out


def through_bf16(tree):
    """The control: what the round program returned, passed through bfloat16,
    as a program with bfloat16 anywhere on the parameter path would hand it
    back."""
    import jax.numpy as jnp

    return {k: np.asarray(jnp.asarray(v).astype(jnp.bfloat16).astype(jnp.float32))
            for k, v in tree.items()}


def program_rounds(exp, params0, cohort, small_cohort, key_of):
    """The three check rounds through the engine's compiled round program.
    ``params0``: host copy of the seeded weights."""
    import jax

    def round_(lr, users, epoch):
        new, ms = exp.engine.train_round(to_device(params0), key_of(epoch), lr,
                                         np.asarray(users, np.int32),
                                         exp.train_data)
        jax.block_until_ready(new)
        return to_host(new), {k: np.asarray(v)[:len(users)] for k, v in ms.items()
                     if k in ("n", "loss_sum", "rate")}

    from .harness import CHECK_EPOCH, SMALL_EPOCH

    return {"identity": round_(0.0, cohort, CHECK_EPOCH),
            "check": round_(CHECK_LR, cohort, CHECK_EPOCH),
            "small": round_(float(exp.cfg["lr"]), small_cohort, SMALL_EPOCH)}


def identity_ulp(before, after):
    """Largest |after - before| in spacings of before, over every entry."""
    worst = 0.0
    for k, b in before.items():
        a = after[k]
        if a.dtype != np.float32:
            return math.inf
        d = np.abs(a.astype(np.float64) - b.astype(np.float64))
        u = d / np.spacing(np.abs(b)).astype(np.float64)
        worst = max(worst, float(u.max()))
    return worst


def slice_mask(index, shapes):
    """{name: bool array} true inside the sub-model ``index`` cuts."""
    out = {}
    for k, shape in shapes.items():
        m = np.zeros(shape, bool)
        m[np.ix_(*index[k])] = True
        out[k] = m
    return out


def outside_inside(before, after, mask):
    """(entries outside the slice that changed, entries inside that moved)."""
    outside = inside = 0
    for k, b in before.items():
        changed = after[k].view(np.uint32) != b.view(np.uint32)
        outside += int(np.count_nonzero(changed & ~mask[k]))
        inside += int(np.count_nonzero(changed & mask[k]))
    return outside, inside


def update_norm_gap(before, prog, ref):
    """Worst leaf's gap between the program's and the reference's norm of the
    parameters' change, over the reference's norm of that leaf or of the
    median leaf, whichever is larger.  Returns (gap, leaf)."""
    np_, nr = {}, {}
    for k, b in before.items():
        np_[k] = float(np.linalg.norm((prog[k].astype(np.float64) - b).ravel()))
        nr[k] = float(np.linalg.norm((ref[k].astype(np.float64) - b).ravel()))
    floor = float(np.median(list(nr.values())))
    gaps = {k: abs(np_[k] - nr[k]) / max(nr[k], floor, 1e-30) for k in before}
    leaf = max(gaps, key=gaps.get)
    return gaps[leaf], leaf


def window_rows(window, compiles, first_loss):
    """The window's own verdicts: nothing compiled inside it, and the mean
    client loss of its last round is below ``first_loss``, that of the first
    round trained from the seeded weights (the first warm-up round: cohorts
    differ from round to round, so two neighbouring rounds of a short window
    can rise; my chip run 3, PR 25)."""
    losses = [r["loss"] for r in window["rounds"]]
    fell = bool(losses) and losses[-1] < first_loss
    return [("window_compiles", compiles, 0, compiles == 0),
            ("window_last_loss", losses[-1] if losses else math.nan,
             f"<{first_loss}", bool(fell))]


def check_cohort(cohort, num_active, rates):
    """(slot ids of the check rounds, the distinct clients among them).

    The reference follows one client of every level the window's first cohort
    holds, the first of each, and the check rounds fill all their slots with
    those clients over again: a client's random numbers follow its id, and the
    counted average of every client taken k times is that of every client
    taken once.  So the program trains as many slots as the window does, every
    level of the cohort is compared, and the reference follows at most five
    clients (following all ten took 28-37 s, longer than the window: my chip
    runs 4-6, PR 25)."""
    cohort = np.asarray(cohort)
    _, first = np.unique(np.asarray(rates)[cohort], return_index=True)
    distinct = cohort[np.sort(first)]
    return np.resize(distinct, num_active).astype(np.int32), distinct


def reference_clients(exp, config, cohort, data_split, label_split, slots=None):
    """The cohort as the plain reference sees it: each client's level, labels,
    its share of the benchmark's generated data, and how many of ``slots``
    hold it."""
    rates = np.asarray(exp.cfg["model_rate"], np.float64)
    train = exp.dataset["train"]
    epochs = int(exp.cfg["num_epochs"]["local"])
    clients = []
    for u in (int(u) for u in cohort):
        idx = np.asarray(data_split["train"][u], np.int64)
        c = {"rate": float(rates[u] / exp.cfg["global_model_rate"]),
             "labels": np.asarray(label_split[u], np.int64), "epochs": epochs,
             "copies": 1 if slots is None else int(np.count_nonzero(slots == u))}
        if exp.kind == "vision":
            c["x"], c["y"] = train.data[idx], train.target[idx]
        else:
            c["rows"] = np.asarray(train.token)[idx]
        clients.append(c)
    return clients


def reference_round(ref_module, config, params0, clients, seed):
    """The plain reference's check round: (new global parameters, each
    followed client's mean loss)."""
    from .reference import common

    return common.run_round(ref_module, config, params0, clients, CHECK_LR, seed)


def compare(rounds, reference, params0, cohort, steps, per_step, ref_module,
            config, clients, window, compiles, first_loss):
    """Every number compared, beside its limit.  ``rounds``: what
    :func:`program_rounds` returned; ``reference``: what
    :func:`reference_round` returned.  Returns (correct, rows, detail) with
    rows = [(name, value, limit, ok)]."""
    limits = config["limits"]
    rows = []

    def row(name, value, limit, ok):
        rows.append((name, value, limit, bool(ok)))

    # 1. identity round
    ident, ident_ms = rounds["identity"]
    u = identity_ulp(params0, ident)
    row("identity_ulp", u, float(len(cohort)), u <= len(cohort))

    # 2. invariants
    shapes = {k: v.shape for k, v in params0.items()}
    small_rate = min(config["model"]["level_rates"].values())
    mask = slice_mask(ref_module.index(shapes, config["model"], small_rate), shapes)
    outside, inside = outside_inside(params0, rounds["small"][0], mask)
    row("outside_slice_changed", outside, 0, outside == 0)
    row("inside_slice_moved", inside, ">0", inside > 0)
    want = float(steps * per_step)
    wrong = sum(int(np.count_nonzero(np.asarray(ms["n"]) != want))
                for _, ms in rounds.values())
    row("slots_with_wrong_sample_count", wrong, 0, wrong == 0)
    finite = all(np.all(np.isfinite(ms["loss_sum"])) for _, ms in rounds.values())
    row("check_losses_finite", int(finite), 1, finite)

    # 3. plain reference, one round at CHECK_LR on the window's first cohort
    ref_params, ref_losses = reference
    prog_levels = level_means(rounds["check"][1])
    ref_levels = {}
    for c, l in zip(clients, ref_losses):
        ref_levels.setdefault(c["rate"], []).extend([l] * c["copies"])
    ref_levels = {r: float(np.mean(v)) for r, v in ref_levels.items()}
    global_rate = max(config["model"]["level_rates"].values())
    gap = max(abs(prog_levels.get(r * global_rate, math.nan) - l) / l
              for r, l in ref_levels.items())
    row("level_loss_gap", gap, limits["level_loss_gap"],
        gap <= limits["level_loss_gap"])
    ugap, leaf = update_norm_gap(params0, rounds["check"][0], ref_params)
    row("update_norm_gap", ugap, limits["update_norm_gap"],
        ugap <= limits["update_norm_gap"])

    rows += window_rows(window, compiles, first_loss)
    detail = {"program_level_loss": prog_levels, "reference_level_loss": ref_levels,
              "update_norm_gap_leaf": leaf}
    return all(r[3] for r in rows), rows, detail
