"""staticcheck (ISSUE 3): the AST lint rules (positive / pragma-suppressed /
path-scoped), the jaxpr walkers, and the full program-audit matrix -- the
tier-1 gate that every engine variant keeps its compiled-program contract:
no host callbacks or f64, full donation coverage, exactly one global psum
per fused round, no recompile on fresh-but-identical inputs, and the
level-table FLOP budget."""

import json
import os
import subprocess
import sys
import textwrap

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from heterofl_tpu.staticcheck import audit as audit_mod
from heterofl_tpu.staticcheck.audit import (audit_program, build_setup,
                                            run_audit, _masked_targets)
from heterofl_tpu.staticcheck.jaxpr_walk import (count_psum_over,
                                                find_callbacks, find_f64)
from heterofl_tpu.staticcheck.rules import lint_source, lint_tree

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
IN_SCOPE = "heterofl_tpu/parallel/somefile.py"


# ---------------------------------------------------------------------------
# front 2: AST lint rules
# ---------------------------------------------------------------------------

def _lint(src, relpath=IN_SCOPE):
    return lint_source(textwrap.dedent(src), relpath)


def test_banned_asarray_flagged_and_pragma_suppressed():
    src = """
    import numpy as np
    def f(a):
        return np.asarray(a)
    """
    fs = _lint(src)
    assert [f.rule for f in fs] == ["no-asarray"]
    assert fs[0].where == f"{IN_SCOPE}:4"
    # same-line pragma
    assert _lint("""
    import numpy as np
    def f(a):
        return np.asarray(a)  # staticcheck: allow(no-asarray): reason
    """) == []
    # preceding-comment-block pragma (multi-line reason style)
    assert _lint("""
    import numpy as np
    def f(a):
        # staticcheck: allow(no-asarray): a longer reason that
        # spans two comment lines before the call it licenses
        return np.asarray(a)
    """) == []


def test_pragma_is_rule_scoped():
    """A pragma for one rule must not silence another on the same line."""
    fs = _lint("""
    import numpy as np
    def f(a):
        return float(np.asarray(a))  # staticcheck: allow(no-asarray)
    """)
    assert [f.rule for f in fs] == ["no-float-coercion"]


def test_path_scoping():
    src = """
    import numpy as np
    def f(a):
        return np.asarray(a)
    """
    # ISSUE 5: ops/ and models/ are hot-path scope now (kernel/model code
    # runs inside the round programs); analysis/ stays host-side
    assert len(_lint(src, "heterofl_tpu/models/conv.py")) == 1
    assert len(_lint(src, "heterofl_tpu/ops/kern.py")) == 1
    assert _lint(src, "heterofl_tpu/analysis/summary.py") == []
    assert len(_lint(src, "heterofl_tpu/parallel/engine.py")) == 1
    # nested checkouts still match (prefix anywhere after a slash)
    assert len(_lint(src, "work/heterofl_tpu/parallel/engine.py")) == 1


def test_alias_resolution_variants():
    flagged = _lint("""
    from jax import numpy as weird
    def f(a):
        return weird.asarray(a)
    """)
    assert [f.rule for f in flagged] == ["no-asarray"]
    flagged = _lint("""
    import jax.numpy as jnp
    def f(a):
        return jnp.asarray(a)
    """)
    assert [f.rule for f in flagged] == ["no-asarray"]


def test_wallclock_and_fresh_rng_scoped_to_fed_too():
    src = """
    import time
    import numpy as np
    def f():
        t = time.perf_counter()
        g = np.random.default_rng()
        return t, g
    """
    rules_hit = sorted(f.rule for f in _lint(src, "heterofl_tpu/fed/core.py"))
    assert rules_hit == ["no-fresh-rng", "no-wallclock"]
    assert _lint(src, "heterofl_tpu/data/pipeline.py") == []


def test_block_until_ready_method_call():
    fs = _lint("""
    def f(x):
        return x.block_until_ready()
    """)
    assert [f.rule for f in fs] == ["no-block-until-ready"]


def test_jit_donation_rule():
    base = """
    import jax
    def mk(f):
        return jax.jit(f{})
    """
    assert [f.rule for f in _lint(base.format(""))] == ["jit-needs-donation"]
    assert _lint(base.format(", donate_argnums=(0,)")) == []
    assert _lint(base.format(", donate_argnames='params'")) == []
    # an explicit empty donation IS a stance (the span-mode level programs)
    assert _lint(base.format(", donate_argnums=()")) == []
    # a bare decorator takes no stance either
    fs = _lint("""
    import jax
    @jax.jit
    def f(x):
        return x
    """)
    assert [f.rule for f in fs] == ["jit-needs-donation"]


def test_host_eval_in_driver_rule():
    """ISSUE 4 satellite: host-side eval dispatch (sbn_stats / eval_users /
    eval_global) in driver code is a lint finding -- the superstep fuses
    those phases in-program -- escapable by pragma for the K=1 path."""
    src = """
    def run(exp, params, d):
        bn = exp.evaluator.sbn_stats(params, d)
        local = exp.evaluator.eval_users(params, bn, d)
        return exp.evaluator.eval_global(params, bn, d)
    """
    fs = _lint(src, "heterofl_tpu/entry/common.py")
    assert [f.rule for f in fs] == ["no-host-eval-in-driver"] * 3
    # pragma escape (the K=1 host-loop path carries one per call)
    assert _lint("""
    def run(exp, params, d):
        # staticcheck: allow(no-host-eval-in-driver): K=1 host-loop path
        return exp.evaluator.eval_global(params, {}, d)
    """, "heterofl_tpu/entry/common.py") == []
    # scoped to the driver: engine/eval code and offline analysis are free
    assert _lint(src, "heterofl_tpu/parallel/evaluation.py") == []
    assert _lint(src, "heterofl_tpu/analysis/compare_reference.py") == []


def test_repo_tree_is_lint_clean():
    """The gate itself: the shipped tree has zero unsuppressed findings."""
    fs = lint_tree(REPO, subdirs=["heterofl_tpu"])
    assert fs == [], "\n".join(str(f) for f in fs)


# ---------------------------------------------------------------------------
# front 1: jaxpr walkers
# ---------------------------------------------------------------------------

def test_find_callbacks_inside_scan_body():
    """An op smuggled inside a lax.scan round body is found like a
    top-level one, with provenance."""
    def step(c, _):
        jax.debug.callback(lambda v: None, c)
        return c + 1.0, None

    def f(x):
        out, _ = jax.lax.scan(step, x, None, length=3)
        return out

    hits = find_callbacks(jax.jit(f).trace(np.float32(0.0)).jaxpr)
    assert len(hits) == 1
    name, prov = hits[0]
    assert name == "debug_callback"
    assert "test_staticcheck" in prov


def test_find_f64():
    with jax.enable_x64(True):
        jaxpr = jax.make_jaxpr(lambda x: x.astype(jnp.float64) * 2.0)(
            np.ones(3, np.float32))
    hits = find_f64(jaxpr)
    assert hits and "float64" in hits[0][0]


def test_count_psum_binds_not_leaves():
    """One psum over a (sums, counts) tuple is ONE collective launch --
    the budget the fused round is audited against -- however many eqns
    the installed jax binds for it (one per leaf); a psum that reads an
    earlier psum's result is a second launch."""
    def f2(a, b):
        return jax.lax.psum((a, b), "clients")

    def f1(a, b):
        s = jax.lax.psum(a, "clients")
        return s, jax.lax.psum(b + s, "clients")

    import functools
    from jax.sharding import Mesh, PartitionSpec as P

    mesh = Mesh(np.array(jax.devices()[:2]).reshape(2, 1), ("clients", "data"))
    sm = functools.partial(jax.shard_map, mesh=mesh,
                           in_specs=(P("clients"), P("clients")),
                           out_specs=P(), check_vma=False)
    x = np.ones((4, 2), np.float32)
    assert count_psum_over(jax.jit(sm(f2)).trace(x, x).jaxpr) == 1
    assert count_psum_over(jax.jit(sm(f1)).trace(x, x).jaxpr) == 2


# ---------------------------------------------------------------------------
# the program-audit matrix (the tier-1 gate for the engines)
# ---------------------------------------------------------------------------

@pytest.fixture(scope="module")
def audit_report():
    return run_audit()


def test_audit_matrix_is_green(audit_report):
    assert audit_report.ok, "\n".join(str(f) for f in audit_report.all_findings())


def test_fused_superstep_single_global_psum(audit_report):
    """The PR 2 invariant, now statically enforced: the grouped fused round
    (both placements) performs exactly ONE global psum."""
    for name in ("grouped/span/k8-fused", "grouped/slices/k8-fused"):
        p = audit_report.programs[name]
        assert p.psum_clients == 1, name
        assert p.all_gather == 0, name
        assert set(p.collective_axes) <= {"clients", "data"}, name


def test_eval_fused_program_budgets(audit_report):
    """ISSUE 4: the eval-fused superstep variants keep ONE training psum per
    fused round, with the eval phase's joint (clients, data) reductions --
    sBN moments + Global sums, 2 per traced eval point -- audited as their
    own budget, and full donation coverage intact."""
    from heterofl_tpu.staticcheck.audit import EVAL_PSUM_BUDGET

    k = 8
    expected = {"masked/replicated/k8-eval1": EVAL_PSUM_BUDGET * k,
                "masked/replicated/k8-eval8": EVAL_PSUM_BUDGET,
                "masked/sharded/k8-eval1": EVAL_PSUM_BUDGET * k,
                "grouped/span/k8-eval1-fused": EVAL_PSUM_BUDGET * k,
                "grouped/slices/k8-eval1-fused": EVAL_PSUM_BUDGET * k}
    for name, want in expected.items():
        p = audit_report.programs[name]
        assert p.psum_clients == 1, name
        assert p.psum_eval == want, (name, p.psum_eval)
        assert p.all_gather == 0, name
        assert p.aliased == p.donation_expected > 0, name


def test_donation_coverage_both_engines_both_placements(audit_report):
    """Every program that carries the params donates ALL param leaves and
    every donated leaf is consumed by input-output aliasing."""
    donating = ["masked/replicated/k1", "masked/replicated/k8",
                "masked/sharded/k1", "masked/sharded/k8",
                "grouped/span/combine", "grouped/span/k8-fused",
                "grouped/slices/k8-fused"]
    for name in donating:
        p = audit_report.programs[name]
        assert p.donation_expected > 0, name
        assert p.donated == p.donation_expected, (name, p.donated)
        assert p.aliased == p.donation_expected, (name, p.aliased)


def test_recompile_hazard_flat(audit_report):
    rc = audit_report.recompile
    assert rc["ok"], rc
    for which in ("masked_round", "masked_superstep",
                  "masked_sharded_superstep", "masked_superstep_eval",
                  "grouped_round"):
        assert rc[which]["after_repeat"] == rc[which]["after_warm"], (which, rc)


def test_flop_budget_and_artifact_roundtrip(audit_report):
    fb = audit_report.flop_budget
    assert fb["ok"], fb
    meas = fb["measured_flops"]
    rates = sorted((float(r) for r in meas), reverse=True)
    # strictly decreasing with the level rate: the dense-per-level win
    for hi, lo in zip(rates, rates[1:]):
        assert meas[f"{hi:g}"] > meas[f"{lo:g}"]
    # the artifact serialises and carries per-program memory bytes
    rec = json.loads(audit_report.to_json())
    assert rec["ok"] is True and rec["version"] == 2
    mem = rec["programs"]["masked/replicated/k1"]["memory"]
    assert mem and mem["temp_size_in_bytes"] > 0


def test_wire_memory_reshard_sections_on_every_program(audit_report):
    """ISSUE 7 acceptance: STATICCHECK.json grows wire/memory/reshards
    sections for every audited program variant, and the wire budget of
    every fused training round equals ONE dense global reduction of the
    level-a parameter footprint (sums + count masks, f32) -- or, for the
    ISSUE 8 codec variants, that codec's compressed level-a payload from
    the same table family."""
    from heterofl_tpu.compress import LOSSY_CODECS
    from heterofl_tpu.fed.core import level_byte_table, level_codec_byte_table
    from heterofl_tpu.staticcheck.audit import build_setup, default_audit_cfg

    cfg = default_audit_cfg()
    bt = level_byte_table(cfg)
    level_a_wire = bt[max(bt)]["wire_bytes"]
    assert level_a_wire == 2 * bt[max(bt)]["param_bytes"]
    n_leaves = len(build_setup()["params"])
    codec_wire = {c: level_codec_byte_table(cfg, c, n_leaves=n_leaves)[max(bt)]
                  for c in LOSSY_CODECS}
    for name, p in audit_report.programs.items():
        assert p.wire is not None, name
        assert p.reshards is not None and p.reshards["total"] == 0, name
        if name.endswith("/mh"):
            # ISSUE 17 multi-host variants: the fake 2-process grid puts
            # the clients axis on DCN -- the whole (one-reduction) train
            # payload crosses, and NOTHING else does.  These entries
            # re-audit the SAME program as their single-process twin
            # under the multi-process link model only (wire_only), so
            # they carry no duplicate memory/step-body sections.
            assert p.wire["dcn_bytes"] == p.wire["train_bytes_per_round"], name
            assert p.wire["other_bytes"] == 0, name
            assert p.memory is None, name
        else:
            assert p.memory is not None, name
            assert p.wire["dcn_bytes"] == 0, name  # single-slice audit mesh
        codec = next((c for c in LOSSY_CODECS if name.endswith(f"-{c}")), None)
        if name == "grouped/span/combine":
            assert p.wire["train_bytes_per_round"] == 0
        elif "/level-" in name:  # per-level partial: that level's slice
            rate = float(name.split("level-")[1].split("/")[0])
            assert p.wire["train_bytes_per_round"] == bt[rate]["wire_bytes"], name
        elif name.endswith("-perlevel"):
            # per-level codec map (ISSUE 9 satellite): the bind's payload is
            # the per-level sum -- level-a under its codec, the rest dense
            from heterofl_tpu.fed.core import level_codec_map_byte_table

            cmap = {r: ("int8" if r == max(bt) else "dense") for r in bt}
            expected = sum(level_codec_map_byte_table(
                cfg, cmap, n_leaves=n_leaves).values())
            assert p.wire["train_bytes_per_round"] == expected, name
        elif codec:  # compressed fused round: that codec's level-a payload
            assert p.wire["train_bytes_per_round"] == codec_wire[codec], name
        elif "-arms" in name:
            # arms multiplexer (ISSUE 14): the masked engine's per-arm
            # cohorts batch sums AND counts -- E x the dense reduction;
            # grouped span arms share the host schedule, so the counts
            # payload is arm-invariant: E sum payloads + ONE counts
            e = int(name.split("-arms")[1])
            expected = (e + 1) * level_a_wire // 2 \
                if name.startswith("grouped") else e * level_a_wire
            assert p.wire["train_bytes_per_round"] == expected, name
        else:  # every fused training round (incl. the ISSUE 9 trace/
            # deadline/buffered scheduler variants -- selection arithmetic
            # and post-psum buffering add no wire): the dense level-a
            # reduction
            assert p.wire["train_bytes_per_round"] == level_a_wire, name


def test_ratchet_roundtrip_against_fresh_audit(audit_report):
    """Pinning a baseline from an audit and diffing the same audit against
    it is clean (the --update-baseline / --diff-baseline round-trip), and
    the ratchet only tightens: a doctored baseline below the measured
    metrics regresses the diff."""
    import copy

    from heterofl_tpu.staticcheck.ratchet import baseline_view, diff_reports

    rec = audit_report.to_dict()
    base = baseline_view(rec)
    diff = diff_reports(rec, base)
    assert diff["ok"], diff["regressions"]
    assert not diff["regressions"] and not diff["missing_programs"]

    doctored = copy.deepcopy(base)
    doctored["programs"]["masked/replicated/k1"]["wire.train_bytes_per_round"] -= 4
    diff = diff_reports(rec, doctored)
    assert not diff["ok"]
    assert any(r["metric"] == "wire.train_bytes_per_round"
               for r in diff["regressions"])


def test_auditor_flags_smuggled_io_callback(monkeypatch):
    """End-to-end seeded violation: an io_callback smuggled into the round
    body makes the auditor fail loudly, naming the op AND where it was
    bound."""
    from jax.experimental import io_callback

    from heterofl_tpu.parallel.round_engine import RoundEngine

    orig = RoundEngine._round_core

    def smuggled(self, params, key, lr, user_loc, user_glob, data,
                 resid=None, sched_buf=None):
        new_p, ms, new_resid, new_buf = orig(self, params, key, lr, user_loc,
                                             user_glob, data, resid=resid,
                                             sched_buf=sched_buf)
        # the smuggled host hook (e.g. a sneaky metrics push); the result is
        # discarded but the bind stays in the jaxpr, where the walk finds it
        _ = io_callback(lambda v: np.float32(0.0),
                        jax.ShapeDtypeStruct((), np.float32), lr)
        return new_p, ms, new_resid, new_buf

    monkeypatch.setattr(RoundEngine, "_round_core", smuggled)
    setup = build_setup()
    name, prog, args, expect = _masked_targets(setup)[0]
    rep = audit_program(name, prog, args, expect, setup["mesh"])
    assert not rep.ok
    hits = [f for f in rep.findings if f.rule == "no-host-callback"]
    assert hits, rep.findings
    assert "io_callback" in hits[0].message
    assert "test_staticcheck" in hits[0].message  # provenance of the bind


def test_auditor_flags_lost_donation():
    """Seeded donation regression: a program that stopped donating its
    params (here: a span-mode level program, which donates nothing by
    design) trips both donation checks when held to the donating
    programs' expectation."""
    from heterofl_tpu.staticcheck.audit import _grouped_targets

    setup = build_setup()
    grouped, _names, _ = _grouped_targets(setup)
    name, prog, args, expect = grouped[0]  # span level prog: donates 0
    assert expect["donated"] == 0
    bad_expect = dict(expect,
                      donated=len(jax.tree_util.tree_leaves(setup["params"])))
    rep = audit_program(name, prog, args, bad_expect, setup["mesh"])
    rules = {f.rule for f in rep.findings}
    assert "donation-coverage" in rules and "donation-consumed" in rules, \
        rep.findings


# ---------------------------------------------------------------------------
# donation warnings are errors now (conftest/pytest.ini satellite)
# ---------------------------------------------------------------------------

def test_unused_donation_warning_is_error():
    """'donated buffer unused' can never land silently again: the warning is
    promoted to an error by the test-gate filters."""
    # both inputs are used, both donated, but the single output can consume
    # only one buffer -- the other donation is unusable and must raise
    f = jax.jit(lambda x, y: x + y, donate_argnums=(0, 1))
    with pytest.raises(UserWarning, match="donated buffers were not usable"):
        out = f(jnp.ones((4, 4)), jnp.ones((4, 4)))
        jax.block_until_ready(out)


# ---------------------------------------------------------------------------
# the CLI
# ---------------------------------------------------------------------------

def _run_cli(extra_args, tmp_path, env_extra=None):
    env = dict(os.environ)
    env.update(env_extra or {})
    return subprocess.run(
        [sys.executable, "-m", "heterofl_tpu.staticcheck", "--json",
         "--out", str(tmp_path / "STATICCHECK.json")] + extra_args,
        cwd=REPO, env=env, capture_output=True, text=True, timeout=600)


def test_cli_exits_nonzero_on_seeded_lint_violation(tmp_path):
    bad = tmp_path / "tree" / "heterofl_tpu" / "parallel"
    bad.mkdir(parents=True)
    (bad / "bad.py").write_text(
        "import numpy as np\n\ndef f(a):\n    return np.asarray(a)\n")
    res = _run_cli(["--skip-audit", "--lint-root", str(tmp_path / "tree"),
                    "--no-artifact"], tmp_path)
    assert res.returncode == 1, res.stderr
    rec = json.loads(res.stdout)
    assert rec["ok"] is False
    assert [f["rule"] for f in rec["lint"]] == ["no-asarray"]
    # and the same invocation on a clean tree exits 0
    good = tmp_path / "clean" / "heterofl_tpu" / "parallel"
    good.mkdir(parents=True)
    (good / "ok.py").write_text("def f(a):\n    return a\n")
    res = _run_cli(["--skip-audit", "--lint-root", str(tmp_path / "clean"),
                    "--no-artifact"], tmp_path)
    assert res.returncode == 0, res.stdout + res.stderr


@pytest.mark.slow
def test_cli_full_audit_green_and_writes_artifact(tmp_path):
    """`python -m heterofl_tpu.staticcheck --json` exits 0 on the repo and
    the artifact asserts the acceptance invariants."""
    env_extra = {}
    if jax.config.jax_compilation_cache_dir:
        env_extra["JAX_COMPILATION_CACHE_DIR"] = jax.config.jax_compilation_cache_dir
    res = _run_cli([], tmp_path, env_extra=env_extra)
    assert res.returncode == 0, res.stdout[-2000:] + res.stderr[-2000:]
    rec = json.loads((tmp_path / "STATICCHECK.json").read_text())
    assert rec["ok"] is True
    assert rec["programs"]["grouped/span/k8-fused"]["psum_clients"] == 1
    assert rec["programs"]["grouped/slices/k8-fused"]["psum_clients"] == 1
    for name, p in rec["programs"].items():
        assert p["aliased"] == p["donation_expected"], name


# ---------------------------------------------------------------------------
# the hot-step kernel budget (ISSUE 5)
# ---------------------------------------------------------------------------

def test_step_body_kernel_counts_recorded_and_budgeted(audit_report):
    """Every audited program records its scan-body kernel stats; the two
    level-a critical-path programs are held to STEP_BODY_BUDGET."""
    from heterofl_tpu.staticcheck.audit import STEP_BODY_BUDGET

    for name, budget in STEP_BODY_BUDGET.items():
        p = audit_report.programs[name]
        assert p.step_body is not None and p.step_body["fusions"] > 0, name
        assert p.step_body_budget == budget, name
        assert p.step_body["instructions"] <= budget, (name, p.step_body)
    # recorded (not budgeted) everywhere else too
    k8 = audit_report.programs["masked/replicated/k8"]
    assert k8.step_body is not None and k8.step_body["instructions"] > 0


def _audit_masked_k1(engine_cls):
    from heterofl_tpu.staticcheck.audit import PSUM_BUDGET

    setup = build_setup()
    cfg, model, mesh = setup["cfg"], setup["model"], setup["mesh"]
    eng = engine_cls(model, cfg, mesh)
    fix = (eng.fix_rates,) if eng.fix_rates is not None else ()
    data = tuple(setup["data"]) + fix
    n_dev = mesh.shape["clients"]
    slots = setup["users"] + ((-setup["users"]) % n_dev)
    sds = jax.ShapeDtypeStruct((slots,), np.int32)
    n_leaves = len(jax.tree_util.tree_leaves(setup["params"]))
    return audit_program(
        "masked/replicated/k1", eng._build_train(),
        (setup["params"], setup["key"], setup["lr"], sds, sds) + data,
        {"donated": n_leaves, "psum": PSUM_BUDGET}, mesh)


def test_step_body_budget_catches_a_new_per_leaf_chain():
    """The seeded regression the budget exists for: a second per-leaf chain
    in the step (here the gradients' global norm taken once more, a reduce
    per leaf) must trip the step-body-budget check on the masked k1 program."""
    from heterofl_tpu.parallel import RoundEngine
    from heterofl_tpu.utils.optim import clip_by_global_norm

    class SecondChain(RoundEngine):
        def _apply_update(self, p, grads, opt, masks, n_glob, lr, has=None):
            grads, _ = clip_by_global_norm(grads, 1e6)
            return super()._apply_update(p, grads, opt, masks, n_glob, lr, has=has)

    rep = _audit_masked_k1(SecondChain)
    assert not rep.ok
    hits = [f for f in rep.findings if f.rule == "step-body-budget"]
    assert hits, rep.findings
    assert rep.step_body["instructions"] > rep.step_body_budget


def test_step_body_budget_does_not_see_unhoisted_masks():
    """What the count cannot see, pinned so nobody reads the budget as a
    guard of the hoist: masks re-materialised in the step from the width
    rate are loop-invariant and XLA:CPU moves them out itself; derived from a
    value of the step they fuse into the update's own fusions.  Neither adds
    an instruction to the body."""
    from heterofl_tpu.parallel import RoundEngine

    class UnhoistedMasks(RoundEngine):
        def _local_setup(self, p, wr):
            p, opt, _ = super()._local_setup(p, wr)
            return p, opt, wr  # the rate rides where the masks would

        def _apply_update(self, p, grads, opt, wr, n_glob, lr, has=None):
            masks = self._grad_masks({k: g.shape for k, g in grads.items()},
                                     wr + 0.0 * n_glob)
            return super()._apply_update(p, grads, opt, masks, n_glob, lr, has=has)

    rep = _audit_masked_k1(UnhoistedMasks)
    assert rep.ok, rep.findings
    assert rep.step_body["instructions"] <= rep.step_body_budget - 5


def test_scan_body_kernel_count_parses_hlo():
    """The HLO walker finds the while body and counts its fusions on a
    minimal scanned program."""
    from heterofl_tpu.staticcheck.jaxpr_walk import (scan_body_kernel_count,
                                                     while_body_stats)

    def f(c, _):
        return jnp.sin(c) * 2.0 + jnp.cos(c), None

    prog = jax.jit(lambda c: jax.lax.scan(f, c, None, length=64),
                   donate_argnums=())
    text = prog.lower(jnp.ones((128,), jnp.float32)).compile().as_text()
    stats = while_body_stats(text)
    assert stats, "no while body found in scanned program HLO"
    body = scan_body_kernel_count(text)
    assert body["body"] in stats and body["instructions"] > 0


def test_shadowed_inline_import_rule():
    """ISSUE 6 satellite: a function-body import of a module the file
    already imports at module level is flagged in entry/ (the
    entry/common.py inline `import math` regression); genuinely lazy
    imports (name not bound at module level) stay legal, and the pragma
    suppresses with a reason."""
    src = """
    import math
    import json

    def f(x):
        import math
        return math.ceil(x)
    """
    fs = _lint(src, "heterofl_tpu/entry/common.py")
    assert [f.rule for f in fs] == ["no-shadowed-inline-import"]
    # scoped to entry/: engine code may structure imports freely
    assert _lint(src, "heterofl_tpu/parallel/engine.py") == []
    # a lazy import of something NOT bound at module level is fine
    assert _lint("""
    import math

    def f():
        from heterofl_tpu.parallel.grouped import GroupedRoundEngine
        return GroupedRoundEngine
    """, "heterofl_tpu/entry/common.py") == []
    # from-import shadowing counts; aliases resolve by bound name
    fs = _lint("""
    from os import path

    def g():
        from os import path
        return path
    """, "heterofl_tpu/entry/x.py")
    assert [f.rule for f in fs] == ["no-shadowed-inline-import"]
    assert _lint("""
    import math

    def f():
        import math  # staticcheck: allow(no-shadowed-inline-import): reason
        return math
    """, "heterofl_tpu/entry/x.py") == []
    # module-level conditional imports (try/except fallback, platform
    # guard) rebind the module name on purpose -- not a shadow
    assert _lint("""
    import json

    try:
        import ujson as json
    except ImportError:
        import json
    """, "heterofl_tpu/entry/x.py") == []


def test_lint_scope_covers_ops_and_models():
    """ISSUE 5 satellite: the banned-call rules now apply to ops/ and
    models/ (kernel/model code runs INSIDE the round programs)."""
    src = """
    import numpy as np
    import time
    def f(a):
        t = time.time()
        return np.asarray(a), float(a[0]), t
    """
    for scope in ("heterofl_tpu/ops/kernel.py", "heterofl_tpu/models/m.py"):
        rules = {f.rule for f in _lint(src, scope)}
        assert {"no-asarray", "no-float-coercion", "no-wallclock"} <= rules, \
            (scope, rules)
    # data/ stays out of scope for the kernel rules
    assert _lint(src, "heterofl_tpu/data/pipeline.py") == []
