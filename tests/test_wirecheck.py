"""wirecheck (ISSUE 7): the static wire-bytes model, the HBM footprint
budgets, the reshard detector, the baseline ratchet, and the stale-pragma
lint -- including the four seeded regressions the acceptance criteria name
(an extra psum, an un-donated leaf, an injected reshard, inflated peak
bytes), each tripping its distinct named finding."""

import copy
import functools
import json
import os
import textwrap

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from heterofl_tpu.staticcheck.audit import (_grouped_targets, _masked_targets,
                                            audit_program, build_setup)
from heterofl_tpu.staticcheck.jaxpr_walk import (collective_payload_rows,
                                                 find_reshards, reshard_ops)
from heterofl_tpu.staticcheck.memory import (analytic_budget, check_memory,
                                             collect_memory)
from heterofl_tpu.staticcheck.ratchet import (baseline_view, diff_reports,
                                              load_baseline, write_baseline)
from heterofl_tpu.staticcheck.report import AuditReport, ProgramReport
from heterofl_tpu.staticcheck.rules import lint_source
from heterofl_tpu.staticcheck.wire import (check_wire, classify, dcn_axes_of,
                                           participants_of, program_wire,
                                           ring_allreduce_bytes)

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


@pytest.fixture(scope="module")
def setup():
    """One small audit setup shared by the seeded-regression tests."""
    return build_setup()


# ---------------------------------------------------------------------------
# the wire model
# ---------------------------------------------------------------------------

def test_ring_allreduce_bytes():
    # 2 (p-1)/p x payload; a single participant reduces locally (0 wire)
    assert ring_allreduce_bytes(1000, 1) == 0
    assert ring_allreduce_bytes(1000, 2) == 1000
    assert ring_allreduce_bytes(1000, 8) == 1750


class _Dev:
    def __init__(self, process_index):
        self.process_index = process_index


class _FakeMesh:
    def __init__(self, devices, axis_names):
        self.devices = devices
        self.axis_names = axis_names


def test_dcn_axis_classification():
    """A mesh axis whose traversal crosses a process boundary is
    DCN-eligible; single-process meshes are all-ICI."""
    one_proc = _FakeMesh(np.array([[_Dev(0)], [_Dev(0)]]), ("clients", "data"))
    assert dcn_axes_of(one_proc) == ()
    # two processes split along the clients axis
    two_proc = _FakeMesh(np.array([[_Dev(0), _Dev(0)], [_Dev(1), _Dev(1)]]),
                         ("clients", "data"))
    assert dcn_axes_of(two_proc) == ("clients",)
    assert classify(("clients",), ("clients",)) == "dcn"
    assert classify(("data",), ("clients",)) == "ici"
    assert participants_of(("clients", "data"), _FakeMesh(
        np.array([[_Dev(0)] * 3] * 4), ("clients", "data"))) == 12


def _tiny_mesh(n=2):
    from jax.sharding import Mesh

    return Mesh(np.array(jax.devices()[:n]).reshape(n, 1),
                ("clients", "data"))


def test_program_wire_prices_psum_payload():
    """One psum bind over a (sums, counts) pair is priced at the summed
    per-participant operand bytes, attributed to the training axis."""
    from jax.sharding import PartitionSpec as P

    mesh = _tiny_mesh()

    def f(a, b):
        return jax.lax.psum((a, b), "clients")

    sm = jax.shard_map(f, mesh=mesh, in_specs=(P("clients"), P("clients")),
                   out_specs=(P(), P()), check_vma=False)
    x = np.ones((4, 8), np.float32)  # per-device (2, 8) f32 = 64 bytes
    jaxpr = jax.jit(sm).trace(x, x).jaxpr
    rows = collective_payload_rows(jaxpr)
    assert len(rows) == 1 and rows[0]["primitive"] == "psum"
    assert rows[0]["payload_bytes"] == 2 * 2 * 8 * 4
    wire = program_wire(jaxpr, mesh)
    assert wire["train_bytes_per_round"] == 128
    assert wire["eval_bytes_total"] == 0 and wire["dcn_bytes"] == 0
    assert wire["collectives"][0]["scope"] == "ici"
    assert wire["collectives"][0]["ring_bytes_per_device"] == \
        ring_allreduce_bytes(128, 2)

    rep = ProgramReport(name="t")
    check_wire(rep, wire, expected_train_bytes=128, n_eval_points=0)
    assert rep.ok
    rep2 = ProgramReport(name="t")
    check_wire(rep2, wire, expected_train_bytes=64, n_eval_points=0)
    assert not rep2.ok
    assert [f.rule for f in rep2.findings] == ["wire-budget"]


def test_wire_dcn_budget():
    rep = ProgramReport(name="t")
    wire = {"train_bytes_per_round": 0, "eval_bytes_total": 0,
            "eval_payloads": [], "other_bytes": 0, "collectives": [],
            "dcn_bytes": 100, "dcn_axes": ["clients"]}
    check_wire(rep, wire, expected_train_bytes=0, n_eval_points=0,
               dcn_budget_bytes=0)
    assert [f.rule for f in rep.findings] == ["wire-dcn"]


def test_wire_unbudgeted_collective_trips(setup):
    """A reduction smuggled past the psum bind count (pmax over clients)
    still shows up by its payload: bytes outside the train/eval buckets
    are zero in every green program."""
    from jax.sharding import PartitionSpec as P

    mesh = _tiny_mesh()

    def f(a, b):
        s = jax.lax.psum((a, b), "clients")
        return s, jax.lax.pmax(a, "clients")  # the smuggled reduction

    sm = jax.shard_map(f, mesh=mesh, in_specs=(P("clients"), P("clients")),
                   out_specs=((P(), P()), P()), check_vma=False)
    x = np.ones((4, 8), np.float32)
    wire = program_wire(jax.jit(sm).trace(x, x).jaxpr, mesh)
    assert wire["other_bytes"] == 64  # the per-device pmax operand
    rep = ProgramReport(name="t")
    check_wire(rep, wire, expected_train_bytes=128, n_eval_points=0)
    assert [f_.rule for f_ in rep.findings] == ["wire-unbudgeted"]
    assert "pmax" in rep.findings[0].message


def test_level_param_table_is_byte_table_view():
    """level_param_table is a count view over level_byte_table -- one
    source of truth for parameter footprints."""
    from heterofl_tpu.fed.core import (PARAM_ITEMSIZE, level_byte_table,
                                       level_param_table)
    from heterofl_tpu.staticcheck.audit import default_audit_cfg

    cfg = default_audit_cfg()
    bt, pt = level_byte_table(cfg), level_param_table(cfg)
    assert set(bt) == set(pt)
    for r in bt:
        assert bt[r]["param_bytes"] == pt[r] * PARAM_ITEMSIZE
        assert bt[r]["wire_bytes"] == 2 * bt[r]["param_bytes"]


# ---------------------------------------------------------------------------
# seeded regression 1: an EXTRA PSUM trips wire-budget (and psum-budget)
# ---------------------------------------------------------------------------

def test_seeded_extra_psum_trips_wire_budget(setup, monkeypatch):
    """A second global reduction smuggled into the round body is caught by
    BOTH the bind-count budget and the byte-accurate wire budget."""
    from heterofl_tpu.parallel.round_engine import RoundEngine

    orig = RoundEngine._round_core

    def doubled(self, params, key, lr, user_loc, user_glob, data,
                resid=None, sched_buf=None):
        new_p, ms, new_resid, new_buf = orig(self, params, key, lr, user_loc,
                                             user_glob, data, resid=resid,
                                             sched_buf=sched_buf)
        leak = jax.lax.psum(lr, "clients")  # the extra 4-byte global psum
        k0 = next(iter(new_p))
        new_p = dict(new_p)
        new_p[k0] = new_p[k0] + 0.0 * leak
        return new_p, ms, new_resid, new_buf

    monkeypatch.setattr(RoundEngine, "_round_core", doubled)
    name, prog, args, expect = _masked_targets(setup)[0]
    rep = audit_program(name, prog, args, expect, setup["mesh"])
    rules = {f.rule for f in rep.findings}
    assert "psum-budget" in rules
    assert "wire-budget" in rules, rep.findings
    msg = next(f for f in rep.findings if f.rule == "wire-budget").message
    # the finding names measured vs budgeted bytes (payload grew by 4)
    assert str(expect["wire_bytes"] + 4) in msg and str(expect["wire_bytes"]) in msg


# ---------------------------------------------------------------------------
# seeded regression 2: an UN-DONATED LEAF trips hbm-donation-savings
# ---------------------------------------------------------------------------

def test_seeded_undonated_leaf_trips_donation_savings(setup):
    """A program that stopped donating its carry loses the aliasing bytes:
    besides the count mismatches, the HBM accounting names the bytes that
    are now silently double-buffered."""
    grouped, _names, _ = _grouped_targets(setup)
    name, prog, args, expect = grouped[0]  # span level prog: donates 0
    assert expect["donated"] == 0
    n_leaves = len(jax.tree_util.tree_leaves(setup["params"]))
    rep = audit_program(name, prog, args, dict(expect, donated=n_leaves),
                        setup["mesh"])
    rules = {f.rule for f in rep.findings}
    assert "hbm-donation-savings" in rules, rep.findings
    acct = rep.memory_budget["donation"]
    assert acct["saved_bytes"] == 0
    assert acct["expected_saved_bytes"] == expect["mem"]["param_bytes"] > 0


# ---------------------------------------------------------------------------
# seeded regression 3: an INJECTED RESHARD trips the reshard detector
# ---------------------------------------------------------------------------

def test_seeded_reshard_trips_detector(setup, monkeypatch):
    """A ppermute smuggled into the round body is an explicit data-movement
    collective: zero are allowed in any round program."""
    from heterofl_tpu.parallel.round_engine import RoundEngine

    orig = RoundEngine._round_core

    def shifted(self, params, key, lr, user_loc, user_glob, data,
                resid=None, sched_buf=None):
        new_p, ms, new_resid, new_buf = orig(self, params, key, lr, user_loc,
                                             user_glob, data, resid=resid,
                                             sched_buf=sched_buf)
        n = self.mesh.shape["clients"]
        k0 = next(iter(new_p))
        new_p = dict(new_p)
        new_p[k0] = jax.lax.ppermute(
            new_p[k0], "clients", [(i, (i + 1) % n) for i in range(n)])
        return new_p, ms, new_resid, new_buf

    monkeypatch.setattr(RoundEngine, "_round_core", shifted)
    name, prog, args, expect = _masked_targets(setup)[0]
    jaxpr = prog.trace(*args).jaxpr
    hits = find_reshards(jaxpr)
    assert hits and hits[0][0] == "ppermute"
    assert "test_wirecheck" in hits[0][1]  # provenance of the bind
    rep = audit_program(name, prog, args, expect, setup["mesh"])
    assert not rep.ok
    hits = [f for f in rep.findings if f.rule == "reshard"]
    assert hits and "ppermute" in hits[0].message
    assert rep.reshards["total"] >= 1


def test_reshard_ops_parses_optimized_hlo_text():
    """The HLO half counts GSPMD-introduced data movement: sync and async
    `-start` forms count once, `-done` halves are skipped."""
    text = textwrap.dedent("""\
        %a2a.1 = f32[4]{0} all-to-all(f32[4]{0} %p), dimensions={0}
        %cp = f32[4]{0} collective-permute(f32[4]{0} %p), channel_id=1
        %cps = (f32[4]{0}, f32[4]{0}) collective-permute-start(f32[4]{0} %p)
        %cpd = f32[4]{0} collective-permute-done((f32[4]{0}, f32[4]{0}) %cps)
        %ar = f32[4]{0} all-reduce(f32[4]{0} %p), to_apply=%sum
        """)
    counts = reshard_ops(text)
    assert counts["all-to-all"] == 1
    assert counts["collective-permute"] == 2  # sync + start, not done
    assert counts["total"] == 3
    assert reshard_ops("%ar = f32[4]{0} all-reduce(f32[4]{0} %p)")["total"] == 0


# ---------------------------------------------------------------------------
# seeded regression 4: INFLATED PEAK BYTES trip hbm-budget
# ---------------------------------------------------------------------------

def test_seeded_inflated_temp_trips_hbm_budget(setup):
    """A program whose HBM footprint blows past what its declared shapes
    justify fails the audit instead of the TPU: a 4 MiB working set against
    a few-bytes analytic model lands far over the bound."""
    def f(x):
        a = jnp.full((1024, 1024), x)  # 4 MiB materialised temp
        return (a @ a).sum()

    rep = audit_program(
        "seeded/inflated-temp", jax.jit(f), (np.float32(1.0),),
        {"donated": 0, "psum": 0, "wire_bytes": 0,
         "mem": {"param_bytes": 4, "activation_bytes": 4,
                 "clients_per_device": 1}},
        setup["mesh"])
    hits = [f_ for f_ in rep.findings if f_.rule == "hbm-budget"]
    assert hits, rep.findings
    assert "temp_size_in_bytes" in hits[0].message
    assert rep.memory["temp_size_in_bytes"] > rep.memory_budget["temp_budget"]


def test_check_memory_budget_fields():
    budget = analytic_budget(param_bytes=100, activation_bytes=50,
                             clients_per_device=2, staged_arg_bytes=1000,
                             train_payload_bytes=200)
    rep = ProgramReport(name="t")
    check_memory(rep, {"temp_size_in_bytes": budget["temp_budget"],
                       "argument_size_in_bytes": 0,
                       "output_size_in_bytes": 0}, budget)
    assert rep.ok  # at the bound is fine
    rep2 = ProgramReport(name="t")
    check_memory(rep2, {"temp_size_in_bytes": budget["temp_budget"] + 1,
                        "argument_size_in_bytes": 0,
                        "output_size_in_bytes": 0}, budget)
    assert [f.rule for f in rep2.findings] == ["hbm-budget"]


# ---------------------------------------------------------------------------
# satellite: absent memory_analysis() fields are LOUD findings
# ---------------------------------------------------------------------------

def test_missing_memory_analysis_is_loud():
    """The old getattr-skip silently produced an empty record; now an
    absent field on a compiled flagship program is a named finding."""
    fields, findings = collect_memory(None, "p")
    assert fields is None
    assert [f.rule for f in findings] == ["memory-analysis-missing"]

    class Partial:  # argument/output there, temp gone dark
        argument_size_in_bytes = 10
        output_size_in_bytes = 5

    fields, findings = collect_memory(Partial(), "p")
    assert [f.rule for f in findings] == ["memory-analysis-missing"]
    assert "temp_size_in_bytes" in findings[0].message
    assert fields == {"argument_size_in_bytes": 10, "output_size_in_bytes": 5}

    class Full(Partial):
        temp_size_in_bytes = 7

    fields, findings = collect_memory(Full(), "p")
    assert not findings
    assert fields["peak_bytes"] == 22


# ---------------------------------------------------------------------------
# satellite: stale-pragma lint
# ---------------------------------------------------------------------------

IN_SCOPE = "heterofl_tpu/parallel/somefile.py"


def _lint(src, relpath=IN_SCOPE):
    return lint_source(textwrap.dedent(src), relpath)


def test_stale_pragma_dead_suppression():
    """A pragma whose rule no longer fires on the lines it covers is
    reported instead of rotting silently."""
    live = _lint("""
    import numpy as np
    def f(a):
        return np.asarray(a)  # staticcheck: allow(no-asarray): reason
    """)
    assert live == []
    dead = _lint("""
    import numpy as np
    def f(a):
        return np.array(a)  # staticcheck: allow(no-asarray): rotted
    """)
    assert [f.rule for f in dead] == ["stale-pragma"]
    assert "no-asarray" in dead[0].message


def test_stale_pragma_unknown_and_out_of_scope_rule():
    fs = _lint("""
    def f(a):
        return a  # staticcheck: allow(no-such-rule): typo'd id
    """)
    assert [f.rule for f in fs] == ["stale-pragma"]
    assert "unknown rule id" in fs[0].message
    # a driver-only rule pragma'd in parallel/ can never suppress anything
    fs = _lint("""
    def f(ev):
        return ev  # staticcheck: allow(no-host-eval-in-driver): wrong tree
    """)
    assert [f.rule for f in fs] == ["stale-pragma"]
    assert "not scoped" in fs[0].message


def test_stale_pragma_reports_only_dead_half_of_multi_id():
    fs = _lint("""
    import numpy as np
    def f(a):
        return np.asarray(a)  # staticcheck: allow(no-asarray, no-device-get): half-dead
    """)
    assert [f.rule for f in fs] == ["stale-pragma"]
    assert "no-device-get" in fs[0].message
    assert "allow(no-asarray)" not in fs[0].message


def test_stale_pragma_comment_block_coverage():
    """A pragma in a comment block covers the statement the block precedes
    -- it is live when that statement violates the rule."""
    assert _lint("""
    import numpy as np
    def f(a):
        # staticcheck: allow(no-asarray): a longer reason that
        # spans two comment lines before the call it licenses
        return np.asarray(a)
    """) == []


# ---------------------------------------------------------------------------
# the baseline ratchet (jax-free)
# ---------------------------------------------------------------------------

def _mini_report(fusions=10, temp=1000, donated=2, wire=64, flops=100.0,
                 fail=False, extra_program=None):
    rep = AuditReport()
    rep.config = {"flagship": False, "data_name": "X", "model_name": "m",
                  "num_users": 2, "levels": [1.0],
                  "mesh": {"clients": 8, "data": 1}}
    p = ProgramReport(name="prog/a", donation_expected=donated)
    p.psum_clients = 1
    p.donated = p.aliased = donated
    p.flops = flops
    p.memory = {"temp_size_in_bytes": temp, "argument_size_in_bytes": 10,
                "output_size_in_bytes": 5}
    p.wire = {"train_bytes_per_round": wire, "eval_bytes_total": 0,
              "other_bytes": 0, "dcn_bytes": 0}
    p.reshards = {"total": 0}
    p.step_body = {"fusions": fusions, "instructions": 200}
    if fail:
        p.fail("psum-budget", "seeded failure")
    rep.add_program(p)
    if extra_program:
        rep.add_program(ProgramReport(name=extra_program))
    rep.flop_budget = {"ok": True}
    rep.recompile = {"ok": True}
    rep.generated_at = "2026-01-01T00:00:00+00:00"
    return rep


def test_ratchet_clean_roundtrip_and_file_io(tmp_path):
    rep = _mini_report()
    path = str(tmp_path / "BASE.json")
    write_baseline(path, rep.to_dict())
    base = load_baseline(path)
    assert base["version"] == 2
    diff = diff_reports(rep.to_dict(), base)
    assert diff["ok"] and not diff["regressions"]
    assert diff["baseline_generated_at"] == rep.generated_at


def test_ratchet_headroom_and_exact_metrics():
    base = baseline_view(_mini_report(fusions=100).to_dict())
    # +10% fusions sits inside the 15% headroom; +20% regresses
    ok = diff_reports(_mini_report(fusions=110).to_dict(), base)
    assert ok["ok"], ok["regressions"]
    bad = diff_reports(_mini_report(fusions=120).to_dict(), base)
    assert not bad["ok"]
    assert [r["metric"] for r in bad["regressions"]] == ["step_body.fusions"]
    # wire bytes are exact: +1 byte regresses
    bad = diff_reports(_mini_report(wire=65).to_dict(), base)
    assert [r["metric"] for r in bad["regressions"]] == \
        ["wire.train_bytes_per_round"]
    # improvements are recorded, never failed: the ratchet only tightens
    better = diff_reports(_mini_report(fusions=50, wire=32).to_dict(), base)
    assert better["ok"]
    assert {i["metric"] for i in better["improvements"]} >= \
        {"step_body.fusions", "wire.train_bytes_per_round"}


def test_ratchet_change_bad_and_dark_metrics():
    base = baseline_view(_mini_report(donated=2).to_dict())
    # donation coverage has ONE right answer: shrinking it also regresses
    bad = diff_reports(_mini_report(donated=1).to_dict(), base)
    assert any(r["metric"] == "donated" for r in bad["regressions"])
    # a metric going dark (None where the baseline had a number) regresses
    rep = _mini_report()
    rep.programs["prog/a"].wire = None
    bad = diff_reports(rep.to_dict(), base)
    assert any(r["metric"] == "wire.train_bytes_per_round"
               and r["current"] is None for r in bad["regressions"])


def test_ratchet_program_set_and_config_drift():
    base = baseline_view(_mini_report(extra_program="prog/b").to_dict())
    shrunk = diff_reports(_mini_report().to_dict(), base)
    assert not shrunk["ok"] and shrunk["missing_programs"] == ["prog/b"]
    grown = diff_reports(_mini_report(extra_program="prog/c").to_dict(),
                         baseline_view(_mini_report().to_dict()))
    assert grown["ok"] and grown["new_programs"] == ["prog/c"]
    # incomparable configs are a single loud regression, not a metric soup
    other = _mini_report()
    other.config = dict(other.config, num_users=1000)
    drift = diff_reports(other.to_dict(),
                         baseline_view(_mini_report().to_dict()))
    assert not drift["ok"]
    assert [r["metric"] for r in drift["regressions"]] == ["config"]
    assert "--update-baseline" in drift["regressions"][0]["message"]


# ---------------------------------------------------------------------------
# the CLI: exit codes, --json schema, ratchet round-trip
# ---------------------------------------------------------------------------

@pytest.fixture
def cli(monkeypatch, tmp_path):
    """In-process CLI runner with the program audit stubbed to a fabricated
    report (the real-audit CLI path is covered by the slow test in
    test_staticcheck.py): returns (run, paths)."""
    import heterofl_tpu.staticcheck.__main__ as cli_mod
    import heterofl_tpu.staticcheck.audit as audit_mod

    state = {"report": _mini_report()}
    monkeypatch.setattr(cli_mod, "_pin_cpu_for_audit", lambda: None)
    monkeypatch.setattr(audit_mod, "run_audit",
                        lambda **kw: copy.deepcopy(state["report"]))
    out = str(tmp_path / "STATICCHECK.json")
    baseline = str(tmp_path / "BASELINE.json")

    def run(*extra):
        return cli_mod.main(["--skip-lint", "--out", out,
                             "--baseline", baseline] + list(extra))

    run.state = state
    run.out = out
    run.baseline = baseline
    return run


def test_cli_green_exit_and_json_schema(cli, capsys):
    assert cli("--json") == 0
    rec = json.loads(capsys.readouterr().out)
    assert sorted(rec) == ["arms", "config", "flop_budget", "generated_at",
                           "key_streams", "lattice", "lint", "ok", "programs",
                           "ratchet", "recompile", "sampler", "version",
                           "wire_frontier"]
    prog = rec["programs"]["prog/a"]
    for key in ("wire", "memory", "reshards", "step_body", "psum_clients",
                "donated", "aliased", "flops", "findings"):
        assert key in prog, key
    assert rec["ratchet"] == {"checked": False}
    assert json.loads(open(cli.out).read())["ok"] is True


def test_cli_ratchet_roundtrip_then_regress(cli, capsys):
    # pin, then diff the identical audit: clean, exit 0
    assert cli("--update-baseline") == 0
    assert os.path.exists(cli.baseline)
    assert cli("--diff-baseline") == 0
    rec = json.loads(open(cli.out).read())
    assert rec["ratchet"]["checked"] and rec["ratchet"]["ok"]
    capsys.readouterr()
    # regress a metric past its headroom: exit 2 (audit itself stays green)
    cli.state["report"] = _mini_report(fusions=20)
    assert cli("--diff-baseline", "--json") == 2
    rec = json.loads(capsys.readouterr().out)
    assert rec["ok"] is True and rec["ratchet"]["ok"] is False
    assert [r["metric"] for r in rec["ratchet"]["regressions"]] == \
        ["step_body.fusions"]
    # and re-pinning after the intentional change makes the diff clean again
    assert cli("--update-baseline") == 0
    assert cli("--diff-baseline") == 0


def test_cli_audit_failure_beats_ratchet_exit(cli, capsys):
    assert cli("--update-baseline") == 0
    cli.state["report"] = _mini_report(fail=True)
    assert cli("--diff-baseline") == 1  # audit failure keeps exit 1
    capsys.readouterr()


def test_cli_refuses_to_pin_failing_audit(cli, capsys):
    cli.state["report"] = _mini_report(fail=True)
    assert cli("--update-baseline") == 1
    assert not os.path.exists(cli.baseline)
    captured = capsys.readouterr()
    assert "refusing" in captured.err
    # the refusal does NOT short-circuit the run: the failing artifact is
    # still written and the findings still print, like a plain failing run
    assert json.loads(open(cli.out).read())["ok"] is False
    assert "psum-budget" in captured.out


def test_cli_missing_baseline_is_a_regression(cli, capsys):
    assert cli("--diff-baseline", "--json") == 2
    rec = json.loads(capsys.readouterr().out)
    assert rec["ratchet"]["checked"] and not rec["ratchet"]["ok"]
    assert "--update-baseline" in rec["ratchet"]["regressions"][0]["message"]


def test_cli_diff_needs_audit(cli):
    with pytest.raises(SystemExit):
        cli("--diff-baseline", "--skip-audit")


def test_committed_baseline_matches_committed_artifact():
    """The repo's committed STATICCHECK_BASELINE.json is the pinned view of
    the committed STATICCHECK.json: the ratchet diff between them is clean,
    so CI's --diff-baseline run starts from a green line."""
    with open(os.path.join(REPO, "STATICCHECK.json")) as f:
        artifact = json.load(f)
    baseline = load_baseline(os.path.join(REPO, "STATICCHECK_BASELINE.json"))
    diff = diff_reports(artifact, baseline)
    assert diff["ok"], diff["regressions"]
