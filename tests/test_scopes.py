"""The round program names its parts (ISSUE 26): every scope of
``obs.trace.SCOPES`` reaches the ``op_name`` metadata of the instructions it
covers, autodiff splits ``step/model`` into forward and backward, the Pallas
kernels carry their ``name=``, and a scope is a name only -- the optimised
program is the same instruction for instruction with ``jax.named_scope``
patched away.

Compiles here run with the persistent compile cache OFF: metadata is outside
the cache key, so a warm cache would hand back whichever of the two programs
was compiled first (the cache trap of PERF.md).
"""

import collections
import contextlib
import re

import jax
import numpy as np
import pytest

from heterofl_tpu.models import make_model
from heterofl_tpu.obs import trace
from heterofl_tpu.parallel import GroupedRoundEngine, RoundEngine, make_mesh
from heterofl_tpu.parallel.evaluation import Evaluator
from heterofl_tpu.staticcheck.jaxpr_walk import iter_eqns
from heterofl_tpu.utils.compile_cache import no_persistent_cache

from test_round import _lm_setup, _vision_setup

ROUND = ["round/gather", "round/local_train", "round/aggregate",
         "round/aggregate/psum"]
STEP = ["step/batch", "step/update", "linear", "norm", "loss"]
CASES = {
    "vision": STEP + ["conv", "step/batch/augment"],
    "lm": STEP + ["embed", "attn"],
    "grouped": STEP + ["conv", "step/batch/augment"],
}


def _case(case):
    """(cfg, cohort, data stacks) of a tiny case."""
    if case == "lm":
        cfg, data = _lm_setup()
        return cfg, np.arange(4, dtype=np.int32), data
    cfg, _, data = _vision_setup()
    return cfg, np.array([0, 2, 4, 6], np.int32), data


def _round_args(eng, params, users, data):
    fix = (eng.fix_rates,) if eng.fix_rates is not None else ()
    return (params, jax.random.key(0), np.float32(0.1), users, users, *data, *fix)


def _program(case):
    """(jitted round program, its arguments) at a tiny size."""
    cfg, users, data = _case(case)
    key, lr, mesh = jax.random.key(0), np.float32(0.1), make_mesh(2, 1)
    if case == "grouped":
        eng = GroupedRoundEngine(cfg, mesh)
        params = eng.global_model.init(jax.random.key(0))
        rate = float(max(cfg["model_rate"]))
        return eng._level_prog(rate, 2), (params, key, lr, users[:2], *data)
    model = make_model(cfg)
    eng = RoundEngine(model, cfg, mesh)
    return eng._build_train(), _round_args(eng, model.init(jax.random.key(0)),
                                           users, data)


def _op_names(prog, args):
    """The ``op_name`` of every located operation of the lowered program."""
    text = prog.lower(*args).as_text(debug_info=True)
    return set(re.findall(r'loc\("([^"]+)"', text))


def _opcode_counts(prog, args):
    text = prog.lower(*args).compile().as_text()
    return collections.Counter(
        m.group(1) for m in re.finditer(
            r"^\s+(?:ROOT )?\S+ = .*?\s([a-z][\w\-]*)\(", text, re.M))


@pytest.mark.parametrize("case", sorted(CASES))
def test_every_scope_reaches_the_op_names(case):
    prog, args = _program(case)
    # a leading slash: inside the scan body's own function the lowering
    # writes paths relative to it, before XLA inlines the call
    names = ["/" + n for n in _op_names(prog, args)]
    for scope in ROUND + CASES[case]:
        assert any(f"/{scope}/" in n for n in names), \
            f"{case}: no op_name carries {scope!r}"
    # entered inside the differentiated function: autodiff marks the sides
    assert any("/jvp(step/model)/" in n for n in names)
    assert any("/transpose(jvp(step/model))/" in n for n in names)
    leaf = "conv" if case != "lm" else "embed"
    assert any(f"/jvp(step/model)/{leaf}/" in n for n in names)
    assert any(f"/transpose(jvp(step/model))/{leaf}/" in n for n in names)
    # the optimizer tail is outside the differentiated function
    assert not any("jvp(step/update" in n or "jvp(step/batch" in n for n in names)


@pytest.mark.parametrize("case", sorted(CASES))
def test_a_scope_is_a_name_and_nothing_else(case, monkeypatch):
    """Instruction count per opcode of the optimised program, with and
    without the scopes."""
    with no_persistent_cache():
        scoped = _opcode_counts(*_program(case))
        monkeypatch.setattr(jax, "named_scope",
                            lambda name: contextlib.nullcontext())
        plain_prog, plain_args = _program(case)
        assert not any("round/" in n or "step/" in n
                       for n in _op_names(plain_prog, plain_args))
        plain = _opcode_counts(plain_prog, plain_args)
    assert sum(scoped.values()) > 500
    assert scoped == plain


@pytest.mark.parametrize("case", sorted(CASES))
def test_the_step_carries_the_leaves(case):
    """The K=1 round program's local-step scan carries one buffer per
    parameter leaf and per momentum leaf, each in the shape the model reads
    it in, and no flat buffer of the whole tree (ISSUE 27: flattening,
    packing, unpacking and unflattening were 76 % / 83 % of the step on the
    v5e); no kernel is in the step, and the update is outside the
    differentiated function."""
    prog, args = _program(case)
    names = ["/" + n for n in _op_names(prog, args)]
    for kept in ("step/update", "jvp(step/model)", "transpose(jvp(step/model))"):
        assert any(f"/{kept}/" in n for n in names), f"{case}: no {kept!r}"

    eqns = list(iter_eqns(jax.make_jaxpr(prog)(*args).jaxpr))
    assert not any(e.primitive.name == "pallas_call" for e in eqns)
    scans = [e for e in eqns if e.primitive.name == "scan"]
    assert len(scans) == 1  # the local-step loop, under the client vmap
    nc, nk = scans[0].params["num_consts"], scans[0].params["num_carry"]
    # [slots, *leaf] each: the vmapped clients' axis leads
    carried = collections.Counter(
        tuple(v.aval.shape[1:]) for v in scans[0].invars[nc:nc + nk])
    params = args[0]  # grouped: level a's dense sub-model is the global model
    leaves = collections.Counter(tuple(v.shape) for v in params.values())
    total = sum(int(np.prod(v.shape)) for v in params.values())
    assert (total,) not in carried  # no flat buffer of FlatSpec.total
    for shape, n in leaves.items():
        assert carried[shape] == 2 * n, (shape, carried[shape], n)  # params + momentum
    # what else rides: the optimizer's step counter and the three metric sums
    assert sum(carried.values()) - 2 * len(params) == carried[()] == 4


def test_eval_bodies_are_scoped():
    cfg, ds, _ = _vision_setup()
    model = make_model(cfg)
    params = model.init(jax.random.key(0))
    ev = Evaluator(model, cfg, make_mesh(2, 1))
    x = ds["test"].data[:80].reshape(4, 20, 28, 28, 1)
    y = ds["test"].target[:80].reshape(4, 20)
    w = np.ones((4, 20), np.float32)
    key = jax.random.key(0)
    def scopes(prog, *args):  # relative inside a scan body (see above)
        return ["/" + n for n in _op_names(prog, args)]

    sbn = scopes(ev._build_sbn(), params, x, w)
    assert any("/eval/sbn/while/" in n for n in sbn) and any("/norm/" in n for n in sbn)
    bn = jax.eval_shape(ev._build_sbn(), params, x, w)
    glob = scopes(ev._build_global(), params, bn, key, x, y, w)
    assert any("/eval/global/while/" in n for n in glob) and any("/conv/" in n for n in glob)
    users = scopes(ev._build_users(), params, bn, key, np.ones(2, np.float32),
                   x.reshape(2, 2, 20, 28, 28, 1), y.reshape(2, 2, 20),
                   w.reshape(2, 2, 20), np.ones((2, 10), np.float32))
    assert any("/eval/users/" in n for n in users) and any("/loss/" in n for n in users)


def test_the_vocabulary_is_closed():
    assert len(set(trace.SCOPES)) == len(trace.SCOPES)
    with pytest.raises(ValueError, match="Not valid scope"):
        trace.scope("step/modle")
    with pytest.raises(ValueError, match="Not valid scope"):
        trace.scoped("convolution")
    with trace.scope("round/gather"):
        pass


@pytest.mark.parametrize("kernel, module", [
    ("masked_bn_fwd", "pallas_norm"),
    ("masked_bn_bwd", "pallas_norm"), ("int8_pack", "quant"),
    ("latent_attn_fwd", "pallas_attention"), ("latent_attn_bwd", "pallas_attention"),
    ("gq_attn_fwd", "pallas_attention"), ("gq_attn_bwd", "pallas_attention"),
    ("band_attn_fwd", "pallas_attention"), ("band_attn_bwd", "pallas_attention"),
    ("ssm_scan_fwd", "pallas_ssm"), ("ssm_scan_bwd", "pallas_ssm")])
def test_every_pallas_call_is_named(kernel, module):
    import importlib
    import inspect

    src = inspect.getsource(importlib.import_module(f"heterofl_tpu.ops.{module}"))
    kernels = trace.KERNELS + trace.EXTRA_KERNELS + trace.SSM_KERNELS
    assert kernel in kernels and len(set(kernels)) == len(kernels)
    # by its name in the call, or (the scan's pair, one call for both) handed to it
    assert f'name="{kernel}"' in src or f'"{kernel}",' in src
    assert src.count("pallas_call(") == src.count("        name=")


# ---------------------------------------------------------------------------
# the scopes ISSUE 28 added (obs.trace.EXTRA_SCOPES)
# ---------------------------------------------------------------------------

def _kanana_program(chunk):
    from test_round import _chunk_case

    cfg, data = _chunk_case("kanana2")
    cfg = dict(cfg, round_chunk=chunk)
    model = make_model(cfg)
    eng = RoundEngine(model, cfg, make_mesh(2, 1))
    users = np.arange(8, dtype=np.int32)
    return eng._build_train(), _round_args(eng, model.init(jax.random.key(0)), users, data)


def test_the_expert_layers_and_the_chunk_carry_their_names():
    """Every scope of `EXTRA_SCOPES` reaches the chunked Kanana-2 round's
    `op_name`s, nested as the program nests them: `mla`, `rope`, `attn` and
    the four `moe/*` under `step/model`, forward and backward; `attn` holds
    the scores and no projection; `round/chunk` encloses the local training
    of a chunk; the unchunked program has no `round/chunk`."""
    assert not set(trace.EXTRA_SCOPES) & set(trace.SCOPES)
    prog, args = _kanana_program(2)
    # of the COMPILED program: the expert layers are the body of a scan, a
    # function of its own whose paths the lowering writes relative to it and
    # XLA completes when it inlines the call
    names = ["/" + n for n in re.findall(
        r'op_name="([^"]+)"', prog.lower(*args).compile().as_text())]
    for s in trace.EXTRA_SCOPES:
        assert any(f"/{s}/" in n for n in names), f"no op_name carries {s!r}"
    for s in ("mla", "rope", "attn", "moe/router", "moe/dispatch", "moe/experts",
              "moe/shared"):
        for wrap in ("jvp(step/model)", "transpose(jvp(step/model))"):
            if s == "moe/router" and wrap.startswith("transpose"):
                continue  # top-k has no backward; the scores' lies under it
            assert any(f"/{wrap}/" in n and f"/{s}/" in n for n in names), (s, wrap)
    assert any("/round/chunk/round/local_train/" in n for n in names)
    assert any("/round/chunk/round/aggregate/" in n for n in names)
    attn = [n for n in names if "/attn/" in n]
    assert attn and not any("/linear/" in n for n in attn)
    plain, plain_args = _kanana_program(None)
    assert not any("round/chunk" in n for n in _op_names(plain, plain_args))
    with pytest.raises(ValueError, match="Not valid scope"):
        trace.scope("moe/expert")


def test_the_mixers_scopes_are_a_vocabulary_of_their_own():
    """`MIXER_SCOPES` (ISSUE 32) is disjoint from the two older tuples, which
    the accepted benchmark mirrors name for name; `scope()` takes all three,
    and a scope of two words names its parent (`shortconv/gate` lies inside
    `shortconv`).  That the LFM2 round enters them: tests/test_lfm2.py."""
    assert trace.MIXER_SCOPES == ("shortconv", "shortconv/gate", "gqa")
    assert not set(trace.MIXER_SCOPES) & set(trace.SCOPES + trace.EXTRA_SCOPES)
    for s in trace.SCOPES + trace.EXTRA_SCOPES + trace.MIXER_SCOPES:
        with trace.scope(s):
            pass
    assert trace.SCOPE_VERSION >= 4  # bumped with the new names (the compile cache's key)


def test_the_indexers_scopes_are_a_vocabulary_of_their_own():
    """`SPARSE_SCOPES` (ISSUE 35) is disjoint from the three older tuples,
    which the accepted benchmark's readers mirror name for name; `scope()`
    takes all four and refuses a neighbour of theirs.  That the Keye round
    enters them, in the forward and its recomputation only:
    tests/test_keye.py."""
    older = trace.SCOPES + trace.EXTRA_SCOPES + trace.MIXER_SCOPES
    assert trace.SPARSE_SCOPES == ("sparse/index", "sparse/select")
    assert not set(trace.SPARSE_SCOPES) & set(older)
    for s in older + trace.SPARSE_SCOPES:
        with trace.scope(s):
            pass
    with pytest.raises(ValueError, match="Not valid scope"):
        trace.scope("sparse")
    assert trace.SCOPE_VERSION >= 5


def test_the_loops_scopes_are_a_vocabulary_of_their_own():
    """`LOOP_SCOPES` (ISSUE 40) is disjoint from the four older tuples, which
    the accepted benchmark's readers mirror name for name; `scope()` takes all
    five and refuses a neighbour of theirs.  That the Ouro round enters them,
    with the attention's scopes inside `loop/pass`: tests/test_ouro.py."""
    older = trace.SCOPES + trace.EXTRA_SCOPES + trace.MIXER_SCOPES + trace.SPARSE_SCOPES
    assert trace.LOOP_SCOPES == ("loop/pass", "loop/head", "loop/exit")
    assert not set(trace.LOOP_SCOPES) & set(older)
    for s in older + trace.LOOP_SCOPES:
        with trace.scope(s):
            pass
    with pytest.raises(ValueError, match="Not valid scope"):
        trace.scope("loop/gate")
    assert trace.SCOPE_VERSION >= 6


def test_the_windows_scope_is_a_vocabulary_of_its_own():
    """`WINDOW_SCOPES` (ISSUE 42) is disjoint from the five older tuples, which
    the accepted benchmark's readers mirror name for name; `scope()` takes all
    six and refuses a neighbour of theirs.  That the Laguna round enters it,
    around the sliding layers' score / softmax / value part alone:
    tests/test_laguna.py."""
    older = trace.SCOPES + trace.EXTRA_SCOPES + trace.MIXER_SCOPES + trace.SPARSE_SCOPES \
        + trace.LOOP_SCOPES
    assert trace.WINDOW_SCOPES == ("swa",)
    assert not set(trace.WINDOW_SCOPES) & set(older)
    for s in older + trace.WINDOW_SCOPES:
        with trace.scope(s):
            pass
    with pytest.raises(ValueError, match="Not valid scope"):
        trace.scope("swa/window")
    assert trace.SCOPE_VERSION >= 7
