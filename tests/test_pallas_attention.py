"""The fused causal attentions (ops/pallas_attention.py) against their
oracles, the ``jnp`` forms ``ops.layers.blockwise_latent_attention`` and
``blockwise_gq_attention`` (with ``select=`` for the selected kernels):
on the CPU the kernels run in interpret mode, which says that the tiling,
the skipped and the masked tiles, the online softmax and the hand-written
backward are right; what Mosaic accepts is tests/test_tpu_compile.py's.

Operands are bfloat16-representable and the scale a power of two, so the
kernel's casts are exact and what is left is its bfloat16 probabilities
against the oracle's float32 ones: 2^-9 of a term, held to 1 % of the largest
element (a wrong mask, a dropped tile or a missing head moves O(1))."""

import re
from functools import partial

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from heterofl_tpu.ops import layers as L
from heterofl_tpu.ops import pallas_attention as PA

SCALE = 0.125


def _normal(key, shapes, keep=None):
    """bfloat16-representable operands of ``shapes``; with ``keep`` (one count
    a shape) the head dims past the count are zero, as a narrow client's are
    under the masked engine."""
    ops = [jax.random.normal(k, s).astype(jnp.bfloat16).astype(jnp.float32)
           for k, s in zip(jax.random.split(key, len(shapes)), shapes)]
    if keep is not None:
        ops = [jnp.where(jnp.arange(x.shape[-1]) < c, x, 0.0) for x, c in zip(ops, keep)]
    return ops[:-1], ops[-1]


def _latent_operands(key, N=1, S=256, H=2, dn=128, dr=64, dv=128, active=None):
    """qn, qr, kn, kr, v (heads first, the one rotary key ``[N, S, dr]``) and a
    probe of the output's shape; ``active = (an, ar, av)`` active head dims."""
    shapes = [(N, H, S, dn), (N, H, S, dr), (N, H, S, dn), (N, S, dr), (N, H, S, dv),
              (N, H, S, dv)]
    an, ar, av = active or (None,) * 3
    return _normal(key, shapes, active and [an, ar, an, ar, av, av])


def _gq_operands(key, N=1, S=256, H=4, Hkv=2, d=64, active=None):
    """q, k, v (heads first, ``Hkv`` key/value heads for ``H`` query heads) and
    a probe of the output's shape; ``active`` active head dims."""
    shapes = [(N, H, S, d), (N, Hkv, S, d), (N, Hkv, S, d), (N, H, S, d)]
    return _normal(key, shapes, active and [active] * 4)


#: family -> (operands, the kernels in interpret mode at a tile, the oracle)
FAMILIES = {
    "latent": (_latent_operands,
               lambda bq, bk: lambda *a: PA.fused_latent_attention(
                   *a, block_q=bq, block_k=bk, interpret=True),
               lambda *a: L.blockwise_latent_attention(*a, block=64)),
    "gq": (_gq_operands,
           lambda bq, bk: lambda *a: PA.fused_gq_attention(
               *a, block_q=bq, block_k=bk, interpret=True),
           lambda *a: L.blockwise_gq_attention(*a, block=64)),
}


def _out_and_grads(attention, ops, probe, scale):
    def loss(*a):
        o = attention(*a, scale)
        return jnp.sum(o * probe), o

    grads, o = jax.grad(loss, argnums=tuple(range(len(ops))), has_aux=True)(*ops)
    return (o,) + grads


def _close(got, want):
    for g, w in zip(got, want):
        assert g.shape == w.shape and g.dtype == w.dtype
        np.testing.assert_allclose(g, w, rtol=0, atol=1e-2 * float(jnp.abs(w).max()))


_SHARED = ["tiles-128x128", "tiles-256x128", "tiles-128x256", "tiles-256x256", "tiles-384x128",
           "narrow-client", "vmap-clients"]


@pytest.mark.parametrize("case", _SHARED + ["rotary-key-heads"] + ["gq-" + c for c in _SHARED]
                         + ["gq-group-1", "gq-group-4", "gq-group-8"])
def test_fused_attention_is_the_blockwise_attention(case):
    """Output and the gradients of every operand, latent attention's kernels
    and (``gq-``) grouped-query attention's: at several query and key tiles
    (the diagonal crosses a tile, lies on its corner, or a query tile spans
    three key tiles); with zero-suffix head dims, which stay zeros; under
    ``vmap`` over clients with a per-client scale; the shared rotary key's
    gradient, the sum over heads of what each head's own copy would get; and
    a key/value head's gradients, the sum over its group of 1, 4 or 8 query
    heads of what a copy a query head would get."""
    family, case = ("gq", case[len("gq-"):]) if case.startswith("gq-") else ("latent", case)
    operands, fused, oracle = FAMILIES[family]
    if case.startswith("tiles-"):
        bq, bk = (int(t) for t in case[len("tiles-"):].split("x"))
        ops, probe = operands(jax.random.key(1), S=768 if bq == 384 else 256)
        _close(_out_and_grads(fused(bq, bk), ops, probe, SCALE),
               _out_and_grads(oracle, ops, probe, SCALE))
    elif case == "narrow-client":
        ops, probe = operands(jax.random.key(2), active=(8, 4, 8) if family == "latent" else 8)
        got = _out_and_grads(fused(128, 128), ops, probe, 0.25)
        _close(got, _out_and_grads(oracle, ops, probe, 0.25))
        if family == "latent":
            o, dqn, dqr, dkn, dkr, dv = got
            assert not np.any(o[..., 8:]) and not np.any(dkn[..., 8:]) and not np.any(dkr[..., 4:])
        else:
            assert not any(np.any(x[..., 8:]) for x in got)  # o, dq, dk, dv
    elif case == "vmap-clients":
        clients = [operands(k, S=128) for k in jax.random.split(jax.random.key(3), 3)]
        ops = [jnp.stack(x) for x in zip(*(c[0] for c in clients))]
        probe = jnp.stack([c[1] for c in clients])
        scales = jnp.asarray([0.125, 0.25, 0.0625])

        def over_clients(attention):
            return jax.vmap(lambda o, p, s: _out_and_grads(attention, o, p, s))(ops, probe, scales)

        _close(over_clients(fused(128, 128)), over_clients(oracle))
    elif case == "rotary-key-heads":
        H = 4
        ops, probe = operands(jax.random.key(4), S=128, H=H)
        qn, qr, kn, kr, v = ops

        def own_copy_a_head(kr_heads):  # plain attention, one rotary key a head
            s = (jnp.einsum("nhqd,nhkd->nhqk", qn, kn)
                 + jnp.einsum("nhqd,nhkd->nhqk", qr, kr_heads)) * SCALE
            s = jnp.where(jnp.tril(jnp.ones(s.shape[-2:], bool)), s, -jnp.inf)
            return jnp.sum(jnp.einsum("nhqk,nhkd->nhqd", jax.nn.softmax(s, -1), v) * probe)

        a_head = jax.grad(own_copy_a_head)(jnp.repeat(kr[:, None], H, axis=1))
        dkr = _out_and_grads(fused(128, 128), ops, probe, SCALE)[4]
        _close([dkr], [jnp.sum(a_head, axis=1)])
    else:
        G, Hkv = int(case[len("group-"):]), 2
        (q, k, v), probe = operands(jax.random.key(5), N=2, S=128, H=G * Hkv, Hkv=Hkv)

        def own_copy_a_head(q, k_heads, v_heads):  # plain attention, every head its own k, v
            s = jnp.einsum("nhqd,nhkd->nhqk", q, k_heads) * SCALE
            s = jnp.where(jnp.tril(jnp.ones(s.shape[-2:], bool)), s, -jnp.inf)
            o = jnp.einsum("nhqk,nhkd->nhqd", jax.nn.softmax(s, -1), v_heads)
            return jnp.sum(o * probe), o

        (dq, dk, dv), o = jax.grad(own_copy_a_head, argnums=(0, 1, 2), has_aux=True)(
            q, jnp.repeat(k, G, axis=1), jnp.repeat(v, G, axis=1))
        over_group = [x.reshape(2, Hkv, G, 128, 64).sum(axis=2) for x in (dk, dv)]
        _close(_out_and_grads(fused(128, 128), (q, k, v), probe, SCALE), [o, dq] + over_group)


# ---------------------------------------------------------------------------
# the third family: grouped-query attention over the keys an indexer selected
# (``sel_attn_fwd`` / ``sel_attn_bwd``), at the Keye cell's head geometry
# scaled down: eight query heads of 128 a key/value head
# ---------------------------------------------------------------------------

def _sel_operands(key, N=1, S=1024, G=8, Hkv=1, d=128, active=None):
    """q, k, v heads first and a probe of the output's shape."""
    return _gq_operands(key, N=N, S=S, H=G * Hkv, Hkv=Hkv, d=d, active=active)


def _indexer_selection(key, N, S, topk, block):
    """What ``select_keys`` gives a random indexer: per query block of
    ``block`` rows None (the block ends at or before ``topk``) or its 0/1
    choice of ``topk`` of the keys up to the block's end."""
    qi, ki, wi = (jax.random.normal(k, s) for k, s in
                  zip(jax.random.split(key, 3), [(N, 2, S, 8), (N, S, 8), (N, 2, S)]))
    return L.select_keys(qi, ki, wi, topk, block)[0]


def _sel_fused(select, block, bq, bk):
    return lambda *a: PA.fused_selected_attention(*a, select, block, block_q=bq, block_k=bk,
                                                  interpret=True)


def _sel_oracle(select, block):
    return lambda *a: L.blockwise_gq_attention(*a, block, select)


FAMILIES["sel"] = (_sel_operands, _sel_fused, _sel_oracle)


@pytest.mark.parametrize("case", [
    "tiles-512x512", "tiles-256x256", "tiles-128x128", "tiles-256x512-group-16", "tiles-128x256",
    "masked-first-tiles", "keeps-every-key", "vmap-clients", "narrow-client"])
def test_selected_kernels_are_the_block_loop_under_the_selection(case):
    """Output and the gradients of ``q``, ``k``, ``v`` against
    ``blockwise_gq_attention(select=)``: at every (query, key) tile the rule
    ``sel_tile_for`` can return (rows of several tiles, ``topk`` of two query
    tiles, so that tiles with and without a selection both occur, on and off
    the diagonal), and at a key tile wider than the query tile's; for queries
    NONE of whose selected keys lies in their first key tiles (the running
    max is still ``MASKED`` there: what the sum collects is wiped by the
    first real score); a selection that keeps every causal key, bit-equal in
    the forward to the kernels given no selection at all; under ``vmap`` over
    clients with a per-client selection and scale; zero-suffix head dims."""
    operands, fused, oracle = FAMILIES["sel"]
    if case.startswith("tiles-"):
        bq, bk = (int(t) for t in case.split("-")[1].split("x"))
        G = 16 if case.endswith("group-16") else 8
        S = {"512x512": 1536, "256x256": 768, "128x128": 384, "256x512": 1024, "128x256": 512}[
            f"{bq}x{bk}"]
        assert PA.sel_tile_for(S, 128, G) == (bq, bk) or (bq, bk) == (128, 256)
        ops, probe = operands(jax.random.key(1), S=S, G=G)
        select = _indexer_selection(jax.random.key(11), 1, S, 2 * bq, bq)
        assert select[1] is None and select[2] is not None
        _close(_out_and_grads(fused(select, bq, bq, bk), ops, probe, SCALE),
               _out_and_grads(oracle(select, bq), ops, probe, SCALE))
    elif case == "masked-first-tiles":
        # every query from 128 on selects the 128 keys that end with itself, so all of
        # the key tiles before its diagonal's neighbour are fully masked for it
        S, t = 512, 128
        ops, probe = operands(jax.random.key(2), S=S)
        window = (jnp.arange(S)[:, None] - jnp.arange(S)[None, :] < t)[None]
        select = [None] + [window[:, i * t:(i + 1) * t, :(i + 1) * t] for i in range(1, S // t)]
        assert not np.any(select[3][:, :, :2 * t])  # two whole tiles without a selected key
        got = _out_and_grads(fused(select, t, t, t), ops, probe, SCALE)
        _close(got, _out_and_grads(oracle(select, t), ops, probe, SCALE))
        assert all(np.all(np.isfinite(x)) for x in got)
    elif case == "keeps-every-key":
        S, t = 512, 128
        ops, probe = operands(jax.random.key(3), S=S)
        every = [None, None] + [jnp.ones((1, t, (i + 1) * t), bool) for i in range(2, S // t)]
        for bq, bk in ((128, 128), (128, 256)):
            a = fused(every, t, bq, bk)(*ops, SCALE)
            np.testing.assert_array_equal(a, fused([None] * (S // t), t, bq, bk)(*ops, SCALE))
        _close(_out_and_grads(fused(every, t, t, t), ops, probe, SCALE),
               _out_and_grads(lambda *a: L.blockwise_gq_attention(*a, t), ops, probe, SCALE))
    elif case == "vmap-clients":
        S, t = 384, 128
        clients = [operands(k, S=S, G=2) for k in jax.random.split(jax.random.key(4), 3)]
        ops = [jnp.stack(x) for x in zip(*(c[0] for c in clients))]
        probe = jnp.stack([c[1] for c in clients])
        scales = jnp.asarray([0.125, 0.25, 0.0625])
        selects = [_indexer_selection(k, 1, S, t, t) for k in jax.random.split(jax.random.key(5), 3)]
        masks = [jnp.stack(m) for m in zip(*(s[1:] for s in selects))]  # block 0 has none

        def over_clients(attention):
            return jax.vmap(lambda o, p, s, *m: _out_and_grads(attention([None, *m], t), o, p, s))(
                ops, probe, scales, *masks)

        _close(over_clients(lambda select, block: fused(select, block, t, t)), over_clients(oracle))
    else:
        S, t = 256, 128
        ops, probe = operands(jax.random.key(6), S=S, active=8)
        select = _indexer_selection(jax.random.key(7), 1, S, t, t)
        got = _out_and_grads(fused(select, t, t, t), ops, probe, 0.25)
        _close(got, _out_and_grads(oracle(select, t), ops, probe, 0.25))
        assert not any(np.any(x[..., 8:]) for x in got)  # o, dq, dk, dv


def _sel_toy(policy, S=256, t=128):
    """``(loss(ws, x), ws, x)``: two layers ``x + attention(x * w)`` of the
    selected kernels (interpret mode; 2 query heads of 128 on one key/value
    head, the second query block selecting) under ``jax.checkpoint(policy=)``,
    one after the other, so that a layer's kernels are counted apart."""
    (q, k, _), _ = _sel_operands(jax.random.key(21), S=S, G=2)
    select = _indexer_selection(jax.random.key(22), 1, S, t, t)
    attend = _sel_fused(select, t, t, t)

    @partial(jax.checkpoint, policy=policy)
    def layer(x, w):
        return x + attend(x * w, (k + x[:, :1]) * w, k * w, SCALE)

    def loss(ws, x):
        for w in ws:
            x = layer(x, w)
        return jnp.sum(x * x)

    return loss, [jnp.float32(1.0), jnp.float32(0.5)], q


def test_a_checkpoint_that_saves_the_forwards_names_runs_no_second_forward_kernel():
    """``o`` and the log-sum-exp of ``sel_attn_fwd`` carry ``SEL_OUT`` and
    ``SEL_LSE`` inside the rule's forward, where they are the backward's
    residuals: under ``save_only_these_names`` a two-layer toy's gradient
    holds one ``sel_attn_fwd`` and one ``sel_attn_bwd`` a layer, under a bare
    ``jax.checkpoint`` a second forward a layer (the recomputation); loss and
    gradients are bitwise equal."""
    from heterofl_tpu.staticcheck.jaxpr_walk import iter_eqns  # every call, a shared jaxpr's too

    out = {}
    for name, policy in (("kept", jax.checkpoint_policies.save_only_these_names(
            PA.SEL_OUT, PA.SEL_LSE)), ("bare", None)):
        loss, ws, x = _sel_toy(policy)
        grad = jax.value_and_grad(loss, argnums=(0, 1))
        kernels = [e.params["name"] for e in iter_eqns(jax.make_jaxpr(grad)(ws, x))
                   if e.primitive.name == "pallas_call"]
        out[name] = (sorted(kernels), jax.jit(grad)(ws, x))
    assert out["kept"][0] == ["sel_attn_bwd"] * 2 + ["sel_attn_fwd"] * 2
    assert out["bare"][0] == ["sel_attn_bwd"] * 2 + ["sel_attn_fwd"] * 4
    for got, want in zip(jax.tree_util.tree_leaves(out["kept"][1]),
                         jax.tree_util.tree_leaves(out["bare"][1])):
        np.testing.assert_array_equal(got, want)


@pytest.mark.parametrize("grad", [False, True], ids=["forward", "gradient"])
def test_outside_a_checkpoint_the_names_are_the_identity(grad, monkeypatch):
    """A direct call of the selected kernels under no policy lowers FOR A TPU
    to the program it was before the names, the kernels' serialized bodies
    with their call stacks included: the text of the same call with
    ``checkpoint_name`` taken out."""
    import jax.ad_checkpoint

    (q, k, v), probe = _sel_operands(jax.random.key(23), S=256, G=2)
    select = _indexer_selection(jax.random.key(24), 1, 256, 128, 128)

    def fn(q, k, v):
        return jnp.sum(PA.fused_selected_attention(q, k, v, SCALE, select, 128, block_q=128,
                                                   block_k=128) * probe)

    texts = []
    for name in (jax.ad_checkpoint.checkpoint_name, lambda x, name: x):
        monkeypatch.setattr(jax.ad_checkpoint, "checkpoint_name", name)
        # a function of its own a turn (jit's trace cache goes by identity), lowered by
        # one line: a kernel's body carries the stack of its call
        fresh = jax.jit(jax.grad(fn, argnums=(0, 1, 2)) if grad else partial(fn))
        texts.append(fresh.trace(q, k, v).lower(lowering_platforms=("tpu",)).as_text())
    assert texts[0].count("tpu_custom_call") == (2 if grad else 1)
    assert texts[0] == texts[1]


def _sel_shapes(S, d, topk=256, block=128):
    """Shapes of ``selected_gq_attention``'s operands at 8 query heads on 2
    key/value heads of ``d`` and of the selection's blocks (None: no mask)."""
    return ([(1, 8, S, d), (1, 2, S, d), (1, 2, S, d)],
            [None if start + block <= topk else (1, min(block, S - start), min(start + block, S))
             for start in range(0, S, block)])


def _sel_jaxpr(attention, S, d, block=128):
    """The jaxpr of ``attention(q, k, v, 0.1, select, block)`` on shapes."""
    shapes, blocks = _sel_shapes(S, d, block=block)

    def run(q, k, v, *masks):
        it = iter(masks)
        return attention(q, k, v, 0.1, [None if b is None else next(it) for b in blocks], block)

    return jax.make_jaxpr(run)(*(jax.ShapeDtypeStruct(s, jnp.float32) for s in shapes),
                               *(jax.ShapeDtypeStruct(b, bool) for b in blocks if b is not None))


def _calls(family, S, dims, backend, monkeypatch):
    """Names of the ``pallas_call``s in the family's attention function's
    program (``causal_latent_attention`` at head dims ``dn, dr, dv``,
    ``causal_gq_attention`` / ``selected_gq_attention`` at ``d``) when jax
    reports ``backend``."""
    monkeypatch.setattr(jax, "default_backend", lambda: backend)
    if family == "sel":
        jaxpr = _sel_jaxpr(L.selected_gq_attention, S, dims)
    else:
        if family == "latent":
            dn, dr, dv = dims
            attention = L.causal_latent_attention
            shapes = [(1, 2, S, dn), (1, 2, S, dr), (1, 2, S, dn), (1, S, dr), (1, 2, S, dv)]
        else:
            attention = L.causal_gq_attention
            shapes = [(1, 8, S, dims), (1, 2, S, dims), (1, 2, S, dims)]
        jaxpr = jax.make_jaxpr(lambda *a: attention(*a, 0.1))(
            *(jax.ShapeDtypeStruct(s, jnp.float32) for s in shapes))
    text = str(jaxpr)
    assert text.count("pallas_call") == text.count("_attn_")
    return re.findall(r"\w+_attn_\w+", text)


@pytest.mark.parametrize("family, S, dims, backend, fused", [
    ("latent", 512, (128, 64, 128), "tpu", True),     # the Kanana-2 cell's head dims
    ("latent", 384, (256, 128, 128), "tpu", True),    # 128-position tiles
    ("latent", 512, (128, 64, 128), "cpu", False),    # the CPU takes the jnp form
    ("latent", 500, (128, 64, 128), "tpu", False),    # positions that make no whole tile
    ("latent", 512, (8, 4, 8), "tpu", False),         # a rate-1/16 client's own widths
    ("latent", 512, (128, 64, 64), "tpu", False),     # value dims that do not fill the lanes
    ("gq", 2048, 64, "tpu", True),                    # the LFM2 cell's head dim and positions
    ("gq", 384, 128, "tpu", True),                    # 128-position tiles, whole-lane heads
    ("gq", 2048, 64, "cpu", False),
    ("gq", 500, 64, "tpu", False),
    ("gq", 2048, 4, "tpu", False),                    # a rate-1/16 client's own width
    ("gq", 2048, 32, "tpu", False),                   # a rate-1/2 client's
    ("sel", 1024, 128, "tpu", True),                  # the Keye cell's head dim
    ("sel", 384, 256, "tpu", True),                   # 128-position tiles
    ("sel", 1024, 128, "cpu", False),
    ("sel", 500, 128, "tpu", False),
    ("sel", 1024, 8, "tpu", False),                   # a rate-1/16 client's own width
    ("sel", 1024, 64, "tpu", False),                  # a rate-1/2 client's: half the lanes
], ids=["cell-dims", "tile-128", "cpu", "ragged-positions", "narrow-widths", "half-lane-values",
        "gq-cell-dims", "gq-tile-128", "gq-cpu", "gq-ragged-positions", "gq-narrow-widths",
        "gq-half-width", "sel-cell-dims", "sel-tile-128", "sel-cpu", "sel-ragged-positions",
        "sel-narrow-widths", "sel-half-width"])
def test_which_form_runs_is_decided_by_backend_and_shapes(family, S, dims, backend, fused,
                                                          monkeypatch):
    kernel = {"latent": "latent_attn_fwd", "gq": "gq_attn_fwd", "sel": "sel_attn_fwd"}[family]
    assert _calls(family, S, dims, backend, monkeypatch) == ([kernel] if fused else [])


@pytest.mark.parametrize("backend, d", [("cpu", 128), ("tpu", 8)], ids=["cpu", "narrow-slice"])
def test_selected_attention_falls_back_to_the_parents_block_loop(backend, d, monkeypatch):
    """Off a TPU, and for a client's narrow slice at its own widths on one,
    ``selected_gq_attention`` traces to the program it was before the kernels:
    ``blockwise_gq_attention`` with the selection, the jaxpr equal as text."""
    monkeypatch.setattr(jax, "default_backend", lambda: backend)
    assert L.selected_attention_tile(1024, d, 4) is None
    got = _sel_jaxpr(L.selected_gq_attention, 1024, d)
    want = _sel_jaxpr(lambda q, k, v, scale, select, block: L.blockwise_gq_attention(
        q, k, v, scale, block, select), 1024, d)
    assert str(got) == str(want) and "pallas_call" not in str(got)


@pytest.mark.parametrize("rate, compute_dtype", [(1.0, None), (0.25, None), (0.5, jnp.bfloat16)],
                         ids=["rate-1", "rate-1/4", "bfloat16-operands"])
def test_attention_block_is_the_reshape_and_swapaxes_formulation(rate, compute_dtype):
    """``models.kanana2.latent_attention`` (head-indexed products, the rotary
    turn on ``[N, H, S, dr]`` with the pair swap taken on the weight, the
    output projection from ``[N, H, S, dv]``) against the formulation it
    replaced, written out here: ``linear`` to ``[N, S, H * d]``, ``reshape``
    to heads, the pair swap by rotating the activation's lanes, ``swapaxes``
    to heads first, and back for the output projection.  The same products in
    another order of their results: the block's output and every gradient to
    float32 round-off."""
    from heterofl_tpu.models.kanana2 import latent_attention, latent_attention_shapes

    N, S, D, H, dn, dr, dv, R = 2, 24, 32, 4, 16, 8, 16, 12
    theta, scale = 1e4, 0.2
    shapes = latent_attention_shapes(D, H, dn, dr, dv, R)
    lp = {n: jax.random.normal(k, s) / np.sqrt(s[0])
          for (n, s), k in zip(shapes.items(), jax.random.split(jax.random.key(5), len(shapes)))}
    lp["attn.kv_norm.g"] = jnp.linspace(0.5, 1.5, R)
    h, probe = (jax.random.normal(k, (N, S, D)) for k in jax.random.split(jax.random.key(6)))

    def sc(x):
        return x / rate

    def kv_norm(c, g):
        return L.masked_rms_norm(c, g, jnp.ones((R,)), jnp.float32(R))

    def swapaxes_form(lp, h):
        def lin(x, w):
            return L.linear(x, w, compute_dtype=compute_dtype)

        def heads_first(x, d):
            return jnp.swapaxes(x.reshape(N, S, H, d), 1, 2)

        qn, qr = sc(lin(h, lp["attn.q.n.w"])), sc(lin(h, lp["attn.q.r.w"]))
        c, kr = sc(lin(h, lp["attn.kv_a.c.w"])), sc(lin(h, lp["attn.kv_a.r.w"]))
        c = kv_norm(c, lp["attn.kv_norm.g"])
        kn, v = sc(lin(c, lp["attn.kv_b.k.w"])), sc(lin(c, lp["attn.kv_b.v.w"]))
        pos = jnp.arange(S)
        qr = qr.reshape(N, S, H, dr)  # the turn on the activation: its lanes rotated
        qr = L.rope_interleaved(qr, L.rope_swap(qr), pos, theta).reshape(N, S, H * dr)
        kr = L.rope_interleaved(kr, L.rope_swap(kr), pos, theta)
        ops = [heads_first(qn, dn), heads_first(qr, dr), heads_first(kn, dn), kr,
               heads_first(v, dv)]
        if compute_dtype is not None:
            ops = [x.astype(compute_dtype) for x in ops]
        o = L.causal_latent_attention(*ops, scale).astype(jnp.float32)
        return sc(lin(jnp.swapaxes(o, 1, 2).reshape(N, S, H * dv), lp["attn.o.w"]))

    def heads_first_form(lp, h):
        return latent_attention(lp, h, heads=H, theta=theta, scale=scale, sc=sc,
                                kv_norm=kv_norm, compute_dtype=compute_dtype)

    def out_and_grads(block):
        def loss(lp, h):
            y = block(lp, h)
            return jnp.sum(y * probe), y

        (dlp, dh), y = jax.grad(loss, argnums=(0, 1), has_aux=True)(lp, h)
        return [y, dh] + [dlp[k] for k in sorted(dlp)]

    tol = 1e-5 if compute_dtype is None else 2e-2  # bfloat16 rounds a result where it is written
    for got, want in zip(out_and_grads(heads_first_form), out_and_grads(swapaxes_form)):
        assert got.shape == want.shape and got.dtype == want.dtype
        np.testing.assert_allclose(got, want, rtol=0, atol=tol * float(jnp.abs(want).max()))
