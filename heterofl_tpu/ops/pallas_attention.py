"""Pallas TPU kernels for causal attention (``attn``): latent, then (below) grouped-query.

The score / softmax / value part of ``ops.layers.causal_latent_attention``
as one fused online-softmax ("flash") kernel with a hand-written backward,
so a score tile lives in VMEM only: the blockwise ``jnp`` form writes every
query block's float32 scores ``[N, H, block, <=S]`` to HBM and reads them
back for the mask, the max, the exponent, the sum and the value product.

* ``latent_attn_fwd``: a (row, head, query tile) runs over the key tiles up
  to the diagonal (tiles above it are neither fetched nor computed; only
  tiles the diagonal crosses are masked), ``s = qn kn^T + qr kr^T`` with
  running max, sum and value accumulator in float32; writes the output and
  one float32 log-sum-exp a (row, head, position).
* ``latent_attn_bwd``: from ``q``, ``k``, ``v``, the log-sum-exp,
  ``delta = sum(o * do)`` and ``do`` a (row, head, key tile) runs over the
  query tiles from the diagonal down, recomputes a tile's probabilities
  (transposed, so the row statistics broadcast along sublanes) and
  accumulates ``dkn, dkr, dv`` in the key tile's output blocks and ``dqn,
  dqr`` in an output block that holds the head's whole sequence.  The rotary key is ONE head:
  the kernel writes its gradient a head, the caller sums over heads.

Precision: operands of every product bfloat16, accumulated in float32 (what
the chip's default precision makes of float32 operands in the ``jnp`` form);
scores, mask, max, exponent, sum, log-sum-exp, ``delta`` and every
accumulator float32.  The softmax scale is not a kernel operand: the caller
multiplies ``q`` by it in float32 before the cast, which also carries a
per-client scale through ``vmap`` and leaves its gradient to autodiff.

THE RULE: which pair a layer takes, at which tile, and what stays resident.
``S`` positions, heads of ``d`` dims, ``G`` query heads a key/value head; a
tile of positions is the largest of :data:`TILES` that divides ``S``; where no
pair takes the shapes (a client's narrow slice, ragged rows) the caller runs
the ``jnp`` block loop.

=====================  ==========================  ==============  ===========================
pair                   taken where                 value width     resident over the grid
=====================  ==========================  ==============  ===========================
``latent_attn_*``      :func:`tile_for`: ``dn``,   ``dv`` of 128s  backward, keys outer: a
                       ``dv`` of 128s, ``dr`` of                   head's ``dq`` ``[S, dn+dr]``
                       64s                                         float32
``gq_attn_*``          :func:`gq_plan`: no         any ``dv`` of   backward, keys outer: a
                       window, ``d`` of 64s, and   64s (None: the  group's ``dq`` ``[G, d, S]``
                       that ``dq`` within          query's ``d``)  float32 (LFM2 4 x 64 x
                       :data:`GQ_RESIDENT_BYTES`                   2,048: 2 MB; Ouro 1 x 128 x
                       (or ``d`` not of 128s)                      2,048: 1 MB), double-buffered
``gq_attn_*``          a differential pair's       128 on ``d``    as above: 2 x 64 x 8,192 =
                       softmax: ``d`` 64, group    64              4.2 MB of ``dq``; ``dv`` a
                       2, 8,192 positions, no                      key tile ``[128, tk]``
                       window (Phi-4-flash)
``band_attn_*``        :func:`gq_plan`: a window,  ``dv == d``     both kernels, query tiles
                       or a ``dq`` beyond that     only (a value   outer: a key/value head's
                       (Laguna: 8 x 128 x 8,192 =  of its own      ``dk``, ``dv`` ``[d, S]``
                       34 MB, 6 x ... = 25 MB);    under a window  float32 (4 MB each at 128 x
                       ``d`` of 128s               takes the       8,192) and a group's ``dq``
                                                   block loop)     tile in scratch
``sel_attn_*``         :func:`sel_tile_for`: a     ``dv == d``     as ``band_attn_*``, and the
                       selection mask; ``d`` of                    mask a ``[tk, tq]`` int8
                       128s                                        tile a step
=====================  ==========================  ==============  ===========================

The query tile of the last two is the largest with ``G x tile <= 4,096``
columns side by side (a score tile ``[key tile, G x tile]`` float32 is 8 MB of
VMEM at 512 keys); under a window both tiles of the band pair are at most half
the window (measured: scripts/swa_ab.py, the note in :func:`gq_plan`).
"""

from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp
from jax import lax

from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

#: lanes of a vector register: the row statistics are kept replicated over them
LANES = 128
#: what a masked score is set to (finite, 0.7 of float32's largest: no ``inf - inf``)
MASKED = -2.38e38

_NT = (((1,), (1,)), ((), ()))  # a b^T
_NN = (((1,), (0,)), ((), ()))  # a b


def _dot(a, b, dims):
    return lax.dot_general(a, b, dims, preferred_element_type=jnp.float32)


def _over_lanes(x, n):
    """``x`` ``[rows, LANES]`` (replicated) as ``[rows, n]``, ``n`` whole lanes."""
    return jnp.tile(x, (1, n // LANES))


def _causal(s, q0, k0, keys_first: bool):
    """``s`` with the pairs (query < key) masked; ``s`` ``[tq, tk]``, or
    ``[tk, tq]`` if ``keys_first``; ``q0``/``k0`` the tile's first positions."""
    q = q0 + lax.broadcasted_iota(jnp.int32, s.shape, 1 if keys_first else 0)
    k = k0 + lax.broadcasted_iota(jnp.int32, s.shape, 0 if keys_first else 1)
    return jnp.where(q >= k, s, MASKED)


def _when_needed(i, j, tq, tk, step):
    """Run ``step(masked)`` for the tile (query tile ``i``, key tile ``j``)
    unless it lies above the diagonal; masked only if the diagonal crosses it."""
    needed = j * tk <= i * tq + tq - 1
    crossed = j * tk + tk - 1 > i * tq
    pl.when(jnp.logical_and(needed, crossed))(partial(step, True))
    pl.when(jnp.logical_and(needed, jnp.logical_not(crossed)))(partial(step, False))


def _fwd_kernel(qn_ref, qr_ref, kn_ref, kr_ref, v_ref, o_ref, lse_ref, m_s, l_s, *,
                tq: int, tk: int):
    i, j = pl.program_id(2), pl.program_id(3)

    @pl.when(j == 0)
    def _():
        m_s[...] = jnp.full_like(m_s, MASKED)
        l_s[...] = jnp.zeros_like(l_s)
        o_ref[...] = jnp.zeros_like(o_ref)  # the value accumulator until the last key tile

    def step(masked):
        s = _dot(qn_ref[...], kn_ref[...], _NT) + _dot(qr_ref[...], kr_ref[...], _NT)
        if masked:
            s = _causal(s, i * tq, j * tk, keys_first=False)
        m_prev = m_s[...]
        m_next = jnp.maximum(m_prev, jnp.max(s, axis=-1, keepdims=True))
        p = jnp.exp(s - _over_lanes(m_next, tk))
        alpha = jnp.exp(m_prev - m_next)
        l_s[...] = alpha * l_s[...] + jnp.sum(p, axis=-1, keepdims=True)
        m_s[...] = m_next
        v = v_ref[...]
        o_ref[...] = _over_lanes(alpha, v.shape[-1]) * o_ref[...] \
            + _dot(p.astype(v.dtype), v, _NN)

    _when_needed(i, j, tq, tk, step)

    @pl.when(j == pl.num_programs(3) - 1)
    def _():
        l = l_s[...]
        o_ref[...] = o_ref[...] / _over_lanes(l, o_ref.shape[-1])
        lse_ref[...] = m_s[...] + jnp.log(l)


def _bwd_kernel(qn_ref, qr_ref, kn_ref, kr_ref, v_ref, do_ref, lse_ref, delta_ref,
                dqn_ref, dqr_ref, dkn_ref, dkr_ref, dv_ref, *, tq: int, tk: int):
    j, i = pl.program_id(2), pl.program_id(3)

    @pl.when(jnp.logical_and(j == 0, i == 0))
    def _():  # a head's whole sequence, resident over (j, i)
        dqn_ref[...] = jnp.zeros_like(dqn_ref)
        dqr_ref[...] = jnp.zeros_like(dqr_ref)

    @pl.when(i == 0)
    def _():  # a key tile's, resident over i
        dkn_ref[...] = jnp.zeros_like(dkn_ref)
        dkr_ref[...] = jnp.zeros_like(dkr_ref)
        dv_ref[...] = jnp.zeros_like(dv_ref)

    def step(masked):
        qn, qr, kn, kr = qn_ref[...], qr_ref[...], kn_ref[...], kr_ref[...]
        do = do_ref[...]
        st = _dot(kn, qn, _NT) + _dot(kr, qr, _NT)               # [tk, tq]
        if masked:
            st = _causal(st, i * tq, j * tk, keys_first=True)
        pt = jnp.exp(st - lse_ref[...])                           # lse [1, tq]
        dv_ref[...] += _dot(pt.astype(do.dtype), do, _NN)
        dst = pt * (_dot(v_ref[...], do, _NT) - delta_ref[...])
        ds = dst.T.astype(kn.dtype)                               # [tq, tk]
        dst = dst.astype(qn.dtype)
        dkn_ref[...] += _dot(dst, qn, _NN)
        dkr_ref[...] += _dot(dst, qr, _NN)
        rows = pl.ds(pl.multiple_of(i * tq, tq), tq)
        dqn_ref[rows, :] += _dot(ds, kn, _NN)
        dqr_ref[rows, :] += _dot(ds, kr, _NN)

    _when_needed(i, j, tq, tk, step)


def _params(vmem_mb):
    return pltpu.CompilerParams(
        dimension_semantics=("parallel", "parallel", "arbitrary", "arbitrary"),
        vmem_limit_bytes=vmem_mb << 20)


def _head_tile(t, d, tile_of):
    """A ``[t, d]`` tile of a ``[N, H, S, d]`` operand; ``tile_of(a, b)`` the
    tile's index along ``S`` at the grid's last two coordinates."""
    return pl.BlockSpec((None, None, t, d), lambda n, h, a, b: (n, h, tile_of(a, b), 0))


def _call_fwd(qn, qr, kn, kr, v, tq, tk, interpret):
    N, H, S, dn = qn.shape
    dr, dv = qr.shape[-1], v.shape[-1]

    def query(i, j):
        return i

    def key(i, j):  # a tile above the diagonal is never fetched
        return jnp.minimum(j, (i * tq + tq - 1) // tk)

    return pl.pallas_call(
        partial(_fwd_kernel, tq=tq, tk=tk),
        grid=(N, H, S // tq, S // tk),
        in_specs=[
            _head_tile(tq, dn, query), _head_tile(tq, dr, query),
            _head_tile(tk, dn, key),
            pl.BlockSpec((None, tk, dr), lambda n, h, i, j: (n, key(i, j), 0)),
            _head_tile(tk, dv, key),
        ],
        out_specs=[_head_tile(tq, dv, query), _head_tile(tq, LANES, query)],
        out_shape=[jax.ShapeDtypeStruct((N, H, S, dv), jnp.float32),
                   jax.ShapeDtypeStruct((N, H, S, LANES), jnp.float32)],
        scratch_shapes=[pltpu.VMEM((tq, LANES), jnp.float32)] * 2,
        compiler_params=_params(32),
        interpret=interpret,
        name="latent_attn_fwd",
    )(qn, qr, kn, kr, v)


def _call_bwd(qn, qr, kn, kr, v, do, lse, delta, tq, tk, interpret):
    N, H, S, dn = qn.shape
    dr, dv = qr.shape[-1], v.shape[-1]

    def key(j, i):
        return j

    def query(j, i):  # a tile above the diagonal is never fetched
        return jnp.maximum(i, (j * tk) // tq)

    def row_stat():
        return pl.BlockSpec((None, None, 1, tq), lambda n, h, j, i: (n, h, 0, query(j, i)))

    def whole(d):
        return pl.BlockSpec((None, None, S, d), lambda n, h, j, i: (n, h, 0, 0))

    f32 = jnp.float32
    return pl.pallas_call(
        partial(_bwd_kernel, tq=tq, tk=tk),
        grid=(N, H, S // tk, S // tq),
        in_specs=[
            _head_tile(tq, dn, query), _head_tile(tq, dr, query),
            _head_tile(tk, dn, key),
            pl.BlockSpec((None, tk, dr), lambda n, h, j, i: (n, j, 0)),
            _head_tile(tk, dv, key), _head_tile(tq, dv, query),
            row_stat(), row_stat(),
        ],
        out_specs=[whole(dn), whole(dr), _head_tile(tk, dn, key),
                   _head_tile(tk, dr, key), _head_tile(tk, dv, key)],
        out_shape=[jax.ShapeDtypeStruct((N, H, S, d), f32) for d in (dn, dr, dn, dr, dv)],
        compiler_params=_params(64),
        interpret=interpret,
        name="latent_attn_bwd",
    )(qn, qr, kn, kr, v, do, lse, delta)


@partial(jax.custom_vjp, nondiff_argnums=(5, 6, 7))
def _flash(qn, qr, kn, kr, v, tq, tk, interpret):
    """Heads first: ``qn``/``kn`` ``[N, H, S, dn]``, ``qr`` ``[N, H, S, dr]``,
    ``v`` and the result ``[N, H, S, dv]``; ``kr`` ``[N, S, dr]``; ``q``
    already scaled.  Float32 in, out and in every gradient."""
    return _flash_fwd(qn, qr, kn, kr, v, tq, tk, interpret)[0]


def _flash_fwd(qn, qr, kn, kr, v, tq, tk, interpret):
    ops = tuple(x.astype(jnp.bfloat16) for x in (qn, qr, kn, kr, v))
    o, lse = _call_fwd(*ops, tq, tk, interpret)
    return o, (ops, o, lse[..., 0])


def _flash_bwd(tq, tk, interpret, res, do):
    ops, o, lse = res
    delta = jnp.sum(o * do, axis=-1)
    dqn, dqr, dkn, dkr, dv = _call_bwd(
        *ops, do.astype(jnp.bfloat16), lse[:, :, None, :], delta[:, :, None, :],
        tq, tk, interpret)
    return dqn, dqr, dkn, jnp.sum(dkr, axis=1), dv


_flash.defvjp(_flash_fwd, _flash_bwd)


#: positions a tile takes, queries and keys alike: the largest that divides ``S``
TILES = (512, 256, 128)


def tile_for(S: int, dn: int, dr: int, dv: int):
    """The tile the kernels take at these shapes, None if they take none:
    whole tiles of positions, and head dims that fill the lanes (the rotary
    ones half of them); a client's narrow slice at its own widths does not."""
    if dn % LANES or dv % LANES or dr % (LANES // 2):
        return None
    return next((t for t in TILES if S % t == 0), None)


def fused_latent_attention(qn, qr, kn, kr, v, scale, *, block_q: int, block_k: int,
                           interpret: bool = False):
    """``ops.layers.causal_latent_attention`` through the kernels above, its
    operands and result in its layouts (heads first, ``[N, H, S, d]``; ``kr``
    ``[N, S, dr]``), float32 out; tiles of ``block_q`` queries by ``block_k``
    keys."""
    qn, qr, kn, kr, v = (x.astype(jnp.float32) for x in (qn, qr, kn, kr, v))
    return _flash(qn * scale, qr * scale, kn, kr, v, block_q, block_k, interpret)


# ---------------------------------------------------------------------------
# Grouped-query attention (``ops.layers.causal_gq_attention``): kernels
# ``gq_attn_fwd`` / ``gq_attn_bwd``.  Two things differ from the kernels above.
#
# POSITIONS ON THE LANES.  The operands are ``[N, H, d, S]``.  With a 64-wide
# head dim on the lanes instead, half of every tile is empty, a ``[.., S,
# 64]`` array is stored 128 wide in HBM, and the custom call's layout is
# pushed back through the projections, the head norms and the rotary turn,
# which then ran 3.9 ms a layer pass slower than in the layout the compiler
# gives them when left alone, positions minor (PERF.md, PR 34).  So scores
# are keys-first in both kernels, ``[tk, queries]``: the row statistics are
# ``[1, queries]`` rows that broadcast along sublanes, the max and the sum
# run over sublanes, and no tile is ever transposed.
#
# THE GROUP IN ONE GRID STEP.  A step takes the tiles of ALL the query heads
# that read one key/value head, side by side on the lanes (``[G, d, tq] ->
# [d, G * tq]``): a key/value tile is fetched once a group and never repeated
# in HBM, and ``dk`` / ``dv`` of a key/value head are the products' own sum
# over the group's columns.  One product a score against a key head a group,
# where latent attention's is two against one shared rotary key: bodies of
# their own, the helpers above shared.
# ---------------------------------------------------------------------------

_TN = (((0,), (0,)), ((), ()))  # a^T b


def _side_by_side(ref):
    """A group's tiles ``[G, d, t]`` as ``[d, G * t]``."""
    return jnp.concatenate([ref[g] for g in range(ref.shape[0])], axis=1)


def _group_causal(st, q0, k0, tq):
    """:func:`_causal`, keys first, for a group's tiles side by side: column
    ``c`` of the ``G * tq`` is position ``q0 + c % tq``."""
    q = q0 + lax.rem(lax.broadcasted_iota(jnp.int32, st.shape, 1), tq)
    k = k0 + lax.broadcasted_iota(jnp.int32, st.shape, 0)
    return jnp.where(q >= k, st, MASKED)


def _gq_start(q_ref, q_s, m_s, l_s, acc_s):
    """A query tile's first key tile: the group's tiles side by side (resident
    over the key tiles) and the running max, sum and value accumulator."""
    q_s[...] = _side_by_side(q_ref)
    m_s[...] = jnp.full_like(m_s, MASKED)
    l_s[...] = jnp.zeros_like(l_s)
    acc_s[...] = jnp.zeros_like(acc_s)


def _gq_softmax_step(st, v_ref, m_s, l_s, acc_s):
    """One key tile of the online softmax: ``st`` ``[tk, G * tq]`` the tile's
    (masked) scores, keys first."""
    m_prev = m_s[...]                                             # [1, G * tq]
    m_next = jnp.maximum(m_prev, jnp.max(st, axis=0, keepdims=True))
    pt = jnp.exp(st - m_next)
    alpha = jnp.exp(m_prev - m_next)
    l_s[...] = alpha * l_s[...] + jnp.sum(pt, axis=0, keepdims=True)
    m_s[...] = m_next
    v = v_ref[...]
    acc_s[...] = alpha * acc_s[...] + _dot(v, pt.astype(v.dtype), _NN)  # [d, G * tq]


def _gq_finish(o_ref, lse_ref, m_s, l_s, acc_s, tq):
    """A query tile's last key tile: the group's outputs and log-sum-exp."""
    l = l_s[...]
    o = acc_s[...] / l
    for g in range(o_ref.shape[0]):
        o_ref[g] = o[:, g * tq:(g + 1) * tq]
    lse_ref[...] = m_s[...] + jnp.log(l)


def _gq_bwd_step(st, q, do, k, v_ref, lse_ref, delta_ref, dk_ref, dv_ref, dq_s, kt, tk):
    """One key tile of a query-tiles-outer backward: from the (masked) scores
    ``st`` ``[tk, G * tq]`` of key tile ``kt`` the key/value head's ``dk`` /
    ``dv`` at that tile's columns and the group's ``dq`` tile in scratch."""
    pt = jnp.exp(st - lse_ref[...])                               # lse [1, G * tq]
    cols = pl.ds(pl.multiple_of(kt * tk, tk), tk)
    dv_ref[:, cols] += _dot(do, pt.astype(do.dtype), _NT)         # [d, tk]
    dst = (pt * (_dot(v_ref[...], do, _TN) - delta_ref[...])).astype(q.dtype)
    dk_ref[:, cols] += _dot(q, dst, _NT)
    dq_s[...] += _dot(k, dst, _NN)                                # [d, G * tq]


def _gq_fwd_kernel(q_ref, k_ref, v_ref, o_ref, lse_ref, q_s, m_s, l_s, acc_s, *, tq: int, tk: int):
    i, j = pl.program_id(2), pl.program_id(3)
    pl.when(j == 0)(partial(_gq_start, q_ref, q_s, m_s, l_s, acc_s))

    def step(masked):
        st = _dot(k_ref[...], q_s[...], _TN)                          # [tk, G * tq]
        if masked:
            st = _group_causal(st, i * tq, j * tk, tq)
        _gq_softmax_step(st, v_ref, m_s, l_s, acc_s)

    _when_needed(i, j, tq, tk, step)
    pl.when(j == pl.num_programs(3) - 1)(partial(_gq_finish, o_ref, lse_ref, m_s, l_s, acc_s, tq))


def _gq_bwd_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref, dq_ref, dk_ref, dv_ref, *,
                   tq: int, tk: int):
    j, i = pl.program_id(2), pl.program_id(3)

    @pl.when(jnp.logical_and(j == 0, i == 0))
    def _():  # the group's whole sequence, resident over (j, i)
        dq_ref[...] = jnp.zeros_like(dq_ref)

    @pl.when(i == 0)
    def _():  # a key tile's, resident over i
        dk_ref[...] = jnp.zeros_like(dk_ref)
        dv_ref[...] = jnp.zeros_like(dv_ref)

    def step(masked):
        q, do, k = _side_by_side(q_ref), _side_by_side(do_ref), k_ref[...]
        st = _dot(k, q, _TN)                                          # [tk, G * tq]
        if masked:
            st = _group_causal(st, i * tq, j * tk, tq)
        pt = jnp.exp(st - lse_ref[...])                               # lse [1, G * tq]
        dv_ref[...] += _dot(do, pt.astype(do.dtype), _NT)             # [d, tk]
        dst = (pt * (_dot(v_ref[...], do, _TN) - delta_ref[...])).astype(q.dtype)
        dk_ref[...] += _dot(q, dst, _NT)
        dq = _dot(k, dst, _NN)                                        # [d, G * tq]
        cols = pl.ds(pl.multiple_of(i * tq, tq), tq)
        for g in range(dq_ref.shape[0]):
            dq_ref[g, :, cols] += dq[:, g * tq:(g + 1) * tq]

    _when_needed(i, j, tq, tk, step)


def _lane_tile(heads, d, t, tile_of):
    """The ``[d, t]`` tiles of ``heads`` adjacent heads (None: of one, the
    head axis squeezed) in a ``[N, H, d, S]`` operand; the grid's second
    coordinate counts blocks of ``heads``, ``tile_of(a, b)`` is the tile's index
    along ``S`` at its last two."""
    return pl.BlockSpec((None, heads, d, t), lambda n, g, a, b: (n, g, 0, tile_of(a, b)))


def _row_stat(rows, tile_of):
    """A query tile's row statistic (log-sum-exp, ``delta``) of a group, its
    heads side by side: ``[1, G * tq]`` of ``[N, Hkv, S // tq, 1, G * tq]``,
    what the forward writes and the backward broadcasts along keys."""
    return pl.BlockSpec((None, None, None, 1, rows), lambda n, g, a, b: (n, g, tile_of(a, b), 0, 0))


def _call_gq_fwd(q, k, v, tq, tk, interpret):
    N, H, d, S = q.shape
    G, dv = H // k.shape[1], v.shape[2]  # the value's width is its own (``gq_plan``'s ``dv``)

    def query(i, j):
        return i

    def key(i, j):  # a tile above the diagonal is never fetched
        return jnp.minimum(j, (i * tq + tq - 1) // tk)

    return pl.pallas_call(
        partial(_gq_fwd_kernel, tq=tq, tk=tk),
        grid=(N, H // G, S // tq, S // tk),
        in_specs=[_lane_tile(G, d, tq, query), _lane_tile(None, d, tk, key),
                  _lane_tile(None, dv, tk, key)],
        out_specs=[_lane_tile(G, dv, tq, query), _row_stat(G * tq, query)],
        out_shape=[jax.ShapeDtypeStruct((N, H, dv, S), jnp.float32),
                   jax.ShapeDtypeStruct((N, H // G, S // tq, 1, G * tq), jnp.float32)],
        scratch_shapes=[pltpu.VMEM((d, G * tq), q.dtype)]
        + [pltpu.VMEM((1, G * tq), jnp.float32)] * 2 + [pltpu.VMEM((dv, G * tq), jnp.float32)],
        compiler_params=_params(64),
        interpret=interpret,
        name="gq_attn_fwd",
    )(q, k, v)


def _call_gq_bwd(q, k, v, do, lse, delta, tq, tk, interpret):
    N, H, d, S = q.shape
    G, dv = H // k.shape[1], v.shape[2]

    def key(j, i):
        return j

    def query(j, i):  # a tile above the diagonal is never fetched
        return jnp.maximum(i, (j * tk) // tq)

    f32 = jnp.float32
    return pl.pallas_call(
        partial(_gq_bwd_kernel, tq=tq, tk=tk),
        grid=(N, H // G, S // tk, S // tq),
        in_specs=[_lane_tile(G, d, tq, query), _lane_tile(None, d, tk, key),
                  _lane_tile(None, dv, tk, key), _lane_tile(G, dv, tq, query),
                  _row_stat(G * tq, query), _row_stat(G * tq, query)],
        out_specs=[pl.BlockSpec((None, G, d, S), lambda n, g, j, i: (n, g, 0, 0)),
                   _lane_tile(None, d, tk, key), _lane_tile(None, dv, tk, key)],
        out_shape=[jax.ShapeDtypeStruct(q.shape, f32), jax.ShapeDtypeStruct(k.shape, f32),
                   jax.ShapeDtypeStruct(v.shape, f32)],
        compiler_params=_params(64),
        interpret=interpret,
        name="gq_attn_bwd",
    )(q, k, v, do, lse, delta)


@partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5))
def _gq_flash(q, k, v, tq, tk, interpret):
    """Positions on the lanes: ``q`` and the result ``[N, H, d, S]``, ``k`` and
    ``v`` ``[N, Hkv, d, S]``; ``q`` already scaled.  Float32 in, out and in
    every gradient."""
    return _gq_flash_fwd(q, k, v, tq, tk, interpret)[0]


def _gq_flash_fwd(q, k, v, tq, tk, interpret):
    ops = tuple(x.astype(jnp.bfloat16) for x in (q, k, v))
    o, lse = _call_gq_fwd(*ops, tq, tk, interpret)
    return _gq_named(ops, o, lse)


def _gq_flash_bwd(tq, tk, interpret, res, do):
    (q, k, v), o, lse = res
    N, H, _, S = q.shape
    kv = k.shape[1]
    delta = jnp.sum(o * do, axis=2).reshape(N, kv, H // kv, S // tq, tq)
    delta = jnp.swapaxes(delta, 2, 3).reshape(lse.shape)  # the log-sum-exp's layout (_row_stat)
    return _call_gq_bwd(q, k, v, do.astype(jnp.bfloat16), lse, delta, tq, tk, interpret)


_gq_flash.defvjp(_gq_flash_fwd, _gq_flash_bwd)


def gq_tile_for(S: int, d: int):
    """:func:`tile_for` for grouped-query heads: whole tiles of positions and
    head dims of whole half-lanes (64: the block is the array's whole last
    dim); a client's narrow slice at its own widths takes none."""
    if d % (LANES // 2):
        return None
    return next((t for t in TILES if S % t == 0), None)


def fused_gq_attention(q, k, v, scale, *, block_q: int, block_k: int, interpret: bool = False):
    """``ops.layers.causal_gq_attention`` through the kernels above, its
    operands and result in its layouts (heads first, ``[N, H, S, d]``; query
    heads ``[g * H/Hkv, (g + 1) * H/Hkv)`` read key/value head ``g``), float32
    out; tiles of ``block_q`` queries a head by ``block_k`` keys.  The
    kernels' own layout has the positions minor: the swaps here are the
    compiler's to fold into whoever writes ``q``, ``k``, ``v`` and reads the
    result."""
    qt, kt, vt = (jnp.swapaxes(x.astype(jnp.float32), 2, 3) for x in (q, k, v))
    return jnp.swapaxes(_gq_flash(qt * scale, kt, vt, block_q, block_k, interpret), 2, 3)


# ---------------------------------------------------------------------------
# Selected grouped-query attention (``ops.layers.selected_gq_attention``):
# kernels ``sel_attn_fwd`` / ``sel_attn_bwd``.  The grouped-query kernels'
# layout and group (positions on the lanes, a key/value head's query heads
# side by side), and two things of their own.
#
# THE SELECTION AS A MASK TILE.  A learned indexer's 0/1 choice (``ops.layers.
# select_keys``) is ONE mask a row, alike for every head: ``[N, S keys, S
# queries]`` int8, keys first as the scores are, read a ``[tk, tq]`` tile a
# grid step beside ``q``, ``k``, ``v`` and met with the diagonal's mask where
# the diagonal crosses the tile.  A query tile that ends at or before
# ``first`` (the indexer's ``topk``: such a query keeps every causal key)
# never looks at its mask tile.  The mask gets no gradient.
#
# A FULLY MASKED TILE IS HARMLESS.  Under the diagonal alone every query sees
# key 0 in its first key tile, so the running max is real from the start.  A
# selection can leave a query without a key in its first key tiles: its max
# is still ``MASKED``, ``exp(MASKED - MASKED) = 1`` and the sum and the
# accumulator collect garbage, until the first real score arrives and ``alpha
# = exp(MASKED - real) = 0`` wipes both.  That is sound because every query
# selects at least one causal key (``min(t + 1, topk) >= 1``); after it, a
# masked score gives ``exp(MASKED - real) = 0``, as it does against the
# log-sum-exp in the backward.
#
# THE BACKWARD RUNS QUERY TILES OUTER (the header's rule says why: a group's
# ``dq`` outgrows VMEM at these shapes, a key/value head's ``dk`` and ``dv`` do
# not), so a group's ``dq`` tile is accumulated over the key tiles up to the
# diagonal, on the grid of the forward: five products a tile, one kernel.
#
# THE FORWARD'S RESULTS CARRY NAMES.  ``o`` and the log-sum-exp are the primal
# output and the backward kernel's residuals at once, so a ``jax.checkpoint``
# whose policy saves ``SEL_OUT`` and ``SEL_LSE`` runs no second forward kernel
# in its backward (``models/keye.py``'s layer).  Under no such policy a name
# is the identity.
# ---------------------------------------------------------------------------

SEL_OUT, SEL_LSE = "sel_out", "sel_lse"


def _selection_bias(sel_ref, selects, causal, q0, k0):
    """0 where a pair (key, query) of the tile is kept, ``MASKED`` where not,
    float32 ``[tk, tq]``: kept = chosen by the indexer (or ``selects`` false:
    the query tile is one that keeps every causal key), and, if ``causal``
    (the diagonal crosses the tile), query >= key."""
    keep = sel_ref[...].astype(jnp.int32) + jnp.where(selects, 0, 1) > 0
    if causal:
        q = q0 + lax.broadcasted_iota(jnp.int32, keep.shape, 1)
        k = k0 + lax.broadcasted_iota(jnp.int32, keep.shape, 0)
        keep = jnp.logical_and(keep, q >= k)
    return jnp.where(keep, 0.0, MASKED)


def _when_selected(i, j, tq, tk, first, step):
    """:func:`_when_needed` with the selection: ``step(bias_of)`` for a needed
    tile, ``bias_of`` None (nothing masked: below the diagonal in a query tile
    that ends at or before ``first``) or what gives :func:`_selection_bias`
    from the mask tile's ref."""
    selects = i * tq + tq > first

    def tile(crossed):
        if crossed:
            step(partial(_selection_bias, selects=selects, causal=True, q0=i * tq, k0=j * tk))
        else:
            pl.when(selects)(partial(step, partial(
                _selection_bias, selects=True, causal=False, q0=None, k0=None)))
            pl.when(jnp.logical_not(selects))(partial(step, None))

    _when_needed(i, j, tq, tk, tile)


def _sel_fwd_kernel(q_ref, k_ref, v_ref, sel_ref, o_ref, lse_ref, q_s, m_s, l_s, acc_s, *,
                    tq: int, tk: int, first: int):
    i, j = pl.program_id(2), pl.program_id(3)
    G = q_ref.shape[0]
    pl.when(j == 0)(partial(_gq_start, q_ref, q_s, m_s, l_s, acc_s))

    def step(bias_of):
        st = _dot(k_ref[...], q_s[...], _TN)                          # [tk, G * tq]
        if bias_of is not None:
            st = st + jnp.tile(bias_of(sel_ref), (1, G))
        _gq_softmax_step(st, v_ref, m_s, l_s, acc_s)

    _when_selected(i, j, tq, tk, first, step)
    pl.when(j == pl.num_programs(3) - 1)(partial(_gq_finish, o_ref, lse_ref, m_s, l_s, acc_s, tq))


def _sel_bwd_kernel(q_ref, k_ref, v_ref, sel_ref, do_ref, lse_ref, delta_ref,
                    dq_ref, dk_ref, dv_ref, q_s, do_s, dq_s, *, tq: int, tk: int, first: int):
    i, j = pl.program_id(2), pl.program_id(3)
    G = q_ref.shape[0]

    @pl.when(jnp.logical_and(i == 0, j == 0))
    def _():  # the key/value head's whole sequence, resident over (i, j)
        dk_ref[...] = jnp.zeros_like(dk_ref)
        dv_ref[...] = jnp.zeros_like(dv_ref)

    @pl.when(j == 0)
    def _():  # the group's query tile, resident over the key tiles
        q_s[...] = _side_by_side(q_ref)
        do_s[...] = _side_by_side(do_ref)
        dq_s[...] = jnp.zeros_like(dq_s)

    def step(bias_of):
        q, do, k = q_s[...], do_s[...], k_ref[...]
        st = _dot(k, q, _TN)                                          # [tk, G * tq]
        if bias_of is not None:
            st = st + jnp.tile(bias_of(sel_ref), (1, G))
        _gq_bwd_step(st, q, do, k, v_ref, lse_ref, delta_ref, dk_ref, dv_ref, dq_s, j, tk)

    _when_selected(i, j, tq, tk, first, step)

    @pl.when(j == pl.num_programs(3) - 1)
    def _():
        for g in range(G):
            dq_ref[g] = dq_s[:, g * tq:(g + 1) * tq]


def _sel_specs(G, d, tq, tk):
    """Block specs of a grid step (row, key/value head, query tile ``i``, key
    tile ``j``), both kernels': a group's query tiles, the key/value tile (one
    above the diagonal is never fetched), the mask tile, the row statistics."""
    def query(i, j):
        return i

    def key(i, j):
        return jnp.minimum(j, (i * tq + tq - 1) // tk)

    return (_lane_tile(G, d, tq, query), _lane_tile(None, d, tk, key),
            pl.BlockSpec((None, tk, tq), lambda n, g, i, j: (n, key(i, j), i)),
            _row_stat(G * tq, query))


def _call_sel_fwd(q, k, v, sel, tq, tk, first, interpret):
    N, H, d, S = q.shape
    G = H // k.shape[1]
    group, kv, mask, stat = _sel_specs(G, d, tq, tk)
    return pl.pallas_call(
        partial(_sel_fwd_kernel, tq=tq, tk=tk, first=first),
        grid=(N, H // G, S // tq, S // tk),
        in_specs=[group, kv, kv, mask],
        out_specs=[group, stat],
        out_shape=[jax.ShapeDtypeStruct(q.shape, jnp.float32),
                   jax.ShapeDtypeStruct((N, H // G, S // tq, 1, G * tq), jnp.float32)],
        scratch_shapes=[pltpu.VMEM((d, G * tq), q.dtype)]
        + [pltpu.VMEM((1, G * tq), jnp.float32)] * 2 + [pltpu.VMEM((d, G * tq), jnp.float32)],
        compiler_params=_params(64),
        interpret=interpret,
        name="sel_attn_fwd",
    )(q, k, v, sel)


def _call_sel_bwd(q, k, v, sel, do, lse, delta, tq, tk, first, interpret):
    N, H, d, S = q.shape
    G = H // k.shape[1]
    group, kv, mask, stat = _sel_specs(G, d, tq, tk)
    whole = pl.BlockSpec((None, None, d, S), lambda n, g, i, j: (n, g, 0, 0))
    f32 = jnp.float32
    return pl.pallas_call(
        partial(_sel_bwd_kernel, tq=tq, tk=tk, first=first),
        grid=(N, H // G, S // tq, S // tk),
        in_specs=[group, kv, kv, mask, group, stat, stat],
        out_specs=[group, whole, whole],
        out_shape=[jax.ShapeDtypeStruct(q.shape, f32), jax.ShapeDtypeStruct(k.shape, f32),
                   jax.ShapeDtypeStruct(v.shape, f32)],
        scratch_shapes=[pltpu.VMEM((d, G * tq), q.dtype)] * 2
        + [pltpu.VMEM((d, G * tq), f32)],
        compiler_params=_params(64),
        interpret=interpret,
        name="sel_attn_bwd",
    )(q, k, v, sel, do, lse, delta)


@partial(jax.custom_vjp, nondiff_argnums=(4, 5, 6, 7))
def _sel_flash(q, k, v, sel, tq, tk, first, interpret):
    """:func:`_gq_flash` over the keys ``sel`` keeps: ``sel`` ``[N, S keys, S
    queries]`` int8, read for the query tiles that end after ``first``."""
    return _sel_flash_fwd(q, k, v, sel, tq, tk, first, interpret)[0]


def _sel_flash_fwd(q, k, v, sel, tq, tk, first, interpret):
    from jax.ad_checkpoint import checkpoint_name  # here: the lines above this family stay put

    ops = tuple(x.astype(jnp.bfloat16) for x in (q, k, v))
    o, lse = _call_sel_fwd(*ops, sel, tq, tk, first, interpret)
    o, lse = checkpoint_name(o, SEL_OUT), checkpoint_name(lse, SEL_LSE)
    return o, (ops, sel, o, lse)


def _sel_flash_bwd(tq, tk, first, interpret, res, do):
    (q, k, v), sel, o, lse = res
    N, H, _, S = q.shape
    kv = k.shape[1]
    delta = jnp.sum(o * do, axis=2).reshape(N, kv, H // kv, S // tq, tq)
    delta = jnp.swapaxes(delta, 2, 3).reshape(lse.shape)  # the log-sum-exp's layout (_row_stat)
    return _call_sel_bwd(q, k, v, sel, do.astype(jnp.bfloat16), lse, delta, tq, tk, first,
                         interpret) + (None,)


_sel_flash.defvjp(_sel_flash_fwd, _sel_flash_bwd)


def sel_tile_for(S: int, d: int, group: int):
    """:func:`gq_tile_for` for the selected kernels, (query tile, key tile),
    by the header's rule: head dims that fill the lanes, whole tiles of
    positions, a group's query tiles side by side no wider than 4,096 columns
    (eight heads at 512 were 3 % ahead of 256 in the layer's block, PERF.md,
    PR 36)."""
    if d % LANES:
        return None
    tk = next((t for t in TILES if S % t == 0), None)
    tq = next((t for t in TILES if S % t == 0 and group * t <= 4096), None)
    return None if tq is None else (tq, tk)


def fused_selected_attention(q, k, v, scale, select, block: int, *, block_q: int, block_k: int,
                             interpret: bool = False):
    """``ops.layers.selected_gq_attention`` through the kernels above, its
    operands and result in its layouts (heads first, ``[N, H, S, d]``) and
    ``select`` as ``ops.layers.select_keys`` gives it for query blocks of
    ``block`` rows: per block None (every causal key) or the 0/1 choice ``[N,
    q, keys up to the block's end]``.  The blocks become one keys-first int8
    mask ``[N, S, S]`` (a byte a pair; what lies above the diagonal or in a
    None block is never looked at)."""
    N, _, S, _ = q.shape
    cols, first = [], 0
    for i, m in enumerate(select):
        start, end = i * block, min((i + 1) * block, S)
        if m is None:
            cols.append(jnp.ones((N, S, end - start), jnp.int8))
            first = end if first == start else first
        else:
            cols.append(jnp.pad(jnp.swapaxes(m, 1, 2).astype(jnp.int8),
                                ((0, 0), (0, S - end), (0, 0))))
    sel = jnp.concatenate(cols, axis=2)
    qt, kt, vt = (jnp.swapaxes(x.astype(jnp.float32), 2, 3) for x in (q, k, v))
    return jnp.swapaxes(_sel_flash(qt * scale, kt, vt, sel, block_q, block_k, first, interpret),
                        2, 3)


# ---------------------------------------------------------------------------
# WHAT ``gq_attn_bwd`` READS CARRIES NAMES TOO (here, below every kernel of the
# other families, and not beside ``_gq_flash_fwd``: a Mosaic kernel's
# compile-cache key holds its call stack, so no line above moves; the band
# pair's forward names its three through the same helper, so an edit here
# moves the Laguna cell's kernels alone).  ``o`` and the log-sum-exp of
# ``gq_attn_fwd`` are the primal output and the backward kernel's residuals at
# once, and the third residual is the kernels' own operands: ``q`` (scaled),
# ``k`` and ``v`` in bfloat16 with the positions minor, half the bytes of the
# float32 heads they were cast from.  A ``jax.checkpoint`` whose policy saves
# ``GQ_OUT``, ``GQ_LSE`` and ``GQ_OPS`` runs in its backward no second forward
# kernel and nothing of what leads up to it (``models/ouro.py``'s layer: no
# second ``q`` / ``k`` / ``v`` product, turn or cast).  Under no such policy
# (``models/lfm2.py``'s layers) a name is the identity.
# ---------------------------------------------------------------------------

GQ_OUT, GQ_LSE, GQ_OPS = "gq_out", "gq_lse", "gq_ops"


def _gq_named(ops, o, lse, names=(GQ_OUT, GQ_LSE, GQ_OPS)):
    """What ``_gq_flash_fwd`` returns, ``(o, residuals)``, each of the three
    residuals under its name (``o`` in the primal output too); ``names`` the
    band pair's for ``_band_flash_fwd``."""
    from jax.ad_checkpoint import checkpoint_name

    o, lse = checkpoint_name(o, names[0]), checkpoint_name(lse, names[1])
    return o, (checkpoint_name(ops, names[2]), o, lse)


# ---------------------------------------------------------------------------
# Grouped-query attention under a BAND (``ops.layers.sliding_gq_attention``,
# and the diagonal alone where ``gq_attn_bwd``'s resident ``dq`` does not fit:
# :func:`gq_plan`): kernels ``band_attn_fwd`` / ``band_attn_bwd``.  The
# grouped-query kernels' layout and group, the selected pair's grid (query
# tiles outer, ``dk`` / ``dv`` resident), and one thing of their own.
#
# THE KEY-TILE AXIS IS THE BAND'S EXTENT.  ``window`` is static: query ``i``
# sees key ``j`` if ``j <= i`` and ``i - j < window`` (None: the diagonal
# alone).  Query tile ``i`` meets the key tiles ``first(i) .. last(i)`` only,
# ``first = max(0, i * tq - window + 1) // tk`` and ``last = (i * tq + tq - 1)
# // tk``; the grid's last axis has ``max_i(last - first) + 1`` steps (2 at
# tiles of 512 under a window of 512, 3 at 256, ``S / tk`` without a window)
# and step ``j`` is key tile ``first(i) + j``: a tile below the band is neither
# fetched nor computed, as one above the diagonal never was (its step repeats
# the last tile's index, which fetches nothing, and runs nothing).  Each of
# the two edges is met only in the tiles it crosses.  A query's first visited
# tile may hold no key it sees (the band's lower edge cuts a tile's corner
# off): harmless, for the reason the selected kernels give, since every query
# sees itself.
#
# WHAT ``band_attn_bwd`` READS CARRIES NAMES, as the grouped-query pair's does
# (:func:`_gq_named`): ``o`` and the log-sum-exp of ``band_attn_fwd``
# (``BAND_OUT``, ``BAND_LSE``) and the kernels' own bfloat16 operands
# (``BAND_OPS``).  ``models/laguna.py``'s layers keep all three, full and
# sliding, lone and scanned (its ``kept``): their backward runs no second
# forward kernel and nothing of what leads up to it.
# ---------------------------------------------------------------------------

BAND_OUT, BAND_LSE, BAND_OPS = "band_out", "band_lse", "band_ops"


def _band_first(i, tq, tk, window):
    """The first key tile query tile ``i`` meets."""
    return 0 if window is None else jnp.maximum(i * tq - (window - 1), 0) // tk


def _band_tiles(S, tq, tk, window):
    """(first, last) key tile of every query tile of a row of ``S`` positions
    (the last tile of either axis may be short: the ``jnp`` block loop's)."""
    return [(0 if window is None else max(0, start - window + 1) // tk,
             (min(start + tq, S) - 1) // tk) for start in range(0, S, tq)]


def band_extent(S: int, tq: int, tk: int, window):
    """(key tiles visited, key tiles on or under the diagonal) of one head's
    row of ``S`` positions at query tiles of ``tq`` and key tiles of ``tk``."""
    tiles = _band_tiles(S, tq, tk, window)
    return sum(last - first + 1 for first, last in tiles), sum(last + 1 for _, last in tiles)


def _band_steps(S, tq, tk, window):
    """Steps of the grid's key-tile axis: the most key tiles a query tile meets."""
    return max(last - first + 1 for first, last in _band_tiles(S, tq, tk, window))


def _band_scores(k, q, q0, k0, tq, window, diag, low):
    """A tile's scores ``[tk, G * tq]`` (keys first, a group's query tiles side
    by side) with the pairs outside the band masked: above the diagonal if
    ``diag`` (it crosses the tile), below the window if ``low`` (its lower
    edge does); ``q0`` / ``k0`` the tile's first positions.  The mask is made
    once for a ``[tk, tq]`` tile, as a bias of 0 or ``MASKED``, and added to
    every head of the group (under a window every visited tile is crossed by
    an edge, so the mask is on the kernels' main path)."""
    st = _dot(k, q, _TN)
    if not (diag or low):
        return st
    shape = (st.shape[0], tq)
    qp = q0 + lax.broadcasted_iota(jnp.int32, shape, 1)
    kp = k0 + lax.broadcasted_iota(jnp.int32, shape, 0)
    keep = qp >= kp if diag else qp - kp < window
    if diag and low:
        keep = jnp.logical_and(keep, qp - kp < window)
    return st + jnp.tile(jnp.where(keep, 0.0, MASKED), (1, st.shape[1] // tq))


def _when_in_band(i, kt, tq, tk, window, step):
    """Run ``step(diag, low)`` for the tile (query tile ``i``, key tile
    ``kt >= first(i)``) unless it lies above the diagonal; ``diag`` / ``low``
    say which edge crosses it (static flags: up to four bodies, two without a
    window)."""
    needed = kt * tk <= i * tq + tq - 1
    diag = kt * tk + tk - 1 > i * tq
    if window is None:
        pl.when(jnp.logical_and(needed, diag))(partial(step, True, False))
        pl.when(jnp.logical_and(needed, jnp.logical_not(diag)))(partial(step, False, False))
        return
    low = i * tq + tq - 1 - kt * tk >= window
    for d in (True, False):
        for w in (True, False):
            cond = jnp.logical_and(diag if d else jnp.logical_not(diag),
                                   low if w else jnp.logical_not(low))
            pl.when(jnp.logical_and(needed, cond))(partial(step, d, w))


def _band_fwd_kernel(q_ref, k_ref, v_ref, o_ref, lse_ref, q_s, m_s, l_s, acc_s, *,
                     tq: int, tk: int, window):
    i, j = pl.program_id(2), pl.program_id(3)
    kt = _band_first(i, tq, tk, window) + j
    pl.when(j == 0)(partial(_gq_start, q_ref, q_s, m_s, l_s, acc_s))

    def step(diag, low):
        st = _band_scores(k_ref[...], q_s[...], i * tq, kt * tk, tq, window, diag, low)
        _gq_softmax_step(st, v_ref, m_s, l_s, acc_s)

    _when_in_band(i, kt, tq, tk, window, step)
    pl.when(j == pl.num_programs(3) - 1)(partial(_gq_finish, o_ref, lse_ref, m_s, l_s, acc_s, tq))


def _band_bwd_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref, dq_ref, dk_ref, dv_ref,
                     q_s, do_s, dq_s, *, tq: int, tk: int, window):
    i, j = pl.program_id(2), pl.program_id(3)
    kt = _band_first(i, tq, tk, window) + j

    @pl.when(jnp.logical_and(i == 0, j == 0))
    def _():  # the key/value head's whole sequence, resident over (i, j)
        dk_ref[...] = jnp.zeros_like(dk_ref)
        dv_ref[...] = jnp.zeros_like(dv_ref)

    @pl.when(j == 0)
    def _():  # the group's query tile, resident over the key tiles
        q_s[...] = _side_by_side(q_ref)
        do_s[...] = _side_by_side(do_ref)
        dq_s[...] = jnp.zeros_like(dq_s)

    def step(diag, low):
        q, do, k = q_s[...], do_s[...], k_ref[...]
        st = _band_scores(k, q, i * tq, kt * tk, tq, window, diag, low)
        _gq_bwd_step(st, q, do, k, v_ref, lse_ref, delta_ref, dk_ref, dv_ref, dq_s, kt, tk)

    _when_in_band(i, kt, tq, tk, window, step)

    @pl.when(j == pl.num_programs(3) - 1)
    def _():
        for g in range(dq_ref.shape[0]):
            dq_ref[g] = dq_s[:, g * tq:(g + 1) * tq]


def _band_specs(G, d, tq, tk, window):
    """Block specs of a grid step (row, key/value head, query tile ``i``, step
    ``j`` of the band), both kernels': a group's query tiles, the key/value
    tile ``first(i) + j`` (one above the diagonal is never fetched), the row
    statistics."""
    def query(i, j):
        return i

    def key(i, j):
        return jnp.minimum(_band_first(i, tq, tk, window) + j, (i * tq + tq - 1) // tk)

    return _lane_tile(G, d, tq, query), _lane_tile(None, d, tk, key), _row_stat(G * tq, query)


def _call_band_fwd(q, k, v, tq, tk, window, interpret):
    N, H, d, S = q.shape
    G = H // k.shape[1]
    group, kv, stat = _band_specs(G, d, tq, tk, window)
    return pl.pallas_call(
        partial(_band_fwd_kernel, tq=tq, tk=tk, window=window),
        grid=(N, H // G, S // tq, _band_steps(S, tq, tk, window)),
        in_specs=[group, kv, kv],
        out_specs=[group, stat],
        out_shape=[jax.ShapeDtypeStruct(q.shape, jnp.float32),
                   jax.ShapeDtypeStruct((N, H // G, S // tq, 1, G * tq), jnp.float32)],
        scratch_shapes=[pltpu.VMEM((d, G * tq), q.dtype)]
        + [pltpu.VMEM((1, G * tq), jnp.float32)] * 2 + [pltpu.VMEM((d, G * tq), jnp.float32)],
        compiler_params=_params(64),
        interpret=interpret,
        name="band_attn_fwd",
    )(q, k, v)


def _call_band_bwd(q, k, v, do, lse, delta, tq, tk, window, interpret):
    N, H, d, S = q.shape
    G = H // k.shape[1]
    group, kv, stat = _band_specs(G, d, tq, tk, window)
    whole = pl.BlockSpec((None, None, d, S), lambda n, g, i, j: (n, g, 0, 0))
    f32 = jnp.float32
    return pl.pallas_call(
        partial(_band_bwd_kernel, tq=tq, tk=tk, window=window),
        grid=(N, H // G, S // tq, _band_steps(S, tq, tk, window)),
        in_specs=[group, kv, kv, group, stat, stat],
        out_specs=[group, whole, whole],
        out_shape=[jax.ShapeDtypeStruct(q.shape, f32), jax.ShapeDtypeStruct(k.shape, f32),
                   jax.ShapeDtypeStruct(v.shape, f32)],
        scratch_shapes=[pltpu.VMEM((d, G * tq), q.dtype)] * 2
        + [pltpu.VMEM((d, G * tq), f32)],
        compiler_params=_params(64),
        interpret=interpret,
        name="band_attn_bwd",
    )(q, k, v, do, lse, delta)


@partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5, 6))
def _band_flash(q, k, v, tq, tk, window, interpret):
    """:func:`_gq_flash` under the band of ``window`` (None: the diagonal
    alone), query tiles outer."""
    return _band_flash_fwd(q, k, v, tq, tk, window, interpret)[0]


def _band_flash_fwd(q, k, v, tq, tk, window, interpret):
    ops = tuple(x.astype(jnp.bfloat16) for x in (q, k, v))
    o, lse = _call_band_fwd(*ops, tq, tk, window, interpret)
    return _gq_named(ops, o, lse, (BAND_OUT, BAND_LSE, BAND_OPS))


def _band_flash_bwd(tq, tk, window, interpret, res, do):
    (q, k, v), o, lse = res
    N, H, _, S = q.shape
    kv = k.shape[1]
    delta = jnp.sum(o * do, axis=2).reshape(N, kv, H // kv, S // tq, tq)
    delta = jnp.swapaxes(delta, 2, 3).reshape(lse.shape)  # the log-sum-exp's layout (_row_stat)
    return _call_band_bwd(q, k, v, do.astype(jnp.bfloat16), lse, delta, tq, tk, window, interpret)


_band_flash.defvjp(_band_flash_fwd, _band_flash_bwd)


def fused_band_attention(q, k, v, scale, window, *, block_q: int, block_k: int,
                         interpret: bool = False):
    """``ops.layers.sliding_gq_attention`` (``window`` None:
    ``causal_gq_attention``) through the kernels above, operands and result
    heads first (``[N, H, S, d]``), float32 out; tiles of ``block_q`` queries a
    head by ``block_k`` keys."""
    if window is not None and window >= q.shape[2]:
        window = None
    qt, kt, vt = (jnp.swapaxes(x.astype(jnp.float32), 2, 3) for x in (q, k, v))
    return jnp.swapaxes(_band_flash(qt * scale, kt, vt, block_q, block_k, window, interpret),
                        2, 3)


#: the largest ``dq`` (float32, a group's whole sequence) ``gq_attn_bwd`` keeps
#: resident; beyond it :func:`gq_plan` hands the layer to the band pair
GQ_RESIDENT_BYTES = 8 << 20

def gq_plan(S: int, d: int, group: int, window=None, dv=None):
    """THE RULE (the header's): which kernel pair grouped-query attention
    takes at ``S`` positions, ``group`` query heads of ``d`` dims a key/value
    head, under the diagonal alone (``window`` None) or a window as well, and
    at which tiles: None (the ``jnp`` block loop) or ``(pair, query tile, key
    tile)`` with ``pair`` ``"gq"`` or ``"band"``.  ``dv`` the value's width
    where it is not the query's (None: the query's; differential attention
    reads ONE 128-wide value with two 64-wide queries and keys): the ``gq``
    pair takes any ``dv`` of 64s, the band pair ``dv == d`` only."""
    if window is not None and window >= S:
        window = None
    tile = gq_tile_for(S, d)
    if tile is None:
        return None
    own = dv is not None and dv != d
    if own and dv % (LANES // 2):
        return None
    if window is None and (d % LANES or group * d * S * 4 <= GQ_RESIDENT_BYTES):
        return "gq", tile, tile
    if d % LANES or own:
        return None
    # under a window both tiles are at most half of it: the band then holds two
    # thirds of the pairs of the tiles it crosses, and the backward's score
    # tiles stay clear of VMEM's edge (a sliding layer's block of the Laguna
    # cell, forward + backward: 512 x 512 30.7 ms, 512 x 256 25.4, 256 x 256
    # 24.2, 256 x 128 24.7, 128 x 128 26.0; my chip call 2, PR 42)
    most = 4096 // group if window is None else min(4096 // group, max(window // 2, TILES[-1]))
    tq = next((t for t in TILES if S % t == 0 and t <= most), None)
    if tq is None:
        return None
    tk = tile if window is None else tq
    return "band", tq, tk
