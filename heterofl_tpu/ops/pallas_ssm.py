"""Pallas TPU kernels for the Mamba-2 chunked selective scan (``ssm/scan``).

``ops.layers.ssm_chunked_scan`` as one fused kernel pair with a hand-written
backward, so everything whose size is ``chunk x chunk`` or ``head_dim x state``
a head lives in VMEM only: the ``jnp`` form writes each chunk's float32 decay
matrix a head, its product with the ``C B^T`` scores, what every chunk leaves
and the state before every chunk to HBM and reads them back, forward and
transposed (at 8,192 positions, 64 heads of 64 in 8 groups and a state of 128:
0.8 GB a layer against 0.34 GB of ``x``, ``dt``, ``B``, ``C`` and ``y``).
What stays ``jnp`` is of ``dt``'s size: ``la = dt a`` and its cumulative sum
inside a chunk (:func:`fused_ssm_scan`).

With ``l`` the inclusive cumulative sum of ``la`` inside a chunk of ``Q``
positions, ``X = x dt`` of a head and chunk ``[Q, P]``, ``S`` the state the
chunks before it left, kept here as ``[Ns, P]`` (the transpose of the
recurrence's ``[P, Ns]``):

    y   = (C B^T * D) X + exp(l) * (C S),    D_ij = exp(l_i - l_j) if j <= i else 0
    S' <- exp(l_last) * S + B^T (X * exp(l_last - l))

* ``ssm_scan_fwd``: grid (row, group, chunk), the chunk axis last and
  sequential; a step holds the group's ``C``, ``B`` ``[Q, Ns]``, its ``R``
  heads' ``x`` side by side on the lanes ``[Q, R P]``, and their ``dt`` and
  ``l``.  ``C B^T`` once a group; per head the masked DIFFERENCE ``exp(l_i -
  l_j)`` (never ``exp(l_i) exp(-l_j)``: ``dt`` is not clamped and the
  published range overflows the second factor); the state of the group's
  heads ``[Ns, R P]`` float32 in a VMEM scratch that persists over the chunk
  axis and is zeroed at a row's first chunk.  As the ``custom_vjp``'s forward
  it also writes the state BEFORE each chunk (float32, ``[Ns, R P]`` a group
  and chunk: the one thing beside ``y`` that goes to HBM).
* ``ssm_scan_bwd``: the same grid with the chunks in reverse, carrying the
  state's cotangent ``dS`` the same way; from the forward's operands, ``dy``
  and the state before the chunk it rebuilds ``D`` and the scores and writes
  the cotangents of ``x``, ``dt``, ``l``, ``B`` and ``C``.

HEADS ON THE LANES.  A head narrower than the 128 lanes shares a lane tile
with its neighbours: a product that is a head's alone (``(C B^T * D) X`` and
its two transposes) takes the tile with the other heads' lanes zeroed, so it
runs 128 wide and its result is zero outside the head's lanes; the two
products with the state run over all the group's heads at once.  A per-head
scalar of a position (``dt``, ``l``) comes as a column ``[Q, 1]`` a head --
the kernels read ``[Q, 2 R]``: ``dt``'s columns, then ``l``'s -- and is spread
over the head's lanes by selects; ``l`` comes again positions-minor ``[R,
Q]`` for the ``l_j`` of ``D``.  A sum over a head's lanes is ``log2(P)``
rotations of the tile, all its heads at once.  The cotangents of ``dt`` and
``l`` leave in the layouts they came in, ``l``'s in two parts: the sums of
the cotangent of ``l_i - l_j`` along its rows and along its columns.

Precision: the log decays, their differences and exponentials, the carried
state and ``dS`` float32; the operands of every product at the default matmul
precision -- bfloat16, accumulated in float32, which is what the chip makes of
the ``jnp`` form's float32 operands; float32 at "highest" under
``jax.default_matmul_precision("highest")``, which the ``jnp`` form obeys too.
"""

from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp
from jax import lax

from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

LANES = 128
#: the log decay of a pair above the diagonal: ``exp`` of it is 0
MASKED = -1e30
#: the most lanes a group's heads may take side by side (a step holds about
#: forty ``[chunk, lanes]`` float32 values at once: 20 MB at chunks of 128)
MOST_LANES = 1024

_NN = (((1,), (0,)), ((), ()))  # a b
_NT = (((1,), (1,)), ((), ()))  # a b^T
_TN = (((0,), (0,)), ((), ()))  # a^T b


def ssm_plan(S: int, H: int, P: int, G: int, Ns: int, chunk: int):
    """THE RULE: the block ``(chunk, lanes)`` of a grid step -- a chunk's
    positions by a group's heads side by side -- for a row of ``S`` positions,
    ``H`` heads of ``P`` dims in ``G`` groups and a state of ``Ns``, or None
    where the kernels take no such shapes and the caller runs the ``jnp``
    form: a row that is no whole number of chunks, a chunk or a state that is
    no multiple of 128 (a ``[chunk, chunk]`` decay tile, a ``[chunk, Ns]``
    operand), a group whose heads fill no whole lanes (a client's narrow
    slice at its own widths), a head that does not divide a lane tile."""
    lanes = H // G * P
    if S % chunk or chunk % LANES or Ns % LANES or LANES % P or lanes % LANES \
            or lanes > MOST_LANES:
        return None
    return chunk, lanes


# ---------------------------------------------------------------------------
# heads side by side on the lanes
# ---------------------------------------------------------------------------

def _tiles(R, P):
    """The lane tiles of ``R`` heads of ``P`` lanes: (lane slice, the heads in
    it, each with its number inside the tile)."""
    per = LANES // P
    return [(slice(t * LANES, (t + 1) * LANES), [(t * per + k, k) for k in range(per)])
            for t in range(R // per)]


def _head_of_lane(Q, P):
    return lax.broadcasted_iota(jnp.int32, (Q, LANES), 1) // P


def _spread(cols, P):
    """``cols``, a ``[Q, 1]`` column a head, as ``[Q, R P]``: head ``r``'s
    value on its ``P`` lanes."""
    Q = cols[0].shape[0]
    out = []
    for _, heads in _tiles(len(cols), P):
        tile = jnp.broadcast_to(cols[heads[0][0]], (Q, LANES))
        for r, k in heads[1:]:
            tile = jnp.where(_head_of_lane(Q, P) == k, cols[r], tile)
        out.append(tile)
    return out[0] if len(out) == 1 else jnp.concatenate(out, axis=1)


def _gather(v, R, P):
    """The sum of ``v`` ``[Q, R P]`` over each head's lanes, ``R`` columns
    ``[Q, 1]``: ``log2(P)`` rotations of a lane tile, every head of it at once
    (a lane then holds the sum of the ``P`` lanes ending at it, so a head's sum
    stands at its last lane)."""
    cols = []
    for lanes, heads in _tiles(R, P):
        tile, shift = v[:, lanes], 1
        while shift < P:
            tile = tile + pltpu.roll(tile, shift, 1)
            shift *= 2
        cols += [tile[:, (k + 1) * P - 1:(k + 1) * P] for _, k in heads]
    return cols


def _own(tile, k, P):
    """``tile`` ``[Q, 128]`` with the lanes of the heads other than its ``k``-th zeroed."""
    return jnp.where(_head_of_lane(tile.shape[0], P) == k, tile, 0.0)


def _side_by_side(cols):
    """Columns ``[Q, 1]`` as ``[Q, len(cols)]``."""
    at = lax.broadcasted_iota(jnp.int32, (cols[0].shape[0], len(cols)), 1)
    out = jnp.zeros(at.shape, jnp.float32)
    for i, col in enumerate(cols):
        out = jnp.where(at == i, col, out)
    return out


def _causal(Q):
    return lax.broadcasted_iota(jnp.int32, (Q, Q), 0) >= lax.broadcasted_iota(jnp.int32, (Q, Q), 1)


def _decay(li, lj, causal):
    """``D`` ``[Q, Q]`` of a head from its ``l`` as a column and as a row."""
    return jnp.exp(jnp.where(causal, li - lj, MASKED))


def _dot_on(ops):
    """A product on operands of dtype ``ops``, accumulated in float32."""
    return partial(lax.dot_general, preferred_element_type=jnp.float32,
                   precision=lax.Precision.HIGHEST if ops == jnp.float32 else None)


def _columns(cols_ref, R):
    """(``dt``'s columns, ``l``'s columns) of the ``[Q, 2 R]`` block, ``[Q, 1]`` a head."""
    both = cols_ref[...]
    return ([both[:, i:i + 1] for i in range(j, j + R)] for j in (0, R))


# ---------------------------------------------------------------------------
# the kernels
# ---------------------------------------------------------------------------

def _fwd_kernel(x_ref, cols_ref, rows_ref, b_ref, c_ref, y_ref, *rest, P: int, ops, keep: bool):
    before_ref, s_ref = rest if keep else (None,) + rest
    dot = _dot_on(ops)

    @pl.when(pl.program_id(2) == 0)
    def _():
        s_ref[...] = jnp.zeros_like(s_ref)

    Q, R = x_ref.shape[0], rows_ref.shape[0]
    causal = _causal(Q)
    dt, l = _columns(cols_ref, R)
    e = _spread(l, P)                                             # l, a head on its lanes
    last = e[Q - 1:, :]
    xd = x_ref[...] * _spread(dt, P)
    b, c = b_ref[...].astype(ops), c_ref[...].astype(ops)
    s = s_ref[...].astype(jnp.float32)
    if keep:
        before_ref[...] = s
    # what the chunks before left, read by C and decayed to each position
    y_ref[...] = dot(c, s.astype(ops), _NN) * jnp.exp(e)
    # the chunk's own positions
    cb = dot(c, b, _NT)
    for lanes, heads in _tiles(R, P):
        tile = xd[:, lanes]
        y_ref[:, lanes] += sum(
            dot((cb * _decay(l[r], rows_ref[r:r + 1, :], causal)).astype(ops),
                _own(tile, k, P).astype(ops), _NN)
            for r, k in heads)
    # what the chunk leaves
    s_ref[...] = (jnp.exp(last) * s
                  + dot(b, (xd * jnp.exp(last - e)).astype(ops), _TN)).astype(s_ref.dtype)


def _bwd_kernel(x_ref, cols_ref, rows_ref, b_ref, c_ref, before_ref, dy_ref,
                dx_ref, dcols_ref, drows_ref, db_ref, dc_ref, ds_ref, *, P: int, ops):
    dot = _dot_on(ops)

    @pl.when(pl.program_id(2) == 0)
    def _():  # the row's last chunk: nothing reads the state it leaves
        ds_ref[...] = jnp.zeros_like(ds_ref)

    Q, R = x_ref.shape[0], rows_ref.shape[0]
    causal = _causal(Q)
    dt, l = _columns(cols_ref, R)
    e = _spread(l, P)
    last = e[Q - 1:, :]
    to_end, kept = jnp.exp(last - e), jnp.exp(last)
    x, dy = x_ref[...], dy_ref[...]
    dts = _spread(dt, P)
    xd = x * dts
    b, c = b_ref[...].astype(ops), c_ref[...].astype(ops)
    s = before_ref[...]
    gs = ds_ref[...].astype(jnp.float32)                          # cotangent of the state it leaves
    s_o, gs_o = s.astype(ops), gs.astype(ops)

    # the read of the state before the chunk: y += exp(e) * (C S)
    dz = dy * jnp.exp(e)
    dz_o = dz.astype(ops)
    de = dz * dot(c, s_o, _NN)                                    # cotangent of e, a head's lanes apart
    dc = dot(dz_o, s_o, _NT)
    # the state the chunk leaves: S' = kept * S + B^T (xd * to_end)
    u = xd * to_end
    du = dot(b, gs_o, _NN)
    db = dot(u.astype(ops), gs_o, _NT)
    dxd = du * to_end
    v = du * u                                                    # cotangent of (last - e)
    dlast = jnp.sum(v, axis=0, keepdims=True) + jnp.sum(gs * s, axis=0, keepdims=True) * kept
    ds_ref[...] = (kept * gs + dot(c, dz_o, _TN)).astype(ds_ref.dtype)
    at_last = lax.broadcasted_iota(jnp.int32, e.shape, 0) == Q - 1
    de = jnp.where(at_last, de - v + dlast, de - v)
    dl = _gather(de, R, P)

    # the chunk's own positions: y += (cb * D) xd
    cb = dot(c, b, _NT)
    dcb = jnp.zeros_like(cb)
    ddt = []
    for lanes, heads in _tiles(R, P):
        xd_o = xd[:, lanes].astype(ops)
        own = dxd[:, lanes]
        for r, k in heads:
            d = _decay(l[r], rows_ref[r:r + 1, :], causal)
            dy_o = _own(dy[:, lanes], k, P).astype(ops)
            dm = dot(dy_o, xd_o, _NT) * d                         # cotangent of cb a head
            own = own + dot((cb * d).astype(ops), dy_o, _TN)
            dcb = dcb + dm
            # the cotangent of l_i - l_j, summed along either axis of ONE array:
            # the two parts of l's cotangent cancel to float32, as the jnp form's do
            t = dm * cb
            dl[r] = dl[r] + jnp.sum(t, axis=1, keepdims=True)
            drows_ref[r:r + 1, :] = -jnp.sum(t, axis=0, keepdims=True)
        dx_ref[:, lanes] = own * dts[:, lanes]
        ddt += _gather(own * x[:, lanes], len(heads), P)
    dcols_ref[...] = _side_by_side(ddt + dl)
    dcb_o = dcb.astype(ops)
    dc_ref[...] = dc + dot(dcb_o, b, _NN)
    db_ref[...] = db + dot(dcb_o, c, _TN)


# ---------------------------------------------------------------------------
# the calls
# ---------------------------------------------------------------------------

def _specs(Q, lanes, Ns, R, at):
    """Block specs of a grid step (row, group, step ``i`` of the chunk axis),
    ``at(i)`` the chunk it holds: a group's heads' ``x`` / ``y`` ``[Q, R P]``
    of ``[N, S, H P]``; their ``dt`` and ``l`` as columns ``[Q, 2 R]`` of ``[N,
    chunks, G, Q, 2 R]`` and ``l`` again as rows ``[R, Q]`` of ``[N, chunks,
    G, R, Q]``; the group's ``B`` / ``C`` ``[Q, Ns]`` of ``[N, S, G Ns]``; a
    state ``[Ns, R P]`` of ``[N, chunks, G, Ns, R P]``."""
    def small(*block):
        return pl.BlockSpec((None, None, None) + block, lambda n, g, i: (n, at(i), g, 0, 0))

    return {"heads": pl.BlockSpec((None, Q, lanes), lambda n, g, i: (n, at(i), g)),
            "cols": small(Q, 2 * R), "rows": small(R, Q),
            "group": pl.BlockSpec((None, Q, Ns), lambda n, g, i: (n, at(i), g)),
            "state": small(Ns, lanes)}


def _call(kernel, name, reverse, ins, outs, state, interpret):
    """One of the two kernels over the grid (row, group, chunk), the chunks
    from the row's last to its first if ``reverse``: ``ins`` the operands and
    ``outs`` the results' shapes, each with its block's name in :func:`_specs`."""
    x, rows, b = ins[0][1], ins[2][1], ins[3][1]
    _, nc, G, R, Q = rows.shape
    lanes, Ns = x.shape[2] // G, b.shape[2] // G
    spec = _specs(Q, lanes, Ns, R, (lambda i: nc - 1 - i) if reverse else (lambda i: i))
    return pl.pallas_call(
        kernel,
        grid=(x.shape[0], G, nc),
        in_specs=[spec[k] for k, _ in ins],
        out_specs=[spec[k] for k, _ in outs],
        out_shape=[jax.ShapeDtypeStruct(shape, jnp.float32) for _, shape in outs],
        scratch_shapes=[pltpu.VMEM((Ns, lanes), state)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary"), vmem_limit_bytes=64 << 20),
        interpret=interpret,
        name=name,
    )(*(t for _, t in ins))


def _operands(x, cols, rows, b, c):
    return [("heads", x), ("cols", cols), ("rows", rows), ("group", b), ("group", c)]


def _call_fwd(x, cols, rows, b, c, P, ops, state, interpret, keep):
    _, nc, G, _, _ = rows.shape
    before = (x.shape[0], nc, G, b.shape[2] // G, x.shape[2] // G)
    return _call(partial(_fwd_kernel, P=P, ops=ops, keep=keep), "ssm_scan_fwd", False,
                 _operands(x, cols, rows, b, c),
                 [("heads", x.shape)] + [("state", before)] * keep, state, interpret)


def _call_bwd(x, cols, rows, b, c, before, dy, P, ops, state, interpret):
    ins = _operands(x, cols, rows, b, c)
    return _call(partial(_bwd_kernel, P=P, ops=ops), "ssm_scan_bwd", True,
                 ins + [("state", before), ("heads", dy)],
                 [(k, t.shape) for k, t in ins], state, interpret)


@partial(jax.custom_vjp, nondiff_argnums=(5, 6, 7, 8))
def _scan(x, cols, rows, b, c, P, ops, state, interpret):
    """``y`` ``[N, S, H P]`` from ``x`` ``[N, S, H P]``, ``cols`` ``[N, chunks,
    G, Q, 2 R]`` (a group's heads' time step, then their cumulative log decay
    inside the chunk ``l``, a column a head), ``rows`` ``[N, chunks, G, R, Q]``
    (``l`` again, positions minor), ``b`` / ``c`` ``[N, S, G Ns]``; heads of
    ``P`` dims, products on operands of dtype ``ops``, the carried state and
    its cotangent of dtype ``state`` (float32; the tests' bfloat16 copy shows
    what it would cost).  Float32 in, out and in every gradient; ``l``'s
    cotangent comes in two parts, one through each of its layouts."""
    return _call_fwd(x, cols, rows, b, c, P, ops, state, interpret, False)[0]


def _scan_fwd(x, cols, rows, b, c, P, ops, state, interpret):
    y, before = _call_fwd(x, cols, rows, b, c, P, ops, state, interpret, True)
    return y, (x, cols, rows, b, c, before)


def _scan_bwd(P, ops, state, interpret, res, dy):
    return tuple(_call_bwd(*res, dy, P, ops, state, interpret))


_scan.defvjp(_scan_fwd, _scan_bwd)


def _operand_dtype():
    """The dtype the default matmul precision gives a product's float32
    operands on the chip: float32 at "highest", else bfloat16."""
    return jnp.float32 if jax.config.jax_default_matmul_precision in ("highest", "float32") \
        else jnp.bfloat16


def fused_ssm_scan(x, dt, la, b, c, chunk: int, *, interpret: bool = False, state=jnp.float32):
    """``ops.layers.ssm_chunked_scan`` through the kernels above, its operands
    and result in its layouts: ``x`` ``[N, S, H, P]``, ``dt`` and ``la = dt a``
    ``[N, S, H]``, ``b`` / ``c`` ``[N, S, G, Ns]``, ``y`` ``[N, S, H, P]``;
    shapes :func:`ssm_plan` takes.  What stays ``jnp`` and differentiates by
    itself is the size of ``dt`` (2 MB a layer): ``la``, its cumulative sum
    inside a chunk, and the two layouts the kernels read them in.  ``x dt`` is
    the kernels' (a ``[.., H, P]`` product of heads narrower than the lanes
    costs XLA a materialised broadcast and two copies of ``x``'s size), so no
    array of the heads' width is made, copied or turned on the way in or out."""
    N, S, H, P = x.shape
    G, Ns = b.shape[2:]

    def cols(t):  # [N, S, H] -> [N, chunks, G, Q, R]
        return jnp.moveaxis(t.reshape(N, S // chunk, chunk, G, H // G), 2, 3)

    l = jnp.cumsum(cols(la), axis=3)
    y = _scan(x.reshape(N, S, H * P), jnp.concatenate([cols(dt), l], axis=-1),
              jnp.swapaxes(l, 3, 4), b.reshape(N, S, G * Ns), c.reshape(N, S, G * Ns),
              P, _operand_dtype(), state, interpret)
    return y.reshape(N, S, H, P)
