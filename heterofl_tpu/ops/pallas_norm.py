"""Pallas TPU kernels for the train-time (sBN-free) batch norm.

The masked-width BN in the round step (ops/layers.py:batch_norm, mode
"batch") is bandwidth-bound: XLA materialises the weighted moments and the
normalisation as separate HBM passes over the activation.  These kernels fuse
each direction -- forward: one accumulation pass (weighted sum / sumsq /
count) and one normalise pass with the statistics living in VMEM scratch
between phases; backward (custom VJP): one pass accumulating ``db``/``dg``
and one pass emitting ``dx`` from the standard BN backward formula.  Width
masking needs no extra input: masked channels carry ``g == b == 0``, which
zeroes their output exactly like the XLA path.

Opt-in via ``cfg['pallas_norm'] = True`` (see models/norms.py); the XLA path
still serves running/collect modes and cross-device (sync-BN) reductions.
Not measured against the XLA op on the chip since PRs 1-18.
"""

from __future__ import annotations

from functools import partial
from typing import Optional

import jax
import jax.numpy as jnp

from jax.experimental import pallas as pl

from ..obs.trace import scoped


def _vmem(shape):
    from jax.experimental.pallas import tpu as pltpu

    return pltpu.VMEM(shape, jnp.float32)


def _masks(i, w_ref, m_total, block_m):
    """(real-row mask, weight-valid mask) for the current block; block
    padding rows may hold non-finite garbage and must be `where`-ed out, not
    multiplied out."""
    row = jax.lax.broadcasted_iota(jnp.int32, (block_m, 1), 0) + i * block_m
    rowmask = (row < m_total).astype(jnp.float32)
    # where, not multiply: the padding rows of w are undefined VMEM too
    return rowmask, jnp.where(rowmask > 0, w_ref[:], 0.0)


def _bn_fwd_kernel(x_ref, w_ref, g_ref, b_ref, y_ref, st_ref, s1, s2, cnt, *,
                   eps: float, m_total: int, block_m: int):
    phase, i = pl.program_id(0), pl.program_id(1)

    @pl.when(jnp.logical_and(phase == 0, i == 0))
    def _():
        s1[:] = jnp.zeros_like(s1)
        s2[:] = jnp.zeros_like(s2)
        cnt[:] = jnp.zeros_like(cnt)

    rowmask, valid = _masks(i, w_ref, m_total, block_m)

    @pl.when(phase == 0)
    def _():
        x = jnp.where(valid > 0, x_ref[:].astype(jnp.float32), 0.0)
        s1[:] += jnp.sum(x * valid, axis=0, keepdims=True)
        s2[:] += jnp.sum(x * x * valid, axis=0, keepdims=True)
        cnt[:] += jnp.sum(valid, axis=0, keepdims=True)

    @pl.when(phase == 1)
    def _():
        n = jnp.maximum(cnt[0, 0], 1e-6)
        mean = s1[:] / n
        var = jnp.maximum(s2[:] / n - mean * mean, 0.0)
        inv = jax.lax.rsqrt(var + eps)
        x = jnp.where(rowmask > 0, x_ref[:].astype(jnp.float32), 0.0)
        y = (x - mean) * inv * g_ref[:] + b_ref[:]
        y_ref[:] = y.astype(y_ref.dtype)
        st_ref[0:1, :] = mean
        st_ref[1:2, :] = inv
        st_ref[2:3, :] = jnp.full_like(mean, n)


def _bn_bwd_kernel(x_ref, w_ref, g_ref, dy_ref, st_ref, dx_ref, dg_ref, db_ref,
                   a1, a2, *, m_total: int, block_m: int):
    phase, i = pl.program_id(0), pl.program_id(1)

    @pl.when(jnp.logical_and(phase == 0, i == 0))
    def _():
        a1[:] = jnp.zeros_like(a1)
        a2[:] = jnp.zeros_like(a2)

    rowmask, valid = _masks(i, w_ref, m_total, block_m)
    mean = st_ref[0:1, :]
    inv = st_ref[1:2, :]
    n = jnp.maximum(st_ref[2, 0], 1e-6)
    x = jnp.where(rowmask > 0, x_ref[:].astype(jnp.float32), 0.0)
    xhat = (x - mean) * inv
    dy = jnp.where(rowmask > 0, dy_ref[:].astype(jnp.float32), 0.0)

    @pl.when(phase == 0)
    def _():
        a1[:] += jnp.sum(dy, axis=0, keepdims=True)          # db
        a2[:] += jnp.sum(dy * xhat, axis=0, keepdims=True)   # dg

    @pl.when(phase == 1)
    def _():
        g = g_ref[:]
        # dx_k = inv*g*dy_k - w_k*inv/n*(g*db) - w_k*xhat_k*inv/n*(g*dg)
        dx = inv * g * dy \
            - valid * (inv / n) * (g * a1[:]) \
            - valid * xhat * (inv / n) * (g * a2[:])
        dx_ref[:] = dx.astype(dx_ref.dtype)
        dg_ref[:] = a2[:]
        db_ref[:] = a1[:]


def _call_fwd(x2, w, g, b, eps, bm, interpret):
    M, C = x2.shape
    nm = pl.cdiv(M, bm)
    return pl.pallas_call(
        partial(_bn_fwd_kernel, eps=eps, m_total=M, block_m=bm),
        grid=(2, nm),
        in_specs=[
            pl.BlockSpec((bm, C), lambda p, i: (i, 0)),
            pl.BlockSpec((bm, 1), lambda p, i: (i, 0)),
            pl.BlockSpec((1, C), lambda p, i: (0, 0)),
            pl.BlockSpec((1, C), lambda p, i: (0, 0)),
        ],
        out_specs=[
            pl.BlockSpec((bm, C), lambda p, i: (i, 0)),
            pl.BlockSpec((8, C), lambda p, i: (0, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((M, C), x2.dtype),
            jax.ShapeDtypeStruct((8, C), jnp.float32),  # mean/inv/n (+pad)
        ],
        scratch_shapes=[_vmem((1, C)), _vmem((1, C)), _vmem((1, 1))],
        interpret=interpret,
        name="masked_bn_fwd",
    )(x2, w, g.reshape(1, C), b.reshape(1, C))


def _call_bwd(x2, w, g, dy, stats, bm, interpret):
    M, C = x2.shape
    nm = pl.cdiv(M, bm)
    return pl.pallas_call(
        partial(_bn_bwd_kernel, m_total=M, block_m=bm),
        grid=(2, nm),
        in_specs=[
            pl.BlockSpec((bm, C), lambda p, i: (i, 0)),
            pl.BlockSpec((bm, 1), lambda p, i: (i, 0)),
            pl.BlockSpec((1, C), lambda p, i: (0, 0)),
            pl.BlockSpec((bm, C), lambda p, i: (i, 0)),
            pl.BlockSpec((8, C), lambda p, i: (0, 0)),
        ],
        out_specs=[
            pl.BlockSpec((bm, C), lambda p, i: (i, 0)),
            pl.BlockSpec((1, C), lambda p, i: (0, 0)),
            pl.BlockSpec((1, C), lambda p, i: (0, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((M, C), x2.dtype),
            jax.ShapeDtypeStruct((1, C), jnp.float32),
            jax.ShapeDtypeStruct((1, C), jnp.float32),
        ],
        scratch_shapes=[_vmem((1, C)), _vmem((1, C))],
        interpret=interpret,
        name="masked_bn_bwd",
    )(x2, w, g.reshape(1, C), dy, stats)


@partial(jax.custom_vjp, nondiff_argnums=(4, 5, 6))
def _bn2d(x2, w, g, b, eps, bm, interpret):
    y, _ = _call_fwd(x2, w, g, b, eps, bm, interpret)
    return y


def _bn2d_fwd(x2, w, g, b, eps, bm, interpret):
    y, stats = _call_fwd(x2, w, g, b, eps, bm, interpret)
    return y, (x2, w, g, stats)


def _bn2d_bwd(eps, bm, interpret, res, dy):
    x2, w, g, stats = res
    dx, dg, db = _call_bwd(x2, w, g, dy, stats, bm, interpret)
    return dx, jnp.zeros_like(w), dg.reshape(g.shape), db.reshape(g.shape)


_bn2d.defvjp(_bn2d_fwd, _bn2d_bwd)


@scoped("norm")
def batch_norm_pallas(x: jnp.ndarray, g: jnp.ndarray, b: jnp.ndarray,
                      sample_weight: Optional[jnp.ndarray] = None,
                      eps: float = 1e-5, block_m: int = 2048,
                      interpret: Optional[bool] = None) -> jnp.ndarray:
    """Fused, differentiable batch-stat normalisation of an NHWC (or NC)
    tensor.

    Semantics match ``ops.layers.batch_norm(mode='batch')``: per-channel
    weighted moments over all leading axes, biased variance, then
    ``(x - mean) * rsqrt(var + eps) * g + b``.

    ``interpret=None``: real kernel on TPU, interpreter elsewhere (so the
    same model code runs on the CPU test mesh).
    """
    if interpret is None:
        interpret = jax.default_backend() != "tpu"
    orig_shape = x.shape
    C = x.shape[-1]
    n = x.shape[0]
    x2 = x.reshape(-1, C)
    M = x2.shape[0]
    if sample_weight is None:
        w = jnp.ones((M, 1), jnp.float32)
    else:
        w = jnp.repeat(sample_weight.astype(jnp.float32), M // n).reshape(M, 1)
    bm = min(block_m, max(8, M))
    y = _bn2d(x2, w, g, b, eps, bm, interpret)
    return y.reshape(orig_shape)
