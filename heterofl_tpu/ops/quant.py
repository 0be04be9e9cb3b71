"""Quantisation kernels for the wire codecs (ISSUE 8).

Integer *lane packing* is what turns "int8 quantisation" into actual wire
bytes under the one-psum contract: each device packs its quantised values
into the sub-fields of int32 words, the ONE global psum adds the words,
and because every lane is sized so the cross-device lane sums cannot
produce a carry, word addition IS independent per-lane integer
accumulation -- "int8 on the wire, int32 in the accumulator".  The psum
operand aval (``int32[ceil(N/lanes_per_word)]``) is then literally the
compressed payload, which is what lets ``staticcheck/wire.py`` price the
compressed round by equality exactly like the dense one.

The quantise+pack hot pass also has a Pallas TPU fast path over
``ops/flatspec.py``'s flat-tree layout: one kernel over the
lane-packed ``[rows, 128]`` reshape fuses scale/noise/clip/round and the
4-lane pack into a single VMEM pass (off-TPU it runs in interpreter mode
for tests; the XLA path is the default elsewhere and is bit-identical by
construction -- both are pure integer/float elementwise chains).
"""

from __future__ import annotations

from functools import partial
from typing import Optional

import jax
import jax.numpy as jnp

from .flatspec import LANE


def pack_lanes(q: jnp.ndarray, lane_bits: int) -> jnp.ndarray:
    """Pack flat int32 values ``q`` (each in ``[0, 2**lane_bits)``) into
    int32 words, ``32 // lane_bits`` consecutive values per word (flat
    order preserved; tail padded with zero lanes)."""
    per = 32 // lane_bits
    n = q.shape[0]
    pad = (-n) % per
    if pad:
        q = jnp.concatenate([q, jnp.zeros(pad, jnp.int32)])
    q = q.reshape(-1, per)
    w = q[:, 0]
    for i in range(1, per):
        w = jnp.bitwise_or(w, jnp.left_shift(q[:, i], i * lane_bits))
    return w


def unpack_lanes(w: jnp.ndarray, lane_bits: int, n: int) -> jnp.ndarray:
    """Inverse of :func:`pack_lanes` on (possibly psum-accumulated) words:
    returns the first ``n`` int32 lane values.  The arithmetic right shift
    sign-fills on a negative top lane; the mask strips the fill, so lane
    extraction is exact as long as no cross-device lane sum overflowed its
    ``lane_bits`` (the codecs size their lanes to guarantee that)."""
    per = 32 // lane_bits
    mask = (1 << lane_bits) - 1
    cols = [jnp.bitwise_and(jnp.right_shift(w, i * lane_bits), mask)
            for i in range(per)]
    return jnp.stack(cols, axis=1).reshape(-1)[:n]


def stochastic_round(x: jnp.ndarray, key: jax.Array) -> jnp.ndarray:
    """Unbiased stochastic rounding: ``floor(x + U[0,1))`` -- E[result] = x.
    The quantisation primitive of the int8 codec (deterministic rounding
    would bias every round the same way; with error feedback the stochastic
    form keeps the per-round bias zero-mean)."""
    return jnp.floor(x + jax.random.uniform(key, x.shape, jnp.float32))


# ---------------------------------------------------------------------------
# fused quantise + 4-lane pack (the int8 codec's hot pass)
# ---------------------------------------------------------------------------

def _quant_pack_xla(x, scale, key, qmax: int, bias: int):
    q = stochastic_round(x / scale, key)
    q = jnp.clip(q, -qmax, qmax).astype(jnp.int32)
    return pack_lanes(q + bias, 8), q


def _quant_pack_kernel(x_ref, s_ref, u_ref, w_out, q_out, *, qmax: int,
                       bias: int):
    # one pass: scale -> stochastic round -> clip -> bias -> 4-lane pack.
    # Word j of a row packs lanes 4j..4j+3 (flat order, so the words match
    # pack_lanes exactly).  Mosaic has no [bm, 128] -> [bm, 32, 4] shape
    # cast, so the lane de-interleave is four one-hot selection matmuls on
    # the MXU: every operand is an integer below 2**8, exact at any matmul
    # precision.
    q = jnp.clip(jnp.floor(x_ref[:] / s_ref[:] + u_ref[:]),
                 -qmax, qmax).astype(jnp.int32)
    q_out[:] = q
    qb = (q + bias).astype(jnp.float32)
    src = jax.lax.broadcasted_iota(jnp.int32, (LANE, LANE // 4), 0)
    word = jax.lax.broadcasted_iota(jnp.int32, (LANE, LANE // 4), 1)
    w = None
    for i in range(4):
        sel = (src == 4 * word + i).astype(jnp.float32)
        lane = jnp.dot(qb, sel,
                       preferred_element_type=jnp.float32).astype(jnp.int32)
        w = lane if w is None else jnp.bitwise_or(
            w, jnp.left_shift(lane, i * 8))
    w_out[:] = w


def _quant_pack_pallas(x, scale, key, qmax: int, bias: int, block_rows: int,
                       interpret: Optional[bool]):
    from jax.experimental import pallas as pl

    if interpret is None:
        interpret = jax.default_backend() != "tpu"
    n = x.shape[0]
    rows = -(-n // LANE)
    pad = rows * LANE - n

    def pack2d(flat, fill=0.0):
        if pad:
            flat = jnp.concatenate(
                [flat, jnp.full(pad, fill, flat.dtype)])
        return flat.reshape(rows, LANE)

    u = jax.random.uniform(key, (n,), jnp.float32)
    bm = min(block_rows, max(1, rows))
    nm = pl.cdiv(rows, bm)
    # padding lanes divide by scale fill 1.0 and quantise x=0 -> q=0, so the
    # packed tail words beyond ceil(n/4) are sliced off below and the lane
    # values within them never reach the decoder
    w2, q2 = pl.pallas_call(
        partial(_quant_pack_kernel, qmax=qmax, bias=bias),
        grid=(nm,),
        in_specs=[pl.BlockSpec((bm, LANE), lambda i: (i, 0))] * 3,
        out_specs=[pl.BlockSpec((bm, LANE // 4), lambda i: (i, 0)),
                   pl.BlockSpec((bm, LANE), lambda i: (i, 0))],
        out_shape=[jax.ShapeDtypeStruct((rows, LANE // 4), jnp.int32),
                   jax.ShapeDtypeStruct((rows, LANE), jnp.int32)],
        interpret=interpret,
        name="int8_pack",
    )(pack2d(x), pack2d(scale, fill=1.0), pack2d(u))
    words = -(-n // 4)
    return w2.reshape(-1)[:words], q2.reshape(-1)[:n]


def quantize_pack(x: jnp.ndarray, scale: jnp.ndarray, key: jax.Array,
                  qmax: int, bias: int, mode: str = "xla",
                  block_rows: int = 256,
                  interpret: Optional[bool] = None):
    """Stochastic-round ``x / scale`` onto ``[-qmax, qmax]``, bias to
    unsigned, and pack 4 values per int32 word (8-bit lanes).  Returns
    ``(packed_words, q)`` -- ``q`` is the signed quantised grid value the
    encoder needs locally for the error-feedback residual.  ``mode``:
    'xla' (default off-TPU) or 'pallas' (the fused single-pass kernel)."""
    if mode == "xla":
        return _quant_pack_xla(x, scale, key, qmax, bias)
    if mode == "pallas":
        return _quant_pack_pallas(x, scale, key, qmax, bias, block_rows,
                                  interpret)
    raise ValueError(f"Not valid quantize_pack mode: {mode!r}")
