"""The jax wire-codec implementations (ISSUE 8; see package docstring).

Every codec transforms one device's partial aggregation contribution --
the flat ``(update sums, count masks)`` pair in the
:class:`~..ops.flatspec.FlatSpec` layout -- into a payload pytree that
rides ONE ``jax.lax.psum`` bind, then decodes the accumulated payload back
to flat sums/counts.  The contract every codec must keep:

* **one bind**: the whole payload is a single psum (a pytree psum is one
  bind); nothing else crosses the wire.
* **shared decode context**: anything the decoder needs that is not in the
  payload (quantisation grids, block offsets) must be derived from values
  every device already holds identically -- the replicated params carry
  and the round key -- so no side-channel collective is ever needed.
* **local own-decode**: the encoder can compute what the decoder will
  attribute to THIS device, which is what the error-feedback residual
  subtracts (e' = (x + e) - decode(encode(x + e))); with
  ``error_feedback=False`` the residual stays zero and the compression
  error is simply dropped (the A/B the convergence contract tests).

Lossy-codec trajectories depend on the mesh shape (per-device partials are
what gets quantised) and on the program's static slot layout (``cmax`` --
the per-device client bound -- sizes the shared quantisation grid, so two
dispatch granularities agree bitwise only when their slot layouts match)
-- unlike ``dense``, which stays bit-identical to the pre-codec engines
everywhere.
"""

from __future__ import annotations

from typing import Dict, Tuple

import jax
import jax.numpy as jnp

from . import (COUNT_LANE_BITS, SIGN_LANE_BITS, TOPK_BLOCKS, VALUE_LANE_BITS,
               codec_payload_bytes, lane_words, resid_slots)
from ..ops.quant import pack_lanes, quantize_pack, unpack_lanes

#: PRNG salts of the codec streams (disjoint from the engines' 13/98 and
#: the rate/user salts in fed.core)
QUANT_NOISE_SALT = 9173
TOPK_BLOCK_SALT = 9177


class WireCodec:
    """Shared scaffolding: spec, participant count, lane-capacity guards."""

    name = "?"

    def __init__(self, spec, participants: int, error_feedback: bool = True,
                 axis: str = "clients"):
        self.spec = spec
        self.p = int(participants)
        self.ef = bool(error_feedback)
        self.axis = axis
        self.resid_slots = resid_slots(self.name)

    def payload_bytes(self) -> int:
        return codec_payload_bytes(self.name, self.spec.total,
                                   len(self.spec.names))

    def _leaf_expand(self, per_leaf: jnp.ndarray) -> jnp.ndarray:
        """[n_leaves] -> flat [total] (each leaf's scalar broadcast over its
        segment of the flat layout)."""
        return jnp.concatenate([
            jnp.broadcast_to(per_leaf[i], (self.spec.sizes[k],))
            for i, k in enumerate(self.spec.names)])

    def _device_key(self, key: jax.Array, salt: int) -> jax.Array:
        """Per-device codec key: decorrelates stochastic rounding across
        participants (inside shard_map) while staying deterministic."""
        k = jax.random.fold_in(key, salt)
        if self.axis is not None:
            k = jax.random.fold_in(k, jax.lax.axis_index(self.axis))
        return k

    def zero_payload(self):
        """The codec's IDENTITY payload: what a non-participating device
        ships into the shared psum bind so the accumulated payload decodes
        as if that device contributed nothing.  All-zero for every codec
        -- int8 lanes carry ``+bias`` per PARTICIPANT and the decoder
        subtracts ``participants x bias``, signsgd's decode subtracts
        ``participants`` from the doubled positive count, and topk/dense
        ship raw values -- PROVIDED the codec was constructed with
        ``participants`` = the devices that actually encode (the grouped
        ``slices`` per-level layout, ISSUE 14 satellite: each level's
        codec counts its slice rows, every other row ships this)."""
        raise NotImplementedError

    def _check_count_capacity(self, cmax: int, lane_bits: int) -> None:
        """Counts ride exact integer lanes: the cross-device lane sum (at
        most participants x per-device clients) must fit ``lane_bits``."""
        if self.p * cmax > (1 << lane_bits) - 1:
            raise ValueError(
                f"wire codec {self.name!r}: count lanes overflow -- "
                f"{self.p} participants x {cmax} clients/device exceeds the "
                f"{lane_bits}-bit lane capacity {(1 << lane_bits) - 1}; "
                f"shrink the per-round cohort or use the dense codec")


class Int8Codec(WireCodec):
    """Per-leaf stochastic-rounding quantisation, int32 psum accumulation.

    Each value is rounded onto a shared per-leaf grid whose scale derives
    from the replicated params carry (``cmax x max|p_leaf|`` bounds the
    magnitude of a partial sum of ``cmax`` clipped sub-models), written
    into an 8-bit lane with enough headroom that the sum over all
    ``participants`` lanes cannot carry -- so the word-wise int32 psum IS
    exact per-lane integer accumulation.  Out-of-range values clip; the
    clip error joins the rounding error in the residual.  Counts are small
    integers and ride their own 8-bit lanes LOSSLESSLY.
    """

    name = "int8"

    def __init__(self, spec, participants, error_feedback=True,
                 axis="clients", mode=None):
        super().__init__(spec, participants, error_feedback, axis)
        # per-device grid: 8-bit lanes keep ceil(log2 p) headroom bits for
        # the cross-device sum, the rest are quantisation levels
        head = (self.p - 1).bit_length()
        if VALUE_LANE_BITS - head < 2:
            raise ValueError(
                f"int8 wire codec supports at most "
                f"{1 << (VALUE_LANE_BITS - 2)} participants on the "
                f"reduction axis (got {self.p}): fewer than 4 quantisation "
                f"levels would remain per lane")
        self.levels = 1 << (VALUE_LANE_BITS - head)
        self.bias = self.levels // 2
        self.qmax = self.bias - 1
        if mode is None:
            mode = "pallas" if jax.default_backend() == "tpu" else "xla"
        self.mode = mode

    def zero_payload(self):
        n = self.spec.total
        return {"q": jnp.zeros(lane_words(n, VALUE_LANE_BITS), jnp.int32),
                "c": jnp.zeros(lane_words(n, COUNT_LANE_BITS), jnp.int32)}

    def _scale_flat(self, params: Dict[str, jnp.ndarray],
                    cmax: int) -> jnp.ndarray:
        per_leaf = jnp.stack([jnp.max(jnp.abs(params[k]))
                              for k in self.spec.names])
        return self._leaf_expand((cmax * per_leaf + 1e-3) / self.qmax)

    def encode(self, sums, cnts, resid, params, key, cmax: int):
        self._check_count_capacity(cmax, COUNT_LANE_BITS)
        s = self._scale_flat(params, cmax)
        x = sums + resid[0] if self.ef else sums
        words, q = quantize_pack(x, s, self._device_key(key, QUANT_NOISE_SALT),
                                 self.qmax, self.bias, mode=self.mode)
        new_resid = (x - q.astype(jnp.float32) * s)[None] if self.ef \
            else jnp.zeros_like(resid)
        payload = {"q": words,
                   "c": pack_lanes(jnp.round(cnts).astype(jnp.int32),
                                   COUNT_LANE_BITS)}
        return payload, new_resid

    def decode(self, agg, params, key, cmax: int):
        s = self._scale_flat(params, cmax)
        qsum = unpack_lanes(agg["q"], VALUE_LANE_BITS, self.spec.total) \
            - self.p * self.bias
        sums = qsum.astype(jnp.float32) * s
        cnts = unpack_lanes(agg["c"], COUNT_LANE_BITS,
                            self.spec.total).astype(jnp.float32)
        return sums, cnts


class SignSGDCodec(WireCodec):
    """1-bit signs with a per-leaf scale, EF-signSGD style.

    Each device sends one sign bit per element (4-bit lanes, so up to 15
    participants can accumulate without carries) plus its per-leaf mean
    magnitude as a tiny f32 vector IN THE SAME psum bind; the decoder
    reconstructs ``mean_scale x (positives - negatives)``.  The residual
    uses the device's OWN scale (what the mean attributes to it in
    expectation) -- the standard EF-signSGD approximation.
    """

    name = "signsgd"

    def __init__(self, spec, participants, error_feedback=True,
                 axis="clients"):
        super().__init__(spec, participants, error_feedback, axis)
        if self.p > (1 << SIGN_LANE_BITS) - 1:
            raise ValueError(
                f"signsgd wire codec supports at most "
                f"{(1 << SIGN_LANE_BITS) - 1} participants on the reduction "
                f"axis (got {self.p}): the sign lanes would carry")

    def zero_payload(self):
        n = self.spec.total
        return {"b": jnp.zeros(lane_words(n, SIGN_LANE_BITS), jnp.int32),
                "s": jnp.zeros(len(self.spec.names), jnp.float32),
                "c": jnp.zeros(lane_words(n, COUNT_LANE_BITS), jnp.int32)}

    def _leaf_means(self, x: jnp.ndarray) -> jnp.ndarray:
        ax = jnp.abs(x)
        return jnp.stack([
            jnp.mean(jax.lax.dynamic_slice(ax, (self.spec.offsets[k],),
                                           (self.spec.sizes[k],)))
            for k in self.spec.names])

    def encode(self, sums, cnts, resid, params, key, cmax: int):
        self._check_count_capacity(cmax, COUNT_LANE_BITS)
        x = sums + resid[0] if self.ef else sums
        s_leaf = self._leaf_means(x)
        s_flat = self._leaf_expand(s_leaf)
        pos = (x >= 0)
        new_resid = (x - jnp.where(pos, s_flat, -s_flat))[None] if self.ef \
            else jnp.zeros_like(resid)
        payload = {"b": pack_lanes(pos.astype(jnp.int32), SIGN_LANE_BITS),
                   "s": s_leaf,
                   "c": pack_lanes(jnp.round(cnts).astype(jnp.int32),
                                   COUNT_LANE_BITS)}
        return payload, new_resid

    def decode(self, agg, params, key, cmax: int):
        npos = unpack_lanes(agg["b"], SIGN_LANE_BITS,
                            self.spec.total).astype(jnp.float32)
        sbar = self._leaf_expand(agg["s"] / self.p)
        sums = sbar * (2.0 * npos - self.p)
        cnts = unpack_lanes(agg["c"], COUNT_LANE_BITS,
                            self.spec.total).astype(jnp.float32)
        return sums, cnts


class TopKCodec(WireCodec):
    """Rotating-block sparsification riding the flat width-mask layout.

    The flat update splits into :data:`~.TOPK_BLOCKS` contiguous blocks;
    each round ships ONE block -- index drawn from the round key, so every
    device (and the decoder) picks the same block with no index exchange
    -- as raw f32 values AND counts.  Both residual slots accumulate the
    unsent blocks, so when a block finally ships it carries matching
    multi-round sums and counts (the combine's sum/count stays a mean);
    coordinates outside the block contribute zero count, and
    ``combine_counted``'s stale rule keeps their previous global value.
    With ``error_feedback=False`` the unsent blocks are simply dropped.
    """

    name = "topk"

    def __init__(self, spec, participants, error_feedback=True,
                 axis="clients"):
        super().__init__(spec, participants, error_feedback, axis)
        self.blocks = TOPK_BLOCKS
        if spec.total < self.blocks:
            raise ValueError(f"topk wire codec needs at least {self.blocks} "
                             f"flat elements (got {spec.total})")
        self.block_len = -(-spec.total // self.blocks)

    def zero_payload(self):
        return {"v": jnp.zeros(self.block_len, jnp.float32),
                "c": jnp.zeros(self.block_len, jnp.float32)}

    def _offset(self, key: jax.Array) -> jnp.ndarray:
        # identical on every device: derived from the (replicated) round key
        b = jax.random.randint(jax.random.fold_in(key, TOPK_BLOCK_SALT),
                               (), 0, self.blocks)
        return jnp.minimum(b * self.block_len,
                           self.spec.total - self.block_len)

    def encode(self, sums, cnts, resid, params, key, cmax: int):
        off = self._offset(key)
        k = self.block_len
        if self.ef:
            xv, xc = sums + resid[0], cnts + resid[1]
            vals = jax.lax.dynamic_slice(xv, (off,), (k,))
            cblk = jax.lax.dynamic_slice(xc, (off,), (k,))
            zero = jnp.zeros((k,), jnp.float32)
            new_resid = jnp.stack([
                jax.lax.dynamic_update_slice(xv, zero, (off,)),
                jax.lax.dynamic_update_slice(xc, zero, (off,))])
        else:
            vals = jax.lax.dynamic_slice(sums, (off,), (k,))
            cblk = jax.lax.dynamic_slice(cnts, (off,), (k,))
            new_resid = jnp.zeros_like(resid)
        return {"v": vals, "c": cblk}, new_resid

    def decode(self, agg, params, key, cmax: int):
        off = self._offset(key)
        zeros = jnp.zeros((self.spec.total,), jnp.float32)
        sums = jax.lax.dynamic_update_slice(zeros, agg["v"], (off,))
        cnts = jax.lax.dynamic_update_slice(zeros, agg["c"], (off,))
        return sums, cnts


def compressed_psum(codec: WireCodec, axis: str,
                    params: Dict[str, jnp.ndarray],
                    summed: Dict[str, jnp.ndarray],
                    counts: Dict[str, jnp.ndarray],
                    resid: jnp.ndarray, key: jax.Array, cmax: int
                    ) -> Tuple[Dict[str, jnp.ndarray],
                               Dict[str, jnp.ndarray], jnp.ndarray]:
    """quantise -> ONE global psum -> dequantise: THE compressed twin of
    the engines' ``psum((summed, counts), axis)``, used by both the masked
    round core and the grouped fused superstep.  ``resid`` is this device's
    ``[resid_slots, total]`` error-feedback carry; ``cmax`` the static
    per-device max contributing clients (it sizes the quantisation range
    and the count-lane capacity check)."""
    spec = codec.spec
    payload, new_resid = codec.encode(spec.flatten(summed),
                                      spec.flatten(counts),
                                      resid, params, key, cmax)
    agg = jax.lax.psum(payload, axis)
    sum_hat, cnt_hat = codec.decode(agg, params, key, cmax)
    return spec.unflatten(sum_hat), spec.unflatten(cnt_hat), new_resid
