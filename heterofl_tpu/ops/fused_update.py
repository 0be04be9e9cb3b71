"""Fused masked-SGD optimizer epilogue + flat scan carry for the hot step.

The local-step tail of both round engines (``parallel/round_engine.py``,
``_local_train_vision``/``_local_train_lm``) is a chain of elementwise ops
executed every local step: grad mean-normalise, width ``param_mask``
multiply, ``clip_by_global_norm``, the SGD momentum / weight-decay update,
and (vision) the two ``has``-gated ``jnp.where`` tree_maps that skip
all-padding batches -- all PER LEAF, with the ``lax.scan`` carrying every
param/momentum leaf separately.  This module holds the other form: the
tail as one primitive over ONE flattened-tree buffer.

What the chip said about the two (TPU v5e, the benchmark's cells; ledger,
PR 26): the idea here was that the round is per-step-latency-bound
("~20 ms/step", MEASUREMENTS.md, an older tree) and that every kernel
taken out of the scan body is time won.  Measured, the flat carry COSTS
the step: flatten / pack / unpack / unflatten (``carry_ms.step``) were
60.0 of the 78.6 ms ResNet-18 step and 107.9 of the LM's 129.4, the Pallas
kernel itself 8.2 / 14.0 ms at 328-330 GB/s (40 % of 819 GB/s), the model
8.7 / 3.7.  A leaf view of a 1-D buffer is a copy on a tiled layout, a
``[rows, 128]`` reshape with rows no multiple of 8 is a relayout, and the
``vmap`` over ten client slots pads the kernel's slot axis to 16.  So
since PR 27 ``cfg['fused_update']: True`` resolves on a TPU to the tree
carry with the per-leaf chain (:func:`resolve_fused_mode`), and the forms
below run where they are asked for by name, and off the TPU:

* :class:`FlatSpec` packs a param tree into a single contiguous f32 vector
  (row-major leaf order; each leaf a contiguous segment; a per-leaf view is
  a slice + reshape -- free on XLA:CPU, a copy per leaf per step on the
  TPU's tiled layouts).  The engines carry ``(params_flat, momentum_flat)``
  through the scan -- the carry tuple shrinks from O(leaves) to O(1)
  buffers, and the model fwd/bwd sees ordinary leaf views unflattened
  inside the step.  Its once-a-round users (the wire codecs, the scheduler's
  buffer, ``ops/quant.py``, ``staticcheck/audit.py``) are outside the scan.
* ``'xla'`` (what ``True`` resolves to off-TPU): every numeric op of the
  epilogue stays PER-LEAF -- literally the reference chain's ops on the
  reference chain's arrays (a reduce over a flat-buffer view and a
  flat-concat elementwise tail were both measured to lower with a
  different association/contraction on XLA:CPU) -- and only the carry is
  flat (no gain from it was ever measured on a chip).  Bit-identity vs the
  reference chain is proven by tests for the full engine matrix at the
  repo's standard
  test config (conv + transformer; masked x replicated/sharded, grouped
  x span/slices, K in {1, 8}, with/without the eval mask).  On much
  deeper bodies (ResNet-18: 56 leaves, ~400 fusions/step) XLA's global
  fusion choices shift reduce emission by 1 ulp somewhere in the loop
  body, which SGD then amplifies chaotically -- a single local step is
  still bitwise exact (pinned by test), multi-round trajectories agree
  the way the masked-vs-sliced engines do (float association level).
* ``'pallas'`` (only by name since PR 27): a flattened-tree Pallas
  TPU kernel over the lane-packed ``[rows, 128]`` reshape -- phase 0
  accumulates the global-norm sum of squares in an SMEM scalar (the
  two-phase reduction), phase 1 is the single elementwise update pass.
  The step's three scalars (denom, lr, has) ride in a ``(1, 3)`` SMEM
  operand: Mosaic stores no scalar to VMEM, and the 2-D shape is what
  lets ``vmap`` over client slots window it per slot.
  Elementwise bits match the reference chain exactly; the norm reduction
  is associated per block instead of per leaf, so when clipping actually
  engages the scale may differ in the last ulp (tests pin bit-identity in
  the no-clip regime and value agreement under clipping).

Only SGD (momentum + weight decay, the optimizer every federated reference
config uses) is fused; other optimizers keep the reference chain.
"""

from __future__ import annotations

from functools import partial
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp

from ..obs.trace import scope


#: lane width of the flattened-tree packing (TPU vector lane count)
LANE = 128


def resolve_fused_mode(cfg: Dict[str, Any]) -> Optional[str]:
    """Map ``cfg['fused_update']`` to an implementation name or None.

    ``True`` (the default) resolves by the backend the program is compiled
    for: on TPU to None -- the tree carry with the per-leaf reference chain,
    the leaves where the model reads them and the gradients arrive (the
    module docstring has what the flat carry cost on the v5e) -- and to the
    flat-carry XLA form elsewhere.
    ``False`` keeps the reference op chain on every backend; ``"xla"`` /
    ``"pallas"`` force a flat-carry implementation.  Non-SGD optimizers
    always keep the reference chain (the fused primitive implements exactly
    torch-parity SGD momentum + weight decay).
    """
    fu = cfg.get("fused_update", True)
    if not fu or cfg.get("optimizer_name") != "SGD":
        return None
    if fu is True:
        return None if jax.default_backend() == "tpu" else "xla"
    if fu in ("xla", "pallas"):
        return fu
    raise ValueError(f"Not valid fused_update: {fu!r} "
                     f"(use True/False/'xla'/'pallas')")


class FlatSpec:
    """Static packing of a ``{name: array}`` tree into one flat f32 vector.

    Leaf order is sorted-key order -- the same order jax flattens a dict,
    hence the same leaf order ``clip_by_global_norm`` reduces in, which is
    what keeps the fused norm bit-compatible with the reference chain.
    Instances are trace-time constants (shapes only)."""

    def __init__(self, shapes: Dict[str, Tuple[int, ...]]):
        self.names = sorted(shapes)
        self.shapes = {k: tuple(shapes[k]) for k in self.names}
        self.sizes = {}
        self.offsets = {}
        off = 0
        for k in self.names:
            sz = 1
            for d in self.shapes[k]:
                sz *= d
            self.sizes[k] = sz
            self.offsets[k] = off
            off += sz
        self.total = off

    @classmethod
    def of(cls, tree: Dict[str, jnp.ndarray]) -> "FlatSpec":
        return cls({k: v.shape for k, v in tree.items()})

    def flatten(self, tree: Dict[str, jnp.ndarray]) -> jnp.ndarray:
        return jnp.concatenate(
            [jnp.ravel(tree[k]).astype(jnp.float32) for k in self.names])

    def unflatten(self, flat: jnp.ndarray) -> Dict[str, jnp.ndarray]:
        return {k: self.leaf(flat, k) for k in self.names}

    def leaf(self, flat: jnp.ndarray, k: str) -> jnp.ndarray:
        off = self.offsets[k]
        return flat[off:off + self.sizes[k]].reshape(self.shapes[k])


# ---------------------------------------------------------------------------
# the XLA fallback: per-leaf norm terms + one flat elementwise chain
# ---------------------------------------------------------------------------

def _xla_flat(spec, pf, grads, bf, masks, denom, lr, momentum, wd, max_norm,
              has):
    from ..utils.optim import clip_by_global_norm

    # every numeric op stays PER-LEAF -- literally the reference chain's
    # ops on the reference chain's arrays, so the whole update is the same
    # f32 bit pattern by construction (both a reduce over a flat-buffer
    # view and a flat-concat elementwise tail were measured to lower with
    # different association/contraction on XLA:CPU); only the CARRY is flat
    # (O(1) loop-carried buffers instead of O(leaves); leaf views in, one
    # flatten out -- copies on the chip, see the module docstring)
    with scope("update/kernel"):  # the per-leaf chain stands in for the kernel
        pt, bt = spec.unflatten(pf), spec.unflatten(bf)
        gm = {k: (grads[k] / denom) * masks[k] for k in spec.names}
        gm, _ = clip_by_global_norm(gm, max_norm)
        nb = {k: momentum * bt[k] + gm[k] + wd * pt[k] for k in spec.names}
        np_ = {k: pt[k] - lr * nb[k] for k in spec.names}
        if has is not None:
            np_ = {k: jnp.where(has, np_[k], pt[k]) for k in spec.names}
            nb = {k: jnp.where(has, nb[k], bt[k]) for k in spec.names}
    with scope("update/flatten"):
        return spec.flatten(np_), spec.flatten(nb)


# ---------------------------------------------------------------------------
# the Pallas TPU kernel: two-phase norm reduction + one elementwise pass
# ---------------------------------------------------------------------------

def _fused_sgd_kernel(g_ref, p_ref, b_ref, m_ref, s_ref, p_out, b_out, acc,
                      *, momentum: float, wd: float, max_norm: float,
                      rows_total: int, block_rows: int):
    from jax.experimental import pallas as pl

    phase, i = pl.program_id(0), pl.program_id(1)

    @pl.when(jnp.logical_and(phase == 0, i == 0))
    def _():
        acc[0] = 0.0

    # block-padding rows may hold undefined VMEM: `where` them out, never
    # multiply (the pallas_norm.py lesson)
    row = jax.lax.broadcasted_iota(jnp.int32, (block_rows, 1), 0) \
        + i * block_rows
    rowmask = row < rows_total
    denom, lr, has = s_ref[0, 0], s_ref[0, 1], s_ref[0, 2]
    gm = jnp.where(rowmask, (g_ref[:] / denom) * m_ref[:], 0.0)

    @pl.when(phase == 0)
    def _():
        acc[0] += jnp.sum(gm * gm)

    @pl.when(phase == 1)
    def _():
        total = jnp.sqrt(acc[0])
        scale = jnp.minimum(1.0, max_norm / (total + 1e-6))
        pv = jnp.where(rowmask, p_ref[:], 0.0)
        bv = jnp.where(rowmask, b_ref[:], 0.0)
        nb = momentum * bv + gm * scale + wd * pv
        pn = pv - lr * nb
        keep = has > 0.0
        p_out[:] = jnp.where(keep, pn, pv)
        b_out[:] = jnp.where(keep, nb, bv)


def _pallas_flat(spec, pf, grads, bf, masks, denom, lr, momentum, wd,
                 max_norm, has, block_rows, interpret):
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    with scope("update/flatten"):
        gf, mf = spec.flatten(grads), spec.flatten(masks)
    rows = -(-spec.total // LANE)
    pad = rows * LANE - spec.total

    def pack(flat):
        if pad:
            flat = jnp.concatenate([flat, jnp.zeros(pad, jnp.float32)])
        return flat.reshape(rows, LANE)

    with scope("update/pack"):
        has_val = jnp.float32(1.0) if has is None else has.astype(jnp.float32)
        scal = jnp.stack([denom, lr.astype(jnp.float32), has_val]).reshape(1, 3)
        packed = (pack(gf), pack(pf), pack(bf), pack(mf))
    bm = min(block_rows, max(1, rows))
    nm = pl.cdiv(rows, bm)
    kernel = pl.pallas_call(
        partial(_fused_sgd_kernel, momentum=momentum, wd=wd,
                max_norm=max_norm, rows_total=rows, block_rows=bm),
        grid=(2, nm),
        in_specs=[
            pl.BlockSpec((bm, LANE), lambda p, i: (i, 0)),
            pl.BlockSpec((bm, LANE), lambda p, i: (i, 0)),
            pl.BlockSpec((bm, LANE), lambda p, i: (i, 0)),
            pl.BlockSpec((bm, LANE), lambda p, i: (i, 0)),
            pl.BlockSpec(memory_space=pltpu.SMEM),
        ],
        out_specs=[
            pl.BlockSpec((bm, LANE), lambda p, i: (i, 0)),
            pl.BlockSpec((bm, LANE), lambda p, i: (i, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((rows, LANE), jnp.float32),
            jax.ShapeDtypeStruct((rows, LANE), jnp.float32),
        ],
        scratch_shapes=[pltpu.SMEM((1,), jnp.float32)],
        interpret=interpret,
        name="fused_sgd",
    )
    with scope("update/kernel"):
        p2, b2 = kernel(*packed, scal)
    with scope("update/unpack"):
        return p2.reshape(-1)[:spec.total], b2.reshape(-1)[:spec.total]


# ---------------------------------------------------------------------------
# entry points
# ---------------------------------------------------------------------------

def fused_sgd_flat(spec: FlatSpec, p_flat, grads: Dict[str, jnp.ndarray],
                   b_flat, masks: Dict[str, jnp.ndarray],
                   n_glob, lr, *, momentum: float, weight_decay: float,
                   max_norm: float = 1.0, has=None, mode: str = "xla",
                   block_rows: int = 256, interpret: Optional[bool] = None
                   ) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """One fused masked-SGD step over the flat carry:
    ``(new_params_flat, new_momentum_flat)``.

    ``p_flat``/``b_flat`` are the packed carry buffers; ``grads``/``masks``
    stay trees (grads are differentiated per-leaf so the norm terms reduce
    over the same arrays, in the same order, as the reference chain).
    Semantics are exactly the reference op chain over the packed tree::

        g   = (g / max(n_glob, 1e-6)) * mask          # mean-normalise+mask
        g   = g * min(1, 1 / (||g||_2 + 1e-6))        # clip_by_global_norm
        buf = momentum * buf + g + weight_decay * p   # torch SGD
        p   = p - lr * buf
        p, buf = where(has, new, old)                 # all-padding skip

    ``has=None`` skips the gating (the LM path).  ``mode``: 'xla' or
    'pallas'; ``interpret=None`` runs the real kernel on TPU and the
    interpreter elsewhere (the CPU test mesh).
    """
    # staticcheck: allow(no-asarray): traced-value dtype coercion inside the
    # jitted step (n_glob/lr are already on device; no host wrap happens)
    denom = jnp.maximum(jnp.asarray(n_glob, jnp.float32), 1e-6)
    lr = jnp.asarray(lr, jnp.float32)  # staticcheck: allow(no-asarray): traced dtype coercion
    if mode == "xla":
        return _xla_flat(spec, p_flat, grads, b_flat, masks, denom, lr,
                         momentum, weight_decay, max_norm, has)
    if mode == "pallas":
        if interpret is None:
            interpret = jax.default_backend() != "tpu"
        return _pallas_flat(spec, p_flat, grads, b_flat, masks, denom, lr,
                            momentum, weight_decay, max_norm, has,
                            block_rows, interpret)
    raise ValueError(f"Not valid fused-update mode: {mode!r}")


def masked_sgd_step(params: Dict[str, jnp.ndarray],
                    grads: Dict[str, jnp.ndarray],
                    bufs: Dict[str, jnp.ndarray],
                    masks: Dict[str, jnp.ndarray],
                    n_glob, lr, *, momentum: float, weight_decay: float,
                    max_norm: float = 1.0, has=None, mode: str = "xla",
                    block_rows: int = 256,
                    interpret: Optional[bool] = None
                    ) -> Tuple[Dict[str, jnp.ndarray], Dict[str, jnp.ndarray]]:
    """Tree-level wrapper of :func:`fused_sgd_flat` (kernel unit tests and
    one-off callers; the engines keep the flat buffers in the scan carry
    and call the flat form directly)."""
    spec = FlatSpec.of(params)
    np_, nb = fused_sgd_flat(
        spec, spec.flatten(params), grads, spec.flatten(bufs), masks,
        n_glob, lr, momentum=momentum, weight_decay=weight_decay,
        max_norm=max_norm, has=has, mode=mode, block_rows=block_rows,
        interpret=interpret)
    return spec.unflatten(np_), spec.unflatten(nb)
