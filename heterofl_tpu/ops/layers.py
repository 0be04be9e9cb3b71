"""Masked primitive layers.

These primitives make the **masked full-width** execution strategy exact: a
HeteroFL sub-model is always a *prefix* slice of the global tensors
(ref ``src/fed.py:46-48``), so running the full-width model with the suffix
channels held at zero produces bit-identical math to the sliced sub-model --
provided every op that mixes channels uses masked statistics.  Per-channel ops
(conv, BN, instance norm, ReLU, pooling) commute with zero-masking for free;
LayerNorm / GroupNorm need the active count ``k`` instead of the full width,
implemented here.

Conventions: NHWC activations, HWIO conv kernels, ``[in, out]`` linear
kernels -- the native layouts for XLA:TPU.
"""

from __future__ import annotations

import math
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
from jax import lax

from ..obs.trace import scope, scoped

#: THE conv dimension-number convention (models/layout.py re-exports it as
#: part of the explicit layout policy; one owner, two consumers)
CONV_DIMENSION_NUMBERS: Tuple[str, str, str] = ("NHWC", "HWIO", "NHWC")


@scoped("conv")
def conv2d(x: jnp.ndarray, w: jnp.ndarray, b: Optional[jnp.ndarray] = None,
           stride: int = 1, padding: int = 1,
           compute_dtype: Optional[jnp.dtype] = None,
           impl: Optional[str] = None) -> jnp.ndarray:
    """3x3/1x1 convolution, NHWC x HWIO -> NHWC.

    ``compute_dtype`` (e.g. bfloat16) casts the MXU operands while
    accumulating in float32 -- the TPU mixed-precision recipe; params stay
    float32 outside the op.

    ``impl='im2col'`` expresses the op as patch extraction + matmul.  Under
    ``vmap`` with per-client kernels (the federated round engine's hot path)
    the direct form lowers to a ``feature_group_count=clients`` grouped
    convolution whose small per-group channel counts under-tile the 128x128
    MXU; the im2col form instead keeps patch extraction a *shared-kernel*
    dense conv (vmap folds clients into the batch dim) and turns only the
    kernel application into a batched matmul, which the MXU executes
    natively.  Numerically identical (same f32 accumulation); see
    tests/test_models.py::test_conv2d_im2col_matches_direct.
    """
    if compute_dtype is not None:
        x, w = x.astype(compute_dtype), w.astype(compute_dtype)
    if impl == "im2col":
        kh, kw, cin, cout = w.shape
        if (kh, kw) == (1, 1) and padding == 0:
            # 1x1 conv IS a matmul on strided pixels; skip patch extraction
            patches = x[:, ::stride, ::stride, :]
            y = patches @ w.reshape(cin, cout)
        else:
            patches = lax.conv_general_dilated_patches(
                x, filter_shape=(kh, kw), window_strides=(stride, stride),
                padding=((padding, padding), (padding, padding)),
                dimension_numbers=CONV_DIMENSION_NUMBERS)
            # patch features are ordered (C, kh, kw); transpose w to match
            w_flat = jnp.transpose(w, (2, 0, 1, 3)).reshape(kh * kw * cin, cout)
            y = patches @ w_flat
    else:
        y = lax.conv_general_dilated(
            x, w,
            window_strides=(stride, stride),
            padding=((padding, padding), (padding, padding)),
            dimension_numbers=CONV_DIMENSION_NUMBERS,
        )
    if compute_dtype is not None:
        y = y.astype(jnp.float32)  # XLA:TPU accumulates bf16 convs in f32
    if b is not None:
        y = y + b
    return y


@scoped("linear")
def linear(x: jnp.ndarray, w: jnp.ndarray, b: Optional[jnp.ndarray] = None,
           compute_dtype: Optional[jnp.dtype] = None) -> jnp.ndarray:
    if compute_dtype is not None:
        x, w = x.astype(compute_dtype), w.astype(compute_dtype)
        y = (x @ w).astype(jnp.float32)
    else:
        y = x @ w
    if b is not None:
        y = y + b
    return y


@scoped("embed")
def embed(table: jnp.ndarray, ids: jnp.ndarray) -> jnp.ndarray:
    return jnp.take(table, ids, axis=0)


def scaler(x: jnp.ndarray, rate, train: bool) -> jnp.ndarray:
    """HeteroFL Scaler: ``x / rate`` in training, identity in eval
    (ref src/modules/modules.py:9-11)."""
    return x / rate if train else x


def max_pool2(x: jnp.ndarray) -> jnp.ndarray:
    """MaxPool2d(2) with floor semantics (torch default)."""
    return lax.reduce_window(x, -jnp.inf, lax.max, (1, 2, 2, 1), (1, 2, 2, 1), "VALID")


def global_avg_pool(x: jnp.ndarray) -> jnp.ndarray:
    """AdaptiveAvgPool2d(1) + flatten: NHWC -> NC."""
    return jnp.mean(x, axis=(1, 2))


@scoped("norm")
def batch_norm(x: jnp.ndarray, g: jnp.ndarray, b: jnp.ndarray, *,
               mode: str = "batch",
               running: Optional[Tuple[jnp.ndarray, jnp.ndarray]] = None,
               sample_weight: Optional[jnp.ndarray] = None,
               eps: float = 1e-5,
               axis_name=None):
    """Static batch norm (momentum=None, per-channel) for NHWC or NC inputs.

    Parity: ``nn.BatchNorm2d(C, momentum=None, track_running_stats=track)``
    (ref models/conv.py:14).  ``mode``:

    * ``"batch"``   -- normalise with batch statistics (training, and eval of a
      ``track=False`` model, which torch also normalises with batch stats).
    * ``"running"`` -- normalise with provided ``running = (mean, var)``
      (eval after sBN recalibration).
    * ``"collect"`` -- like ``"batch"`` but also return
      ``(batch_mean, batch_var_unbiased)`` for cumulative-average
      recalibration (momentum=None => CMA, ref SURVEY §5.4).

    ``sample_weight``: optional ``[N]`` 0/1 weights so padded examples do not
    pollute the statistics (the reference's final partial batch has exact
    semantics; we pad + mask instead).

    ``axis_name``: synchronised BN -- batch statistics are reduced with
    ``psum`` across that mesh axis, so a batch sharded over devices sees
    exactly the full-batch statistics (needed for intra-client batch DP to be
    numerically identical to single-device execution).

    Per-channel statistics mean masked-out channels are exactly equivalent to
    the sliced sub-model's BN for the active channels.
    """
    axes = tuple(range(x.ndim - 1))  # all but channel
    if mode == "running":
        mean, var = running
        y = (x - mean) / jnp.sqrt(var + eps) * g + b
        return y, None
    w = None
    if sample_weight is not None:
        w = sample_weight.reshape((-1,) + (1,) * (x.ndim - 1))
        w = jnp.broadcast_to(w, x.shape)
    # staticcheck: allow(no-float-coercion): static shape product, not a
    # device value
    n_local = float(math.prod(x.shape[a] for a in axes))
    if axis_name is not None:
        # Cross-device sync: one-pass (sum, sumsq, count) psums -- the only
        # form expressible as single-shot collectives.
        if w is None:
            s1 = jnp.sum(x, axis=axes, keepdims=True, dtype=jnp.float32)
            s2 = jnp.sum(x * x, axis=axes, keepdims=True, dtype=jnp.float32)
            # staticcheck: allow(no-asarray): trace-time static count scalar
            n = jnp.asarray(n_local, jnp.float32) * jax.lax.psum(1.0, axis_name)
        else:
            s1 = jnp.sum(x * w, axis=axes, keepdims=True, dtype=jnp.float32)
            s2 = jnp.sum(w * x * x, axis=axes, keepdims=True, dtype=jnp.float32)
            n = jax.lax.psum(jnp.sum(w, axis=axes, keepdims=True, dtype=jnp.float32),
                             axis_name)
        s1 = jax.lax.psum(s1, axis_name)
        s2 = jax.lax.psum(s2, axis_name)
        d = jnp.maximum(n, 1e-6)
        mean = s1 / d
        var = jnp.maximum(s2 / d - mean * mean, 0.0)
    else:
        # Single-device: two-pass mean-then-centered-var (torch parity form).
        # The one-pass E[x^2]-mean^2 alternative was A/B'd on TPU and is
        # perf-neutral (19.71 vs 19.85 ms/step, MEASUREMENTS.md) -- XLA's
        # fusion makes the second read ~free at these shapes -- while its
        # uncentered sums are measurably more reduction-order-sensitive
        # (masked-vs-sliced divergence grows ~5x), so the tighter two-pass
        # form wins.
        if w is None:
            # staticcheck: allow(no-asarray): trace-time static count scalar
            n = jnp.asarray(n_local, jnp.float32)
            mean = jnp.sum(x, axis=axes, keepdims=True, dtype=jnp.float32) / n
            var = jnp.sum((x - mean) ** 2, axis=axes, keepdims=True,
                          dtype=jnp.float32) / n
        else:
            n = jnp.sum(w, axis=axes, keepdims=True, dtype=jnp.float32)
            d = jnp.maximum(n, 1e-6)  # all-padding batches: 0-stats, not NaN
            mean = jnp.sum(x * w, axis=axes, keepdims=True, dtype=jnp.float32) / d
            var = jnp.sum(w * (x - mean) ** 2, axis=axes, keepdims=True,
                          dtype=jnp.float32) / d
    y = (x - mean) / jnp.sqrt(var + eps) * g + b
    if mode == "collect":
        unbiased = var * n / jnp.maximum(n - 1, 1)
        return y, (mean.reshape(-1), unbiased.reshape(-1))
    return y, None


@scoped("norm")
def masked_layer_norm(x: jnp.ndarray, g: jnp.ndarray, b: jnp.ndarray,
                      mask: jnp.ndarray, k, eps: float = 1e-5) -> jnp.ndarray:
    """LayerNorm over the last axis counting only the ``k`` active dims.

    ``mask`` is the 0/1 activity mask over the last axis; ``k = sum(mask)``
    (passed separately so it can be a traced scalar).  For a full-width model
    (mask all ones) this is standard LayerNorm (eps=1e-5, biased var, parity
    with ``nn.LayerNorm``).  ``g``/``b`` are zero at masked dims, which zeroes
    the output there.
    """
    xm = x * mask
    mean = jnp.sum(xm, axis=-1, keepdims=True) / k
    var = jnp.sum(mask * (xm - mean) ** 2, axis=-1, keepdims=True) / k
    return (xm - mean) / jnp.sqrt(var + eps) * g + b


@scoped("norm")
def dynamic_group_norm(x: jnp.ndarray, g: jnp.ndarray, b: jnp.ndarray,
                       num_groups: int, mask: jnp.ndarray, k,
                       eps: float = 1e-5) -> jnp.ndarray:
    """GroupNorm(G) whose group boundaries follow the *active* channel count.

    A sliced sub-model with ``k`` channels splits **its** channels into G
    contiguous groups of ``k/G`` (ref models/conv.py:20); since active
    channels are a prefix, the equivalent full-width op assigns channel ``c``
    to group ``floor(c*G/k)`` and computes masked statistics per group over
    (H, W, group-channels).  Requires ``G | k`` (torch enforces divisibility).

    ``num_groups=C`` (instance norm) and ``num_groups=1`` (layer norm over
    CHW) are handled by the same formula.  NHWC input.
    """
    C = x.shape[-1]
    c_idx = jnp.arange(C)
    gid = jnp.clip((c_idx * num_groups) // jnp.maximum(k, 1), 0, num_groups - 1)
    onehot = (jax.nn.one_hot(gid, num_groups) * mask[:, None])  # [C, G]
    spatial = 1
    for a in range(1, x.ndim - 1):
        spatial *= x.shape[a]
    occ = jnp.sum(onehot, axis=0)  # active channels per group
    n_per_group = jnp.maximum(occ * spatial, 1.0)
    xm = x * mask
    # Per-sample, per-group sums via matmul over the channel axis.
    sum_g = jnp.einsum("...c,cg->...g", xm, onehot)
    red_axes = tuple(range(1, x.ndim - 1))
    mean_g = jnp.sum(sum_g, axis=red_axes, keepdims=True) / n_per_group  # [N,1..,G]
    mean_c = jnp.einsum("...g,cg->...c", mean_g, onehot)
    d = (xm - mean_c) * mask
    var_g = jnp.sum(jnp.einsum("...c,cg->...g", d * d, onehot), axis=red_axes, keepdims=True) / n_per_group
    var_c = jnp.einsum("...g,cg->...c", var_g, onehot)
    y = d / jnp.sqrt(var_c + eps) * g + b
    return y * mask


def masked_logits(out: jnp.ndarray, label_mask: Optional[jnp.ndarray], enabled: bool) -> jnp.ndarray:
    """Zero-fill logits of classes outside the client's label set
    (ref models/conv.py:66-69 -- zero fill, *not* -inf)."""
    if label_mask is None or not enabled:
        return out
    with scope("loss"):
        return jnp.where(label_mask == 0, 0.0, out)


@scoped("loss")
def cross_entropy(logits: jnp.ndarray, labels: jnp.ndarray,
                  sample_weight: Optional[jnp.ndarray] = None) -> jnp.ndarray:
    """Mean cross entropy; class axis is the LAST axis of ``logits``.

    ``sample_weight`` broadcasts over the label shape (used to neutralise
    padded examples).  Matches ``F.cross_entropy(reduction='mean')``.
    """
    logp = jax.nn.log_softmax(logits, axis=-1)
    nll = -jnp.take_along_axis(logp, labels[..., None], axis=-1)[..., 0]
    if sample_weight is None:
        return jnp.mean(nll)
    w = jnp.broadcast_to(sample_weight.reshape(sample_weight.shape + (1,) * (nll.ndim - sample_weight.ndim)),
                         nll.shape)
    return jnp.sum(nll * w) / jnp.maximum(jnp.sum(w), 1e-12)
