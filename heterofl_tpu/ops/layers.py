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
from functools import partial
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


def _product(spec: str, x, w, compute_dtype):
    """``einsum(spec, x, w)`` as :func:`linear` takes its product."""
    if compute_dtype is None:
        return jnp.einsum(spec, x, w)
    return jnp.einsum(spec, x.astype(compute_dtype), w.astype(compute_dtype)).astype(jnp.float32)


@scoped("linear")
def linear_heads(x: jnp.ndarray, w: jnp.ndarray, heads: int,
                 compute_dtype: Optional[jnp.dtype] = None) -> jnp.ndarray:
    """:func:`linear` whose output columns are ``heads`` heads, written heads
    first: ``x`` ``[N, S, K]``, ``w`` ``[K, heads * d]`` -> ``[N, heads, S,
    d]``.  The product itself emits the layout the attention reads (the
    compiler folds the order of a product's result into the product, and the
    transposed one into its cotangents), where ``linear`` + ``reshape`` +
    ``swapaxes`` writes the activation and then copies it."""
    return _product("nsk,khd->nhsd", x, w.reshape(w.shape[0], heads, -1), compute_dtype)


@scoped("linear")
def heads_linear(x: jnp.ndarray, w: jnp.ndarray,
                 compute_dtype: Optional[jnp.dtype] = None) -> jnp.ndarray:
    """:func:`linear` over the concatenated heads of a heads-first ``x``:
    ``x`` ``[N, H, S, d]``, ``w`` ``[H * d, K]`` -> ``[N, S, K]``."""
    return _product("nhsd,hdk->nsk", x, w.reshape(x.shape[1], x.shape[3], -1), compute_dtype)


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


#: positions a block of the head-and-loss takes (memory, not mathematics)
LOSS_BLOCK = 1024


def next_token_loss(xn: jnp.ndarray, labels: jnp.ndarray, logits_of,
                    sample_weight: Optional[jnp.ndarray] = None,
                    block: int = LOSS_BLOCK) -> jnp.ndarray:
    """Mean next-token cross entropy inside each row: position ``t`` predicts
    ``t + 1``; the last position of a window has no target.  ``xn`` ``[N, S,
    D]`` the final normed states, ``labels`` ``[N, S]``, ``logits_of(x)`` the
    head with its label masking on a block ``[block, D]``.  The head is taken
    a block of positions at a time, each block under ``jax.checkpoint``, so
    that ``[T, V]`` logits are never held (T = 4,096, V = 16,032: 263 MB a
    copy, and cross entropy keeps several)."""
    N, S = labels.shape
    T, D = N * S, xn.shape[-1]
    w = jnp.ones((N, S), jnp.float32) if sample_weight is None else \
        jnp.broadcast_to(sample_weight, (N, S)).astype(jnp.float32)
    tgt = jnp.concatenate([labels[:, 1:], labels[:, :1]], axis=1).reshape(T)
    wt = jnp.concatenate([w[:, 1:] * w[:, :-1], jnp.zeros((N, 1), jnp.float32)],
                         axis=1).reshape(T)
    c = block if T % block == 0 else T

    def block_nll(xs):
        x_c, t_c, w_c = xs
        return cross_entropy(logits_of(x_c), t_c, w_c) * jnp.sum(w_c)  # the block's weighted sum

    sums = lax.map(jax.checkpoint(block_nll),
                   (xn.reshape(T // c, c, D), tgt.reshape(T // c, c), wt.reshape(T // c, c)))
    return jnp.sum(sums) / jnp.maximum(jnp.sum(wt), 1e-12)


def pass_token_nll(hs: jnp.ndarray, labels: jnp.ndarray, logits_of,
                   sample_weight: Optional[jnp.ndarray] = None,
                   block: int = LOSS_BLOCK) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """:func:`next_token_loss` before its mean, under each of ``R`` read-outs
    of a looped model (models/ouro.py): ``hs`` ``[R, N, S, D]`` the final
    normed state after each pass, ``labels``, ``logits_of`` and
    ``sample_weight`` as there.  Returns the negative log-likelihood ``[R, N,
    S]`` of position ``t`` against token ``t + 1`` and the weight ``[N, S]``
    of each position's target (zero at a row's last position, which has none
    and is scored against a filler).  A (pass, position) is a row of its own
    to the head, so the ``R * N * S`` rows go through it ``block`` at a time,
    each block under ``jax.checkpoint``: ``[S, V]`` logits of one pass are
    never held (S = 2,048, V = 49,152: 403 MB a copy, four passes, and cross
    entropy keeps several)."""
    R, N, S, D = hs.shape
    T = R * N * S
    w = jnp.ones((N, S), jnp.float32) if sample_weight is None else \
        jnp.broadcast_to(sample_weight, (N, S)).astype(jnp.float32)
    wt = jnp.concatenate([w[:, 1:] * w[:, :-1], jnp.zeros((N, 1), jnp.float32)], axis=1)
    tgt = jnp.concatenate([labels[:, 1:], labels[:, :1]], axis=1)
    tgt = jnp.broadcast_to(tgt, (R, N, S)).reshape(T)
    c = block if T % block == 0 else T

    def block_nll(xs):
        x_c, t_c = xs
        logits = logits_of(x_c)
        with scope("loss"):
            logp = jax.nn.log_softmax(logits, axis=-1)
            return -jnp.take_along_axis(logp, t_c[:, None], axis=-1)[:, 0]

    nll = lax.map(jax.checkpoint(block_nll), (hs.reshape(T // c, c, D), tgt.reshape(T // c, c)))
    return nll.reshape(R, N, S), wt


def exit_log_probs(gate: jnp.ndarray) -> jnp.ndarray:
    """The log of a looped model's exit distribution over its ``R`` passes
    from the exit gate's logits ``gate`` ``[R, ...]`` (pass first): with
    ``lam_t = sigmoid(gate_t)``, ``p_t = lam_t * prod_{j<t}(1 - lam_j)`` for
    ``t < R`` and ``p_R = prod_{j<R}(1 - lam_j)``: the last pass takes what is
    left, whatever its own gate says, so the ``p_t`` sum to 1.  In logs
    (``log lam = log_sigmoid(g)``, ``log(1 - lam) = log_sigmoid(-g)``), so a
    saturated gate gives a finite log and a finite gradient.  ``R`` = 1: ``p_1``
    = 1."""
    zero = jnp.zeros_like(gate[:1])
    stayed = jnp.cumsum(jax.nn.log_sigmoid(-gate[:-1]), axis=0)   # log prod_{j<=t}(1 - lam_j)
    return jnp.concatenate([zero, stayed], axis=0) \
        + jnp.concatenate([jax.nn.log_sigmoid(gate[:-1]), zero], axis=0)


# ---------------------------------------------------------------------------
# Latent attention + shared-and-routed experts (models/kanana2.py); gated
# short convolution + grouped-query attention (models/lfm2.py); a learned
# sparse-attention indexer over grouped-query attention (models/keye.py); a
# looped decoder's read-outs (models/ouro.py: pass_token_nll, exit_log_probs);
# a selective state-space mixer's convolution, chunked scan and gated group
# norm (models/nemotron_h.py, at the file's end).  The routed experts' loop
# (moe_experts) is ONE for every expert family: an expert's body is the
# caller's, SwiGLU's three matrices (swiglu) or two and a squared relu
# (relu2_ffn)
# ---------------------------------------------------------------------------

@scoped("norm")
def masked_rms_norm(x: jnp.ndarray, g: jnp.ndarray, mask: jnp.ndarray, k,
                    eps: float = 1e-6) -> jnp.ndarray:
    """RMSNorm over the last axis counting only the ``k`` active dims
    (``x / sqrt(mean(x^2) + eps) * g`` of the sliced sub-model).  ``g`` is
    zero at masked dims, which zeroes the output there."""
    xm = x * mask
    ms = jnp.sum(xm * xm, axis=-1, keepdims=True) / k
    return xm / jnp.sqrt(ms + eps) * g


@scoped("rope")
def rope_swap(x: jnp.ndarray) -> jnp.ndarray:
    """The rotary turn's pair swap on the last axis: ``(x[2i], x[2i+1]) ->
    (-x[2i+1], x[2i])``, as two lane rotations and a select, not a strided
    gather.  Linear and within pairs of columns, so ``swap(h W) = h swap(W)``
    (a ``[K, H * d]`` weight's heads hold whole pairs): for the rotary QUERY
    the model swaps the weight and takes a second product.  Rotating that
    activation instead costs more than the product: the chip's compiler turns
    a rotation of a 64-wide last axis into four slices (``[.., 63]``, ``[..,
    1]``) written out at 128 lanes each, 0.27 GB a turn of the cell's ``[2,
    32, 2048, 64]``, and the turn, its recomputation and its backward were 3.0
    ms of a layer's 14.2 (PERF.md, PR 31)."""
    even = (jnp.arange(x.shape[-1]) % 2) == 0
    return jnp.where(even, -jnp.roll(x, -1, axis=-1), jnp.roll(x, 1, axis=-1))


@scoped("rope")
def rope_interleaved(x: jnp.ndarray, swapped: jnp.ndarray, pos: jnp.ndarray, theta: float,
                     axis: int = 1, full: Optional[int] = None, freqs=None,
                     factor: Optional[float] = None) -> jnp.ndarray:
    """Rotary embedding on interleaved pairs ``(2i, 2i+1)`` of the last axis
    (``rope_interleave: true``): ``x cos + swapped sin`` with ``swapped`` the
    pair swap of ``x`` (:func:`rope_swap`), ``theta_i = theta^(-2i/full)`` and
    ``full`` the GLOBAL model's rotary width (default: the last axis, which it
    is in the masked full-width model): a client's sliced prefix of whole
    pairs keeps the frequencies of the pairs it holds, and zeros (masked
    pairs) stay zeros.  ``x`` ``[N, S, ..., d]`` and ``pos`` ``[S]``, the
    positions on ``axis`` (2 for heads-first ``[N, H, S, d]``).

    ``freqs``: the GLOBAL head's frequency of every pair instead (a static
    table of ``full / 2`` numbers, ``theta`` unread: YaRN's blend of two
    tables, models/laguna.py); ``factor``: what cos and sin are multiplied by
    (YaRN's ``attention_factor``).  A head of which only a part turns hands
    that part alone to this function."""
    d = x.shape[-1]
    if freqs is None:
        inv = theta ** (-(jnp.arange(d) // 2 * 2).astype(jnp.float32) / (full or d))
    else:
        # staticcheck: allow(no-asarray): trace-time static frequency table
        inv = jnp.repeat(jnp.asarray(freqs, jnp.float32), 2)[:d]
    ang = pos.astype(jnp.float32)[:, None] * inv[None, :]           # [S, d]
    view = [1] * x.ndim
    view[axis], view[-1] = x.shape[axis], d
    cos, sin = jnp.cos(ang).reshape(view), jnp.sin(ang).reshape(view)
    if factor is not None:
        cos, sin = cos * factor, sin * factor
    return x * cos + swapped * sin


def swiglu(x, wg, wu, wd, sc, compute_dtype=None):
    """``sc((silu(sc(x wg)) * sc(x wu)) wd)``; ``sc`` the HeteroFL Scaler."""
    h = jax.nn.silu(sc(linear(x, wg, compute_dtype=compute_dtype))) \
        * sc(linear(x, wu, compute_dtype=compute_dtype))
    return sc(linear(h, wd, compute_dtype=compute_dtype))


#: query rows a block of the causal attention takes (memory, not mathematics)
ATTN_BLOCK = 256


@scoped("attn")
def causal_latent_attention(qn, qr, kn, kr, v, scale, block: int = ATTN_BLOCK):
    """Causal softmax attention of latent-attention heads, the score /
    softmax / value part only: ``scores = (qn kn^T + qr kr^T) * scale``.

    Heads first, the layout :func:`linear_heads` writes and the kernels read:
    ``qn``/``kn`` ``[N, H, S, dn]`` (no-position dims), ``qr`` ``[N, H, S,
    dr]`` and ``kr`` ``[N, S, dr]`` (rotary dims, ONE key head shared by all
    query heads), ``v`` and the result ``[N, H, S, dv]``.  Softmax in
    float32, float32 out.

    On a TPU, where the positions make whole tiles and the head dims fill
    the lanes (``pallas_attention.tile_for``), the fused kernels of
    ops/pallas_attention.py: a score tile lives in VMEM only.  Elsewhere (the
    CPU; a client's narrow slice at its own widths)
    :func:`blockwise_latent_attention` in query blocks of ``block`` rows."""
    if jax.default_backend() == "tpu":
        from . import pallas_attention  # jax's Pallas: a second of import, paid where it is used

        tile = pallas_attention.tile_for(qn.shape[2], qn.shape[-1], qr.shape[-1], v.shape[-1])
        if tile is not None:
            return pallas_attention.fused_latent_attention(
                qn, qr, kn, kr, v, scale, block_q=tile, block_k=tile)
    return blockwise_latent_attention(qn, qr, kn, kr, v, scale, block)


def _causal_blocks(scores, values, qs, ks, v, scale, block: int, select=None, window=None):
    """The one blockwise causal softmax loop: query blocks of ``block`` rows
    against the keys up to the block's end, each block under
    ``jax.checkpoint``, so no ``[S, S]`` score matrix of a whole row is ever
    held, in the forward or for the backward, and key blocks above the
    diagonal are never computed.  Every operand has its positions on axis -2;
    ``scores(*q_blocks, *k_blocks)`` gives ``[..., q, k]`` and ``values(p,
    v_block)`` the block's result, positions on axis -2 again.  ``select``
    (:func:`select_keys`): per block None or a further mask ``[N, q, k]`` on
    its scores, alike for every head.  ``window`` (a sliding layer's): query
    ``i`` sees key ``j`` only if ``i - j < window`` as well, and a block's keys
    start at the first one its first query sees: what lies wholly below the
    band is never sliced out, let alone computed."""
    S = v.shape[-2]
    if window is not None and window >= S:
        window = None  # the band holds every causal pair: the diagonal alone
    outs = []
    for i, start in enumerate(range(0, S, block)):
        end = min(start + block, S)
        first = 0 if window is None else max(0, start - window + 1)

        def one(qs_b, ks_b, v_b, *sel_b, start=start, end=end, first=first):
            s = scores(*qs_b, *ks_b).astype(jnp.float32) * scale
            q_pos, k_pos = jnp.arange(start, end)[:, None], jnp.arange(first, end)[None, :]
            keep = q_pos >= k_pos
            if window is not None:
                keep = keep & (q_pos - k_pos < window)
            for m in sel_b:
                keep = keep & m.reshape(m.shape[:1] + (1,) * (s.ndim - 3) + m.shape[1:])
            s = jnp.where(keep, s, -jnp.inf)
            return values(jax.nn.softmax(s, axis=-1), v_b)

        sel_b = () if select is None or select[i] is None else (select[i],)
        outs.append(jax.checkpoint(one)(tuple(q[..., start:end, :] for q in qs),
                                        tuple(k[..., first:end, :] for k in ks),
                                        v[..., first:end, :], *sel_b))
    return outs[0] if len(outs) == 1 else jnp.concatenate(outs, axis=-2)


def blockwise_latent_attention(qn, qr, kn, kr, v, scale, block: int = ATTN_BLOCK):
    """:func:`causal_latent_attention` in plain ``jnp`` (and the fused
    kernels' oracle), block by block (:func:`_causal_blocks`)."""
    return _causal_blocks(
        lambda qn_b, qr_b, kn_b, kr_b: jnp.einsum("nhqd,nhkd->nhqk", qn_b, kn_b)
        + jnp.einsum("nhqd,nkd->nhqk", qr_b, kr_b),
        lambda p, v_b: jnp.einsum("nhqk,nhkd->nhqd", p, v_b),
        (qn, qr), (kn, kr), v, scale, block)


@scoped("attn")
def causal_gq_attention(q, k, v, scale, block: int = ATTN_BLOCK):
    """Causal softmax attention of grouped-query heads, the score / softmax /
    value part only, heads first: ``q`` ``[N, H, S, d]``, ``k`` and ``v``
    ``[N, Hkv, S, d]`` with ``H`` a multiple of ``Hkv``: query heads ``[g *
    H/Hkv, (g + 1) * H/Hkv)`` read key/value head ``g``, which is never
    repeated in memory.  Softmax in float32, float32 out ``[N, H, S, d]``.

    On a TPU, where the positions make whole tiles and the head dim is a
    multiple of 64, the fused kernel pair ``pallas_attention.gq_plan`` names
    for the layer's (group, head dim, positions): a score tile lives in VMEM
    only.  Elsewhere (the CPU; a client's narrow slice at its own widths)
    :func:`blockwise_gq_attention` in query blocks of ``block`` rows."""
    return _planned_gq_attention(q, k, v, scale, block, None)


def _planned_gq_attention(q, k, v, scale, block, window):
    """Grouped-query attention under the diagonal (``window`` None) or under
    the diagonal and a window, through what ``pallas_attention.gq_plan``
    gives its shapes on a TPU and through the ``jnp`` block loop elsewhere."""
    if jax.default_backend() == "tpu":
        from . import pallas_attention

        plan = pallas_attention.gq_plan(*q.shape[2:], q.shape[1] // k.shape[1], window, v.shape[-1])
        if plan is not None:
            pair, tq, tk = plan
            if pair == "gq":
                return pallas_attention.fused_gq_attention(q, k, v, scale, block_q=tq, block_k=tk)
            return pallas_attention.fused_band_attention(q, k, v, scale, window,
                                                         block_q=tq, block_k=tk)
    return blockwise_gq_attention(q, k, v, scale, block, window=window)


def blockwise_gq_attention(q, k, v, scale, block: int = ATTN_BLOCK, select=None, window=None):
    """:func:`causal_gq_attention` in plain ``jnp`` (and the fused kernels'
    oracle): latent attention's block loop (:func:`_causal_blocks`) with the
    query heads grouped by the key/value head they read; ``window`` a sliding
    layer's (:func:`sliding_gq_attention`)."""
    N, H, S, d = q.shape
    kv = k.shape[1]
    o = _causal_blocks(
        lambda q_b, k_b: jnp.einsum("ngjqd,ngkd->ngjqk", q_b, k_b),
        lambda p, v_b: jnp.einsum("ngjqk,ngkd->ngjqd", p, v_b),
        (q.reshape(N, kv, H // kv, S, d),), (k,), v, scale, block, select, window)
    return o.reshape(N, H, S, v.shape[-1])


@scoped("attn")
def selected_gq_attention(q, k, v, scale, select, block: int):
    """:func:`causal_gq_attention` whose softmax runs over the keys a learned
    indexer chose for each query (:func:`select_keys` in the same ``block``),
    alike for every head.

    On a TPU, where :func:`selected_attention_tile` finds tiles, the fused
    kernels ``sel_attn_fwd`` / ``sel_attn_bwd``, which read the 0/1 choice a
    mask tile beside ``q``, ``k``, ``v``: a score tile lives in VMEM only.
    Elsewhere the ``jnp`` block loop with the selection as a further mask on
    each score block (:func:`blockwise_gq_attention`, the kernels' oracle)."""
    tile = selected_attention_tile(q.shape[2], q.shape[-1], q.shape[1] // k.shape[1])
    if tile is not None:
        from . import pallas_attention

        return pallas_attention.fused_selected_attention(
            q, k, v, scale, select, block, block_q=tile[0], block_k=tile[1])
    return blockwise_gq_attention(q, k, v, scale, block, select)


def selected_attention_tile(S: int, d: int, group: int):
    """The (query, key) tile :func:`selected_gq_attention` gives its fused
    kernels at ``S`` positions and ``group`` query heads of ``d`` dims a
    key/value head, None where it takes the block loop: off a TPU, or where
    the shapes make no whole tiles (``pallas_attention.sel_tile_for``: head
    dims a multiple of 128, positions of 128; a client's narrow slice at its
    own widths takes none)."""
    if jax.default_backend() != "tpu":
        return None
    from . import pallas_attention

    return pallas_attention.sel_tile_for(S, d, group)


def top_k_mask(x, k: int):
    """The ``k`` largest entries of each row of ``x`` ``[..., n]`` (float32,
    ``n >= k``) as a 0/1 mask, equal values to the lower position (and -0.0
    below 0.0): the set ``lax.top_k`` returns, without its sort and without
    a scatter.  The k-th
    largest VALUE is found bit by bit on the order-preserving integer image
    of a float (32 counting passes over ``x``), then the position that cuts
    the entries equal to it (``n.bit_length()`` more)."""
    n = x.shape[-1]
    bits = lax.bitcast_convert_type(x, jnp.int32)
    top = jnp.uint32(1 << 31)
    u = lax.bitcast_convert_type(bits ^ ((bits >> 31) & 0x7FFFFFFF), jnp.uint32) ^ top

    def count(m):
        return jnp.sum(m, axis=-1, keepdims=True, dtype=jnp.int32)

    def value_bit(i, t):  # the largest t with k or more entries >= t
        cand = t | lax.shift_right_logical(top, i.astype(jnp.uint32))
        return jnp.where(count(u >= cand) >= k, cand, t)

    kth = lax.fori_loop(0, 32, value_bit, jnp.zeros(u.shape[:-1] + (1,), jnp.uint32))
    above, tie = u > kth, u == kth
    need = k - count(above)  # of the entries equal to the k-th value, from the left
    pos = lax.broadcasted_iota(jnp.int32, u.shape, u.ndim - 1)

    def position_bit(i, last):  # the largest position with fewer than `need` ties before it
        cand = last | (jnp.int32(1 << (n.bit_length() - 1)) >> i)
        return jnp.where(count(tie & (pos < cand)) < need, cand, last)

    last = lax.fori_loop(0, n.bit_length(), position_bit,
                         jnp.zeros(u.shape[:-1] + (1,), jnp.int32))
    return above | (tie & (pos <= last))


def select_keys(qi, ki, wi, topk: int, block: int):
    """A learned sparse-attention indexer's choice of keys (``sa_config``):
    ``I[t, s] = sum_j wi[t, j] * relu(qi[t, j] . ki[s])`` over the indexer's
    heads ``j``, and for query ``t`` the ``min(t + 1, topk)`` causal keys of
    largest ``I`` (equal scores to the lower position).  ``qi`` ``[N, Hi, S,
    di]``, ``ki`` ``[N, S, di]`` (one key head), ``wi`` ``[N, Hi, S]``.

    Query blocks of ``block`` rows against the keys up to the block's end, as
    :func:`_causal_blocks` takes them: returns, per block, None where every
    causal key is chosen (the block ends at or before ``topk``) or the 0/1
    choice ``[N, q, k]`` (to be met with the causal mask), and the counts
    ``[2]`` = (chosen causal pairs, causal pairs) in float32.  The scores,
    ``[Hi, S, S]`` if held whole, live a block at a time, in float32 at
    "highest" matmul precision: they decide a discrete set, as a router's
    do.  No gradient: the choice is read as a mask."""
    N, _, S, _ = qi.shape
    qi, ki, wi = (lax.stop_gradient(t.astype(jnp.float32)) for t in (qi, ki, wi))
    select, chosen = [], 0.0
    for start in range(0, S, block):
        end = min(start + block, S)
        causal = jnp.arange(start, end)[:, None] >= jnp.arange(end)[None, :]
        if end <= topk:
            select.append(None)
            chosen += N * jnp.sum(causal, dtype=jnp.float32)
            continue
        with scope("sparse/index"):
            s = jnp.einsum("nhqd,nkd->nhqk", qi[:, :, start:end], ki[:, :end],
                           precision=lax.Precision.HIGHEST)
            score = jnp.sum(jax.nn.relu(s) * wi[:, :, start:end, None], axis=1)
        with scope("sparse/select"):
            keep = top_k_mask(jnp.where(causal, score, -jnp.inf), topk)
            select.append(keep)
            chosen += jnp.sum(keep & causal, dtype=jnp.float32)
    return select, jnp.stack([chosen, jnp.float32(N * (S * (S + 1) // 2))])


def short_conv(b, c, u, taps):
    """The gated short convolution between a conv mixer's two projections
    (``model_type: lfm2_moe``): ``z = b * u``; ``y[t] = sum_j taps[j] * z[t -
    (L - 1) + j]``, depthwise, causal, zero to the left of a row's first
    position; result ``c * y``.  ``b``, ``c``, ``u`` ``[N, S, D]``, ``taps``
    ``[L, D]`` (tap ``j`` of channel ``d``: the published ``conv.weight[d, 0,
    j]``).  Rows never mix and a channel reads only itself, so a masked
    channel stays zero.  Shifted slices, not ``lax.conv``: elementwise work
    that the chip's compiler fuses with the gates around it (no standalone
    copy in the compiled mixer, tests/test_tpu_compile.py; 1.5 ms a step)."""
    with scope("shortconv/gate"):
        L, S = taps.shape[0], b.shape[1]
        z = jnp.pad(b * u, ((0, 0), (L - 1, 0), (0, 0)))
        y = sum(taps[j] * lax.slice_in_dim(z, j, j + S, axis=1) for j in range(L))
        return c * y


@scoped("moe/router")
def moe_route(h, w_router, bias, top_k: int, scaling: float, sum_eps: float = 0.0,
              softmax: bool = False):
    """A router over ALL experts, its product in float32 at "highest" matmul
    precision.  Sigmoid scoring (``scoring_func: sigmoid``, ``topk_method:
    noaux_tc`` with one group): ``s = sigmoid(h Wr)``, ``sel = top_k(s +
    bias)`` (the selection bias is read here only; no gradient reaches it),
    ``w = s[sel] / (sum(s[sel]) + sum_eps) * scaling`` (``lfm2_moe`` adds 1e-6
    to the sum, ``deepseek_v3`` nothing).  ``softmax``: ``s = softmax(h Wr)``
    over all experts, ``bias`` None (``norm_topk_prob``: the same division).
    ``h`` ``[T, D]``.  Returns ``(sel [T, k] int32, w [T, k])``."""
    logits = jnp.dot(h.astype(jnp.float32), w_router.astype(jnp.float32),
                     precision=lax.Precision.HIGHEST)
    s = jax.nn.softmax(logits, axis=-1) if softmax else jax.nn.sigmoid(logits)
    _, sel = lax.top_k(s if bias is None else s + lax.stop_gradient(bias), top_k)
    w = jnp.take_along_axis(s, sel, axis=-1)
    total = jnp.sum(w, axis=-1, keepdims=True)
    if sum_eps:
        total = total + sum_eps
    return sel.astype(jnp.int32), w / total * scaling


#: rows of ONE expert that a step of the grouped loop computes
MOE_TILE = 256


def _gather_rows(x, idx):
    """``x[idx]`` along the first axis, zero where ``idx < 0``."""
    out = x[jnp.maximum(idx, 0)]
    return jnp.where((idx >= 0).reshape(idx.shape + (1,) * (out.ndim - idx.ndim)), out, 0)


def _expert_tile(body, compute_dtype, x, *weights_inv):
    *ws, inv = weights_inv
    return body(x, *ws, lambda v: v * inv, compute_dtype)


def _tile_weights(e, *stacked):
    return tuple(lax.dynamic_index_in_dim(s, e, 0, keepdims=False) for s in stacked)


# The routed experts' part over RAGGED groups: the (token, held expert) pairs
# lie sorted by expert, each expert's group padded to whole tiles, and a loop
# with as many steps as there are tiles IN USE takes one tile through its
# expert.  ``rows`` ``[tiles * tile]``: the pair (t * K + k) of a row, -1 for
# padding; ``slot`` ``[T, K]``: the row of a pair, -1 for a pair held elsewhere.
# The map is known in both directions, so both directions are gathers: no
# scatter-add, forward or backward.  A trip count known only at run time has
# no reverse-mode rule, hence the custom one: the backward is the same loop,
# each tile's cotangents from ``jax.vjp`` of the tile.
#
# What lies round the loop -- its result buffer, the combine -- is sized one of
# two ways.  FULL: by the only static bound on the held pairs, all ``T * K`` of
# them (``rows``' length).  COMPACT: by :func:`moe_capacity`, two tiles a held
# expert, and the combine as long as a token's held choices.  Both compute
# every pair and add a token's terms in the same order, so to the same bits
# wherever a multiply and an add stay two roundings (XLA:CPU contracts them by
# what it fuses); which runs is decided by the tiles in use (``lax.cond``),
# where the shapes make compact worth building.

def moe_capacity(held: int, tile: int, n_rows: int):
    """The rows the compact dispatch gives the loop's buffers: twice its tile
    a held expert (a tile is already two expected groups or more,
    ``models.decoder.expert_tile``), so the fallback runs only where the held
    experts' load doubles once more.  None where that is more than half the
    bound ``n_rows``: there the full-size path is the whole program, with no
    ``cond`` in it (a quarter of the experts held on four choices a token)."""
    cap = 2 * held * tile
    return cap if 2 * cap <= n_rows else None


def _tiles_forward(body, compute_dtype, tile, n_buf, h, K, rows, tile_expert, n_tiles, ws, inv):
    """The loop: ``(y [n_buf, D], computed)``, a tile's rows through its expert."""

    def step(i, carry):
        y, done = carry
        with scope("moe/dispatch"):
            r = lax.dynamic_slice(rows, (i * tile,), (tile,))
            x_t = _gather_rows(h, r // K)
        with scope("moe/experts"):
            y_t = _expert_tile(body, compute_dtype, x_t, *_tile_weights(tile_expert[i], *ws), inv)
        return (lax.dynamic_update_slice(y, y_t, (i * tile, 0)),
                done + jnp.sum((r >= 0).astype(jnp.int32)))

    return lax.fori_loop(0, n_tiles, step,
                         (jnp.zeros((n_buf, h.shape[1]), h.dtype), jnp.int32(0)))


def _tiles_backward(body, compute_dtype, tile, n_buf, h, w, dout, rows, tile_expert, n_tiles,
                    ws, inv):
    """The same loop backward: ``(dx [n_buf, D], dw_rows [n_buf], *dws)``."""
    K, w_flat = w.shape[1], w.reshape(-1)

    def step(i, carry):
        dx, dw_rows, *dws = carry
        with scope("moe/dispatch"):
            r = lax.dynamic_slice(rows, (i * tile,), (tile,))
            x_t, d_t = _gather_rows(h, r // K), _gather_rows(dout, r // K)
            w_t = _gather_rows(w_flat, r)
        with scope("moe/experts"):
            e = tile_expert[i]
            y_t, vjp = jax.vjp(partial(_expert_tile, body, compute_dtype), x_t,
                               *_tile_weights(e, *ws), inv)
            dx_t, *dws_t, _ = vjp(d_t * w_t[:, None])
            dws = [lax.dynamic_update_index_in_dim(
                acc, lax.dynamic_index_in_dim(acc, e, 0, keepdims=False) + d, e, 0)
                for acc, d in zip(dws, dws_t)]
        return (lax.dynamic_update_slice(dx, dx_t, (i * tile, 0)),
                lax.dynamic_update_slice(dw_rows, jnp.sum(y_t * d_t, axis=-1), (i * tile,)),
                *dws)

    return lax.fori_loop(
        0, n_tiles, step,
        (jnp.zeros((n_buf, h.shape[1]), h.dtype), jnp.zeros((n_buf,), w.dtype),
         *(jnp.zeros_like(m) for m in ws)))


def _held_first(slot):
    """A token's held choices moved to the front of its ``K``, STABLY:
    ``(front [K, T], place [K, T, K], m)`` with ``front[i, t]`` the row of
    token ``t``'s ``i``-th held choice (-1 past its last), ``place[i, t, k]``
    whether choice ``k`` is that one, and ``m`` the most held choices of any
    token.  A count along ``K``: no sort."""
    live = slot >= 0                                               # [T, K]
    rank = jnp.cumsum(live, axis=1) - 1
    place = live[None] & (rank[None] == jnp.arange(slot.shape[1])[:, None, None])
    front = jnp.sum(jnp.where(place, slot[None] + 1, 0), axis=2) - 1
    return front, place, jnp.max(jnp.sum(live, axis=1))


def _rows_summed(y, front, m, scale=None):
    """``sum_{i < m} scale[i] * y[front[i]]`` ``[T, D]``: the first ``m`` of
    ``front``'s ``K`` gathers as ONE fused sum, picked by ``lax.switch`` among
    the ``K + 1`` lengths there are (a loop of ``m`` steps would carry the
    ``[T, D]`` sum through memory every step).  A token's held choices in
    their own order and ``x + 0 = x``: the sum over all ``K`` slots, term for
    term."""

    def first(n):
        def summed(y, front, scale):
            if n == 0:
                return jnp.zeros((front.shape[1], y.shape[1]), y.dtype)
            return sum(_gather_rows(y, front[i]) if scale is None
                       else scale[i][:, None] * _gather_rows(y, front[i]) for i in range(n))
        return summed

    return lax.switch(m, [first(n) for n in range(front.shape[0] + 1)], y, front, scale)


def _experts_forward(cap, body, compute_dtype, tile, h, w, rows, slot, tile_expert, n_tiles, ws,
                     inv):
    """One dispatch's loop and combine.  ``cap`` None, FULL: the loop's buffer
    as long as ``rows``, a combine of ``K`` gathers.  COMPACT, where the tiles
    in use fit ``cap`` rows: a buffer of ``cap`` rows, the combine a token's
    held choices long."""
    K = w.shape[1]
    y, done = _tiles_forward(body, compute_dtype, tile, rows.shape[0] if cap is None else cap,
                             h, K, rows, tile_expert, n_tiles, ws, inv)
    with scope("moe/dispatch"):
        if cap is None:
            return sum(w[:, k, None] * _gather_rows(y, slot[:, k]) for k in range(K)), done
        front, place, m = _held_first(slot)
        w_front = jnp.sum(jnp.where(place, w[None], 0), axis=2)   # one term a sum: exact
        return _rows_summed(y, front, m, w_front), done


def _experts_backward(cap, body, compute_dtype, tile, h, w, rows, slot, tile_expert, n_tiles, ws,
                      inv, dout):
    """:func:`_experts_forward`'s cotangents ``(dh, dw, dws)``, sized alike."""
    dx, dw_rows, *dws = _tiles_backward(
        body, compute_dtype, tile, rows.shape[0] if cap is None else cap, h, w, dout, rows,
        tile_expert, n_tiles, ws, inv)
    with scope("moe/dispatch"):
        if cap is None:
            dh = sum(_gather_rows(dx, slot[:, k]) for k in range(w.shape[1]))
        else:
            front, _, m = _held_first(slot)
            dh = _rows_summed(dx, front, m)
        dw = _gather_rows(dw_rows, slot)
    return dh, dw, tuple(dws)


def _either_dispatch(fn, body, compute_dtype, tile, n_tiles, ws, rows, *args):
    """``fn(cap, ...)`` full, or by a ``lax.cond`` on the tiles in use compact,
    where :func:`moe_capacity` names a capacity for these shapes."""
    cap = moe_capacity(ws[0].shape[0], tile, rows.shape[0])
    if cap is None:
        return fn(None, body, compute_dtype, tile, *args)
    return lax.cond(n_tiles * tile <= cap, partial(fn, cap, body, compute_dtype, tile),
                    partial(fn, None, body, compute_dtype, tile), *args)


@partial(jax.custom_vjp, nondiff_argnums=(0, 1, 2))
def _grouped_experts(body, compute_dtype, tile, h, w, rows, slot, tile_expert, n_tiles, ws, inv):
    """``(out, computed)``: ``out[t] = sum_k w[t, k] * expert(h[t])`` over the
    pairs with a row; ``computed`` counts the pairs the loop took.  ``body(x,
    *matrices, sc, compute_dtype)`` is one expert on a tile of rows
    (:func:`swiglu`, :func:`relu2_ffn`), ``ws`` its matrices, each stacked over
    the held experts."""
    return _either_dispatch(_experts_forward, body, compute_dtype, tile, n_tiles, ws, rows,
                            h, w, rows, slot, tile_expert, n_tiles, ws, inv)


def _grouped_experts_fwd(body, compute_dtype, tile, *args):
    return _grouped_experts(body, compute_dtype, tile, *args), args


def _grouped_experts_bwd(body, compute_dtype, tile, res, cts):
    n_tiles, ws, inv = res[5:]
    # the forward's branch, from the same residual
    dh, dw, dws = _either_dispatch(_experts_backward, body, compute_dtype, tile, n_tiles, ws,
                                   res[2], *res, cts[0])
    return dh, dw, None, None, None, None, dws, jnp.zeros_like(inv)


_grouped_experts.defvjp(_grouped_experts_fwd, _grouped_experts_bwd)


def _sorted_groups(e, is_held, held: int, tile: int, n_rows: int):
    """The index arrays of pairs sorted by expert.  ``e`` ``[n]``: a pair's
    held expert, ``held`` for none (``is_held`` says the same).  Returns
    ``(counts [held], slot [n], rows [n_rows], tile_expert [n_rows // tile],
    ends [held])``; ``rows`` names a pair by its place in ``e``, ``ends[j]``
    counts the tiles of the groups up to and with expert ``j``'s."""
    n = e.shape[0]
    onehot = (e[:, None] == jnp.arange(held)[None, :]).astype(jnp.int32)
    counts = jnp.sum(onehot, axis=0)                          # [held]
    pos = jnp.sum((jnp.cumsum(onehot, axis=0) - 1) * onehot, axis=1)
    order = jnp.argsort(e, stable=True).astype(jnp.int32)     # pairs by expert
    starts = jnp.cumsum(counts) - counts                      # of a group in `order`
    tiles = -(-counts // tile)                                # tiles of a group
    ends = jnp.cumsum(tiles)
    slot = jnp.where(is_held, (ends - tiles)[jnp.minimum(e, held - 1)] * tile + pos, -1)
    tile_expert = jnp.minimum(jnp.searchsorted(ends, jnp.arange(n_rows // tile),
                                               side="right"), held - 1).astype(jnp.int32)
    row = jnp.arange(n_rows, dtype=jnp.int32)
    ex = tile_expert[row // tile]
    rank = row - (ends - tiles)[ex] * tile                    # of a row in its group
    rows = jnp.where(rank < counts[ex], order[jnp.minimum(starts[ex] + rank, n - 1)], -1)
    return counts, slot, rows, tile_expert, ends


def _compact_groups(e, is_held, held: int, tile: int, n_rows: int, cap: int, K: int):
    """:func:`_sorted_groups`'s arrays, to the last entry, where the tiles in
    use fit ``cap`` rows, for pairs that lie ``K`` a token: the rows' arrays at
    ``cap`` entries, and no gather but the one that lays the sorted pairs out
    in tiles (a gather of single integers costs the chip 7 ns an entry; the
    full-size arrays take five of ``n_rows`` entries and one of ``T * K``).
    A pair's place in its group is counted, not searched for: the pairs of its
    expert among the tokens before it (a running count down ``T`` of ``[held,
    T]`` token counts) and among its own token's earlier choices; a row's
    expert and its group's numbers are picked by comparing against the
    ``held`` group ends.  The positions lie on the minor axis throughout."""
    T = e.shape[0] // K
    experts = jnp.arange(held, dtype=jnp.int32)
    hot = (e.reshape(T, K).T[None] == experts[:, None, None]).astype(jnp.int32)   # [held, K, T]
    a_token = jnp.sum(hot, axis=1)                                # [held, T] pairs of a token
    counts = jnp.sum(a_token, axis=1)
    starts = jnp.cumsum(counts) - counts
    tiles = -(-counts // tile)
    ends = jnp.cumsum(tiles)
    base = (ends - tiles) * tile                                  # first row of a group
    earlier = (jnp.cumsum(a_token, axis=1) - a_token)[:, None, :] + jnp.cumsum(hot, axis=1) - hot
    slot = jnp.sum(hot * (base[:, None, None] + earlier), axis=0).T.reshape(-1)
    slot = jnp.where(is_held, slot, -1)
    order = jnp.argsort(e, stable=True).astype(jnp.int32)[:cap]   # the held pairs come first
    row = jnp.arange(cap, dtype=jnp.int32)
    ex = jnp.minimum(jnp.sum(row[None] // tile >= ends[:, None], axis=0), held - 1)
    mine = ex[None] == experts[:, None]                           # [held, cap]: a row's group

    def of_group(table):
        return jnp.sum(jnp.where(mine, table[:, None], 0), axis=0)

    rank = row - of_group(base)
    rows = jnp.where(rank < of_group(counts),
                     order[jnp.minimum(of_group(starts) + rank, cap - 1)], -1)
    rest = n_rows - cap
    return (counts, slot, jnp.concatenate([rows, jnp.full((rest,), -1, jnp.int32)]),
            jnp.concatenate([ex[::tile], jnp.full((rest // tile,), held - 1, jnp.int32)]), ends)


def moe_experts(h, sel, w, experts, first: int, sc, compute_dtype=None,
                tile: int = MOE_TILE, body=swiglu):
    """The routed part of an expert layer for the experts HELD here:
    ``y[t] = sum over k with sel[t, k] held of w[t, k] * expert(h[t])``.

    AN EXPERT'S BODY IS THE CALLER'S: ``body(x, *matrices, sc,
    compute_dtype)`` on a tile of rows, :func:`swiglu` (three matrices) unless
    the family says otherwise (:func:`relu2_ffn`: two).  ``experts``: the held
    experts' matrices in ``body``'s order, each stacked on a leading axis
    (SwiGLU's ``(wg, wu, wd)``: ``[held, D, F]`` x 2, ``[held, F, D]``); they
    are experts ``[first, first + held)`` of the layer.  What absent experts
    would add is left out (the caller's share of an expert-parallel layer;
    nothing here stands in for the other shares).

    No capacity and no dropped token: every (token, held expert) pair is
    computed, whatever the router did.  The pairs are sorted by expert, each
    expert's group padded to whole tiles of ``tile`` rows, and
    :func:`_grouped_experts` runs the tiles in use, so the work follows the
    pairs there are and not a bound on them.  The bound, all ``T * K`` pairs
    on held experts, sizes the FULL dispatch: the sort, the index arrays, the
    loop's result buffer and a combine of ``K`` gathers.  Where
    :func:`moe_capacity` names a capacity (a sixteenth of the experts held:
    one pair in sixteen lands here), a ``lax.cond`` on the tiles in use takes
    the COMPACT dispatch instead -- index arrays counted over the held pairs'
    rows alone (of the sort only its first ``capacity`` places are read),
    buffers of the capacity, a combine as long as a token's held choices --
    which adds a token's terms in the same order; past the capacity the full one runs.  Alone or
    in a scan a ``cond`` runs one branch; UNDER A CLIENT ``vmap`` IT RUNS
    BOTH (``parallel/round_engine.py`` ``_train_slots``: the expert cells run
    ``round_chunk`` 1, which has none).

    Returns ``(y [T, D], counters)``: ``tokens`` ``[held]`` pairs per held
    expert, ``assign`` ``[3]`` = (pairs routed, pairs on held experts, pairs
    on held experts that were not computed -- always 0), ``compact`` ``[2]`` =
    (1 if the compact dispatch ran, 1)."""
    T, K = sel.shape
    held, A = experts[0].shape[0], T * K
    with scope("moe/dispatch"):
        local = sel.reshape(A) - first
        is_held = (local >= 0) & (local < held)
        e = jnp.where(is_held, local, held)                       # held = none
        n_rows = (A // tile + held) * tile                        # >= any sum of padded groups
        cap = moe_capacity(held, tile, n_rows)
        if cap is None:
            compact = jnp.float32(0.0)
            counts, slot, rows, tile_expert, ends = _sorted_groups(e, is_held, held, tile, n_rows)
        else:  # a compare and a reduction say whether the tiles in use fit
            load = jnp.sum(e[:, None] == jnp.arange(held)[None, :], axis=0, dtype=jnp.int32)
            fits = jnp.sum(-(-load // tile)) * tile <= cap
            compact = fits.astype(jnp.float32)
            counts, slot, rows, tile_expert, ends = lax.cond(
                fits, partial(_compact_groups, held=held, tile=tile, n_rows=n_rows, cap=cap, K=K),
                partial(_sorted_groups, held=held, tile=tile, n_rows=n_rows), e, is_held)
    y, computed = _grouped_experts(body, compute_dtype, tile, h, w.astype(h.dtype), rows,
                                   slot.reshape(T, K), tile_expert, ends[-1], tuple(experts),
                                   sc(jnp.ones((), h.dtype)))
    n_held = jnp.sum(counts)
    assign_ct = jnp.stack([jnp.int32(A), n_held, n_held - computed]).astype(jnp.float32)
    return y, {"tokens": counts.astype(jnp.float32), "assign": assign_ct,
               "compact": jnp.stack([compact, jnp.float32(1.0)])}


def gq_attention_tile(S: int, d: int):
    """The tile :func:`causal_gq_attention` gives its fused kernels at ``S``
    positions and heads of ``d`` dims, None where it takes the block loop: its
    own test, said once more for a caller that counts (``models/ouro.py``), as
    :func:`selected_attention_tile` is.  (At the file's end: a Mosaic kernel's
    compile-cache key holds its call stack, so no line above moves.)"""
    if jax.default_backend() != "tpu":
        return None
    from . import pallas_attention

    return pallas_attention.gq_tile_for(S, d)


@scoped("swa")
def sliding_gq_attention(q, k, v, scale, window: int, block: int = ATTN_BLOCK):
    """:func:`causal_gq_attention` of a SLIDING layer: query ``i`` sees key
    ``j`` if ``j <= i`` and ``i - j < window`` (itself and the ``window - 1``
    before it).  On a TPU the ``band_attn_fwd`` / ``band_attn_bwd`` kernels,
    which fetch and compute only the key tiles the band crosses; elsewhere the
    block loop, whose blocks start at the band's lower edge.  Under the scope
    ``swa``, as the full layers' stays ``attn``."""
    return _planned_gq_attention(q, k, v, scale, block, window)


def sliding_attention_tiles(S: int, d: int, group: int, window: int, block: int = ATTN_BLOCK):
    """What :func:`sliding_gq_attention` runs at these shapes, for a caller
    that counts (``models/laguna.py``): ``(fused, visited, causal)`` = whether
    the kernel pair takes it, and the key tiles (blocks, for the block loop)
    it visits of those on or under the diagonal, from the grid's extents."""
    from . import pallas_attention  # Pallas: imported where a Laguna model is built

    plan = pallas_attention.gq_plan(S, d, group, window) if jax.default_backend() == "tpu" \
        else None
    tq, tk = (block, block) if plan is None else plan[1:]
    visited, causal = pallas_attention.band_extent(S, tq, tk, window)
    return plan is not None, visited, causal


def band_attention_planned(S: int, d: int, group: int, window=None) -> bool:
    """Whether :func:`causal_gq_attention` (``window`` None) or
    :func:`sliding_gq_attention` takes the ``band_attn_fwd`` / ``band_attn_bwd``
    pair at these shapes, whose results and operands carry names: for a caller
    that counts (``models/laguna.py``), as :func:`gq_attention_tile` is."""
    if jax.default_backend() != "tpu":
        return False
    from . import pallas_attention

    plan = pallas_attention.gq_plan(S, d, group, window)
    return plan is not None and plan[0] == "band"


# ---------------------------------------------------------------------------
# A selective state-space mixer (Mamba-2 / SSD; models/nemotron_h.py) and a
# two-matrix expert.  Plain ``jax.numpy`` but for the scan's kernel pair on a
# TPU (ops/pallas_ssm.py): no other family's Mosaic call stack passes here
# ---------------------------------------------------------------------------

def relu2_ffn(x, wu, wd, sc, compute_dtype=None):
    """``sc(relu(sc(x wu))**2 wd)`` (``mlp_hidden_act: relu2``): a two-matrix
    feed-forward, no gate; ``sc`` the HeteroFL Scaler."""
    h = jnp.square(jax.nn.relu(sc(linear(x, wu, compute_dtype=compute_dtype))))
    return sc(linear(h, wd, compute_dtype=compute_dtype))


@scoped("ssm/conv")
def causal_conv_silu(x, taps, bias):
    """``silu(conv(x) + bias)``, the convolution a state-space mixer runs over
    its ``x``, ``B`` and ``C`` channels: depthwise, causal, ``y[t] = sum_j
    taps[j] * x[t - (L - 1) + j]``, zero to the left of a row's first position.
    ``x`` ``[N, S, C]``, ``taps`` ``[L, C]`` (tap ``j`` of channel ``c``: the
    published ``conv1d.weight[c, 0, j]``), ``bias`` ``[C]``.  Rows never mix
    and a channel reads only itself, so a masked channel (taps and bias zero)
    stays zero.  Shifted slices, as :func:`short_conv`."""
    L, S = taps.shape[0], x.shape[1]
    z = jnp.pad(x, ((0, 0), (L - 1, 0), (0, 0)))
    y = sum(taps[j] * lax.slice_in_dim(z, j, j + S, axis=1) for j in range(L))
    return jax.nn.silu(y + bias)


@scoped("ssm/scan")
def ssm_chunked_scan(x, dt, a, b, c, chunk: int):
    """The selective state-space recurrence of Mamba-2, chunk by chunk (the
    SSD form of arXiv:2405.21060).  Per row, head ``h`` of group ``g`` (the
    heads ``[g * H/G, (g + 1) * H/G)`` share ``B`` and ``C``), from a zero
    state ``[P, Ns]``:

        S_t = exp(dt_t a_h) S_{t-1} + dt_t x_t B_t^T;    y_t = S_t C_t

    ``x`` ``[N, S, H, P]``, ``dt`` ``[N, S, H]`` (after its softplus), ``a``
    ``[H]`` (negative), ``b`` / ``c`` ``[N, S, G, Ns]``; returns ``(y [N, S, H,
    P], keep)`` with ``keep`` ``[2]`` = (the sum of ``exp(dt a)`` over rows,
    positions and heads, their count): the share of the state a position keeps.
    The skip ``D x`` is the caller's.

    With ``l_t`` the cumulative sum of ``dt a`` inside a chunk of ``chunk``
    positions, position ``i`` of a chunk reads ``sum_{j <= i} exp(l_i - l_j)
    (C_i . B_j) dt_j x_j`` from its own chunk (a ``[chunk, chunk]`` product a
    group, masked and decayed a head, against ``x``) and ``exp(l_i) C_i
    S_prev`` from the state the chunks before it left, which a ``lax.scan``
    over the row's chunks carries: ``S_c = exp(l_last) S_{c-1} + sum_j
    exp(l_last - l_j) dt_j x_j B_j^T``.  So the work is matrix products, the
    sequential part is one elementwise step a chunk, and the backward keeps a
    state a CHUNK and none a position (``[S, H, P, Ns]`` float32 is 17 GB a
    layer at 8,192 positions, 64 heads of 64 and a state of 128).  The log
    decays, their exponentials and the carried state are float32; the
    products take their operands at the default matmul precision, as
    :func:`linear` does.  A row that is no whole number of chunks is padded on
    the right with ``dt = 0`` (a position that neither decays nor writes).

    On a TPU, where :func:`ssm_scan_plan` finds a block (whole chunks, chunk
    and state multiples of 128, a group's heads filling whole lanes), what
    follows ``la = dt a`` runs as the fused kernels ``ssm_scan_fwd`` /
    ``ssm_scan_bwd`` of ``pallas_ssm``: the same mathematics at the same
    precision, with a chunk's decay matrices, its decayed scores and the
    chunks' states in VMEM only.  Elsewhere (the CPU; a client's narrow slice
    at its own widths; a ragged row) the ``jnp`` form below, which is the
    kernels' oracle."""
    N, S, H, P = x.shape
    G, Ns = b.shape[2:]
    R, Q = H // G, chunk
    la = dt * a                                                   # [N, S, H] log decay, <= 0
    keep = jnp.stack([jnp.sum(jnp.exp(la)), jnp.float32(N * S * H)])
    if ssm_scan_plan(S, H, P, G, Ns, chunk) is not None:
        from . import pallas_ssm

        return pallas_ssm.fused_ssm_scan(x, dt, la, b, c, chunk), keep
    xd = x * dt[..., None]
    pad = -S % Q
    if pad:
        la, xd, b, c = (jnp.pad(t, ((0, 0), (0, pad)) + ((0, 0),) * (t.ndim - 2))
                        for t in (la, xd, b, c))
    nc = (S + pad) // Q
    xd = xd.reshape(N, nc, Q, G, R, P)
    b, c = b.reshape(N, nc, Q, G, Ns), c.reshape(N, nc, Q, G, Ns)
    cum = jnp.cumsum(la.reshape(N, nc, Q, G, R), axis=2)          # l_i, inclusive
    last = cum[:, :, -1]                                          # [N, nc, G, R]

    # a chunk's own positions
    lh = jnp.moveaxis(cum, 2, -1)                                 # [N, nc, G, R, Q]
    i_j = lh[..., :, None] - lh[..., None, :]                     # l_i - l_j
    causal = jnp.arange(Q)[:, None] >= jnp.arange(Q)[None, :]
    decay = jnp.exp(jnp.where(causal, i_j, -jnp.inf))             # [N, nc, G, R, Qi, Qj]
    cb = jnp.einsum("nciga,ncjga->ncgij", c, b)                   # [N, nc, G, Qi, Qj]
    y = jnp.einsum("ncgrij,ncjgrp->ncigrp", cb[:, :, :, None] * decay, xd)

    # what a chunk leaves, and the scan that carries it
    to_end = jnp.exp(last[:, :, None] - cum)                      # [N, nc, Q, G, R]
    left = jnp.einsum("ncjgrp,ncjga->ncgrpa", xd * to_end[..., None], b)

    def carry(state, chunk_):
        left_c, last_c = chunk_
        return state * jnp.exp(last_c)[..., None, None] + left_c, state  # the state BEFORE it

    _, before = lax.scan(carry, jnp.zeros((N, G, R, P, Ns), jnp.float32),
                         (jnp.moveaxis(left, 1, 0), jnp.moveaxis(last, 1, 0)))
    before = jnp.moveaxis(before, 0, 1)                           # [N, nc, G, R, P, Ns]
    y = y + jnp.einsum("nciga,ncgrpa->ncigrp", c, before) * jnp.exp(cum)[..., None]
    return y.reshape(N, nc * Q, H, P)[:, :S], keep


def ssm_scan_plan(S: int, H: int, P: int, G: int, Ns: int, chunk: int):
    """The block :func:`ssm_chunked_scan` gives its fused kernels at ``S``
    positions, ``H`` heads of ``P`` dims in ``G`` groups, a state of ``Ns``
    and chunks of ``chunk``, None where it takes the ``jnp`` form: off a TPU,
    or where ``pallas_ssm.ssm_plan`` takes no such shapes."""
    if jax.default_backend() != "tpu":
        return None
    from . import pallas_ssm

    return pallas_ssm.ssm_plan(S, H, P, G, Ns, chunk)


@scoped("ssm/norm")
def gated_group_rms_norm(y, z, g, mask, count, groups: int, eps: float):
    """``RMSNorm_groups(y * silu(z)) * g``: the mean square over each of
    ``groups`` equal blocks of the last axis, counting only a block's
    ``count`` active channels (a HeteroFL slice keeps a prefix of every HEAD,
    and a block holds whole heads: the active channels of one block, not its
    width).  ``mask`` the 0/1 mask over the last axis; ``g`` is zero at masked
    channels, which zeroes the output there."""
    v = y * jax.nn.silu(z) * mask
    vg = v.reshape(v.shape[:-1] + (groups, v.shape[-1] // groups))
    ms = jnp.sum(vg * vg, axis=-1, keepdims=True) / count
    return (vg / jnp.sqrt(ms + eps)).reshape(v.shape) * g


# ---------------------------------------------------------------------------
# The mixers of a decoder-hybrid-decoder (models/phi4flash.py): a Mamba-1
# selective scan, differential attention and a gated memory unit.  Plain
# ``jax.numpy``; the attention's softmaxes through the pairs above.
# ---------------------------------------------------------------------------

@scoped("ssm/scan")
def selective_scan(x, dt, a, b, c, chunk: int, block: int):
    """The selective state-space recurrence of Mamba-1 (arXiv:2312.00752): a
    decay for every (channel, state) pair, no heads.  Per row, from a zero
    state ``H`` ``[E, Ns]``:

        H_t = exp(dt_t[:, None] * a) * H_{t-1} + (dt_t * x_t)[:, None] * B_t[None, :]
        y_t = H_t C_t

    ``x`` / ``dt`` ``[N, S, E]`` (``dt`` after its softplus), ``a`` ``[E, Ns]``
    (negative), ``b`` / ``c`` ``[N, S, Ns]``; returns ``(y [N, S, E], keep)``
    with ``keep`` ``[2]`` = (the sum of ``exp(dt a)`` over rows, positions,
    channels and states, their count): the share of the state a position
    keeps, over every channel of the arrays it is given.  The skip ``D x`` is
    the caller's.

    A ``lax.scan`` over the row's chunks of ``chunk`` positions carries ``H``
    (``[N, Ns, E]``: the channels on the lanes), each chunk under
    ``jax.checkpoint``: the backward keeps a state a CHUNK and never a state a
    position (``[S, E, Ns]`` float32 is 2.7 GB a tensor at 8,192 positions and
    5,120 channels).  Inside a chunk the recurrence is the ASSOCIATIVE
    combination of (decay, input) pairs, ``(a2 a1, a2 b1 + b2)``, taken in two
    levels: the chunk's blocks of ``block`` positions side by side, each run
    position by position from a zero state (what it leaves and its whole
    decay), the blocks' start states from those one after another, and each
    block once more from its true start, read by ``C``.  Every factor is a
    product of decays in (0, 1], so it stays finite for any ``dt``; the
    cumulative log-decay form's ``exp(-l_j)`` overflows once ``dt a`` summed
    over a chunk passes 88, which a trained time step can (``dt`` 1 on a decay
    of 16 does in six positions), and ``lax.associative_scan``'s log-depth
    tree moves the ``[chunk, E, Ns]`` tensors some sixteen times where this
    form reads and writes a block's state once a position.  No matrix product:
    decays, states and the read by ``C`` (a sum over ``Ns``) are float32
    elementwise.  A row that is no whole number of chunks is padded on the
    right with ``dt = 0`` (a position that neither decays nor writes)."""
    N, S, E = x.shape
    Ns = a.shape[1]
    at = a.T                                                      # [Ns, E]
    block = min(block, chunk)
    Q = -(-min(chunk, S) // block) * block                        # whole blocks
    pad = -S % Q
    u = dt * x
    if pad:
        dt, u, b, c = (jnp.pad(t, ((0, 0), (0, pad), (0, 0))) for t in (dt, u, b, c))
    nc, nb = (S + pad) // Q, Q // block

    def by_chunk(t):  # [N, S, F] -> [chunks, block, N, blocks, F]
        return jnp.moveaxis(t.reshape(N, nc, nb, block, t.shape[-1]), (1, 3), (0, 1))

    @jax.checkpoint
    def one(state, chunk_):
        dt_c, u_c, b_c, c_c = chunk_

        def step(h, t):  # one position of every block: h [N, blocks, Ns, E]
            decay = jnp.exp(dt_c[t][:, :, None, :] * at)
            return decay * h + u_c[t][:, :, None, :] * b_c[t][..., None], decay

        # each block from a zero state: what it leaves, and its whole decay
        h = jnp.zeros((N, nb, Ns, E), jnp.float32)
        whole, kept = jnp.ones_like(h), jnp.float32(0.0)
        for t in range(block):
            h, decay = step(h, t)
            whole, kept = whole * decay, kept + jnp.sum(decay)
        # the blocks one after another: the state each starts from
        starts = []
        for j in range(nb):
            starts.append(state)
            state = whole[:, j] * state + h[:, j]
        # each block again from its true start, read by C
        h, ys = jnp.stack(starts, axis=1), []
        for t in range(block):
            h, _ = step(h, t)
            ys.append(jnp.sum(h * c_c[t][..., None], axis=2))     # [N, blocks, E]
        return state, (jnp.stack(ys, axis=2), kept)

    _, (y, kept) = lax.scan(one, jnp.zeros((N, Ns, E), jnp.float32),
                            tuple(by_chunk(t) for t in (dt, u, b, c)))
    y = jnp.moveaxis(y, 0, 1).reshape(N, nc * Q, E)[:, :S]        # [chunks, N, blocks, block, E]
    # a padded position's decay is 1 for every (channel, state)
    keep = jnp.stack([jnp.sum(kept) - N * pad * E * Ns, jnp.float32(N * S * E * Ns)])
    return y, keep


def differential_attention_planned(S: int, d: int, group: int, dv: int, window=None) -> bool:
    """Whether a softmax of :func:`differential_attention` takes a fused
    kernel pair at these shapes (``pallas_attention.gq_plan`` with the value's
    own width), for a caller that counts (``models/phi4flash.py``), as
    :func:`band_attention_planned` is."""
    if jax.default_backend() != "tpu":
        return False
    from . import pallas_attention

    return pallas_attention.gq_plan(S, d, group, window, dv) is not None


def differential_attention(q1, q2, k1, k2, v, lam, lam0: float, g_sub, window=None, *, scale,
                           mask, count, eps: float = 1e-5, block: int = ATTN_BLOCK):
    """Differential attention (arXiv:2410.05258), the score / softmax / value
    part and the combine, heads first: a query pair's two heads ``q1`` / ``q2``
    ``[N, H, S, d]`` against a key pair's ``k1`` / ``k2`` ``[N, Hkv, S, d]`` and
    ONE value ``v`` ``[N, Hkv, S, dv]`` (a pair's two value heads side by
    side, ``dv = 2 d``), grouped as :func:`causal_gq_attention` groups them:

        a_j = softmax_mask(q_j k_j^T * scale) v,   j = 1, 2
        o = RMSNorm_dv(a_1 - lam * a_2; g_sub, eps) * (1 - lam0)

    the mask causal and, with ``window``, ``t - s < window``.  ``lam`` a
    float32 scalar (learned), ``lam0`` the layer's constant; ``mask`` /
    ``count`` the 0/1 mask over the value's ``dv`` dims and its active dims (a
    HeteroFL slice keeps a prefix of each of the two heads).  Returns ``o``
    ``[N, H, S, dv]`` float32.

    Each softmax is ONE call with the ``dv``-wide value (two score products a
    pair), through what ``pallas_attention.gq_plan`` gives the shapes with the
    value's width as its own: the ``gq_attn`` pair under the diagonal alone,
    the ``jnp`` block loop under a window (the band pair takes no value wider
    than its keys) and off a TPU.  The subtraction, the sub-norm and the
    constant under ``diff``."""
    attend = causal_gq_attention if window is None else partial(sliding_gq_attention, window=window)
    a1, a2 = (attend(q, k, v, scale, block=block) for q, k in ((q1, k1), (q2, k2)))
    with scope("diff"):
        o = (a1 - lam * a2) * mask
        ms = jnp.sum(o * o, axis=-1, keepdims=True) / count
        return o / jnp.sqrt(ms + eps) * g_sub * (1.0 - lam0)


@scoped("gmu")
def gated_memory_unit(h, m, w1, w2, sc, compute_dtype=None):
    """``sc((m * silu(sc(h w1))) w2)``: a gated memory unit (arXiv:2507.06607),
    the normed ``h`` ``[N, S, D]`` gating, element by element, the memory ``m``
    ``[N, S, E]`` that ANOTHER layer's scan wrote; ``sc`` the HeteroFL Scaler.
    A masked channel of ``m`` meets a masked column of ``w1``: the two layers'
    channels are one width group."""
    gate = jax.nn.silu(sc(linear(h, w1, compute_dtype=compute_dtype)))
    return sc(linear(m * gate, w2, compute_dtype=compute_dtype))
