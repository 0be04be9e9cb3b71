"""Norm zoo shared by the vision models.

Parity with the reference's inline norm construction (models/conv.py:13-24,
models/resnet.py:15-31): ``bn`` -> BatchNorm(momentum=None,
track_running_stats=track), ``in`` -> GroupNorm(C, C), ``ln`` -> GroupNorm(1,
C), ``gn`` -> GroupNorm(4, C), ``none`` -> identity.  All masked-width-aware.
"""

from __future__ import annotations

from typing import Dict, Optional, Tuple

import jax.numpy as jnp

from ..config import NORM_TYPES  # noqa: F401  (canonical registry, re-exported)
from ..obs.trace import scope
from ..ops.layers import batch_norm, dynamic_group_norm


def norm_has_params(norm_type: str) -> bool:
    return norm_type != "none"


def norm_init(norm_type: str, size: int) -> Dict[str, jnp.ndarray]:
    """weight=1, bias=0 (ref models/utils.py:4-10)."""
    if norm_type == "none":
        return {}
    return {"g": jnp.ones(size, jnp.float32), "b": jnp.zeros(size, jnp.float32)}


def apply_norm(norm_type: str, x: jnp.ndarray, g: Optional[jnp.ndarray],
               b: Optional[jnp.ndarray], *, mask: jnp.ndarray, k,
               bn_mode: str = "batch",
               bn_running: Optional[Tuple[jnp.ndarray, jnp.ndarray]] = None,
               sample_weight: Optional[jnp.ndarray] = None,
               bn_axis=None, use_pallas: bool = False):
    """Apply one norm site. Returns ``(y, bn_stats_or_None)``.

    ``mask``/``k``: channel activity mask and active count for the client's
    width (full-width callers pass all-ones / the static size).
    """
    if norm_type == "none":
        return x, None
    if norm_type == "bn":
        if (use_pallas and bn_mode == "batch" and bn_running is None
                and bn_axis is None):
            from ..ops.pallas_norm import batch_norm_pallas

            return batch_norm_pallas(x, g, b, sample_weight=sample_weight), None
        return batch_norm(x, g, b, mode=bn_mode, running=bn_running,
                          sample_weight=sample_weight, axis_name=bn_axis)
    if norm_type == "in":
        # GroupNorm(C, C): per-sample per-channel stats over spatial dims.
        axes = tuple(range(1, x.ndim - 1))
        with scope("norm"):
            mean = jnp.mean(x, axis=axes, keepdims=True)
            var = jnp.mean((x - mean) ** 2, axis=axes, keepdims=True)
            return (x - mean) / jnp.sqrt(var + 1e-5) * g + b, None
    if norm_type == "ln":
        return dynamic_group_norm(x, g, b, 1, mask, k), None
    if norm_type == "gn":
        return dynamic_group_norm(x, g, b, 4, mask, k), None
    raise ValueError("Not valid norm")
