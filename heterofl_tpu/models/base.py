"""Model definition container and init helpers.

Models are pure functions over flat ``{name: array}`` param dicts (explicit
pytrees, haiku-style without the framework): ``init(key) -> params`` and
``apply(params, batch, ...) -> (output, bn_stats)``.  Widths are static
(global model sizes); per-client width heterogeneity enters only through the
traced ``width_rate``/``scaler_rate`` scalars and the masks they induce, so
one compiled program serves every rate level.

Here: the container and the two initialisers every family uses.  What the
decoder-only language models share beyond that (their leaf book, ``apply``'s
prologue and tail, the expert layers' helpers) is ``models/decoder.py``.
"""

from __future__ import annotations

from dataclasses import dataclass, field
from typing import Any, Callable, Dict, List

import jax
import jax.numpy as jnp

from ..config import LM_MODEL_NAMES as LM_KINDS
from .spec import Group, ParamSpec


@dataclass
class ModelDef:
    name: str
    init: Callable[[jax.Array], Dict[str, jnp.ndarray]]
    apply: Callable[..., Any]
    specs: Dict[str, ParamSpec]
    groups: Dict[str, Group]
    bn_sites: List[str] = field(default_factory=list)  # prefixes carrying sBN state
    meta: Dict[str, Any] = field(default_factory=dict)

    @property
    def is_lm(self) -> bool:
        """Trains on token rows (the engines' LM path), whatever the family."""
        return self.meta.get("kind") in LM_KINDS

    def init_bn_state(self) -> Dict[str, Any]:
        """Zeroed running (mean, var) per BN site, matching fresh
        ``track=True`` modules (ref train_classifier_fed.py:127-138)."""
        out = {}
        for site in self.bn_sites:
            size = self.meta["bn_sizes"][site]
            out[site] = (jnp.zeros(size, jnp.float32), jnp.ones(size, jnp.float32))
        return out


def uniform_fan_in(key: jax.Array, shape, fan_in: int) -> jnp.ndarray:
    """torch's default kaiming_uniform(a=sqrt(5)): U(-1/sqrt(fan_in), +)."""
    # staticcheck: allow(no-asarray, no-float-coercion): init-time static
    # fan-in scalar, never on the round path
    bound = 1.0 / jnp.sqrt(jnp.asarray(float(fan_in)))
    return jax.random.uniform(key, shape, jnp.float32, -bound, bound)


def normal_init(key: jax.Array, shape, std: float) -> jnp.ndarray:
    return std * jax.random.normal(key, shape, jnp.float32)
