"""Model definition container and init helpers.

Models are pure functions over flat ``{name: array}`` param dicts (explicit
pytrees, haiku-style without the framework): ``init(key) -> params`` and
``apply(params, batch, ...) -> (output, bn_stats)``.  Widths are static
(global model sizes); per-client width heterogeneity enters only through the
traced ``width_rate``/``scaler_rate`` scalars and the masks they induce, so
one compiled program serves every rate level.
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


def held_experts(expert_share, n: int) -> range:
    """The routed experts a share ``(index, of)`` of an ``of``-way
    expert-parallel layer of ``n`` experts holds: ``[index * n/of, (index +
    1) * n/of)``."""
    index, of = (int(v) for v in expert_share)
    if of < 1 or n % of or not 0 <= index < of:
        raise ValueError(f"Not valid expert_share: {list(expert_share)!r} "
                         f"(index, of) with of dividing the {n} routed experts")
    return range(index * (n // of), (index + 1) * (n // of))


def expert_tile(tokens: int, top_k: int, experts: int) -> int:
    """Rows a step of the expert loop (``ops.layers.moe_experts``) takes:
    twice an expert's expected group (``tokens * top_k / experts`` pairs), in
    whole ``MOE_TILE``s.  An expert is then one step a pass unless its load
    doubles: its float32 weights are read once, and the loop's trip count
    stops following the seed's routing (at 256 rows, half an expected group
    of the LFM2 cell, two seeds' rounds lay 2.7 % apart on the chip and 0.4 %
    at 1,024, no slower; PERF.md, PR 32)."""
    from ..ops.layers import MOE_TILE

    return MOE_TILE * max(1, -(-2 * tokens * top_k // (experts * MOE_TILE)))


def layer_leaves(params: Dict[str, jnp.ndarray], i: int, held=None) -> Dict[str, jnp.ndarray]:
    """Layer ``i``'s leaves (``l{i}.*``) without their prefix; with ``held``
    (an expert layer), its held experts' ``moe.e{j}.{g,u,d}.w`` stacked on a
    leading axis as ``moe.e.{g,u,d}.w``, in that order."""
    pre = f"l{i}."
    lp = {k[len(pre):]: v for k, v in params.items()
          if k.startswith(pre) and ".moe.e" not in k}
    if held is not None:
        for m in "gud":
            lp[f"moe.e.{m}.w"] = jnp.stack([params[f"{pre}moe.e{j}.{m}.w"] for j in held])
    return lp
