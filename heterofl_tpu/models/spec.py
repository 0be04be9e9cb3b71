"""Width groups and parameter slicing specs.

The reference materialises per-client ``param_idx`` index tensors by walking
the state_dict with model-family-specific rules (``src/fed.py:26-159``).  Here
the same information is *declared once* per model as:

* ``Group`` -- a named width axis of the global model (e.g. ResNet stage 2's
  channels).  Given a client's ``width_rate`` it yields a 0/1 activity mask,
  by the :class:`GroupRule` its ``kind`` names (``GROUP_RULES``):
  - ``prefix``: first ``ceil(size * rate)`` entries active (fed.py:46-48);
  - ``per_head``: first ``ceil(head_dim * rate)`` entries of each attention
    head active (fed.py:124-131);
  - ``full``: always fully active (output layers, fed.py:43-44,85-87).
* ``ParamSpec`` -- which group governs each axis of each parameter, plus the
  axis (if any) restricted to the client's label split during aggregation
  (fed.py:193-198,228-233,263-274).

Everything is a pure function of a (possibly traced) ``width_rate`` scalar, so
dynamic-mode rate re-sampling stays inside the jitted round.
"""

from __future__ import annotations

import math
from dataclasses import dataclass, field
from typing import Dict, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np


class GroupRule:
    """How one KIND of width group cuts its axis: everything the engines ask
    of a group -- the traced 0/1 mask and active count (masked engine), the
    static slice / zero-pad (grouped engine) and the host index set (sliced
    engine, the references) -- lives in ONE object per kind, registered in
    :data:`GROUP_RULES`.  A new kind is a new rule; ``fed.core`` and
    :class:`Group` only dispatch.

    The three stock rules keep the exact op sequences the programs were
    audited with (the staticcheck baselines sit on their jaxprs)."""

    def active_count(self, g: "Group", width_rate) -> jnp.ndarray:
        raise NotImplementedError

    def mask(self, g: "Group", width_rate) -> jnp.ndarray:
        raise NotImplementedError

    def indices(self, g: "Group", width_rate: float) -> np.ndarray:
        """Concrete active index set at a static rate (host side)."""
        raise NotImplementedError

    def slice(self, v, g: "Group", width_rate: float, axis: int):
        """``v`` cut to its active entries along ``axis`` (static rate)."""
        raise NotImplementedError

    def pad(self, v, g: "Group", width_rate: float, axis: int):
        """Inverse of :meth:`slice`: zero-pad back to ``g.size``."""
        raise NotImplementedError

    def coupled_width(self, g: "Group", width_rate: float):
        """(kept by this group's rule, kept by a plain prefix of the same
        size) when the group shares its axis with a prefix group
        (``g.coupled``), else None: ``validate_width_geometry``'s check."""
        return None

    def head_width(self, g: "Group", width_rate: float):
        """Dims of ONE head this group keeps at a static rate, for a group of
        heads (None otherwise): groups of one ``g.family`` must agree."""
        return None


class _FullRule(GroupRule):
    """Never sliced (output layers, ref fed.py:43-44,85-87; an expert axis,
    a router's columns)."""

    def active_count(self, g, width_rate):
        # staticcheck: allow(no-asarray): trace-time static group size
        return jnp.asarray(g.size, jnp.int32)

    def mask(self, g, width_rate):
        return jnp.ones(g.size, jnp.float32)

    def indices(self, g, width_rate):
        return np.arange(g.size)

    def slice(self, v, g, width_rate, axis):
        return v

    def pad(self, v, g, width_rate, axis):
        return v


class _PrefixRule(GroupRule):
    """First ``ceil(size * rate)`` entries (ref fed.py:46-48)."""

    def active_count(self, g, width_rate):
        return jnp.ceil(g.size * width_rate).astype(jnp.int32)

    def mask(self, g, width_rate):
        idx = jnp.arange(g.size)
        k = jnp.ceil(g.size * width_rate)
        return (idx < k).astype(jnp.float32)

    def _keep(self, g, width_rate):
        return int(math.ceil(g.size * width_rate))

    def indices(self, g, width_rate):
        return np.arange(g.size)[:self._keep(g, width_rate)]

    def slice(self, v, g, width_rate, axis):
        return jax.lax.slice_in_dim(v, 0, self._keep(g, width_rate), axis=axis)

    def pad(self, v, g, width_rate, axis):
        pads = [(0, 0)] * v.ndim
        pads[axis] = (0, g.size - self._keep(g, width_rate))
        return jnp.pad(v, pads)


class _PerHeadRule(GroupRule):
    """First ``ceil(head_dim * rate)`` entries of each of ``num_heads``
    equal blocks (ref fed.py:124-131).  ``g.multiple`` > 1 rounds a head's
    kept count up to whole multiples (rotary dims travel in pairs)."""

    def _hd(self, g):
        return g.size // g.num_heads

    def _kh(self, g, width_rate):
        kh = jnp.ceil(self._hd(g) * width_rate)
        if g.multiple > 1:
            kh = jnp.ceil(kh / g.multiple) * g.multiple
        return kh

    def _keep(self, g, width_rate):
        kh = int(math.ceil(self._hd(g) * width_rate))
        return -(-kh // g.multiple) * g.multiple

    def active_count(self, g, width_rate):
        return (self._kh(g, width_rate).astype(jnp.int32) * g.num_heads).astype(jnp.int32)

    def mask(self, g, width_rate):
        idx = jnp.arange(g.size)
        return ((idx % self._hd(g)) < self._kh(g, width_rate)).astype(jnp.float32)

    def indices(self, g, width_rate):
        hd, kh = self._hd(g), self._keep(g, width_rate)
        return (np.arange(g.size).reshape(g.num_heads, hd)[:, :kh]).reshape(-1)

    def slice(self, v, g, width_rate, axis):
        hd, kh = self._hd(g), self._keep(g, width_rate)
        shp = v.shape
        v = v.reshape(shp[:axis] + (g.num_heads, hd) + shp[axis + 1:])
        v = jax.lax.slice_in_dim(v, 0, kh, axis=axis + 1)
        return v.reshape(shp[:axis] + (g.num_heads * kh,) + shp[axis + 1:])

    def pad(self, v, g, width_rate, axis):
        hd, kh = self._hd(g), self._keep(g, width_rate)
        shp = v.shape
        v = v.reshape(shp[:axis] + (g.num_heads, kh) + shp[axis + 1:])
        pads = [(0, 0)] * v.ndim
        pads[axis + 1] = (0, hd - kh)
        v = jnp.pad(v, pads)
        return v.reshape(shp[:axis] + (g.size,) + shp[axis + 1:])

    def coupled_width(self, g, width_rate):
        if not g.coupled:
            return None
        return (g.num_heads * self._keep(g, width_rate),
                int(math.ceil(g.size * width_rate)))

    def head_width(self, g, width_rate):
        return self._keep(g, width_rate)


#: kind -> rule.  A model family with a new way to cut an axis registers its
#: rule here (``GROUP_RULES["my_kind"] = MyRule()``) and names the kind in its
#: groups; nothing else in the engines changes.
GROUP_RULES: Dict[str, GroupRule] = {
    "full": _FullRule(), "prefix": _PrefixRule(), "per_head": _PerHeadRule()}


@dataclass(frozen=True)
class Group:
    name: str
    size: int
    kind: str = "prefix"  # a key of GROUP_RULES
    num_heads: int = 1
    multiple: int = 1     # per_head: a head keeps whole multiples of this
    #: per_head: the group cuts the SAME axis a prefix group of this size
    #: cuts (the transformer's q/k/v columns are its embedding dims), so the
    #: two kept counts must agree at every level; False when the heads have
    #: an axis of their own (latent attention's per-head dims)
    coupled: bool = True
    #: per_head: groups of one family cut heads that meet in one product
    #: (grouped-query attention: 32 query heads, 8 key/value heads and the
    #: per-head norms' gains all hold the dims of ONE head shape), so they
    #: must keep the same dims a head, in whole multiples, at every level
    #: (``fed.core.validate_width_geometry``); "" = a family of one
    family: str = ""

    @property
    def rule(self) -> GroupRule:
        try:
            return GROUP_RULES[self.kind]
        except KeyError:
            raise ValueError(f"Not valid group kind: {self.kind!r} (one of "
                             f"{sorted(GROUP_RULES)})") from None

    def active_count(self, width_rate) -> jnp.ndarray:
        """Number of active entries for a client at ``width_rate``."""
        return self.rule.active_count(self, width_rate)

    def mask(self, width_rate) -> jnp.ndarray:
        """0/1 activity mask of shape ``[size]``."""
        return self.rule.mask(self, width_rate)


@dataclass(frozen=True)
class ParamSpec:
    """Slicing rule for one parameter.

    ``axis_groups`` maps tensor axis -> group name.  Unlisted axes are never
    sliced.  ``label_axis`` marks the axis whose rows are restricted to the
    client's label split when aggregating (None for most parameters).
    """

    axis_groups: Dict[int, str] = field(default_factory=dict)
    label_axis: Optional[int] = None


def axis_mask(shape: Tuple[int, ...], axis: int, vec: jnp.ndarray) -> jnp.ndarray:
    """Broadcast a per-axis mask vector across a tensor shape."""
    view = [1] * len(shape)
    view[axis] = shape[axis]
    return vec.reshape(view)


def param_mask(shape: Tuple[int, ...], spec: ParamSpec, groups: Dict[str, Group],
               width_rate, label_mask: Optional[jnp.ndarray] = None,
               with_label: bool = False) -> jnp.ndarray:
    """Activity mask for one parameter (product over its sliced axes).

    With ``with_label=True`` the ``label_axis`` is additionally restricted by
    ``label_mask`` -- this is the aggregation-time *count* mask; without it,
    the distribute-time parameter mask.
    """
    m = jnp.ones((), jnp.float32)
    for axis, gname in spec.axis_groups.items():
        m = m * axis_mask(shape, axis, groups[gname].mask(width_rate))
    if with_label and spec.label_axis is not None and label_mask is not None:
        vec = label_mask.astype(jnp.float32)
        short = shape[spec.label_axis] - vec.shape[0]
        if short > 0:
            # e.g. the transformer's <mask>-token embedding row (vocab+1):
            # outside every label split, never aggregated (ref fed.py:263-268).
            vec = jnp.concatenate([vec, jnp.zeros(short, jnp.float32)])
        m = m * axis_mask(shape, spec.label_axis, vec)
    return jnp.broadcast_to(m, shape) if m.ndim else jnp.full(shape, m)


def mask_params(params: Dict[str, jnp.ndarray], specs: Dict[str, ParamSpec],
                groups: Dict[str, Group], width_rate) -> Dict[str, jnp.ndarray]:
    """Zero the inactive entries of every parameter (distribute-time mask).

    Equivalent to the reference's sub-model extraction (fed.py:165-178): the
    active prefix holds the global values, everything else is zero.
    """
    return {k: v * param_mask(v.shape, specs[k], groups, width_rate) for k, v in params.items()}


def count_masks(params_shapes: Dict[str, Tuple[int, ...]], specs: Dict[str, ParamSpec],
                groups: Dict[str, Group], width_rate,
                label_mask: Optional[jnp.ndarray]) -> Dict[str, jnp.ndarray]:
    """Aggregation-time contribution masks (label-restricted)."""
    return {
        k: param_mask(shape, specs[k], groups, width_rate, label_mask, with_label=True)
        for k, shape in params_shapes.items()
    }
