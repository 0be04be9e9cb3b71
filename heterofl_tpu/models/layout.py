"""Explicit layout/dtype policy for the round programs (ISSUE 5 pass 2).

The hot path is per-step-latency-bound, so a hidden relayout (a transpose
or copy XLA inserts to reconcile a parameter's device layout with the
layout the compute wants) is pure tax -- and the K-round superstep scan
pays it per scan trip if the params carry enters the program in a layout
the scan body does not keep.  This module makes the repo's implicit
conventions an explicit, enforceable policy:

* **Activations NHWC, conv kernels HWIO, linear kernels [in, out]** -- the
  native XLA:TPU layouts (``ops/layers.py`` has always computed in these;
  the dimension-numbers constant now lives HERE and layers.py consumes it,
  so the convention has one owner).
* **Width-group axes minor-most**: every parameter's HeteroFL width axis
  (the axis its ``ParamSpec`` slices -- conv output channels, linear
  output features, BN/embedding vectors) must be the trailing axis, which
  row-major packs into the 128-wide TPU lane dimension.  Lane-packed BN
  moment vectors ((C,) trailing) ride the same rule.  ``check_policy``
  audits a model's spec table against it.
* **Pinned program-entry layouts**: ``param_formats`` emits per-leaf
  ``jax.experimental.layout.Format`` objects (row-major major-to-minor --
  the policy above makes row-major the compute layout) and ``pin_params``
  commits a params tree with them, so the jitted round/superstep programs
  specialise on exactly that layout and the scan carry is never re-laid
  out at the program boundary.  Applied on TPU backends only: XLA:CPU
  (the test mesh) ignores custom device layouts, so there ``pin_params``
  is the identity and the policy is exercised structurally by tests.

Param dtype policy is unchanged and re-stated here: params and optimizer
state are float32; ``compute_dtype`` (bf16) casts MXU operands per-op and
never leaks into stored state.
"""

from __future__ import annotations

from typing import Any, Dict, Tuple

import jax

from ..ops.layers import CONV_DIMENSION_NUMBERS  # noqa: F401  (the policy's
# conv convention -- owned by ops/layers.py, re-exported as policy surface)


def check_policy(specs: Dict[str, Any],
                 shapes: Dict[str, Tuple[int, ...]]) -> Dict[str, int]:
    """Audit a model's param table against the lane policy.

    Row-major packs the TRAILING axis into TPU lanes, and the policy is
    that this axis is a FEATURE axis -- either sliced by a width group
    (conv O, hidden-to-hidden linear out) or the label/classes axis of an
    output head; a weight stored transposed (torch-style [out, in]) would
    put a reduction axis in the lanes.  Returns ``{name: trailing_axis}``
    for every >=2D parameter that violates this (empty = compliant).  The
    models test gate keeps it empty for every model family."""
    bad = {}
    for name, shape in shapes.items():
        if len(shape) < 2:
            continue
        spec = specs.get(name)
        last = len(shape) - 1
        groups = getattr(spec, "axis_groups", None) or {}
        if not groups and getattr(spec, "label_axis", None) is None:
            continue  # unsliced parameter: no lane constraint
        if last not in groups and getattr(spec, "label_axis", None) != last:
            bad[name] = last
    return bad


def param_formats(params, mesh=None, spec=None):
    """Per-leaf pinned-layout ``Format`` objects for a params tree: the
    policy's row-major major-to-minor order (identity permutation), with
    the mesh's replicated sharding attached when given.

    Row-major IS the policy: :func:`check_policy` guarantees the lane axis
    is already trailing, so pinning row-major pins lanes."""
    from jax.experimental.layout import Format, Layout
    from jax.sharding import (NamedSharding, PartitionSpec as P,
                              SingleDeviceSharding)

    if mesh is not None:
        sh = NamedSharding(mesh, P() if spec is None else spec)
    else:  # Format requires a concrete sharding alongside a concrete Layout
        sh = SingleDeviceSharding(jax.devices()[0])

    def one(a):
        return Format(Layout(major_to_minor=tuple(range(a.ndim))), sh)

    return jax.tree_util.tree_map(one, params)


def pin_params(params, mesh=None, policy: str = "auto", formats=None):
    """Commit a params tree with the policy's pinned device layouts.

    ``policy``: 'auto' pins on TPU backends and passes through elsewhere
    (XLA:CPU ignores custom layouts -- pinning there would only add an
    unconditional copy to the test mesh); 'pinned' forces the pin;
    'none' is the identity.  ``formats``: a precomputed
    :func:`param_formats` tree (the steady-state path caches it -- see
    :class:`ParamPinner`).  Returns the (possibly re-put) tree."""
    if policy == "none":
        return params
    if policy == "auto" and jax.default_backend() != "tpu":
        return params
    if policy not in ("auto", "pinned"):
        raise ValueError(f"Not valid layout_policy: {policy!r}")
    return jax.device_put(params,
                          param_formats(params, mesh) if formats is None
                          else formats)


class ParamPinner:
    """Per-engine layout pin with the Format tree cached.

    The formats are static per (param shapes, mesh), so rebuilding the
    per-leaf Format objects every dispatch would be per-round host work on
    exactly the steady-state path the staging layer keeps free of per-call
    wraps; the engines construct ONE pinner and call it at their params
    commit.  Validates the policy at construction (loud config errors at
    engine build, not first dispatch); a no-op callable off-TPU under
    'auto' and always under 'none'."""

    def __init__(self, mesh, policy: str = "auto"):
        if policy not in ("auto", "pinned", "none"):
            raise ValueError(f"Not valid layout_policy: {policy!r}")
        self.mesh = mesh
        self.policy = policy
        self.active = policy == "pinned" or (
            policy == "auto" and jax.default_backend() == "tpu")
        self._formats = None

    def __call__(self, params):
        if not self.active:
            return params
        if self._formats is None:
            self._formats = param_formats(params, self.mesh)
        return jax.device_put(params, self._formats)
