"""The lane policy of a model's parameter table.

Activations are NHWC, conv kernels HWIO, linear kernels ``[in, out]`` --
the native XLA:TPU layouts ``ops/layers.py`` computes in (it owns the
dimension-numbers constant).  Every parameter's HeteroFL width axis (the
axis its ``ParamSpec`` slices -- conv output channels, linear output
features, BN/embedding vectors) is the trailing axis, which row-major packs
into the 128-wide TPU lane dimension.  :func:`check_policy` audits a
model's spec table against that.
"""

from __future__ import annotations

from typing import Any, Dict, Tuple

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
