"""A parameter tree as one flat f32 vector: the layout of what crosses the
wire or waits a round.

:class:`FlatSpec` packs a ``{name: array}`` tree into a single contiguous
vector (sorted-key leaf order, each leaf a contiguous row-major segment).
Its users run once a round, outside the local-step scan: the wire codecs
(``compress/codecs.py``), the grouped engine's per-level payloads
(``parallel/grouped.py``), the scheduler's staleness buffer
(``sched/buffer.py``) and the static audit's payload accounting
(``staticcheck/audit.py``).  :data:`LANE` is the lane width such a vector
is packed to where a kernel reads it (``ops/quant.py``).
"""

from __future__ import annotations

from typing import Dict, Tuple

import jax.numpy as jnp


#: lane width of the flattened-tree packing (TPU vector lane count)
LANE = 128


class FlatSpec:
    """Static packing of a ``{name: array}`` tree into one flat f32 vector.

    Leaf order is sorted-key order -- the same order jax flattens a dict.
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
