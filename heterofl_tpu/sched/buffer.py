"""Buffered asynchronous aggregation (ISSUE 9): the staleness carry.

With ``cfg['schedule']['aggregation']='buffered'`` the server applies
cohort k's update while cohort k+1 trains: inside the fused K-round scan
the carry grows a second buffer holding the PREVIOUS round's reduced
``(update sums, count masks)`` pair -- flat, in the
:class:`~..ops.flatspec.FlatSpec` layout, stacked ``[2, total]`` --
and each round (a) trains its cohort on params that do NOT yet include the
in-flight update (the simulated overlap) and (b) applies the buffered
one-round-stale update with the staleness-discounted mixing weight
:func:`~.staleness_weight` ``(alpha, s=1)``.  Elements no buffered client
held keep the previous global value (the counted-average stale rule,
unchanged).

The buffer rides the scan carry, leaves the program as an output, and is
checkpointed/restored at superstep boundaries exactly like the wire-codec
error-feedback residual -- :class:`_SchedBufCarry` mirrors
:class:`~..parallel.round_engine._WireCodecCarry`, including the donation
policy: buffered programs donate ONLY the buffer carry, because donating
the replicated params carry alongside a params-sized extra output is the
trigger pattern of the XLA:CPU executable-serialization bug that forced
resid-only donation on the codec programs (see _WireCodecCarry).
"""

from __future__ import annotations

from typing import Dict, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from . import staleness_weight
from ..ops.flatspec import FlatSpec

#: rounds the in-scan buffer holds an update before it lands: the carry is
#: depth-1 by construction (cohort k's update applies while k+1 trains)
BUFFER_STALENESS = 1


def buffered_combine(params: Dict[str, jnp.ndarray], buf: jnp.ndarray,
                     summed: Dict[str, jnp.ndarray],
                     counts: Dict[str, jnp.ndarray], spec: FlatSpec,
                     alpha: float
                     ) -> Tuple[Dict[str, jnp.ndarray], jnp.ndarray]:
    """One buffered-async server step: apply the BUFFERED (one-round-stale)
    update to the globals with weight ``staleness_weight(alpha, 1)`` and
    buffer this round's freshly-reduced ``(summed, counts)`` for the next
    round.  ``buf`` is the ``[2, total]`` flat carry; a zero buffer (first
    round, or no buffered contributor for an element) leaves the globals
    untouched -- the stale rule."""
    w = staleness_weight(alpha, BUFFER_STALENESS)
    bsum, bcnt = spec.unflatten(buf[0]), spec.unflatten(buf[1])
    new_p = {k: jnp.where(bcnt[k] > 0,
                          (1.0 - w) * v + w * (bsum[k] / jnp.maximum(bcnt[k], 1.0)),
                          v)
             for k, v in params.items()}
    new_buf = jnp.stack([spec.flatten(summed), spec.flatten(counts)])
    return new_p, new_buf


class _SchedBufCarry:
    """Shared buffered-aggregation scaffolding of both round engines: the
    device-resident staleness buffer with its checkpoint read/restore pair
    (the :class:`~..parallel.round_engine._WireCodecCarry` pattern -- one
    copy on purpose).

    Expects on ``self``: ``mesh``, ``_sched_spec``, ``_sched_buf``
    (initialised to None)."""

    def _sched_buf_shape(self, params) -> Tuple[int, int]:
        return (2, FlatSpec.of(params).total)

    def _ensure_sched_buf(self, params):
        """The committed staleness carry (zeros on first use): built by a
        jitted program so the buffer is PRIVATE and donation-safe,
        replicated (every device applies the identical buffered update
        post-psum)."""
        from jax.sharding import NamedSharding, PartitionSpec as P

        shape = self._sched_buf_shape(params)
        if self._sched_buf is None or tuple(self._sched_buf.shape) != shape:
            sh = NamedSharding(self.mesh, P())
            # staticcheck: allow(jit-needs-donation): one-time zeros init
            # (nothing to donate); steady-state rounds donate the carry
            self._sched_buf = jax.jit(
                lambda: jnp.zeros(shape, jnp.float32), out_shardings=sh)()
        return self._sched_buf

    def sched_buf_host(self):
        """Host copy of the staleness buffer (checkpointing); None for sync
        aggregation or before the first buffered round."""
        if self._sched_buf is None:
            return None
        # replicated carry: every process holds the full value, so the
        # multi-process path reads its local replica (host_fetch)
        from ..parallel.staging import host_fetch
        return host_fetch(self._sched_buf)

    def set_sched_buf(self, arr) -> None:
        """Restore the staleness buffer from a checkpoint (resume):
        committed through a jitted copy so the restored buffer is
        donation-safe."""
        from jax.sharding import NamedSharding, PartitionSpec as P

        sh = NamedSharding(self.mesh, P())
        # staticcheck: allow(no-asarray): checkpoint-restore host
        # normalization; the carry reaches the mesh via the explicit
        # device_put + jitted private copy below
        host = np.asarray(arr, np.float32)
        from ..parallel.staging import commit_global
        # staticcheck: allow(jit-needs-donation): one-time restore copy
        # severing host-buffer aliasing; donating its input would free the
        # caller's checkpoint array
        self._sched_buf = jax.jit(lambda t: t + 0, out_shardings=sh)(
            commit_global(host, sh))
