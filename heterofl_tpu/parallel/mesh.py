"""Device mesh construction.

The communication backend of the framework: clients are laid out along a
``clients`` mesh axis (federated aggregation = ``psum`` over ICI), with an
optional ``data`` axis for intra-client batch / eval-set data parallelism.
This replaces the reference's in-process deepcopy "communication"
(ref src/fed.py:165-178 and SURVEY §2.4) with real XLA collectives.
"""

from __future__ import annotations

from typing import Optional, Sequence

import numpy as np

import jax
from jax.sharding import Mesh


def make_mesh(n_clients: Optional[int] = None, n_data: int = 1,
              devices: Optional[Sequence[jax.Device]] = None,
              n_arms: int = 1) -> Mesh:
    """Build a ``(clients, data)`` mesh -- or ``(arms, clients, data)``
    with ``n_arms > 1`` (ISSUE 14: the ``experiments`` mesh dimension).

    ``n_clients=None`` uses all devices (divided by ``n_data`` and
    ``n_arms``).  On a single chip this degenerates to a 1x1 mesh and the
    collectives become no-ops -- same program, any scale.  The arms axis
    places each experiment arm's whole federation on its own disjoint
    device rows: the per-arm ``psum`` over ``clients`` reduces within an
    arm's rows only, so E arms execute CONCURRENTLY on a mesh a single
    arm cannot fill (the engines' ``arms_placement='mesh'``).
    """
    devices = list(devices if devices is not None else jax.devices())
    n_arms = max(1, int(n_arms))
    if n_clients is None:
        if len(devices) % (n_data * n_arms):
            raise ValueError(f"{len(devices)} devices not divisible by "
                             f"data x arms axes ({n_data} x {n_arms})")
        n_clients = len(devices) // (n_data * n_arms)
    need = n_clients * n_data * n_arms
    if need > len(devices):
        raise ValueError(f"need {need} devices, have {len(devices)}")
    if n_arms > 1:
        arr = np.array(devices[:need]).reshape(n_arms, n_clients, n_data)
        return Mesh(arr, ("arms", "clients", "data"))
    arr = np.array(devices[:need]).reshape(n_clients, n_data)
    return Mesh(arr, ("clients", "data"))


def initialize_distributed() -> bool:
    """Multi-host bring-up: join the JAX distributed runtime when coordinator
    env vars are present, so ``jax.devices()`` spans all hosts and
    :func:`make_mesh` lays the ``clients``/``data`` axes over ICI within a
    slice and DCN across slices (XLA routes collectives accordingly).

    Reads the standard ``JAX_COORDINATOR_ADDRESS`` / ``JAX_NUM_PROCESSES`` /
    ``JAX_PROCESS_ID`` variables (no-op when absent -- single-host runs and
    TPU pod auto-detection need nothing), plus optional
    ``JAX_LOCAL_DEVICE_IDS`` (comma-separated).  Returns True if initialised.
    """
    import os

    addr = os.environ.get("JAX_COORDINATOR_ADDRESS")
    if not addr:
        return False
    import jax as _jax

    # jax.distributed.initialize() only auto-detects num_processes/process_id
    # under a recognised cluster scheduler (SLURM & co.); on a hand-launched
    # pod the documented env vars must be forwarded explicitly -- and must be
    # set *together*: a half-specified pair fails deep inside the runtime with
    # a confusing error, so validate here
    num = os.environ.get("JAX_NUM_PROCESSES")
    pid = os.environ.get("JAX_PROCESS_ID")
    if (num is None) != (pid is None):
        raise RuntimeError(
            "JAX_NUM_PROCESSES and JAX_PROCESS_ID must be set together "
            f"(got JAX_NUM_PROCESSES={num!r}, JAX_PROCESS_ID={pid!r})")
    local = os.environ.get("JAX_LOCAL_DEVICE_IDS")
    # the XLA:CPU backend runs cross-process collectives only through an
    # explicit collectives layer (gloo); without it every multi-process
    # dispatch dies with "Multiprocess computations aren't implemented on
    # the CPU backend" -- select it before the backend initialises (the
    # 2-process CPU probe, tests/test_pod.py; harmless for TPU runs where
    # the platform is not cpu)
    if os.environ.get("JAX_PLATFORMS", "").strip() == "cpu":
        _jax.config.update("jax_cpu_collectives_implementation", "gloo")
    _jax.distributed.initialize(
        coordinator_address=addr,
        num_processes=int(num) if num is not None else None,
        process_id=int(pid) if pid is not None else None,
        local_device_ids=[int(x) for x in local.split(",")] if local else None,
    )
    return True
