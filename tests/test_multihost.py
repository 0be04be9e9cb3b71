"""Multi-host bring-up path (VERDICT r1 weak 6).

``initialize_distributed`` is a no-op in ordinary tests; here it runs for
real: a subprocess joins a single-process JAX distributed runtime (the
coordinator lives in-process), builds the (clients, data) mesh over the
virtual CPU devices, and runs a psum collective -- the same bring-up a TPU
pod takes with multiple processes (ref SURVEY §2.4: the reference has no
distributed backend at all; this is the TPU-native equivalent's smoke
test).  The process-0 checkpoint gate itself cannot be meaningfully
exercised with process_count == 1; its condition lives in
entry/common.py and is asserted by inspection there.
"""

import os
import socket
import subprocess
import sys

import pytest

# spawns a JAX distributed subprocess (fast gate excludes this module)
pytestmark = pytest.mark.slow

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

_CHILD = r"""
import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import PartitionSpec as P

from heterofl_tpu.parallel.mesh import initialize_distributed, make_mesh
from heterofl_tpu.parallel.round_engine import _shard_map  # version-compat shim

assert initialize_distributed() is True, "env vars present -> must initialise"
assert jax.process_count() == 1
assert jax.process_index() == 0
devs = jax.devices()
assert len(devs) == 8, devs
mesh = make_mesh(4, 2, devices=devs)

def body(x):
    return jax.lax.psum(x, "clients")

fn = jax.jit(_shard_map(body, mesh, P("clients"), P("clients")))
x = jnp.arange(8.0).reshape(4, 2)
out = np.asarray(fn(x))
np.testing.assert_allclose(out, np.tile(x.sum(0), (4, 1)))
print("MULTIHOST_OK")
"""


def _free_port() -> int:
    s = socket.socket()
    s.bind(("127.0.0.1", 0))
    port = s.getsockname()[1]
    s.close()
    return port


def test_initialize_distributed_single_process_runtime():
    env = dict(os.environ)
    env.update({
        "JAX_PLATFORMS": "cpu",
        "XLA_FLAGS": "--xla_force_host_platform_device_count=8",
        "PYTHONPATH": REPO,
        "JAX_COORDINATOR_ADDRESS": f"127.0.0.1:{_free_port()}",
        "JAX_NUM_PROCESSES": "1",
        "JAX_PROCESS_ID": "0",
    })
    res = subprocess.run([sys.executable, "-c", _CHILD], env=env,
                         capture_output=True, text=True, timeout=600)
    assert res.returncode == 0, res.stderr[-2000:]
    assert "MULTIHOST_OK" in res.stdout


def test_initialize_distributed_noop_without_env(monkeypatch):
    from heterofl_tpu.parallel.mesh import initialize_distributed

    monkeypatch.delenv("JAX_COORDINATOR_ADDRESS", raising=False)
    assert initialize_distributed() is False
