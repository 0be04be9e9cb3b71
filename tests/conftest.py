"""Test harness: force an 8-device virtual CPU platform before JAX loads.

This is the TPU-native analogue of a fake distributed backend (SURVEY.md §4):
multi-chip sharding is validated on a virtual CPU mesh via
``--xla_force_host_platform_device_count``.

The platform is pinned to cpu before any backend initialises: the tests never
claim an accelerator, wherever they run.
"""

import os

os.environ["JAX_PLATFORMS"] = "cpu"
flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in flags:
    os.environ["XLA_FLAGS"] = (flags + " --xla_force_host_platform_device_count=8").strip()
os.environ.setdefault("JAX_ENABLE_X64", "0")

# Persistent XLA compile cache for the test gate: repeat tier-1 runs skip
# the expensive round-program compiles (about 40 s for the flagship
# program on XLA:CPU).  The dir is CPU-feature-fingerprinted per host; an
# operator-set JAX_COMPILATION_CACHE_DIR wins (utils/compile_cache.py).
from heterofl_tpu.utils.compile_cache import enable_persistent_cache  # noqa: E402

_CACHE_DIR = enable_persistent_cache()

import jax  # noqa: E402

jax.config.update("jax_platforms", "cpu")

# The tier-1 gate MUST run with the persistent compile cache active: without
# it every session re-pays the multi-second round-program compiles, and a
# superstep recompile (one program shape per K, ISSUE 2) silently eats the
# budget instead of showing up as a cache miss.  Fail the whole session
# loudly if the wiring ever breaks.
if not jax.config.jax_compilation_cache_dir:
    raise RuntimeError(
        "tier-1 gate requires the persistent XLA compile cache; "
        "utils/compile_cache.enable_persistent_cache() did not take effect")
if not os.path.isdir(jax.config.jax_compilation_cache_dir):
    raise RuntimeError(
        f"persistent compile cache dir {jax.config.jax_compilation_cache_dir!r} "
        f"does not exist")

# The workers of one run share that directory, and jax writes an entry in
# place (`LRUCache.put`: `exists()`, then `write_bytes`): a worker that looks
# a program up while another is writing it reads half an executable and dies
# in the deserialiser (seen: a segfault under `compiler._cache_read`).  Write
# under a name of the worker's own and rename: a reader finds no entry or a
# whole one.  With eviction on, jax's own file lock covers both sides.
from jax._src import lru_cache as _lru  # noqa: E402

_put_in_place = _lru.LRUCache.put


def _put_whole(self, key, val):
    if self.eviction_enabled or not key:
        return _put_in_place(self, key, val)
    path = self.path / f"{key}{_lru._CACHE_SUFFIX}"
    if not path.exists():
        mine = path.with_name(f"{path.name}.{os.getpid()}.tmp")
        mine.write_bytes(val)
        os.replace(mine, path)


_lru.LRUCache.put = _put_whole

import warnings  # noqa: E402

# JAX donation warnings are ERRORS in the gate (ISSUE 3 satellite): a
# "donated buffers were not usable" warning means a program claims donation
# it cannot honour -- silent memory doubling on the round path.  pytest.ini
# carries the matching filterwarnings entries for pytest runs; these module
# filters cover bare/in-process harnesses that import this conftest.  The
# staticcheck auditor additionally promotes them to audit failures.
warnings.filterwarnings("error", message="Some donated buffers were not usable")
warnings.filterwarnings("error", message="Donation is not implemented")

import numpy as np  # noqa: E402
import pytest  # noqa: E402


@pytest.fixture
def rng():
    return np.random.default_rng(0)


# Every executable a process loads maps memory, a worker keeps all it ever
# loaded (jax's caches hold them), and the kernel allows a process
# `vm.max_map_count` mappings, 65,530 here.  A worker that reaches it dies in
# whatever compile or cache read comes next (seen with the seventh decoder
# family's tests in the suite, PR 50: a segfault under `compiler._cache_read`,
# an abort under `backend_compile_and_load`, each in a test that passes alone;
# the op-by-op model tests load thousands of small executables).  Past half
# the limit a worker drops jax's caches after a test: 3 s, and the programs
# the next tests share come back from the persistent cache.
def _mappings(path="/proc/self/maps"):
    try:
        with open(path, "rb") as f:
            return sum(1 for _ in f)
    except OSError:  # not Linux: nothing to count, nothing to do
        return 0


def _map_limit(path="/proc/sys/vm/max_map_count"):
    try:
        with open(path) as f:
            return int(f.read())
    except (OSError, ValueError):
        return 65530


_MAP_LIMIT = _map_limit()


@pytest.fixture(autouse=True)
def _release_executables():
    yield
    if _mappings() > _MAP_LIMIT // 2:
        import gc

        jax.clear_caches()
        gc.collect()
