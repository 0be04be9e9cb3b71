"""Pod-scale fused superstep (ISSUE 17): the bitwise acceptance gate on a
REAL 2-process ``jax.distributed`` CPU mesh, the host-aligned slices
partition logic, the per-process shard checkpoint format, and the
analytic per-link ICI-vs-DCN split.

The slow half spawns distributed subprocesses through
``heterofl_tpu.parallel.pod`` (the same engine the CI smoke step
drives); the fast half unit-tests the pure pieces:
``link_split`` values, shard-blocks assembly + its corruption modes, the
sharded ``copy_best`` mirror, and the multi-host resume guard's
single-process degenerate case.
"""

import os
import socket
import subprocess
import sys

import numpy as np
import pytest

from heterofl_tpu.staticcheck.wire import link_split, ring_allreduce_bytes
from heterofl_tpu.utils.checkpoint import (
    BLOCKS_KEY, SHARD_SET_KEY, CheckpointCorruptError, checkpoint_path,
    copy_best, dense_from_blocks, is_shard_marker, load_checkpoint_sharded,
    save_checkpoint, save_checkpoint_sharded, shard_path)

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


# ---------------------------------------------------------------------------
# fast: analytic per-link wire split
# ---------------------------------------------------------------------------

def test_link_split_two_process_blocks():
    """8 participants in 2 host blocks: a ring has 8 links of
    2*(7/8)*payload each; exactly 2 cross a process boundary (DCN)."""
    s = link_split(1000, 8, 2)
    per_link = ring_allreduce_bytes(1000, 8)
    assert per_link == 1750
    assert s["bytes_per_link"] == per_link
    assert s["dcn_links"] == 2 and s["ici_links"] == 6
    assert s["dcn_bytes_total"] == 2 * per_link
    assert s["ici_bytes_total"] == 6 * per_link


def test_link_split_single_process_all_ici():
    s = link_split(1000, 8, 1)
    assert s["dcn_links"] == 0 and s["dcn_bytes_total"] == 0
    assert s["ici_links"] == 8
    # a single participant reduces locally: no links at all
    s1 = link_split(1000, 1, 1)
    assert s1["bytes_per_link"] == 0
    assert s1["dcn_links"] == 0 and s1["ici_links"] == 0


# ---------------------------------------------------------------------------
# fast: shard-blocks checkpoint format (no distributed runtime needed --
# the format is plain files + markers; the collective write itself is
# exercised by the slow 2-process tests below)
# ---------------------------------------------------------------------------

def _fake_sharded_ckpt(path, stamp="e3"):
    """Hand-craft the on-disk layout save_checkpoint_sharded produces from
    a 2-process run: two shard files + a header naming them."""
    full = np.arange(8, dtype=np.float32)
    blocks = [{"/resid": {((0, 4),): full[:4]}},
              {"/resid": {((4, 8),): full[4:]}}]
    for i in (0, 1):
        save_checkpoint(shard_path(path, i, 2),
                        {"stamp": stamp, "process": i, "blocks": blocks[i]})
    header = {
        "epoch": 3,
        "resid": {BLOCKS_KEY: True, "shape": (8,), "dtype": "float32",
                  "key": "/resid"},
        SHARD_SET_KEY: {"count": 2, "stamp": stamp,
                        "files": [os.path.basename(shard_path(path, i, 2))
                                  for i in (0, 1)]},
    }
    save_checkpoint(path, header)
    return full


def test_sharded_checkpoint_merges_blocks(tmp_path):
    ck = str(tmp_path / "model" / "c.pkl")
    full = _fake_sharded_ckpt(ck)
    blob = load_checkpoint_sharded(ck)
    assert blob["epoch"] == 3
    assert is_shard_marker(blob["resid"])
    np.testing.assert_array_equal(dense_from_blocks(blob["resid"]), full)


def test_sharded_checkpoint_stamp_mismatch_refused(tmp_path):
    """A torn multi-file rotation (shard from another generation) must
    fail verification, not silently mix generations."""
    ck = str(tmp_path / "model" / "c.pkl")
    full = _fake_sharded_ckpt(ck)
    save_checkpoint(shard_path(ck, 1, 2),
                    {"stamp": "e99", "process": 1,
                     "blocks": {"/resid": {((4, 8),): full[4:]}}})
    with pytest.raises(CheckpointCorruptError, match="stamp"):
        load_checkpoint_sharded(ck)


def test_sharded_checkpoint_missing_shard_refused(tmp_path):
    ck = str(tmp_path / "model" / "c.pkl")
    _fake_sharded_ckpt(ck)
    os.remove(shard_path(ck, 1, 2))
    with pytest.raises(CheckpointCorruptError, match="missing"):
        load_checkpoint_sharded(ck)


def test_dense_from_blocks_coverage_hole_refused():
    marker = {BLOCKS_KEY: True, "shape": (8,), "dtype": "float32",
              "blocks": {((0, 4),): np.zeros(4, np.float32)}}
    with pytest.raises(CheckpointCorruptError, match="coverage holes"):
        dense_from_blocks(marker)


def test_copy_best_mirrors_shard_files(tmp_path):
    """copy_best on a sharded live checkpoint mirrors every shard under
    the best tag's names and rewrites the header's shard set."""
    out = str(tmp_path)
    ck = checkpoint_path(out, "probe", "checkpoint")
    full = _fake_sharded_ckpt(ck)
    copy_best(out, "probe")
    best = checkpoint_path(out, "probe", "best")
    blob = load_checkpoint_sharded(best)
    np.testing.assert_array_equal(dense_from_blocks(blob["resid"]), full)
    # the mirrored shard files exist under the best names; rotating the
    # live shards can no longer tear the best blob
    assert os.path.exists(shard_path(best, 0, 2))
    assert os.path.exists(shard_path(best, 1, 2))


def test_sharded_save_degenerates_to_plain_single_process(tmp_path):
    """A fully-addressable blob on a single-process runtime writes the
    ordinary plain checkpoint -- no shard files, loadable by both
    readers."""
    ck = str(tmp_path / "model" / "c.pkl")
    blob = {"epoch": 7, "params": {"w": np.ones((2, 3), np.float32)}}
    save_checkpoint_sharded(ck, blob)
    assert not os.path.exists(shard_path(ck, 0, 1))
    loaded = load_checkpoint_sharded(ck)
    assert loaded["epoch"] == 7
    np.testing.assert_array_equal(loaded["params"]["w"], blob["params"]["w"])


def test_check_multihost_resume_single_process():
    from heterofl_tpu.entry.common import check_multihost_resume

    assert check_multihost_resume({"epoch": 9}) == 9
    assert check_multihost_resume(None) == 0


# ---------------------------------------------------------------------------
# slow: the real 2-process distributed gates
# ---------------------------------------------------------------------------

def _free_port() -> int:
    s = socket.socket()
    s.bind(("127.0.0.1", 0))
    port = s.getsockname()[1]
    s.close()
    return port


def _pod_env(n_processes, local_devices):
    env = dict(os.environ)
    env.update({
        "JAX_PLATFORMS": "cpu",
        "XLA_FLAGS": f"--xla_force_host_platform_device_count={local_devices}",
        "PYTHONPATH": REPO,
        "JAX_COORDINATOR_ADDRESS": f"127.0.0.1:{_free_port()}",
        "JAX_NUM_PROCESSES": str(n_processes),
    })
    return env


@pytest.mark.slow
def test_pod_two_process_bitwise_and_dcn():
    """THE acceptance gate: a 2-process CPU-mesh fused grouped-slices
    superstep produces params AND per-round metrics bit-identical to the
    single-process run (gloo fixes the reduction association by global
    device rank on both sides), with the REAL process grid classifying
    the clients axis as DCN, the traced program carrying exactly one
    dense reduction per training round, zero reshards, and the sharded
    checkpoint round-tripping."""
    import tempfile

    from heterofl_tpu.parallel.pod import bitwise_match, run_pod_probe

    base = tempfile.mkdtemp(prefix="test_pod_")
    ref_dir = os.path.join(base, "ref")
    pod_dir = os.path.join(base, "pod")
    # align=2 pins the single-process reference to the SAME host-aligned
    # level partition the 2-process mesh forces
    ref = run_pod_probe(ref_dir, n_processes=1, local_devices=8, k=2,
                        align=2)
    pod = run_pod_probe(pod_dir, n_processes=2, local_devices=4, k=2)
    assert ref[0]["slices"] == pod[0]["slices"], "level partitions differ"
    assert ref[0]["dcn_axes"] == []  # one process: nothing crosses hosts
    for r in pod:
        assert r["processes"] == 2 and r["devices"] == 8
        # dcn_axes_of on a REAL 2-process mesh (ISSUE 17 satellite): the
        # clients axis spans both processes
        assert r["dcn_axes"] == ["clients"]
        assert r["dcn_one_reduction"], r["wire"]
        assert r["wire"]["dcn_bytes"] == r["wire"]["train_bytes_per_round"]
        assert r["wire"]["other_bytes"] == 0
        assert r["reshards"] == 0
        assert r["sharded_ckpt_ok"]
    match = bitwise_match(pod_dir, ref_dir)
    assert match["match"], match["mismatches"][:20]


_RESUME_CHILD = r"""
import os, sys
import numpy as np
import jax
from jax.sharding import NamedSharding, PartitionSpec as P

from heterofl_tpu.parallel.mesh import initialize_distributed, make_mesh
from heterofl_tpu.parallel.staging import commit_global
from heterofl_tpu.utils.checkpoint import (dense_from_blocks, is_shard_marker,
                                           load_checkpoint_sharded,
                                           save_checkpoint_sharded, shard_path)
from heterofl_tpu.entry.common import check_multihost_resume

initialize_distributed()
pid, n = jax.process_index(), jax.process_count()
assert n == 2, n
out_dir = sys.argv[1]
mesh = make_mesh(len(jax.devices()), 1)
C = mesh.shape["clients"]
resid_host = np.arange(C * 3, dtype=np.float32).reshape(C, 3)
resid = commit_global(resid_host, NamedSharding(mesh, P("clients")))
ck = os.path.join(out_dir, "model", "probe_checkpoint.pkl")
save_checkpoint_sharded(ck, {"epoch": 5, "resid": resid})
# the collective write left both processes' shard files on the SHARED
# filesystem -- every host can reassemble the full state
assert os.path.exists(shard_path(ck, 0, 2)), "shard 0 missing"
assert os.path.exists(shard_path(ck, 1, 2)), "shard 1 missing"
blob = load_checkpoint_sharded(ck)
assert blob["epoch"] == 5
assert is_shard_marker(blob["resid"])
np.testing.assert_array_equal(dense_from_blocks(blob["resid"]), resid_host)
assert check_multihost_resume(blob) == 5
# divergence: a host resuming from a LOCAL (empty) output_dir must refuse
# loudly before any training dispatch (both processes join the broadcast)
err = None
try:
    check_multihost_resume(blob if pid == 0 else None)
except RuntimeError as e:
    err = str(e)
if pid == 0:
    assert err is None, err
else:
    assert err and "shared filesystem" in err, err
print("POD_RESUME_OK")
"""


@pytest.mark.slow
def test_multihost_resume_shared_filesystem(tmp_path):
    """2-process sharded save -> shared-fs reload -> agreed resume epoch;
    and the local-dir divergence raises on the straggler host."""
    env = _pod_env(2, 4)
    procs = []
    for i in (0, 1):
        e = dict(env)
        e["JAX_PROCESS_ID"] = str(i)
        procs.append(subprocess.Popen(
            [sys.executable, "-c", _RESUME_CHILD, str(tmp_path)], env=e,
            text=True, stdout=subprocess.PIPE, stderr=subprocess.PIPE))
    for i, pr in enumerate(procs):
        try:
            so, se = pr.communicate(timeout=600)
        except subprocess.TimeoutExpired:
            for p2 in procs:
                p2.kill()
            raise
        assert pr.returncode == 0, f"process {i}:\n{se[-3000:]}"
        assert "POD_RESUME_OK" in so
