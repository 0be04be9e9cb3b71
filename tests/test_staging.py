"""Zero-resharding steady-state dispatch (parallel/staging.py, ISSUE 1).

The contract under test: after the first (staging + compile) round, a round
performs NO implicit host->device transfer under either engine and either
placement -- the data stacks are committed once, per-round values move via
explicit ``device_put`` only, and ``jax.transfer_guard_host_to_device``
("disallow" blocks *implicit* transfers, allows explicit ones) is the
oracle.  Plus: donation actually releases the previous round's param
buffers, rate snapping fails loudly at staging, and the pipeline/timer/
packer utilities behave.
"""

import jax
import numpy as np
import pytest

from heterofl_tpu.fed.core import snap_to_levels
from heterofl_tpu.models import make_model
from heterofl_tpu.parallel import (GroupedRoundEngine, MetricsPipeline,
                                   PendingMetrics, PhaseTimer, PlacementCache,
                                   RoundEngine, SlotPacker, make_mesh,
                                   shard_client_data)

from test_round import _vision_setup


# ---------------------------------------------------------------------------
# unit pieces
# ---------------------------------------------------------------------------

def test_snap_to_levels():
    table = [1.0, 0.5, 0.25, 0.125, 0.0625]
    # exact dyadic rates pass through
    np.testing.assert_array_equal(snap_to_levels([1.0, 0.0625], table), [1.0, 0.0625])
    # float32 round-trips snap back onto the table
    f32 = np.asarray([0.1, 0.2], np.float32)  # non-dyadic table, f32-rounded
    out = snap_to_levels(np.asarray(f32, np.float64), [0.1, 0.2])
    np.testing.assert_allclose(out, [0.1, 0.2], rtol=1e-6)
    # unknown / non-dyadic rates against a dyadic table fail loudly, by name
    with pytest.raises(ValueError, match="0.3"):
        snap_to_levels([1.0, 0.3], table)
    assert snap_to_levels([], table).size == 0


def test_grouped_unknown_rate_fails_at_staging():
    """A rate outside the level table raises ValueError in train_round's
    stage phase -- not a KeyError deep in level dispatch (ADVICE r5 item 2)."""
    cfg, ds, data = _vision_setup()
    grp = GroupedRoundEngine(cfg, make_mesh(1, 1))
    with pytest.raises(ValueError, match="level table"):
        grp.train_round(make_model(cfg).init(jax.random.key(0)),
                        np.array([0, 1], np.int32), np.array([1.0, 0.3]),
                        data, 0.05, jax.random.key(0))


def test_placement_cache_commits_once():
    mesh = make_mesh(8, 1)
    cache = PlacementCache(mesh)
    data = (np.arange(16, dtype=np.float32), np.ones(8, np.float32))
    a = cache.replicated("d", data)
    b = cache.replicated("d", data)
    assert all(x is y for x, y in zip(a, b))  # steady state: identity hits
    # a different source tuple restages
    c = cache.replicated("d", (np.arange(16, dtype=np.float32), data[1]))
    assert c[0] is not a[0]
    # sub-mesh entries are keyed by their static (lo, hi) range
    s1 = cache.replicated("d", data, srange=(0, 4))
    s2 = cache.replicated("d", data, srange=(0, 4))
    assert s1[0] is s2[0] and s1[0] is not a[0]
    assert cache.submesh(0, 4) is cache.submesh(0, 4)
    assert cache.submesh(0, 4).devices.size == 4
    # scalars are cached by value
    assert cache.scalar(0.1) is cache.scalar(0.1)
    assert cache.scalar(0.1) is not cache.scalar(0.2)


def test_broadcast_is_donation_safe():
    """PlacementCache.broadcast severs buffer aliasing: donating its output
    must NOT delete the source (device_put's output can alias the source
    shard, which is exactly the bug this method exists to avoid)."""
    import jax.numpy as jnp

    cache = PlacementCache(make_mesh(4, 1))
    x = jnp.arange(8.0)
    y = cache.broadcast(x, (0, 2))
    f = jax.jit(lambda v: v * 2, donate_argnums=(0,))
    jax.block_until_ready(f(y))
    assert not x.is_deleted()


def test_slot_packer_reuses_buffers():
    p = SlotPacker()
    b1 = p.buffer("k", (8,))
    b1[:3] = [5, 6, 7]
    b2 = p.buffer("k", (8,))
    assert b2 is b1  # steady state: no reallocation
    assert (b2 == -1).all()  # and the pad value is reset
    assert p.buffer("k", (16,)) is not b1  # layout change reallocates


def test_phase_timer_accounting():
    t = PhaseTimer()
    with t.phase("stage"):
        pass
    with t.phase("dispatch"):
        pass
    with t.phase("dispatch"):
        pass
    assert set(t.summary()) == {"stage", "dispatch"}
    assert t.calls["dispatch"] == 2
    snap = t.snapshot()
    with t.phase("fetch"):
        pass
    assert set(t.delta(snap)) == {"fetch"}
    # per-superstep amortization: one stage+dispatch cycle pays for K rounds
    t2 = PhaseTimer()
    t2.totals["dispatch"] = 8.0
    assert t2.amortized({}, 4) == {"dispatch": 2.0}
    assert t2.amortized({"dispatch": 4.0}, 2) == {"dispatch": 2.0}


def test_tier1_persistent_compile_cache_active():
    """The ISSUE 2 CI satellite: the tier-1 session must run with the
    persistent compile cache wired up (conftest also hard-fails), so
    superstep recompiles show as cache misses instead of silent 40s stalls."""
    import os

    assert jax.config.jax_compilation_cache_dir
    assert os.path.isdir(jax.config.jax_compilation_cache_dir)


def test_install_cache_counters_counts_compiles():
    """The package's one observer of jax's compile events is the span
    record (obs/spans.py): ``install()`` returns it, counters and all."""
    from heterofl_tpu.obs import spans

    c = spans.install().counters
    assert set(c) == {"compile_requests", "compile_hits", "compile_misses"}
    before = dict(c)
    # a FRESH program shape (unique constant) must consult the enabled
    # persistent cache and strictly bump the request counter -- the strict
    # inequality is the test that the monitoring listener actually fires
    jax.jit(lambda x: x * 3 + 1)(np.arange(931.0)).block_until_ready()
    assert c["compile_requests"] > before["compile_requests"]


def test_metrics_pipeline_batches_and_flushes():
    fetched = []

    def mk(i):
        return PendingMetrics({"n": np.float32(i)},
                              assemble=lambda h: fetched.append(i) or h)

    pipe = MetricsPipeline(fetch_every=3)
    assert pipe.push(1, mk(1)) == [] and pipe.push(2, mk(2)) == []
    assert fetched == []  # nothing materialised yet
    due = pipe.push(3, mk(3))
    assert [tag for tag, _ in due] == [1, 2, 3] and fetched == [1, 2, 3]
    assert len(pipe) == 0
    pipe.push(4, mk(4))
    assert [tag for tag, _ in pipe.flush()] == [4]  # boundary flush
    # fetch_every=1 degenerates to synchronous (parity default)
    pipe1 = MetricsPipeline(1)
    assert [tag for tag, _ in pipe1.push(9, mk(9))] == [9]


# ---------------------------------------------------------------------------
# the tentpole contract: zero implicit H2D transfers in steady state
# ---------------------------------------------------------------------------

def _steady_state_rounds(run_round, params, keys):
    """Round 1 stages + compiles; rounds 2..3 must run under a host->device
    transfer guard that disallows implicit transfers."""
    params, _ = run_round(params, keys[0])
    with jax.transfer_guard_host_to_device("disallow"):
        params, ms = run_round(params, keys[1])
        params, ms = run_round(params, keys[2])
    return params, ms


def test_transfer_guard_masked_replicated():
    cfg, ds, data = _vision_setup()
    model = make_model(cfg)
    eng = RoundEngine(model, cfg, make_mesh(8, 1))
    user_idx = np.array([0, 2, 4, 6], np.int32)
    keys = [jax.random.key(r) for r in range(3)]

    def run(params, key):
        return eng.train_round(params, key, 0.05, user_idx, data)

    params, ms = _steady_state_rounds(run, model.init(jax.random.key(0)), keys)
    assert np.isfinite(np.asarray(ms["loss_sum"])).all()


def test_transfer_guard_masked_sharded():
    cfg, ds, data = _vision_setup()
    cfg = dict(cfg, data_placement="sharded")
    model = make_model(cfg)
    eng = RoundEngine(model, cfg, make_mesh(8, 1))
    data_s = shard_client_data(eng.mesh, tuple(np.asarray(d) for d in data))
    user_idx = np.array([0, 2, 4, 6], np.int32)
    keys = [jax.random.key(r) for r in range(3)]

    def run(params, key):
        return eng.train_round(params, key, 0.05, user_idx, data_s)

    params, ms = _steady_state_rounds(run, model.init(jax.random.key(0)), keys)
    assert np.isfinite(np.asarray(ms["loss_sum"])).all()


@pytest.mark.parametrize("placement", ["span", "slices"])
def test_transfer_guard_grouped(placement):
    cfg, ds, data = _vision_setup()
    model = make_model(cfg)
    grp = GroupedRoundEngine(dict(cfg, level_placement=placement), make_mesh(8, 1))
    assert grp.level_placement == placement
    user_idx = np.array([0, 2, 4, 6, 1, 3], np.int32)
    rates = np.asarray(cfg["model_rate"], np.float32)[user_idx]
    keys = [jax.random.key(r) for r in range(3)]

    def run(params, key):
        # async_metrics: the sums stay on device inside the guard; the D2H
        # fetch (allowed anyway) happens after
        p, pending = grp.train_round(params, user_idx, rates, data, 0.05, key,
                                     async_metrics=True)
        return p, pending

    params, pending = _steady_state_rounds(run, model.init(jax.random.key(0)), keys)
    ms = pending.fetch()
    assert (ms["n"] > 0).all() and np.isfinite(ms["loss_sum"]).all()


# ---------------------------------------------------------------------------
# donation: the previous round's param buffers are actually released
# ---------------------------------------------------------------------------

def test_donation_releases_previous_round_params():
    cfg, ds, data = _vision_setup()
    model = make_model(cfg)
    user_idx = np.array([0, 2, 4, 6], np.int32)
    rates = np.asarray(cfg["model_rate"], np.float32)[user_idx]

    # masked engine: the round program donates its params argument
    eng = RoundEngine(model, cfg, make_mesh(1, 1))
    p0 = model.init(jax.random.key(0))
    p1, _ = eng.train_round(p0, jax.random.key(1), 0.05, user_idx, data)
    jax.block_until_ready(p1)
    assert all(v.is_deleted() for v in p0.values())

    # grouped engine: the combine donates the old globals
    grp = GroupedRoundEngine(cfg, make_mesh(1, 1))
    g0 = model.init(jax.random.key(0))
    g1, _ = grp.train_round(g0, user_idx, rates, data, 0.05, jax.random.key(1))
    jax.block_until_ready(g1)
    assert all(v.is_deleted() for v in g0.values())


def test_transfer_guard_superstep_masked():
    """A steady-state SUPERSTEP dispatch performs no implicit H2D either:
    data committed once, epoch index via explicit scalar staging, sampling
    in-jit -- rounds 2..3 of supersteps run under the disallow guard."""
    cfg, ds, data = _vision_setup()
    model = make_model(cfg)
    eng = RoundEngine(model, cfg, make_mesh(8, 1))
    params = model.init(jax.random.key(0))
    base_key = jax.random.key(7)
    params, pending = eng.train_superstep(params, base_key, 1, 2, data, num_active=4)
    pending.fetch()
    with jax.transfer_guard_host_to_device("disallow"):
        params, pending = eng.train_superstep(params, base_key, 3, 2, data,
                                              num_active=4)
        params, pending = eng.train_superstep(params, base_key, 5, 2, data,
                                              num_active=4)
    ms = pending.fetch()
    assert len(ms) == 2 and np.isfinite(ms[-1]["loss_sum"]).all()


@pytest.mark.parametrize("placement", ["span", "slices"])
def test_transfer_guard_superstep_grouped(placement):
    """Grouped fused superstep: per-superstep slot schedules move via
    explicit device_put only; steady-state supersteps pass the guard."""
    from heterofl_tpu.fed.core import round_users

    cfg, ds, data = _vision_setup()
    model = make_model(cfg)
    grp = GroupedRoundEngine(dict(cfg, level_placement=placement), make_mesh(8, 1))
    base_key = jax.random.key(7)
    rates_vec = np.asarray(cfg["model_rate"], np.float32)

    def sched(epoch0, k):
        users = np.stack([
            np.asarray(round_users(jax.random.fold_in(base_key, epoch0 + r),
                                   cfg["num_users"], 4)) for r in range(k)])
        return users, rates_vec[users]

    params = model.init(jax.random.key(0))
    users, rates = sched(1, 2)
    params, pending = grp.train_superstep(params, base_key, 1, 2, users, rates, data)
    pending.fetch()
    # schedule drawing is host-side sampling (like the drivers' rng), not
    # part of the dispatch contract -- draw outside, dispatch inside
    u3, r3 = sched(3, 2)
    u5, r5 = sched(5, 2)
    with jax.transfer_guard_host_to_device("disallow"):
        params, pending = grp.train_superstep(params, base_key, 3, 2, u3, r3, data)
        params, pending = grp.train_superstep(params, base_key, 5, 2, u5, r5, data)
    ms = pending.fetch()
    assert len(ms) == 2 and np.isfinite(ms[-1]["loss_sum"]).all()


def test_superstep_donation_releases_previous_params():
    """The superstep program donates the params carry: after a dispatch the
    input buffers are released (the liveness contract train_round already
    honors, extended to the scan)."""
    cfg, ds, data = _vision_setup()
    model = make_model(cfg)
    base_key = jax.random.key(0)

    eng = RoundEngine(model, cfg, make_mesh(1, 1))
    p0 = model.init(jax.random.key(0))
    p1, pending = eng.train_superstep(p0, base_key, 1, 2, data, num_active=4)
    jax.block_until_ready(p1)
    pending.fetch()
    assert all(v.is_deleted() for v in p0.values())

    grp = GroupedRoundEngine(cfg, make_mesh(1, 1))
    users = np.array([[0, 2, 4, 6], [1, 3, 5, 7]], np.int32)
    rates = np.asarray(cfg["model_rate"], np.float32)[users]
    g0 = model.init(jax.random.key(0))
    g1, pending = grp.train_superstep(g0, base_key, 1, 2, users, rates, data)
    jax.block_until_ready(g1)
    pending.fetch()
    assert all(v.is_deleted() for v in g0.values())


def test_slices_broadcast_donation_leaves_globals_alive():
    """In slices mode each level program donates its private params
    broadcast; the GLOBAL params must survive all level dispatches (they
    feed the combine) -- the regression the jitted broadcast copy exists
    for."""
    cfg, ds, data = _vision_setup()
    model = make_model(cfg)
    grp = GroupedRoundEngine(dict(cfg, level_placement="slices"), make_mesh(8, 1))
    assert grp.level_placement == "slices"
    user_idx = np.array([0, 2, 4, 6, 1, 3], np.int32)
    rates = np.asarray(cfg["model_rate"], np.float32)[user_idx]
    g0 = model.init(jax.random.key(0))
    g1, ms = grp.train_round(g0, user_idx, rates, data, 0.05, jax.random.key(1))
    jax.block_until_ready(g1)
    assert (ms["n"] > 0).all() and np.isfinite(ms["loss_sum"]).all()
