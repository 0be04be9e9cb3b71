"""O(active) population sampler (ISSUE 11, heterofl_tpu/fed/sampling.py).

The contracts under test:

* the PRP index map is an EXACT bijection on ``[0, num_users)`` for
  awkward sizes (1, 2, 7, primes, powers of two and their neighbours, 1e6)
  and is key-dependent;
* ``round_users`` draws the identical cohort in-jit and on the host for
  BOTH samplers (the one-stream contract), ``sampler='perm'`` reproduces
  the pre-ISSUE-11 draw bit for bit, and an all-ones availability row
  selects exactly the uniform cohort under both samplers;
* the PRP availability walk returns available ids in PRP order with
  ``-1`` spill, deterministically;
* cohort frequencies under the PRP are uniform (chi-square smoke);
* the 1e6-user draw is O(active): >= 10x faster than the permutation
  path, no ``[num_users]``-sized value anywhere in its jaxpr, and O(A)
  python-side allocation (tracemalloc);
* loud ``ValueError``s for num_active/epoch0/k/sampler misuse (ISSUE 11
  satellite);
* schedule commitment: ``ScheduleCommitment`` ledger semantics, and a
  streaming driver run under ``sample_horizon=1`` is bit-identical to the
  stateless default WITH the prefetch overlap intact.
"""

import time
import tracemalloc
import warnings

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from heterofl_tpu import config as C
from heterofl_tpu.fed.core import (USER_SAMPLE_SALT, round_users,
                                   superstep_user_schedule)
from heterofl_tpu.fed.sampling import (AVAIL_OVERDRAW, ScheduleCommitment,
                                       SamplerSpec, prp_map, prp_round_users,
                                       resolve_sampler_cfg)
from heterofl_tpu.models import make_model
from heterofl_tpu.parallel import RoundEngine, make_mesh

from test_round import _vision_setup

HOST_KEY = jax.random.key(0)


# ---------------------------------------------------------------------------
# PRP bijection properties
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("users", [1, 2, 3, 7, 100, 127, 128, 129, 1023,
                                   1024, 1025, 4096, 4097])
def test_prp_bijection_awkward_sizes(users):
    """The keyed index map permutes [0, U) exactly -- including U=1, tiny
    U, primes and powers of two +- 1 (cycle-walking handles every
    non-power-of-4 domain)."""
    img = np.asarray(prp_map(HOST_KEY, np.arange(users), users))
    assert sorted(img.tolist()) == list(range(users))


def test_prp_bijection_1e6():
    """The acceptance scale: an exact bijection on [0, 1e6) (vectorised
    full-image check)."""
    users = 1_000_000
    img = np.sort(np.asarray(prp_map(HOST_KEY, np.arange(users), users)))
    np.testing.assert_array_equal(img, np.arange(users))


def test_prp_key_dependence():
    """Different keys give different permutations (and different rounds'
    fold_in keys give different cohorts)."""
    users = 100
    a = np.asarray(prp_map(jax.random.key(1), np.arange(users), users))
    b = np.asarray(prp_map(jax.random.key(2), np.arange(users), users))
    assert (a != b).any()
    r1 = np.asarray(round_users(jax.random.fold_in(HOST_KEY, 1), users, 10))
    r2 = np.asarray(round_users(jax.random.fold_in(HOST_KEY, 2), users, 10))
    assert (r1 != r2).any()


def test_prp_draw_is_prefix_of_bijection():
    """round_users under 'prp' is exactly the PRP image of [0, A) at the
    salted per-round key -- the O(active) contract (no hidden dependence
    on num_active: growing A extends the cohort, never reshuffles it)."""
    users = 37
    skey = jax.random.fold_in(HOST_KEY, USER_SAMPLE_SALT)
    full = np.asarray(prp_map(skey, np.arange(users), users))
    for a in (1, 5, 17, 37):
        got = np.asarray(round_users(HOST_KEY, users, a, sampler="prp"))
        np.testing.assert_array_equal(got, full[:a], err_msg=f"A={a}")


# ---------------------------------------------------------------------------
# one stream: in-jit == host, perm unchanged, all-ones == uniform
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("sampler", ["perm", "prp"])
def test_in_jit_equals_host_bitwise(sampler):
    users, a = 50, 8
    avail = np.zeros(users, np.uint8)
    avail[::3] = 1
    for av in (None, avail):
        host = np.asarray(round_users(HOST_KEY, users, a, avail=av,
                                      sampler=sampler))
        jitd = np.asarray(jax.jit(
            lambda k, v=None: round_users(k, users, a, avail=v,
                                          sampler=sampler))(
            HOST_KEY, *(() if av is None else (av,))))
        np.testing.assert_array_equal(host, jitd,
                                      err_msg=f"{sampler} avail={av is not None}")


def test_perm_sampler_preserves_legacy_stream_bitwise():
    """sampler='perm' IS the pre-ISSUE-11 draw: the salted full
    permutation prefix (uniform) and the gather + stable-argsort filter
    (availability), reproduced here as the frozen reference."""
    users, a = 23, 7
    key = jax.random.fold_in(HOST_KEY, 5)
    skey = jax.random.fold_in(key, USER_SAMPLE_SALT)
    perm = np.asarray(jax.random.permutation(skey, users))
    np.testing.assert_array_equal(
        np.asarray(round_users(key, users, a, sampler="perm")),
        perm[:a].astype(np.int32))
    avail = np.zeros(users, np.uint8)
    avail[[2, 4, 8, 16]] = 1
    av = avail[perm].astype(np.float32)
    order = np.argsort(-av, kind="stable")[:a]
    ref = np.where(av[order] > 0, perm[order], -1).astype(np.int32)
    np.testing.assert_array_equal(
        np.asarray(round_users(key, users, a, avail=avail, sampler="perm")),
        ref)


@pytest.mark.parametrize("sampler", ["perm", "prp"])
def test_all_ones_availability_is_uniform(sampler):
    users, a = 41, 9
    uni = np.asarray(round_users(HOST_KEY, users, a, sampler=sampler))
    ones = np.asarray(round_users(HOST_KEY, users, a,
                                  avail=np.ones(users, np.uint8),
                                  sampler=sampler))
    np.testing.assert_array_equal(uni, ones)


def test_prp_availability_membership_spill_and_determinism():
    users, a = 32, 6
    avail = np.zeros(users, np.uint8)
    avail[[3, 9, 27]] = 1
    got = np.asarray(round_users(HOST_KEY, users, a, avail=avail,
                                 sampler="prp"))
    # budget = min(U, 4A) = 24 < U: the walk may MISS available users past
    # the window (bounded spill) but may never select an unavailable one
    assert set(got.tolist()) - {-1} <= {3, 9, 27}
    assert (got == np.asarray(round_users(jax.random.key(0), users, a,
                                          avail=avail, sampler="prp"))).all()
    # full-window case: every available user is found, in PRP order
    users2 = 20  # budget = min(20, 24) = 20 = U
    avail2 = np.zeros(users2, np.uint8)
    avail2[[1, 5, 11]] = 1
    got2 = np.asarray(round_users(HOST_KEY, users2, a, avail=avail2,
                                  sampler="prp"))
    assert set(got2.tolist()) - {-1} == {1, 5, 11}
    assert (got2[3:] == -1).all()
    skey = jax.random.fold_in(HOST_KEY, USER_SAMPLE_SALT)
    walk = np.asarray(prp_map(skey, np.arange(users2), users2))
    np.testing.assert_array_equal(got2[:3],
                                  [u for u in walk if avail2[u]][:3])


def test_chi_square_uniform_cohort_frequencies():
    """Selection frequencies over many PRP rounds are uniform: chi-square
    over 50 users at 600 draws of 10 stays well under the df=49 tail
    (mean 49, sd ~9.9; bound 120 is ~7 sd -- a smoke test, not a PRF
    certification)."""
    users, a, rounds = 50, 10, 600
    sched = superstep_user_schedule(HOST_KEY, 0, rounds, users, a,
                                    sampler="prp")
    counts = np.bincount(sched.reshape(-1), minlength=users)
    assert counts.sum() == rounds * a
    expected = rounds * a / users
    chi2 = float(((counts - expected) ** 2 / expected).sum())
    assert chi2 < 120.0, f"chi2={chi2}, counts={counts.tolist()}"


def test_prp_and_perm_are_different_streams():
    """The re-baseline is real: the two samplers draw different cohorts at
    the same key (records drawn under one are not comparable with the other's)."""
    got_prp = np.asarray(round_users(HOST_KEY, 100, 10, sampler="prp"))
    got_perm = np.asarray(round_users(HOST_KEY, 100, 10, sampler="perm"))
    assert (got_prp != got_perm).any()


# ---------------------------------------------------------------------------
# engine stream consistency: in-jit draw == host-packed schedule
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("sampler", ["perm", "prp"])
def test_masked_superstep_in_jit_draw_matches_host_schedule(sampler):
    """The masked engine's in-jit sampler (replicated placement) and a
    host-packed schedule drawn from the same stream produce bit-identical
    params and metrics -- the contract that lets sharded/streaming/grouped
    paths consume host schedules without forking the stream."""
    cfg, ds, data = _vision_setup()
    cfg = dict(cfg, sampler=sampler)
    model = make_model(cfg)
    mesh = make_mesh(4, 1)
    k, a = 2, 4

    eng_jit = RoundEngine(model, cfg, mesh)
    p1 = model.init(jax.random.key(0))
    p1, pend1 = eng_jit.train_superstep(p1, HOST_KEY, 1, k, data,
                                        num_active=a)
    ms1 = pend1.fetch()

    sched = superstep_user_schedule(HOST_KEY, 1, k, cfg["num_users"], a,
                                    sampler=sampler)
    eng_host = RoundEngine(model, cfg, mesh)
    p2 = model.init(jax.random.key(0))
    p2, pend2 = eng_host.train_superstep(p2, HOST_KEY, 1, k, data,
                                         user_schedule=sched)
    ms2 = pend2.fetch()
    for r in range(k):
        for name in ("loss_sum", "score_sum", "n", "rate"):
            np.testing.assert_array_equal(
                np.asarray(ms1[r][name]), np.asarray(ms2[r][name]),
                err_msg=f"{sampler} round {r} {name}")
    for n in sorted(p1):
        np.testing.assert_array_equal(np.asarray(p1[n]), np.asarray(p2[n]),
                                      err_msg=f"{sampler} params {n}")


# ---------------------------------------------------------------------------
# validation (ISSUE 11 satellite)
# ---------------------------------------------------------------------------

def test_round_users_validation():
    with pytest.raises(ValueError, match="num_active=17"):
        round_users(HOST_KEY, 16, 17)
    with pytest.raises(ValueError, match="num_active=-1"):
        round_users(HOST_KEY, 16, -1)
    with pytest.raises(ValueError, match="Not valid sampler"):
        round_users(HOST_KEY, 16, 4, sampler="fisher-yates")


def test_superstep_user_schedule_validation():
    with pytest.raises(ValueError, match="epoch0=-1"):
        superstep_user_schedule(HOST_KEY, -1, 2, 16, 4)
    with pytest.raises(ValueError, match="k=-2"):
        superstep_user_schedule(HOST_KEY, 1, -2, 16, 4)
    with pytest.raises(ValueError, match="num_active=20"):
        superstep_user_schedule(HOST_KEY, 1, 2, 16, 20)
    assert superstep_user_schedule(HOST_KEY, 1, 0, 16, 4).shape == (0, 4)


def test_resolve_sampler_cfg_validation():
    assert resolve_sampler_cfg({}).kind == "prp"
    assert resolve_sampler_cfg({}).horizon is None
    assert not resolve_sampler_cfg({}).committed
    spec = resolve_sampler_cfg({"sampler": "perm", "sample_horizon": 1})
    assert (spec.kind, spec.horizon, spec.committed) == ("perm", 1, True)
    with pytest.raises(ValueError, match="Not valid sampler"):
        resolve_sampler_cfg({"sampler": "uniform"})
    with pytest.raises(ValueError, match="Not valid sample_horizon"):
        resolve_sampler_cfg({"sample_horizon": -1})
    with pytest.raises(ValueError, match="Not valid sample_horizon"):
        resolve_sampler_cfg({"sample_horizon": True})
    with pytest.raises(ValueError, match="Not valid sampler"):
        C.process_control(dict(C.default_cfg(), sampler="bogus"))


# ---------------------------------------------------------------------------
# O(active): draw time, jaxpr footprint, python allocation
# ---------------------------------------------------------------------------

def test_prp_jaxpr_carries_no_population_sized_value():
    """The static O(A)-memory proof: NO value in the traced uniform PRP
    draw has num_users-scale size (the perm path's [U] permutation is the
    counterexample the same walk flags)."""
    users, a = 1_000_000, 100

    def max_aval(sampler):
        jxp = jax.make_jaxpr(
            lambda k: round_users(k, users, a, sampler=sampler))(HOST_KEY)
        sizes = [int(np.prod(v.aval.shape))
                 for eqn in jxp.eqns for v in eqn.outvars]
        return max(sizes) if sizes else 0

    assert max_aval("prp") <= 10 * a
    assert max_aval("perm") >= users  # the walk sees what it should see


@pytest.mark.slow
def test_prp_draw_1e6_time_and_memory():
    """The ISSUE 11 acceptance bound, in-suite: at 1e6 users the PRP draw
    is >= 10x faster than the permutation draw (best of 3, the bench
    microbench's procedure) and allocates O(A) python-side."""
    users, a = 1_000_000, 100

    def best_of(sampler, reps=3):
        round_users(jax.random.fold_in(HOST_KEY, 0), users, a,
                    sampler=sampler)  # warm dispatch caches
        best = float("inf")
        for i in range(reps):
            t0 = time.perf_counter()
            np.asarray(round_users(jax.random.fold_in(HOST_KEY, 1 + i),
                                   users, a, sampler=sampler))
            best = min(best, time.perf_counter() - t0)
        return best

    t_prp, t_perm = best_of("prp"), best_of("perm")
    assert t_perm / t_prp >= 10.0, f"prp {t_prp:.4f}s perm {t_perm:.4f}s"
    tracemalloc.start()
    np.asarray(round_users(jax.random.fold_in(HOST_KEY, 9), users, a,
                           sampler="prp"))
    _, peak = tracemalloc.get_traced_memory()
    tracemalloc.stop()
    assert peak < 1 << 20, f"python-side peak {peak} bytes"


# ---------------------------------------------------------------------------
# schedule commitment (sample_horizon)
# ---------------------------------------------------------------------------

def test_schedule_commitment_ledger():
    c = ScheduleCommitment(1)
    # nothing fetched: superstep 1 and 2 read pre-run state, 3 does not
    assert c.may_draw(1) and c.may_draw(2) and not c.may_draw(3)
    c.commit(1, state={"loss": 1.0})
    assert c.may_draw(3) and not c.may_draw(4)
    assert c.state_for(3) == {"loss": 1.0}
    assert c.state_for(2) is None  # pre-run state
    c.commit(2, state={"loss": 0.5})
    assert c.committed_through == 2
    assert c.may_draw(4) and c.state_for(4) == {"loss": 0.5}
    # horizon 0: strictly output-dependent -- N+1 needs N's own state
    c0 = ScheduleCommitment(0)
    assert c0.may_draw(1) and not c0.may_draw(2)
    c0.commit(1)
    assert c0.may_draw(2)


def _stream_driver_cfg(d, **over):
    cfg = C.default_cfg()
    cfg["control"] = C.parse_control_name("1_8_0.5_iid_fix_a1-b1_bn_1_1")
    cfg["data_name"] = "MNIST"
    cfg["model_name"] = "conv"
    cfg["synthetic"] = True
    cfg["synthetic_sizes"] = {"train": 80, "test": 40}
    cfg["output_dir"] = str(d)
    cfg["override"] = {"num_epochs": {"global": 4, "local": 1},
                       "conv": {"hidden_size": [4, 8]},
                       "batch_size": {"train": 10, "test": 20},
                       "client_store": "stream",
                       "superstep_rounds": 2, "eval_interval": 2, **over}
    return C.process_control(cfg)


def test_driver_sample_horizon_bit_identical_with_prefetch(tmp_path):
    """A streaming driver run under sample_horizon=1 (schedule commitment)
    finishes with the EXACT params of the stateless default, keeps the
    prefetch overlap (no synchronous-staging warning fires), and commits
    every fetched superstep's state."""
    from heterofl_tpu.entry.common import FedExperiment

    mk = _stream_driver_cfg
    base = FedExperiment(mk(tmp_path / "base"), 0).run("Global-Accuracy")
    exp = FedExperiment(mk(tmp_path / "committed", sample_horizon=1), 0)
    assert exp._commitment is not None
    with warnings.catch_warnings():
        warnings.filterwarnings("error", message=".*SYNCHRONOUSLY.*")
        got = exp.run("Global-Accuracy")
    assert exp._commitment.committed_through == exp._ss_fetched > 0
    for n in sorted(base["params"]):
        np.testing.assert_array_equal(np.asarray(base["params"][n]),
                                      np.asarray(got["params"][n]),
                                      err_msg=n)


def test_driver_sample_horizon_zero_serialises_loudly(tmp_path):
    """sample_horizon=0 (strictly output-dependent): each cohort needs the
    PREVIOUS superstep's own fetched state, so the commitment blocks
    prefetch and staging serialises -- with a loud one-time warning naming
    horizon=1 as the overlap-preserving fix -- while the trajectory stays
    bit-identical (stateless samplers ignore the committed state)."""
    from heterofl_tpu.entry.common import FedExperiment

    mk = _stream_driver_cfg
    base = FedExperiment(mk(tmp_path / "base"), 0).run("Global-Accuracy")
    exp = FedExperiment(mk(tmp_path / "h0", sample_horizon=0), 0)
    with pytest.warns(UserWarning, match="sample_horizon=0.*SYNCHRONOUSLY"):
        got = exp.run("Global-Accuracy")
    for n in sorted(base["params"]):
        np.testing.assert_array_equal(np.asarray(base["params"][n]),
                                      np.asarray(got["params"][n]),
                                      err_msg=n)


def test_take_cohort_refuses_uncommitted_state(tmp_path):
    """The commitment guard: if a (hypothetical future) fetch deferral
    left the needed state uncommitted, the synchronous fallback REFUSES to
    draw instead of silently consuming pre-run state."""
    from heterofl_tpu.entry.common import FedExperiment

    exp = FedExperiment(_stream_driver_cfg(tmp_path, sample_horizon=0), 0)
    exp._ss_dispatched = 3  # superstep 4 next; its draw needs state 3
    exp._ss_fetched = 2     # ...which a deferred fetch has not committed
    exp._commitment.commit(2)
    with pytest.raises(RuntimeError, match="sample_horizon=0"):
        exp._take_cohort(7, 2)


def test_sampler_spec_defaults():
    spec = SamplerSpec()
    assert spec.kind == "prp" and spec.horizon is None
    assert AVAIL_OVERDRAW >= 2
    assert prp_round_users(HOST_KEY, 5, 0).shape == (0,)
