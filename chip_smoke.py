#!/usr/bin/env python
"""Chip smoke: the flagship federated round on the TPU, through the entry
points a user calls.  The quickest proof that the system still starts there.

    python chip_smoke.py            # one chip (what the driver runs)
    python chip_smoke.py --chips 4  # the sharded round vs its 1-device twin

One process, no children, no CPU branch: without a TPU it exits non-zero and
prints no result.  Nothing is caught on the way -- a phase that raises ends
the script with a traceback.  One chip, in order:

1. every Pallas kernel against its XLA reference at ResNet-18's real sizes
   (int8 quantise+pack, fused batch norm fwd/bwd);
2. ``entry.train_classifier_fed.main``: 3 masked-engine rounds of the
   README's flagship control (full-width ResNet-18, CIFAR-10 shapes from a
   seed, 100 users, 10 active, 5 local epochs x batch 10 x 500 samples);
3. the same entry again, resuming that checkpoint for one round more on the
   grouped engine, then ``entry.test_classifier_fed.main`` on the result;
4. on a ``FedExperiment`` built as ``run_main`` builds it: the lowered round
   program holds its ``step/model`` and ``step/update`` scopes and no
   ``tpu_custom_call``, and a round of smallest-width clients leaves
   everything outside their slice bit-for-bit untouched.

``--chips 4`` runs none of that: only the flagship round on the default 4x1
mesh and on one device, compared at COMPARE_LR (see the constants below for
why not at the flagship's own learning rate, and what the chip taught about
comparing two compiled programs).

The timings it prints are smoke output, not benchmark numbers.  The last line
of stdout is ``{"ok": true, "device": {...}}`` with the device as jax reports
it.
"""

import argparse
import json
import os
import shutil
import sys
import time

_REPO = os.path.dirname(os.path.abspath(__file__))
FLAGSHIP = "1_100_0.1_iid_fix_a1-b1-c1-d1-e1_bn_1_1"
#: the learning rate the two placements are compared at.  It is a runtime
#: scalar of the round program, so the program is the flagship's, bit for bit.
#: At the flagship's own 0.1, 250 local SGD steps from a random init amplify
#: any rounding difference between two programs to saturation -- 0.53 of the
#: update's norm at default matmul precision, 0.47 at "highest" (measured on
#: the chip, PERF.md PR 23) -- which compares two trajectories of a chaotic
#: system, not two placements.  The lr-0.1 numbers are still printed.
COMPARE_LR = 1e-3
#: update-relative L2 tolerance of the aggregated params at COMPARE_LR.  ANY
#: two differently compiled programs of this round differ by ~0.09 there, at
#: the chip's default (bf16-operand) matmul precision: two ONE-device programs
#: that differ only in the update's implementation by 0.0895, four devices vs
#: one by 0.0929, while one program with its cohort in reversed slot order
#: agrees with itself to 7.8e-7 (measured, PERF.md PR 23).  So this bound only
#: says "no worse than the program-to-program floor"; what pins the
#: PLACEMENT is the per-slot comparison below.
FOUR_CHIP_RTOL = 0.15
#: relative tolerance of each client's mean local loss, slot by slot in
#: cohort order (measured <= 7.1e-4 between placements, while the ten
#: clients' losses themselves spread over 6 %: a client trained on other
#: data or at another level shows)
SLOT_LOSS_RTOL = 2e-3


def flagship_argv(out_dir, rounds, *extra):
    """The README's flagship control on synthetic CIFAR-10 shapes at the
    paper's 500 samples per user, cut to ``rounds`` global rounds."""
    return ["--control_name", FLAGSHIP, "--synthetic", "1",
            "--synthetic_sizes", json.dumps({"train": 50000, "test": 10000}),
            "--override", json.dumps({"num_epochs": {"global": rounds,
                                                     "local": 5}}),
            "--output_dir", out_dir,
            "--data_dir", os.path.join(out_dir, "data"), *extra]


def build_experiment(argv):
    """A FedExperiment exactly as ``entry.common.run_main`` builds it."""
    from heterofl_tpu import config as C
    from heterofl_tpu.entry.common import (FedExperiment, build_cli,
                                           cfg_from_args)

    cfg = cfg_from_args(build_cli("chip_smoke").parse_args(argv))
    cfg["model_name"], cfg["data_name"] = "resnet18", "CIFAR10"
    cfg = C.process_control(cfg)
    exp = FedExperiment(cfg, cfg["init_seed"])
    exp.stage(*exp.make_splits())
    return exp


def check_kernels(total=11_173_962):
    """Each Pallas kernel, compiled by Mosaic, against its XLA reference on
    the chip at the flagship's real sizes (``total``: full-width ResNet-18
    as one flat f32 buffer)."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from heterofl_tpu.ops.layers import batch_norm
    from heterofl_tpu.ops.pallas_norm import batch_norm_pallas
    from heterofl_tpu.ops.quant import quantize_pack, unpack_lanes

    ks = jax.random.split(jax.random.key(0), 8)
    p = jax.random.normal(ks[0], (total,))

    # the pack is exact (the words unpack to the kernel's own grid values);
    # the grid values may differ from XLA's by one level where the two
    # compilers' f32 divides round x/scale differently right at a floor edge
    scale = jnp.full((total,), 0.05, jnp.float32)
    w_p, q_p = jax.jit(lambda x: quantize_pack(x, scale, ks[4], 63, 64,
                                               mode="pallas"))(p)
    w_x, q_x = jax.jit(lambda x: quantize_pack(x, scale, ks[4], 63, 64,
                                               mode="xla"))(p)
    q_p, q_x = np.asarray(q_p), np.asarray(q_x)
    np.testing.assert_array_equal(
        np.asarray(unpack_lanes(w_p, 8, total)), q_p + 64)
    off = np.abs(q_p - q_x)
    if off.max() > 1 or np.count_nonzero(off) > 1e-5 * total:
        raise AssertionError(f"quantize_pack: {np.count_nonzero(off)} grid "
                             f"values off XLA's, max {off.max()}")
    # (the tail word's padding lanes hold the bias in the kernel and zero
    # in the XLA path; they never reach the decoder)
    same = bool(np.array_equal(np.asarray(w_p)[:-1], np.asarray(w_x)[:-1]))
    if not np.count_nonzero(off) and not same:
        raise AssertionError("quantize_pack: equal grid values, other words")
    print(f"chip_smoke: kernel quantize_pack: pack exact, "
          f"{np.count_nonzero(off)}/{total} grid values one level off "
          f"xla's, whole words equal: {same}", flush=True)

    for n, h, c in ((10, 32, 64), (10, 4, 512)):  # first / last stage
        x = jax.random.normal(ks[5], (n, h, h, c))
        gam = 1.0 + 0.1 * jax.random.normal(ks[6], (c,))
        bet = 0.1 * jax.random.normal(ks[7], (c,))
        sw = jnp.ones((n,)).at[-2:].set(0.0)

        def loss(fn):
            def f(x_, g_, b_):
                y = fn(x_, g_, b_)
                return jnp.sum(y * y), y
            return jax.jit(jax.value_and_grad(f, argnums=(0, 1, 2),
                                              has_aux=True))(x, gam, bet)

        (_, y_p), gr_p = loss(lambda x_, g_, b_: batch_norm_pallas(
            x_, g_, b_, sample_weight=sw))
        (_, y_r), gr_r = loss(lambda x_, g_, b_: batch_norm(
            x_, g_, b_, mode="batch", sample_weight=sw)[0])
        np.testing.assert_allclose(np.asarray(y_p), np.asarray(y_r),
                                   rtol=1e-4, atol=1e-4)
        for a, r in zip(gr_p, gr_r):
            np.testing.assert_allclose(np.asarray(a), np.asarray(r),
                                       rtol=1e-3, atol=1e-3)
    print("chip_smoke: kernel batch_norm_pallas fwd+bwd (vs xla): ok",
          flush=True)


def check_losses(tag, losses, need_fall):
    import numpy as np

    print(f"chip_smoke: {tag} per-round mean client loss {losses}",
          flush=True)
    if not np.all(np.isfinite(losses)):
        raise AssertionError(f"{tag}: non-finite loss in {losses}")
    if need_fall and not losses[-1] < losses[0]:
        raise AssertionError(f"{tag}: loss did not fall: {losses}")


def round_program_args(exp):
    """Shape-only arguments of the K=1 round program, as
    ``RoundEngine.train_round`` stages them."""
    import jax
    import jax.numpy as jnp

    def sds(a):
        return jax.ShapeDtypeStruct(a.shape, a.dtype)

    params = jax.eval_shape(exp.model.init, jax.random.key(0))
    key = jax.eval_shape(lambda: jax.random.key(0))
    n_dev = exp.mesh.shape["clients"]
    slots = exp.num_active + (-exp.num_active) % n_dev
    ids = jax.ShapeDtypeStruct((slots,), jnp.int32)
    lr = jax.ShapeDtypeStruct((), jnp.float32)
    data = tuple(sds(a) for a in exp.train_data) + (sds(exp.engine.fix_rates),)
    return (params, key, lr, ids, ids) + data


def one_chip(out_dir):
    import numpy as np

    from heterofl_tpu.entry import test_classifier_fed, train_classifier_fed

    check_kernels()

    # -- 3 masked-engine rounds through the user's entry point ------------
    t0 = time.time()
    res = train_classifier_fed.main(flagship_argv(out_dir, 3))[0]
    wall = time.time() - t0
    losses = res["logger"].history["train/Local-Loss"]
    if len(losses) != 3:
        raise AssertionError(f"expected 3 rounds, logged {len(losses)}")
    check_losses("masked", losses, need_fall=True)
    steady = res["round_times"]
    print(f"chip_smoke: masked engine 3 rounds wall {wall:.1f}s "
          f"(smoke timing, not a benchmark): first round incl. compile "
          f"{res['first_round_time']:.1f}s, steady rounds "
          f"{[round(t, 2) for t in steady]}s (train dispatch+fetch only)",
          flush=True)

    # -- resume that checkpoint: one round more, on the grouped engine ----
    t0 = time.time()
    res = train_classifier_fed.main(flagship_argv(
        out_dir, 4, "--resume_mode", "1", "--strategy", "grouped"))[0]
    losses = res["logger"].history["train/Local-Loss"]
    if len(losses) != 4:
        raise AssertionError(
            f"checkpoint did not resume at round 4: history {losses}")
    # (the resumed round's logged means average in round 3's: the logger is
    # checkpointed before its per-round reset, as the reference pickles it)
    check_losses("resumed+grouped", losses, need_fall=False)
    print(f"chip_smoke: resumed at round 4 on the grouped engine, wall "
          f"{time.time() - t0:.1f}s incl. compiles (smoke timing)",
          flush=True)

    # -- evaluate the checkpoint just written ------------------------------
    ev = test_classifier_fed.main(flagship_argv(out_dir, 4))[0]
    hist = ev["logger_history"]
    for name in ("test/Global-Loss", "test/Global-Accuracy",
                 "test/Local-Loss", "test/Local-Accuracy"):
        if not np.isfinite(hist[name][-1]):
            raise AssertionError(f"eval {name} not finite: {hist[name]}")
    print(f"chip_smoke: eval Global-Accuracy {hist['test/Global-Accuracy'][-1]:.2f}% "
          f"Global-Loss {hist['test/Global-Loss'][-1]:.4f}", flush=True)

    exp = build_experiment(flagship_argv(out_dir, 3))
    check_round_program(exp)
    check_masked_suffix(exp)


def check_round_program(exp):
    """The K=1 program ``run_main``'s engine dispatches: the local step's
    model and update are there under their names, and no kernel is (the step
    carries the parameter and momentum leaves and updates them per leaf)."""
    text = exp.engine._build_train().lower(*round_program_args(exp)).as_text(
        debug_info=True)
    if "step/update" not in text or "step/model" not in text:
        raise AssertionError("round program lost its step/update or step/model scope")
    if "tpu_custom_call" in text:
        raise AssertionError("round program holds a tpu_custom_call: no "
                             "kernel belongs in the local step")
    print("chip_smoke: round program holds step/model and step/update, no "
          "tpu_custom_call", flush=True)


def check_masked_suffix(exp):
    """A round of ten smallest-width clients: everything outside their slice
    of the aggregated params is bit-for-bit the value it had before."""
    import jax
    import numpy as np

    from heterofl_tpu.models.spec import param_mask

    rates = np.asarray(exp.cfg["model_rate"], np.float32)
    small = float(rates.min())
    users = np.flatnonzero(rates == small)[:exp.num_active].astype(np.int32)
    init_key = jax.random.fold_in(exp.host_key, 0)
    before = {k: np.asarray(v) for k, v in exp.model.init(init_key).items()}
    new, ms = exp.engine.train_round(exp.model.init(init_key),
                                     jax.random.fold_in(exp.host_key, 1),
                                     0.1, users, exp.train_data)
    moved = 0
    for k, v in new.items():
        mask = np.asarray(param_mask(v.shape, exp.model.specs[k],
                                     exp.model.groups, small))
        delta = np.asarray(v) - before[k]
        if np.any(delta[mask == 0] != 0.0):
            raise AssertionError(f"{k}: masked suffix moved under rate {small}")
        moved += int(np.count_nonzero(delta[mask != 0]))
    if not moved:
        raise AssertionError("the sub-width round moved nothing at all")
    print(f"chip_smoke: rate-{small} round: masked suffixes exactly "
          f"unchanged, {moved} in-slice entries moved, samples/slot "
          f"{np.asarray(ms['n']).tolist()}", flush=True)


def four_chips(out_dir):
    """The flagship masked round (K=1, same seed, same cohort) on the
    default 4x1 mesh and on a 1x1 mesh; nothing else."""
    import jax
    import numpy as np

    from heterofl_tpu.parallel.mesh import make_mesh
    from heterofl_tpu.parallel.round_engine import RoundEngine

    exp = build_experiment(flagship_argv(out_dir, 1))
    if exp.mesh.shape["clients"] != 4:
        raise AssertionError(f"default mesh is {dict(exp.mesh.shape)}, not "
                             f"4 devices on the clients axis")
    init_key = jax.random.fold_in(exp.host_key, 0)
    key = jax.random.fold_in(exp.host_key, 1)
    users = exp.sample_users(1)
    before = {k: np.asarray(v) for k, v in exp.model.init(init_key).items()}

    def run(engine, tag, lr):
        t0 = time.time()
        p, ms = engine.train_round(exp.model.init(init_key), key, lr, users,
                                   exp.train_data)
        p = {k: np.asarray(v) for k, v in p.items()}
        loss = float(np.asarray(ms["loss_sum"]).sum()
                     / np.asarray(ms["n"]).sum())
        print(f"chip_smoke: {tag} round at lr {lr}: {time.time() - t0:.1f}s "
              f"(smoke timing; the first of an engine includes its compile)",
              flush=True)
        return p, ms, loss

    def compare(lr, four, one):
        (p4, _, loss4), (p1, _, loss1) = four, one
        num = sum(float(np.sum((p4[k] - p1[k]) ** 2)) for k in p1) ** 0.5
        den = sum(float(np.sum((p1[k] - before[k]) ** 2)) for k in p1) ** 0.5
        worst = max(float(np.max(np.abs(p4[k] - p1[k]))) for k in p1)
        print(f"chip_smoke: lr {lr}: 4-device vs 1-device aggregated params "
              f"|p4-p1|/|p1-p0| = {num / den:.3e}, max abs diff {worst:.3e}, "
              f"loss {loss4:.6f} vs {loss1:.6f}", flush=True)
        return num / den, loss4, loss1

    eng1 = RoundEngine(exp.model, exp.cfg,
                       make_mesh(1, 1, devices=jax.devices()[:1]))
    four = {lr: run(exp.engine, "4-device", lr) for lr in (COMPARE_LR, 0.1)}
    # the staged client stacks and the per-slot work sit on four devices
    stacks = exp.engine._staging.replicated("train_data", exp.train_data)
    for a in stacks:
        devs = {s.device for s in a.addressable_shards}
        if len(devs) != 4:
            raise AssertionError(f"staged stack on {len(devs)} device(s)")
    n4 = four[COMPARE_LR][1]["n"]
    slot_devs = {s.device for s in n4.addressable_shards}
    per_dev = [float(np.asarray(s.data).sum()) for s in n4.addressable_shards]
    if len(slot_devs) != 4 or not all(x > 0 for x in per_dev):
        raise AssertionError(f"slots trained on {len(slot_devs)} device(s), "
                             f"samples per device {per_dev}")
    print(f"chip_smoke: staged stacks on 4 devices; samples trained per "
          f"device {per_dev}", flush=True)
    one = {lr: run(eng1, "1-device", lr) for lr in (COMPARE_LR, 0.1)}

    compare(0.1, four[0.1], one[0.1])  # printed, not held to a tolerance
    ratio, loss4, loss1 = compare(COMPARE_LR, four[COMPARE_LR],
                                  one[COMPARE_LR])
    # client by client, in cohort order: same samples, same level, same loss
    ms4 = {k: np.asarray(v)[:len(users)]
           for k, v in four[COMPARE_LR][1].items()}
    ms1 = {k: np.asarray(v)[:len(users)]
           for k, v in one[COMPARE_LR][1].items()}
    slot4, slot1 = ms4["loss_sum"] / ms4["n"], ms1["loss_sum"] / ms1["n"]
    worst = float(np.max(np.abs(slot4 - slot1) / slot1))
    print(f"chip_smoke: lr {COMPARE_LR}: per-client mean loss differs by at "
          f"most {worst:.2e} relative (tolerance {SLOT_LOSS_RTOL}); "
          f"aggregated-params tolerance {FOUR_CHIP_RTOL}", flush=True)
    if not (np.array_equal(ms4["n"], ms1["n"])
            and np.array_equal(ms4["rate"], ms1["rate"])):
        raise AssertionError("4-device slots hold other clients' samples or "
                             "levels than the 1-device slots")
    if not worst <= SLOT_LOSS_RTOL:
        raise AssertionError("a client's local loss depends on placement")
    if not ratio <= FOUR_CHIP_RTOL:
        raise AssertionError("4-device round disagrees with the 1-device one")
    if not np.isclose(loss4, loss1, rtol=SLOT_LOSS_RTOL):
        raise AssertionError("4-device loss disagrees with the 1-device one")


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--chips", type=int, default=1, choices=(1, 4))
    args = ap.parse_args(argv)

    from heterofl_tpu.obs import spans
    from heterofl_tpu.utils.compile_cache import enable_persistent_cache

    cache_dir = enable_persistent_cache()  # ... and the span record's listeners
    import jax

    devs = jax.devices()
    if devs[0].platform != "tpu":
        sys.exit(f"chip_smoke: jax found no TPU (platform "
                 f"{devs[0].platform!r}); this script has no CPU mode")
    if len(devs) != args.chips:
        sys.exit(f"chip_smoke: --chips {args.chips} but jax reports "
                 f"{len(devs)} device(s)")
    import jaxlib
    from importlib.metadata import version

    from heterofl_tpu import native

    print(f"chip_smoke: jax {jax.__version__} jaxlib {jaxlib.__version__} "
          f"libtpu {version('libtpu')} device_kind {devs[0].device_kind} "
          f"x{len(devs)}", flush=True)
    print(f"chip_smoke: native loader built from loader.cpp: "
          f"{native.available()}; compile cache dir {cache_dir}", flush=True)

    # checkpoints (45 MB a generation) stay in the checkout's ignored
    # output/; only the run logs go where the chip tool copies back from
    out_dir = os.path.join(_REPO, "output", f"chip_smoke_{args.chips}")
    shutil.rmtree(out_dir, ignore_errors=True)
    os.makedirs(out_dir)
    t0 = time.time()
    (one_chip if args.chips == 1 else four_chips)(out_dir)
    if os.path.isdir(os.path.join(out_dir, "runs")):
        shutil.copytree(os.path.join(out_dir, "runs"),
                        os.path.join(_REPO, "chiprun_out",
                                     f"chip_smoke_{args.chips}_runs"),
                        dirs_exist_ok=True)
    peak = devs[0].memory_stats()["peak_bytes_in_use"]
    counters = spans.RECORD.counters
    print(f"chip_smoke: total {time.time() - t0:.1f}s; compile cache requests "
          f"{counters['compile_requests']} hits {counters['compile_hits']}; "
          f"peak_bytes_in_use {peak} ({peak / 2**30:.2f} GiB)", flush=True)
    print("chip_smoke: set-up and compile spans: "
          + "; ".join(spans.table(spans.RECORD.summary())), flush=True)
    print(json.dumps({"ok": True, "device": {
        "platform": devs[0].platform, "kind": devs[0].device_kind,
        "count": len(devs)}}), flush=True)


if __name__ == "__main__":
    main()
