#!/usr/bin/env python3
"""The benchmark's command:

    python3 benchmark/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

One process, no children.  Without a TPU holding the chips the cell asks for
it exits non-zero and prints no result line.  In order: set-up (compile cache,
data from the seed in the dataset's own format, the experiment built as
`entry.common.run_main` builds it, weights from the seed, two warm-up rounds,
the check rounds), the measured window of whole training rounds, the plain
reference and the comparison that decides `correct`, and the result line.
`--trace 1` runs a short stretch of rounds under the profiler instead of the
window and reports the per-layer metrics.

`--control program_bf16` also compares what the check rounds return after a
pass through bfloat16, and lets that decide `correct`: for the builder's
measurements of the limits (PERF.md section 2) and for the tests; no benchmark
run sets it.
"""

import argparse
import glob
import json
import os
import sys
import tempfile
import time

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

TRACE_ROUNDS = 2  # traces are large: 2 rounds hold 100-500 local steps


def say(*a):
    print("benchmark:", *a, flush=True)


def parse(argv):
    p = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    p.add_argument("--workload", required=True)
    p.add_argument("--seed", type=int, required=True)
    p.add_argument("--seconds", type=float, required=True)
    p.add_argument("--trace", type=int, choices=(0, 1), default=0)
    p.add_argument("--control", choices=("none", "program_bf16"), default="none")
    return p.parse_args(argv)


def run(args, require_tpu=True):
    t0 = time.perf_counter()
    from benchmark import checks, harness, trace_reduce

    cell, config = harness.load_cell(args.workload)
    chips = int(cell["chips"])

    from heterofl_tpu.utils.compile_cache import enable_persistent_cache

    cache_dir = enable_persistent_cache()
    import jax
    import numpy as np

    from benchmark import weights

    # small programs (the weights, the reference's pieces) are cached too, so
    # that only a checkout's first run compiles anything
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)
    devices = jax.devices()
    if require_tpu and (devices[0].platform != "tpu" or len(devices) < chips):
        print(f"benchmark: {args.workload} needs {chips} TPU chip(s); jax found "
              f"{len(devices)} {devices[0].platform} device(s)", file=sys.stderr)
        return 4
    peaks = harness.load_json("peaks.json")
    if devices[0].device_kind not in peaks:
        print(f"benchmark: no peaks for device kind {devices[0].device_kind!r} "
              f"in benchmark/peaks.json", file=sys.stderr)
        return 4
    peak = peaks[devices[0].device_kind]
    counter = harness.CompileCounter()
    e2e_metrics, layer_metrics = harness.cell_metrics(args.workload)

    with tempfile.TemporaryDirectory(prefix="heterofl_bench_") as work:
        # ---- set-up -----------------------------------------------------
        data_dir, out_dir = os.path.join(work, "data"), os.path.join(work, "out")
        t = time.perf_counter()
        harness.load_module("data", config["data"]["writer"]).write(
            data_dir, config["data_name"], args.seed, config["data"]["sizes"])
        t_data = time.perf_counter() - t
        t = time.perf_counter()
        exp, data_split, label_split = harness.build_experiment(
            harness.experiment_argv(cell, config, args.seed, data_dir, out_dir))
        t_build = time.perf_counter() - t
        if exp.mesh.shape["clients"] != chips:
            raise RuntimeError(f"mesh {dict(exp.mesh.shape)} does not span the "
                               f"cell's {chips} chip(s)")
        if exp.kind != "vision" and exp.cfg["num_tokens"] != config["model"]["num_tokens"]:
            raise RuntimeError(f"vocabulary {exp.cfg['num_tokens']} is not the "
                               f"published {config['model']['num_tokens']}")
        steps, per_step = harness.steps_per_client(exp)
        shapes = {k: tuple(v.shape) for k, v in
                  jax.eval_shape(exp.model.init, jax.random.key(0)).items()}
        params = weights.make_params(shapes, args.seed)
        params0 = checks.to_host(params)
        log = harness.make_round_log(os.path.join(out_dir, "log"))

        def key_of(epoch):
            return jax.random.fold_in(exp.host_key, epoch)

        # two rounds, the second fed the first's result: a round's output
        # carries the pinned layouts, and committing those is a program too
        t = time.perf_counter()
        for epoch in harness.WARMUP_EPOCHS:
            params = exp.train_round(params, epoch, exp.scheduler(epoch), log)
            jax.block_until_ready(params)
        t_warm = time.perf_counter() - t
        rates = np.asarray(exp.cfg["model_rate"], np.float32)
        cohort, distinct = checks.check_cohort(
            exp.sample_users(harness.WINDOW_EPOCH), exp.num_active, rates)
        small = np.resize(np.flatnonzero(rates == rates.min()),
                          exp.num_active).astype(np.int32)  # every slot in use
        ref = harness.load_module("reference", config["reference"])
        clients = checks.reference_clients(exp, config, distinct, data_split,
                                           label_split, slots=cohort)
        t = time.perf_counter()
        rounds = checks.program_rounds(exp, params0, cohort, small, key_of)
        t_checks = time.perf_counter() - t
        compiles_setup = counter.compiles
        setup_s = time.perf_counter() - t0
        say(f"set-up {setup_s:.3f}s: data {t_data:.2f}s, experiment {t_build:.2f}s, "
            f"warm-up rounds {t_warm:.2f}s, check rounds {t_checks:.2f}s; compile "
            f"cache {cache_dir}: {counter.cache_requests} requests, "
            f"{counter.cache_hits} hits ({compiles_setup} programs compiled or loaded)")

        # ---- the window ---------------------------------------------------
        reduction = None
        if args.trace:
            trace_dir = os.path.join(work, "trace")
            phase_spans = exp.phase_timer.trace = trace_reduce.PhaseSpans()
            jax.profiler.start_trace(trace_dir)
            try:
                window = harness.run_window(
                    exp, params, args.seconds, log, label_split,
                    int(cell["traffic"].get("eval_every") or 0),
                    max_rounds=TRACE_ROUNDS,
                    on_round=lambda e: jax.profiler.TraceAnnotation(
                        trace_reduce.ROUND_SPAN, epoch=int(e)))
            finally:
                jax.profiler.stop_trace()
                exp.phase_timer.trace = None
        else:
            window = harness.run_window(
                exp, params, args.seconds, log, label_split,
                int(cell["traffic"].get("eval_every") or 0))
        compiles = counter.compiles - compiles_setup
        device = harness.device_record(devices, chips)
        attempted, failed, done = harness.window_counts(window, exp)
        say(f"window {window['window_s']:.3f}s, {attempted} rounds, "
            f"{done:.0f} client steps, {compiles} compiles; round_s "
            f"{[round(x, 4) for x in window['round_s']]}; mean client loss "
            f"{[round(r['loss'], 4) for r in window['rounds']]}")
        names = sorted({k for p in window["phases"] for k in p})
        say("host phases, mean seconds a round: " + ", ".join(
            f"{k} {sum(p.get(k, 0.0) for p in window['phases']) / attempted:.5f}"
            for k in names))
        for d in devices[:chips]:
            say(f"memory {d}: {d.memory_stats()}")

        if args.trace:
            t = time.perf_counter()
            pb = glob.glob(os.path.join(trace_dir, "plugins", "profile", "*",
                                        "*.xplane.pb"))
            if len(pb) != 1:
                raise RuntimeError(f"expected one xplane.pb, found {pb}")
            trace = trace_reduce.add_phase_spans(
                trace_reduce.load_xplane(pb[0]), phase_spans.events,
                window["round_t0"][0])
            reduction = trace_reduce.reduce(trace)
            say(f"trace {os.path.getsize(pb[0])} bytes reduced in "
                f"{time.perf_counter() - t:.1f}s: {reduction['rounds']} rounds, "
                f"stretch {reduction['window_s']:.3f}s; per device "
                f"{reduction['devices']}; idle by span {reduction['idle_by_span_s']}")

        # ---- correctness, outside set-up and window ----------------------
        t = time.perf_counter()
        reference = checks.reference_round(ref, config, params0, clients, args.seed)
        t_ref = time.perf_counter() - t

        def verdict(rounds_, tag):
            ok_all, rows, detail = checks.compare(
                rounds_, reference, params0, cohort, steps, per_step, ref, config,
                clients, window, compiles, log.rounds[0]["loss"])
            for name, value, limit, ok in rows:
                say(f"{tag} {name}: {value} (limit {limit}) {'ok' if ok else 'FAILED'}")
            say(f"{tag} detail: {detail}")
            return ok_all

        correct = verdict(rounds, "check")
        if args.control == "program_bf16":
            # the sound readings stand above; the verdict is the control's
            correct = verdict({k: (checks.through_bf16(new), ms)
                               for k, (new, ms) in rounds.items()}, "control")
        say(f"reference of {len(clients)} clients {t_ref:.1f}s, reference and "
            f"comparison {time.perf_counter() - t:.1f}s")

        if args.trace:
            flops = harness.load_module("flops", config["flops"])
            first = window["next_epoch"] - len(window["round_s"])
            traced = [np.asarray(exp.sample_users(e)) for e in
                      range(first, window["next_epoch"])]
            model_flops = sum(steps * flops.step_flops(
                config, float(rates[u] / exp.cfg["global_model_rate"]))
                for users in traced for u in users) / len(traced)
            info = {"name": args.workload, "chips": chips,
                    "steps_per_round": steps,
                    "model_flops_per_round": model_flops,
                    "peak_flops_per_s": peak["bf16_flops_per_s"]}
            metrics = {}
            for m in layer_metrics:
                value = harness.load_module("layer_metrics", m["name"]).compute(
                    reduction, window["phases"], info)
                if value is not None:
                    metrics[m["name"]] = {"value": value, "unit": m["unit"]}
            device["busy_s"] = reduction["busy_s"]
            device["window_s"] = reduction["window_s"]
        else:
            values = harness.end_to_end(window, exp, setup_s)
            metrics = {m["name"]: values[m["name"]] for m in e2e_metrics}
        result = {"correct": bool(correct and failed == 0), "attempted": attempted,
                  "failed": failed, "metrics": metrics, "device": device}
        if reduction is not None:
            result["breakdown"] = {"device_ops": reduction["device_ops"],
                                   "idle_gaps": reduction["idle_gaps"]}
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(run(parse(sys.argv[1:])))
