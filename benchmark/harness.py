"""The benchmark's harness: one cell = one configuration file + one workload
file, found by name; a `FedExperiment` built as `entry.common.run_main` builds
it; whole training rounds through its public `train_round`, timed on the host
clock; the arithmetic from rounds to end-to-end metrics.

Everything that belongs to one configuration, one cell or one per-layer metric
sits in a file of its own (`configs/`, `workloads/`, `data/`, `reference/`,
`flops/`, `layer_metrics/`), which this module finds by the name in
`BENCHMARK.json`.  A later cell, configuration or metric adds files and edits
none.
"""

import contextlib
import importlib.util
import json
import os
import sys
import time

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)

#: round numbers the set-up uses (keys and cohorts are functions of the round
#: number and the seed); the window starts after them
WARMUP_EPOCHS, CHECK_EPOCH, SMALL_EPOCH, WINDOW_EPOCH = (1, 2), 3, 4, 5


def load_json(*parts):
    with open(os.path.join(HERE, *parts), encoding="utf-8") as f:
        return json.load(f)


def load_module(kind, name):
    """`benchmark/<kind>/<name>.py`, by path (names hold dots)."""
    path = os.path.join(HERE, kind, name + ".py")
    if not os.path.exists(path):
        raise FileNotFoundError(f"no {kind} file for {name!r}: {path}")
    if kind == "reference":  # a package: its modules share `common`
        if ROOT not in sys.path:
            sys.path.insert(0, ROOT)
        return importlib.import_module(f"benchmark.reference.{name}")
    tag = "benchmark_%s_%s" % (kind, "".join(c if c.isalnum() else "_" for c in name))
    spec = importlib.util.spec_from_file_location(tag, path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def load_cell(name):
    """(cell, configuration) of the workload ``name``."""
    manifest = load_json(os.pardir, "BENCHMARK.json")
    entry = next((w for w in manifest["workloads"] if w["name"] == name), None)
    if entry is None:
        raise KeyError(f"BENCHMARK.json has no workload {name!r}")
    cell = load_json("workloads", name + ".json")
    config = load_json("configs", cell["config"] + ".json")
    if (cell["config"], cell["chips"]) != (entry["config"], entry["chips"]):
        raise ValueError(f"workloads/{name}.json disagrees with BENCHMARK.json")
    return cell, config


def cell_metrics(name):
    """The manifest's (end_to_end, per_layer) metric entries of one cell."""
    manifest = load_json(os.pardir, "BENCHMARK.json")
    def mine(ms):
        return [m for m in ms if name in m.get("workloads", [name])]
    return mine(manifest["end_to_end"]), mine(manifest["per_layer"])


def experiment_argv(cell, config, seed, data_dir, out_dir):
    """The command line a user would give `entry.train_*_fed` for this cell."""
    traffic = cell["traffic"]
    override = {"num_epochs": {"global": int(config["federation"]["global_epochs"]),
                               "local": int(traffic["local_epochs"])}}
    for src in (config.get("cfg_overrides"), traffic.get("cfg_overrides")):
        override.update(src or {})
    return ["--control_name", config["control"],
            "--model_name", config["model_name"],
            "--data_name", config["data_name"],
            "--init_seed", str(int(seed) % 2147483629),
            "--strategy", traffic.get("strategy", "masked"),
            "--mesh", json.dumps(traffic.get("mesh", {"clients": 0, "data": 1})),
            "--data_dir", data_dir, "--output_dir", out_dir,
            "--override", json.dumps(override)]


def build_experiment(argv):
    """A `FedExperiment` as `entry.common.run_main` builds it, staged.
    Returns (exp, data_split, label_split)."""
    from heterofl_tpu import config as C
    from heterofl_tpu.entry.common import FedExperiment, build_cli, cfg_from_args

    cfg = C.process_control(cfg_from_args(build_cli("benchmark").parse_args(argv)))
    exp = FedExperiment(cfg, cfg["init_seed"])
    data_split, label_split = exp.make_splits()
    exp.stage(data_split, label_split)
    return exp, data_split, label_split


def make_round_log(path):
    """The program's `Logger`, keeping what each round appended: the samples
    the round trained (the program's own count) and its mean client loss."""
    from heterofl_tpu.utils.logger import Logger

    class RoundLog(Logger):
        def __init__(self, log_path):
            super().__init__(log_path)
            self.rounds = []  # {"n": samples trained, "loss": mean client loss}

        def append(self, result, tag, n=1, mean=True):
            super().append(result, tag, n=n, mean=mean)
            if tag == "train" and mean and "Local-Loss" in result:
                self.rounds.append({"n": float(n), "loss": float(result["Local-Loss"])})

        def write(self, tag, metric_names):  # one line a round is not wanted
            return ""

    return RoundLog(path)


class CompileCounter:
    """Backend compilations, counted from jax's monitoring events."""

    def __init__(self):
        import jax.monitoring

        self.compiles = 0
        self.cache_requests = 0
        self.cache_hits = 0
        jax.monitoring.register_event_duration_secs_listener(self._duration)
        jax.monitoring.register_event_listener(self._event)

    def _duration(self, event, duration, **kw):
        if event == "/jax/core/compile/backend_compile_duration":
            self.compiles += 1

    def _event(self, event, **kw):
        if event == "/jax/compilation_cache/compile_requests_use_cache":
            self.cache_requests += 1
        elif event == "/jax/compilation_cache/cache_hits":
            self.cache_hits += 1


def steps_per_client(exp):
    """Local SGD steps one client runs in a round, and the samples (rows for
    the language model) the program counts for each step."""
    cfg = exp.cfg
    epochs = int(cfg["num_epochs"]["local"])
    if exp.kind == "vision":
        n = int(exp.train_data[0].shape[1])
        batch = int(cfg["batch_size"]["train"])
        return epochs * -(-n // batch), batch
    rows, tokens = (int(s) for s in exp.train_data[0].shape[1:3])
    return epochs * -(-tokens // int(cfg["bptt"])), rows


def run_window(exp, params, seconds, log, label_split=None, eval_every=0,
               epoch0=WINDOW_EPOCH, clock=time.perf_counter, on_round=None,
               max_rounds=None):
    """Whole training rounds until ``seconds`` have passed at a round
    boundary.  Each round is `exp.train_round` and a wait for its result, as
    `run_main`'s loop dispatches it; ``eval_every`` > 0 runs the program's
    evaluation after every that-many rounds, inside the window.

    Returns the window's record: per-round seconds, what the program logged,
    the final parameters, the whole window's seconds.
    """
    import jax

    times, starts, epoch = [], [], epoch0
    phases = []
    t_start = clock()
    while True:
        lr = exp.scheduler(epoch)
        before = exp.phase_timer.snapshot()
        t0 = clock()
        starts.append(t0)
        with on_round(epoch) if on_round else contextlib.nullcontext():
            params = exp.train_round(params, epoch, lr, log)
            jax.block_until_ready(params)
        times.append(clock() - t0)
        phases.append(exp.phase_timer.delta(before))
        if eval_every and (epoch - epoch0 + 1) % eval_every == 0:
            exp.evaluate(params, epoch, log, label_split)
        epoch += 1
        if clock() - t_start >= seconds or (max_rounds and len(times) >= max_rounds):
            break
    return {"round_s": times, "round_t0": starts, "phases": phases, "window_s": clock() - t_start,
            "rounds": list(log.rounds[-len(times):]), "params": params,
            "next_epoch": epoch}


def window_counts(window, exp):
    """(attempted, failed, client steps) of a window, from the program's own
    sample counts: a round fails on a non-finite loss or a sample count other
    than active clients x local steps x samples a step."""
    import math

    steps, per_step = steps_per_client(exp)
    want = exp.num_active * steps * per_step
    failed, done = 0, 0.0
    for r in window["rounds"]:
        if not math.isfinite(r["loss"]) or r["n"] != want:
            failed += 1
        done += r["n"] / per_step
    attempted = len(window["round_s"])
    failed += attempted - len(window["rounds"])  # a round that logged nothing
    return attempted, failed, done


def end_to_end(window, exp, setup_s):
    """The `--trace 0` metrics."""
    import statistics

    _, _, done = window_counts(window, exp)
    return {"round_s": {"value": statistics.median(window["round_s"]), "unit": "s"},
            "client_steps_per_s": {"value": done / window["window_s"], "unit": "steps/s"},
            "setup_s": {"value": setup_s, "unit": "s"}}


def device_record(devices, chips):
    # the allocator's live buffers and, apart from them, what the runtime
    # reserves for the running program's temporaries (PERF.md section 3)
    peak = 0
    for d in devices[:chips]:
        stats = d.memory_stats() or {}
        peak = max(peak, int(stats.get("peak_bytes_in_use", 0))
                   + int(stats.get("peak_bytes_reserved", 0)))
    return {"platform": devices[0].platform, "kind": devices[0].device_kind,
            "count": len(devices), "memory_peak_bytes": peak}
