"""Centralized (non-federated) baselines.

Parity: ``src/train_classifier.py`` / ``src/train_transformer.py`` (§3.5 of
SURVEY.md): plain epoch training of the global-rate model with a persistent
optimizer, sBN recalibration + test each epoch.  The reference's
``nn.DataParallel`` multi-GPU path (train_classifier.py:65-66) becomes batch
data-parallelism over the whole mesh: each device takes a slice of every
batch and gradients are ``psum``-ed -- the same program at any device count.
"""

from __future__ import annotations

import math
import time
from typing import Any, Dict, Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import PartitionSpec as P

from ..data.datasets import DATASET_STATS
from ..models.base import ModelDef
from ..ops.augment import augment_cifar, normalize_image
from ..data.pipeline import stack_windows as _stack_windows
from ..parallel.round_engine import _ceil_div, _shard_map
from ..utils.optim import clip_by_global_norm, make_optimizer
from .common import _batch_array as _batch_pad


class CentralEngine:
    """Jitted data-parallel epoch for the non-fed baseline."""

    def __init__(self, model: ModelDef, cfg: Dict[str, Any], mesh):
        self.model = model
        self.cfg = cfg
        self.mesh = mesh
        self.is_lm = model.is_lm
        self.norm_stats = cfg.get("norm_stats") or DATASET_STATS.get(cfg["data_name"])
        self.augment = cfg["data_name"].startswith("CIFAR")
        self._opt_init, self._opt_update = make_optimizer(cfg)
        self._epoch = None

    def init_opt(self, params):
        return self._opt_init(params)

    def _build(self):
        model = self.model
        axes = ("clients", "data")

        def body(params, opt, key, lr, *data):
            def stepf(carry, inp):
                p, opt = carry
                *arrs, t = inp
                kk = jax.random.fold_in(key, t)
                if self.is_lm:
                    lab, w = arrs
                    batch = {"label": lab}
                else:
                    xb, yb, w = arrs
                    if self.augment:
                        xb = augment_cifar(jax.random.fold_in(kk, 1), xb)
                    img = normalize_image(xb, *self.norm_stats) if self.norm_stats \
                        else xb.astype(jnp.float32)
                    batch = {"img": img, "label": yb}

                def loss_fn(p):
                    out, _ = model.apply(p, batch, train=True, sample_weight=w,
                                         rng=jax.random.fold_in(kk, 2))
                    n_loc = jnp.sum(w)
                    return out["loss"] * n_loc, (out["score"], n_loc)

                (lsum, (score, n_loc)), grads = jax.value_and_grad(loss_fn, has_aux=True)(p)
                n_tot = jax.lax.psum(n_loc, axes)
                lsum = jax.lax.psum(lsum, axes)
                grads = jax.tree_util.tree_map(
                    lambda g: jax.lax.psum(g, axes) / jnp.maximum(n_tot, 1e-6), grads)
                grads, _ = clip_by_global_norm(grads, 1.0)
                p, opt = self._opt_update(p, grads, opt, lr)
                if self.is_lm:
                    rows = jnp.asarray(batch["label"].shape[0], jnp.float32)
                    rows = jax.lax.psum(rows * (jnp.sum(w) > 0).astype(jnp.float32), axes)
                    metric = jnp.exp(lsum / jnp.maximum(n_tot, 1e-6)) * rows
                    stats = (lsum / jnp.maximum(n_tot, 1e-6) * rows, metric, rows)
                else:
                    correct = jax.lax.psum(jnp.sum((jnp.argmax(score, -1) == batch["label"]) * w), axes)
                    stats = (lsum, correct, n_tot)
                return (p, opt), stats

            S = data[0].shape[0]
            (params, opt), stats = jax.lax.scan(stepf, (params, opt),
                                                tuple(data) + (jnp.arange(S),))
            return params, opt, tuple(jnp.sum(s, 0) for s in stats)

        n_arrs = 2 if self.is_lm else 3
        # batch axis (axis 1 of each [S, B, ...] array) sharded over all devices
        data_specs = tuple(P(None, axes) for _ in range(n_arrs))
        fn = _shard_map(body, self.mesh,
                        in_specs=(P(), P(), P(), P()) + data_specs,
                        out_specs=(P(), P(), P()))
        return jax.jit(fn, donate_argnums=(0, 1))

    def train_epoch(self, params, opt, key, lr, *data):
        """data: vision ``(x [S,B,...]u8, y [S,B], w [S,B])``;
        LM ``(labels [S,B,bptt], w [S,B,bptt])``.  Returns
        ``(params, opt, (loss_sum, metric_sum, n))``."""
        if self._epoch is None:
            self._epoch = self._build()
        return self._epoch(params, opt, key, jnp.asarray(lr, jnp.float32), *data)


class CentralExperiment:
    """Non-federated baseline experiment (data_split_mode 'none')."""

    def __init__(self, cfg: Dict[str, Any], seed: int):
        from .. import config as C
        from ..data import fetch_dataset, process_dataset
        from ..models import make_model
        from ..parallel import make_mesh
        from ..parallel.evaluation import Evaluator
        from ..utils import make_scheduler

        self.seed = seed
        self.rng = np.random.default_rng(seed)
        self.host_key = jax.random.key(seed)
        dataset = fetch_dataset(cfg["data_name"], cfg["data_dir"], synthetic=cfg["synthetic"],
                                seed=seed, synthetic_sizes=cfg.get("synthetic_sizes"),
                                subset=cfg.get("subset", "label"))
        self.cfg, self.dataset = process_dataset(cfg, dataset)
        cfg = self.cfg
        from .common import _maybe_compute_norm_stats

        _maybe_compute_norm_stats(cfg, self.dataset)
        self.tag = C.make_model_tag(seed, cfg)
        self.kind = "transformer" if cfg["model_name"] in C.LM_MODEL_NAMES else "vision"
        self.model = make_model(cfg)
        self.mesh = make_mesh(len(jax.devices()), 1)
        self.engine = CentralEngine(self.model, cfg, self.mesh)
        self.evaluator = Evaluator(self.model, cfg, self.mesh, seed=seed)
        self.scheduler = make_scheduler(cfg)

    def _epoch_batches(self):
        """Shuffled, device-count-padded batches for one epoch."""
        cfg = self.cfg
        n_dev = self.mesh.devices.size
        if self.kind == "vision":
            tr = self.dataset["train"]
            b = cfg["batch_size"]["train"]
            b = _ceil_div(b, n_dev) * n_dev
            perm = self.rng.permutation(len(tr.data))
            x, w = _batch_pad(tr.data[perm], b)
            y, _ = _batch_pad(tr.target[perm], b)
            return x, y, w
        tr = self.dataset["train"]
        from ..data import bptt_windows
        wins = bptt_windows(tr.token, cfg["bptt"])
        xs, ws = _stack_windows(wins, cfg["bptt"])
        r = xs.shape[1]
        rpad = _ceil_div(r, n_dev) * n_dev - r
        if rpad:
            xs = np.concatenate([xs, np.zeros((xs.shape[0], rpad, xs.shape[2]), xs.dtype)], 1)
            ws = np.concatenate([ws, np.zeros((ws.shape[0], rpad, ws.shape[2]), np.float32)], 1)
        return xs, ws

    def run(self, pivot_metric: str, pivot_mode: str = "max"):
        import os

        from ..utils import (Logger, checkpoint_path, copy_best, resume,
                             save_checkpoint)

        cfg = self.cfg
        params = self.model.init(jax.random.fold_in(self.host_key, 0))
        opt = self.engine.init_opt(params)
        last_epoch = 1
        pivot = -float("inf") if pivot_mode == "max" else float("inf")
        logger = Logger(os.path.join(cfg["output_dir"], "runs", f"train_{self.tag}"),
                        use_tensorboard=bool(cfg.get("use_tensorboard")))
        blob = resume(cfg["output_dir"], self.tag, cfg["resume_mode"])
        if blob and "params" in blob:
            params = {k: jnp.asarray(v) for k, v in blob["params"].items()}
            if "epoch" in blob:
                last_epoch = blob["epoch"]
                pivot = blob.get("pivot", pivot)
            if blob.get("opt_state") is not None:  # momentum/moments survive resume
                st = blob["opt_state"]
                opt = type(opt)(jnp.asarray(st.step),
                                jax.tree_util.tree_map(jnp.asarray, st.slots))
        n_epochs = cfg["num_epochs"] if not isinstance(cfg["num_epochs"], dict) \
            else cfg["num_epochs"]["global"]
        # evaluation staging (same arrays as the federated driver's global eval)
        if self.kind == "vision":
            te = self.dataset["test"]
            xg, wg = _batch_pad(te.data, cfg["batch_size"]["test"])
            yg, _ = _batch_pad(te.target, cfg["batch_size"]["test"])
            geval = (xg, yg, wg)
            xs, ws = _batch_pad(self.dataset["train"].data, cfg["batch_size"]["train"])
            sbn_batches = (xs, ws)
        else:
            from ..data import bptt_windows
            xs, ws = _stack_windows(bptt_windows(self.dataset["test"].token, cfg["bptt"]),
                                    cfg["bptt"])
            geval = (xs, ws)
        from ..utils import summarize_sums
        for epoch in range(last_epoch, n_epochs + 1):
            logger.safe(True)
            lr = self.scheduler(epoch)
            t0 = time.time()
            data = self._epoch_batches()
            params, opt, (lsum, msum, n) = self.engine.train_epoch(
                params, opt, jax.random.fold_in(self.host_key, epoch), lr,
                *[jnp.asarray(a) for a in data])
            sums = {"loss_sum": np.asarray(lsum), "score_sum": np.asarray(msum), "n": np.asarray(n)}
            named = summarize_sums(sums, cfg["model_name"], prefix="")
            logger.append(named, "train", n=float(sums["n"]))
            logger.append({"info": [f"Model: {self.tag}", f"Train Epoch: {epoch}",
                                    f"Learning rate: {lr:g}",
                                    f"Epoch time: {time.time()-t0:.2f}s"]}, "train", mean=False)
            logger.write("train", list(named))
            bn = {}
            if self.kind == "vision":
                # staticcheck: allow(no-host-eval-in-driver): centralized
                # (non-federated) epoch loop -- no superstep to fuse into
                bn = self.evaluator.sbn_stats(params, *sbn_batches)
            # staticcheck: allow(no-host-eval-in-driver): centralized loop
            g = self.evaluator.eval_global(params, bn, *geval, epoch=epoch)
            named_g = summarize_sums({k: np.asarray(v) for k, v in g.items()},
                                     cfg["model_name"], prefix="")
            logger.append(named_g, "test", n=g["n"])
            logger.append({"info": [f"Model: {self.tag}", f"Test Epoch: {epoch}"]},
                          "test", mean=False)
            logger.write("test", list(named_g))
            logger.safe(False)
            cur = logger.history.get(f"test/{pivot_metric}", [None])[-1]
            is_best = cur is not None and (cur > pivot if pivot_mode == "max" else cur < pivot)
            if is_best:
                pivot = cur  # update BEFORE saving so a resumed run keeps it
            save_checkpoint(checkpoint_path(cfg["output_dir"], self.tag), {
                "cfg": {k: v for k, v in cfg.items() if k != "vocab"},
                "epoch": epoch + 1, "params": params, "bn_state": bn,
                "pivot": pivot, "logger_history": dict(logger.history),
                "opt_state": opt})
            if is_best:
                copy_best(cfg["output_dir"], self.tag)
            logger.reset()
        return {"params": params, "bn_state": bn, "logger": logger}


def run_central_main(description: str, model_default: str, data_default: str,
                     pivot_metric: str, pivot_mode: str, argv=None):
    from .. import config as C
    from .common import build_cli, cfg_from_args

    parser = build_cli(description)
    args = parser.parse_args(argv)
    cfg = cfg_from_args(args)
    if args.model_name is None:
        cfg["model_name"] = model_default
    if args.data_name is None:
        cfg["data_name"] = data_default
    cfg["control"]["data_split_mode"] = "none"
    cfg = C.process_control(cfg)
    results = []
    for i in range(cfg["num_experiments"]):
        seed = cfg["init_seed"] + i
        exp = CentralExperiment(cfg, seed)
        print(f"Experiment: {exp.tag}")
        results.append(exp.run(pivot_metric, pivot_mode))
    return results
