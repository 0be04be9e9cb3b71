"""Sliced execution strategy: reference-shaped sub-models, one compiled
program per rate level.

The default "masked" strategy (parallel/round_engine.py) runs every client at
full width with channel masks -- the right trade on TPU (uniform shapes, MXU
tiles).  This runner instead materialises *true* sub-models per rate level
(exactly the tensors the reference's ``Federation.distribute`` ships,
fed.py:165-178): clients are grouped by level, each level's clients are
vmapped through a jitted local-train at its own small static shapes, and
aggregation happens host-side via gather/scatter + counted averaging.

Uses: host/CPU debugging, memory-constrained execution, and the round-level
equivalence check against the masked engine (tests/test_sliced.py) -- with
the same PRNG keys both strategies produce the same new global parameters.

NOTE: this is the host-orchestrated DEBUG twin (measured ~30x slower than
the masked engine).  The production dense-per-level path is the mesh-native
``parallel/grouped.py`` (``strategy: grouped``), which keeps the whole
round on device.
"""

from __future__ import annotations

from typing import Any, Dict, List, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from ..models import make_model
from ..models.spec import count_masks as make_count_masks
from ..parallel.round_engine import RoundEngine
from .core import (client_stream_keys, combine_counted, embed_sliced,
                   extract_sliced)


class SlicedFederation:
    """Host-orchestrated federated round over true sliced sub-models."""

    def __init__(self, cfg: Dict[str, Any]):
        self.cfg = cfg
        self.global_rate = cfg["global_model_rate"]
        self.global_model = make_model(cfg)
        self.is_lm = self.global_model.is_lm
        self.levels: Dict[float, Tuple[Any, Any]] = {}
        self._fns: Dict[float, Any] = {}
        for rate in sorted(set(float(r) for r in cfg["model_rate"]), reverse=True):
            model = make_model(cfg, model_rate=rate)
            self.levels[rate] = (model, RoundEngine(model, cfg, mesh=None))

    def _level_fn(self, rate: float):
        """Jitted vmapped local-train for one level (cached)."""
        if rate in self._fns:
            return self._fns[rate]
        model, engine = self.levels[rate]
        sr = rate / self.global_rate
        if self.is_lm:
            def one(p, rows, lm, key, lr):
                return engine._local_train_lm(p, 1.0, rows, lm, key, lr, scaler_rate=sr)
        else:
            def one(p, x, y, m, lm, key, lr):
                return engine._local_train_vision(p, 1.0, x, y, m, lm, key, lr, scaler_rate=sr)
        n_data = 2 if self.is_lm else 4
        fn = jax.jit(jax.vmap(one, in_axes=(0,) * (1 + n_data) + (0, None)))
        self._fns[rate] = fn
        return fn

    def train_round(self, global_params: Dict[str, Any], user_idx: np.ndarray,
                    rates: np.ndarray, data: Tuple, lr: float, key
                    ):
        """One round. ``data`` is the same stacked tuple the masked engine
        takes (vision: ``x[U,N,...], y, m, lm``; LM: ``rows[U,R,T], lm``).
        Client ``u`` uses the PRNG key ``client_stream_keys`` derives from
        its global user id, matching the masked engine on any
        mesh/placement."""
        gp_np = {k: np.asarray(v) for k, v in global_params.items()}
        shapes = {k: v.shape for k, v in gp_np.items()}
        summed = {k: np.zeros(s, np.float32) for k, s in shapes.items()}
        counts = {k: np.zeros(s, np.float32) for k, s in shapes.items()}
        gm = self.global_model
        user_idx = np.asarray(user_idx)
        lm_all = np.asarray(data[-1])

        n_slots = len(user_idx)
        metrics = {"loss_sum": np.zeros(n_slots, np.float32),
                   "score_sum": np.zeros(n_slots, np.float32),
                   "n": np.zeros(n_slots, np.float32),
                   "rate": np.asarray(rates, np.float32)}
        by_level: Dict[float, List[int]] = {}
        for slot, r in enumerate(np.asarray(rates, np.float64)):
            by_level.setdefault(float(r), []).append(slot)

        for rate, slots in sorted(by_level.items(), reverse=True):
            wr = rate / self.global_rate
            sliced = extract_sliced(gp_np, gm.specs, gm.groups, wr)
            params_stack = {k: jnp.asarray(np.broadcast_to(
                v, (len(slots),) + v.shape)) for k, v in sliced.items()}
            u = user_idx[slots]
            keys = client_stream_keys(key, np.asarray(u))
            client_data = tuple(jnp.asarray(np.asarray(a)[u]) for a in data)
            trained, ms = self._level_fn(rate)(params_stack, *client_data, keys,
                                               jnp.asarray(lr, jnp.float32))
            for mk in ("loss_sum", "score_sum", "n"):
                metrics[mk][slots] = np.asarray(ms[mk])
            trained = {k: np.asarray(v) for k, v in trained.items()}
            for ci, slot in enumerate(slots):
                small = {k: trained[k][ci] for k in trained}
                back = embed_sliced(small, gm.specs, gm.groups, wr, shapes)
                cm = {k: np.asarray(v) for k, v in
                      make_count_masks(shapes, gm.specs, gm.groups, wr,
                                       jnp.asarray(lm_all[user_idx[slot]])).items()}
                for k in shapes:
                    summed[k] += back[k] * cm[k]
                    counts[k] += cm[k]
        new = combine_counted(gp_np, summed, counts)
        return {k: np.asarray(v) for k, v in new.items()}, metrics
