"""Jitted evaluation programs: sBN recalibration and test metrics.

sBN ("static batch norm"): federated training runs BN without running stats;
before each evaluation the aggregated global model does one no-grad pass over
the train set with fresh cumulative running statistics (momentum=None CMA),
ref train_classifier_fed.py:127-138.  Here that pass is a ``lax.scan`` over
batches with the batch axis sharded across all mesh devices (``psum`` of
partial sums) -- the whole recalibration is one XLA program.

Evaluation mirrors ref train_classifier_fed.py:141-168: "Local" = per-user
test shards with that user's label mask; "Global" = full test set, no mask.
Users are vmapped and sharded over the ``clients`` axis like the train round.

The per-device batch cores (``_sbn_body``/``_users_body``/``_global_body``)
are pure functions of committed operands, shared by TWO callers: the
standalone host-dispatched programs below (the ``superstep_rounds=1``
reference path) and :class:`FusedEval`, which threads the same bodies into
the round engines' K-round superstep programs so eval windows no longer
break the scan (ISSUE 4 tentpole).  One body, two harnesses -- the
eval-fused superstep is bit-identical to the host loop by construction.
"""

from __future__ import annotations

from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import NamedSharding, PartitionSpec as P

from ..data.datasets import DATASET_STATS
from ..models.base import ModelDef
from .round_engine import _ceil_div, _shard_map
from ..obs.trace import scoped
from .staging import PlacementCache


class Evaluator:
    def __init__(self, model: ModelDef, cfg: Dict[str, Any], mesh, seed: int = 0):
        self.model = model
        self.cfg = cfg
        self.mesh = mesh
        # Eval RNG descends from the EXPERIMENT seed (ref draws fresh noise
        # per pass from the global torch RNG, src/models/transformer.py:148-151,
        # which the experiment seed controls); stream tags 0/1 keep the
        # per-user and global eval streams distinct.
        base = jax.random.key(seed)
        self._users_key = jax.random.fold_in(base, 0)
        self._global_key = jax.random.fold_in(base, 1)
        self.is_lm = model.is_lm
        self.norm_stats = cfg.get("norm_stats") or DATASET_STATS.get(cfg["data_name"])
        self.bptt = cfg.get("bptt", 64)
        self._sbn = None
        self._users = None
        self._global = None
        # eval operands are padded + committed to the mesh once per staged
        # dataset (PlacementCache.memo); repeated eval passes -- host-loop OR
        # eval-fused superstep dispatches -- re-use the same device-resident
        # buffers instead of re-uploading every round
        self._staging = PlacementCache(mesh)

    def _norm(self, x):
        from ..ops.augment import normalize_image

        if self.norm_stats is None:
            return x.astype(jnp.float32)
        return normalize_image(x, *self.norm_stats)

    # -------------------- sBN recalibration --------------------

    @scoped("eval/sbn")
    def _sbn_body(self, params, xb, wb):
        """Per-device sBN moment accumulation (pure; runs under any
        ``shard_map`` whose mesh carries the ``clients``/``data`` axes):
        scan this device's ``[s_local, B, ...]`` train batches, psum the
        moment sums across the whole mesh, return the CMA stats."""
        model = self.model

        def one(carry, inp):
            x, w = inp
            has = (jnp.sum(w) > 0).astype(jnp.float32)
            _, col = model.apply(params, {"img": self._norm(x),
                                          "label": jnp.zeros(x.shape[0], jnp.int32)},
                                 train=True, bn_mode="collect", sample_weight=w)
            sums = {site: (m * has, v * has) for site, (m, v) in col.items()}
            carry_sums, carry_n = carry
            carry_sums = {s: (carry_sums[s][0] + sums[s][0], carry_sums[s][1] + sums[s][1])
                          for s in carry_sums}
            return (carry_sums, carry_n + has), None

        zero = {site: (jnp.zeros(model.meta["bn_sizes"][site]),
                       jnp.zeros(model.meta["bn_sizes"][site]))
                for site in model.bn_sites}
        (sums, n), _ = jax.lax.scan(one, (zero, jnp.zeros(())), (xb, wb))
        # ONE psum bind for moments+count (bit-compatible with two binds;
        # staticcheck audits the eval phase's collective budget separately
        # from the per-training-round psum)
        sums, n = jax.lax.psum((sums, n), ("clients", "data"))
        return {s: (sums[s][0] / jnp.maximum(n, 1.0), sums[s][1] / jnp.maximum(n, 1.0))
                for s in sums}

    def _build_sbn(self):
        fn = _shard_map(self._sbn_body, self.mesh,
                        in_specs=(P(), P(("clients", "data")), P(("clients", "data"))),
                        out_specs=P())
        # staticcheck: allow(jit-needs-donation): sBN reads the live globals
        # and the committed train batches -- donation would delete both
        return jax.jit(fn)

    def _staged_sbn(self, x_batches: np.ndarray, w_batches: np.ndarray):
        """Pad-and-commit the ``[S, B, ...]`` sBN batches once (shared by the
        host-loop program and the eval-fused superstep operands)."""

        def build():
            n_dev = self.mesh.devices.size
            s = x_batches.shape[0]
            pad = (-s) % n_dev
            xb, wb = x_batches, w_batches
            if pad:
                xb = np.concatenate([xb, np.zeros((pad,) + xb.shape[1:], xb.dtype)])
                wb = np.concatenate([wb, np.zeros((pad,) + wb.shape[1:], np.float32)])
            sh = NamedSharding(self.mesh, P(("clients", "data")))
            return jax.device_put(xb, sh), jax.device_put(wb, sh)

        return self._staging.memo("sbn", (x_batches, w_batches), build)

    def sbn_stats(self, params, x_batches: np.ndarray, w_batches: np.ndarray):
        """Cumulative-average BN stats over ``[S, B, ...]`` uint8 batches.

        S must be padded (zero-weight batches) to a multiple of the total
        device count; returns ``{site: (running_mean, running_var)}``.
        """
        if not self.model.bn_sites:
            return {}
        if self._sbn is None:
            self._sbn = self._build_sbn()
        xb, wb = self._staged_sbn(x_batches, w_batches)
        return self._sbn(params, xb, wb)

    # -------------------- evaluation --------------------

    def _eval_batch_metrics(self, params, bn_state, batch, lm, w, key):
        out, _ = self.model.apply(params, batch, train=False,
                                  bn_mode="running" if bn_state else "batch",
                                  bn_state=bn_state or None, label_mask=lm,
                                  sample_weight=w, rng=key)
        n = jnp.sum(w)
        loss = out["loss"]
        if self.is_lm:
            # reference Perplexity is exp(batch CE), size-weighted by rows
            rows = np.float32(batch["label"].shape[0])  # static trace-time constant
            return {"loss_sum": loss * rows, "score_sum": jnp.exp(loss) * rows, "n": rows}
        y = batch["label"]
        correct = jnp.sum((jnp.argmax(out["score"], -1) == y) * w)
        return {"loss_sum": loss * n, "score_sum": correct, "n": n}

    @scoped("eval/users")
    def _users_body(self, params, bn_state, key, valid, x, y, m, lm):
        """Per-device "Local" eval core (pure, shard_map-reusable): vmap this
        device's user shards through their batched test sets; per-user keys
        descend from ``key`` by GLOBAL user position so results are
        mesh-placement-invariant.  No collective -- the per-user sums stay
        sharded over ``clients``."""

        def one_user(xu, yu, mu, lmu, k, v):
            def stepf(acc, inp):
                xb, yb, wb, kk = inp
                ms = self._eval_batch_metrics(params, bn_state,
                                              {"img": self._norm(xb), "label": yb},
                                              lmu, wb, kk)
                return {kk2: acc[kk2] + ms[kk2] for kk2 in acc}, None

            S = xu.shape[0]
            keys = jax.random.split(k, S)
            acc0 = {"loss_sum": jnp.zeros(()), "score_sum": jnp.zeros(()), "n": jnp.zeros(())}
            acc, _ = jax.lax.scan(stepf, acc0, (xu, yu, mu, keys))
            return {kk: v * acc[kk] for kk in acc}

        a = x.shape[0]
        dev = jax.lax.axis_index("clients")
        keys = jax.vmap(lambda i: jax.random.fold_in(key, dev * a + i))(jnp.arange(a))
        return jax.vmap(one_user)(x, y, m, lm, keys, valid)

    def _build_users(self):
        def body(params, bn_state, key, valid, *data):
            return self._users_body(params, bn_state, key, valid, *data)

        fn = _shard_map(body, self.mesh,
                        in_specs=(P(), P(), P(), P("clients"), P("clients"), P("clients"),
                                  P("clients"), P("clients")),
                        out_specs=P("clients"))
        # staticcheck: allow(jit-needs-donation): eval reads the live globals
        # and the once-committed eval operands -- nothing here is consumable
        return jax.jit(fn)

    def _staged_users(self, x, y, m, lm):
        """Pad-and-commit the per-user local-eval operands once: returns the
        committed ``(valid, x, y, m, lm)`` tuple (users padded to the
        clients-axis size, ``valid`` masking the pads)."""
        u = x.shape[0]

        def build():
            n_dev = self.mesh.shape["clients"]
            pad = (-u) % n_dev
            valid = np.concatenate([np.ones(u, np.float32), np.zeros(pad, np.float32)])
            arrs = [x, y, m, lm]
            if pad:
                arrs = [np.concatenate([a, np.zeros((pad,) + a.shape[1:], a.dtype)])
                        for a in arrs]
            sh = NamedSharding(self.mesh, P("clients"))
            return tuple(jax.device_put(a, sh) for a in [valid] + arrs)

        return self._staging.memo("local_eval", (x, y, m, lm), build)

    def eval_users(self, params, bn_state, x, y, m, lm, epoch: int = 0):
        """Per-user "Local" metrics: ``x [U, S, B, ...]`` batched test shards,
        label masks ``lm [U, classes]``.  Returns per-user metric sums.

        ``epoch`` seeds the eval RNG (LM token corruption) so noise is fresh
        each round, matching the reference's per-pass Bernoulli draws
        (ref ``src/models/transformer.py:148-151``) while staying reproducible.
        """
        if self._users is None:
            self._users = self._build_users()
        u = x.shape[0]
        vd, xd, yd, md, lmd = self._staged_users(x, y, m, lm)
        key = jax.random.fold_in(self._users_key, epoch)
        out = self._users(params, bn_state, key, vd, xd, yd, md, lmd)
        # staticcheck: allow(no-asarray): the eval-boundary D2H fetch point
        return {k: np.asarray(v)[:u] for k, v in out.items()}

    @scoped("eval/global")
    def _global_body(self, params, bn_state, key, *data):
        """Per-device "Global" eval core (pure, shard_map-reusable): scan
        this device's slice of the batched test set and psum the metric sums
        across the whole mesh."""
        if self.is_lm:
            rows, w = data  # [s_local, R, bptt], [s_local, R, bptt]

            def stepf(acc, inp):
                lab, wb, kk = inp
                ms = self._eval_batch_metrics(params, bn_state, {"label": lab},
                                              None, wb, kk)
                has = (jnp.sum(wb) > 0).astype(jnp.float32)
                return {k2: acc[k2] + ms[k2] * has for k2 in acc}, None

            S = rows.shape[0]
            keys = jax.random.split(key, S)
            acc0 = {"loss_sum": jnp.zeros(()), "score_sum": jnp.zeros(()), "n": jnp.zeros(())}
            acc, _ = jax.lax.scan(stepf, acc0, (rows, w, keys))
        else:
            x, y, w = data

            def stepf(acc, inp):
                xb, yb, wb, kk = inp
                ms = self._eval_batch_metrics(params, bn_state,
                                              {"img": self._norm(xb), "label": yb},
                                              None, wb, kk)
                return {k2: acc[k2] + ms[k2] for k2 in acc}, None

            S = x.shape[0]
            keys = jax.random.split(key, S)
            acc0 = {"loss_sum": jnp.zeros(()), "score_sum": jnp.zeros(()), "n": jnp.zeros(())}
            acc, _ = jax.lax.scan(stepf, acc0, (x, y, w, keys))
        return jax.lax.psum(acc, ("clients", "data"))

    def _build_global(self):
        def body(params, bn_state, key, *data):
            return self._global_body(params, bn_state, key, *data)

        n_data = 3 if not self.is_lm else 2
        fn = _shard_map(body, self.mesh,
                        in_specs=(P(), P(), P()) + (P(("clients", "data")),) * n_data,
                        out_specs=P())
        # staticcheck: allow(jit-needs-donation): eval reads the live globals
        # and the once-committed eval operands -- nothing here is consumable
        return jax.jit(fn)

    def _staged_global(self, *batched):
        """Pad-and-commit the global-eval operands once (batch axis padded
        to the total device count, sharded over ``(clients, data)``)."""

        def build():
            n_dev = self.mesh.devices.size
            pad = (-batched[0].shape[0]) % n_dev
            sh = NamedSharding(self.mesh, P(("clients", "data")))
            out = []
            for arr in batched:
                if pad:
                    arr = np.concatenate([arr, np.zeros((pad,) + arr.shape[1:], arr.dtype)])
                out.append(jax.device_put(arr, sh))
            return tuple(out)

        return self._staging.memo("global_eval", batched, build)

    def eval_global(self, params, bn_state, *batched, epoch: int = 0):
        """"Global" metrics over the full test set: vision
        ``(x [S,B,...], y [S,B], w [S,B])``; LM ``(rows [S,R,bptt], w)``.

        ``epoch`` seeds the eval RNG so LM corruption noise differs round to
        round (ref ``src/models/transformer.py:148-151``)."""
        if self._global is None:
            self._global = self._build_global()
        padded = self._staged_global(*batched)
        key = jax.random.fold_in(self._global_key, epoch)
        out = self._global(params, bn_state, key, *padded)
        # staticcheck: allow(no-float-coercion): the eval-boundary D2H fetch
        return {k: float(v) for k, v in out.items()}

    # -------------------- eval-fused superstep support --------------------

    def fused(self, sbn_batches: Optional[Tuple[np.ndarray, np.ndarray]] = None,
              local_eval: Optional[Tuple] = None,
              global_eval: Optional[Tuple] = None) -> "FusedEval":
        """Build the :class:`FusedEval` for this experiment: eval operands
        committed ONCE (sharing the host-path memo entries, so the two paths
        read the same device buffers) plus the pure per-device eval core the
        round engines splice into their superstep scan.

        ``sbn_batches``: the ``[S, B, ...]`` train batches for sBN
        recalibration (vision models with BN); ``local_eval``: the per-user
        ``(x, y, m, lm)`` batched test shards (vision); ``global_eval``: the
        batched full test set (always required)."""
        if global_eval is None:
            raise ValueError("fused eval needs the global-eval operands "
                             "(the reference evaluates Global every pass)")
        ops, specs = [], []
        has_sbn = (not self.is_lm and sbn_batches is not None
                   and bool(self.model.bn_sites))
        if has_sbn:
            xb, wb = self._staged_sbn(*sbn_batches)
            ops += [xb, wb]
            specs += [P(("clients", "data"))] * 2
        has_local = not self.is_lm and local_eval is not None
        n_users = 0
        if has_local:
            n_users = int(local_eval[0].shape[0])
            staged = self._staged_users(*local_eval)
            ops += list(staged)
            specs += [P("clients")] * len(staged)
        gops = self._staged_global(*global_eval)
        ops += list(gops)
        specs += [P(("clients", "data"))] * len(gops)
        # the eval PRNG roots ride as committed operands; fold_in(key, epoch)
        # happens in-jit from the scanned round index -- the same derivation
        # the host path performs outside its programs
        keys = self._staging.replicated("fused_eval_keys",
                                        (self._users_key, self._global_key))
        ops += list(keys)
        specs += [P(), P()]
        return FusedEval(self, tuple(ops), tuple(specs), has_sbn, has_local,
                         n_users)


class FusedEval:
    """The evaluator's batch cores packaged for in-superstep use (ISSUE 4).

    ``ops``/``specs``: once-committed device operands and their shard_map
    ``in_specs``, appended verbatim to the engines' superstep program
    arguments (NEVER closure-captured: a captured array would be baked into
    the program as a constant).  ``core(params, epoch, ops)`` is the
    per-device eval phase -- sBN moment accumulation, per-user Local sums
    and the Global psum -- called inside the engines' ``shard_map`` bodies
    on scan steps where the static eval mask fires.  ``out_specs`` is the
    matching output-spec prefix for the eval results stacked over the
    superstep's eval points.
    """

    def __init__(self, evaluator: Evaluator, ops: Tuple, specs: Tuple,
                 has_sbn: bool, has_local: bool, n_users: int):
        self._ev = evaluator
        self.ops = ops
        self.specs = specs
        self.has_sbn = has_sbn
        self.has_local = has_local
        self.n_users = n_users

    @property
    def out_specs(self):
        """Output-spec prefix for one stacked eval result: bn stats and the
        Global sums are replicated, the per-user Local sums stay sharded
        over ``clients`` behind the leading eval-stack axis."""
        return {"bn": P(), "local": P(None, "clients"), "global": P()}

    def core(self, params, epoch, ops) -> Dict[str, Any]:
        """One eval phase, per device: ``ops`` are this device's shards of
        :attr:`ops` in order.  Returns ``{"bn", "local", "global"}`` --
        identical math to the host-dispatched programs (same bodies).

        The phase is fenced with ``optimization_barrier`` on both sides:
        without the fence XLA context-fuses the eval ops with the
        surrounding superstep graph (measured ~1e-7 relative association
        drift on the CE reductions vs the standalone eval programs), which
        would break the bit-identical-to-host-loop contract."""
        params, epoch, ops = jax.lax.optimization_barrier((params, epoch, ops))
        return jax.lax.optimization_barrier(
            self.core_unfenced(params, epoch, ops))

    def core_unfenced(self, params, epoch, ops) -> Dict[str, Any]:
        """The eval phase WITHOUT the optimization_barrier fence: the
        arms-batched supersteps (ISSUE 14) vmap this over the arms axis and
        fence OUTSIDE the vmap (``optimization_barrier`` has no batching
        rule) -- same fusion isolation, one fence per eval point."""
        ev = self._ev
        ukey_root, gkey_root = ops[-2], ops[-1]
        i = 0
        bn: Dict[str, Any] = {}
        if self.has_sbn:
            bn = ev._sbn_body(params, ops[i], ops[i + 1])
            i += 2
        local: Dict[str, Any] = {}
        if self.has_local:
            valid, x, y, m, lm = ops[i:i + 5]
            i += 5
            local = ev._users_body(params, bn, jax.random.fold_in(ukey_root, epoch),
                                   valid, x, y, m, lm)
        g = ev._global_body(params, bn, jax.random.fold_in(gkey_root, epoch),
                            *ops[i:-2])
        return {"bn": bn, "local": local, "global": g}

    def assemble(self, host_tree, eval_epochs) -> list:
        """Host-side reassembly of the fetched eval stack: one dict per eval
        point ``{"epoch", "bn", "local", "global"}``, with the per-user Local
        sums sliced back to the true user count and the Global sums as
        python floats (the host-path ``eval_global`` contract)."""
        out = []
        for j, ep in enumerate(eval_epochs):
            out.append({
                "epoch": int(ep),
                "bn": {site: (mv[0][j], mv[1][j])
                       for site, mv in host_tree["bn"].items()},
                "local": {n: v[j][:self.n_users]
                          for n, v in host_tree["local"].items()},
                # staticcheck: allow(no-float-coercion): host-side assembly of
                # already-fetched numpy sums (the PendingMetrics boundary)
                "global": {n: float(v[j]) for n, v in host_tree["global"].items()},
            })
        return out
