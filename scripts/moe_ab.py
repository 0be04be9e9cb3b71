"""The expert cells' DISPATCH on the chip, alone, at each cell's shapes: what
lies round the loop of `ops.layers.moe_experts` -- the index arrays, the zero
fills of the loop's buffers, the combine -- sized by the bound on the held
pairs (FULL: all `T * K`) against sized by `ops.layers.moe_capacity` (COMPACT:
two tiles a held expert), part by part and as the whole routed part of a
layer, forward and forward + backward.  One layer's pairs come from a seeded
router over all `E` experts (even routing: one pair in `E / held` is held).

The combine is timed four ways on the same rows: `K` gathers of `T` rows as one
fused sum (the full dispatch's, off the full and off the compact buffer); the
first `m` of them as one fused sum picked by `lax.switch` (`m` the most held
choices of a token, the held slots moved to the front of a token's `K`: the
compact dispatch's, `ops.layers._rows_summed`); the same `m` gathers as a loop
that carries the sum; and a sorted, unique scatter-add a tile (a token occurs
once in an expert's tile, in ascending order) -- which adds a token's terms by
EXPERT and so is not the other three's sum bit for bit.

    chiprun -- python scripts/moe_ab.py [seed] [keye,laguna,...]        # on the chip
    chiprun -- python scripts/moe_ab.py share [seed] [keye,...]         # the counters of one round of the cell
    JAX_PLATFORMS=cpu python scripts/moe_ab.py tiny   # the same code at a small size

`share` builds the cell's own experiment (`benchmark/harness.py`, the
benchmark's data and weights from the seed) with telemetry on, trains ONE
round and prints what `obs.split_probes` finishes: `moe_compact_share` (expert
layer applications whose dispatch was the compact one), `moe_held_share`,
`moe_dropped`.  Milliseconds are host-clock over 5 calls, best of 3.  The
numbers land in `chiprun_out/moe_ab.json` (`share`: `moe_share.json`).
"""
import json
import os
import sys
import tempfile
import time
from functools import partial

os.environ.setdefault("TPU_LOG_DIR", "disabled")
sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402
from jax import lax  # noqa: E402

from heterofl_tpu.models.decoder import expert_tile  # noqa: E402
from heterofl_tpu.ops import layers as L  # noqa: E402

#: family -> (cell, tokens a step, choices a token, experts, held, expected
#: groups a tile (None: `MOE_TILE`), hidden, expert width, expert body), as the
#: cell's config and `models/<family>.py` give them
CELLS = {
    "keye": ("keye-vl-2-30b-a3b.fix-a1-e1.train-8k", 8192, 8, 128, 8, 2, 2048, 768, L.swiglu),
    "laguna": ("laguna-xs.2.fix-a1-e1.train-8k", 8192, 8, 256, 16, 2, 2048, 512, L.swiglu),
    "nemotron_h": ("nemotron-3-nano-30b-a3b.fix-a1-e1.train-8k", 8192, 6, 128, 8, 4, 2688, 1856,
                   L.relu2_ffn),
    "kanana2": ("kanana-2-30b-a3b.fix-a1-e1.train", 4096, 6, 128, 8, None, 2048, 768, L.swiglu),
    "lfm2": ("lfm2-8b-a1b.fix-a1-e1.train", 4096, 4, 32, 8, 2, 2048, 1792, L.swiglu),
}
TINY = {"tiny": ("", 256, 4, 64, 4, None, 32, 16, L.swiglu)}


def timed(fn, *args):
    jax.block_until_ready(fn(*args))
    best = float("inf")
    for _ in range(3):
        t = time.perf_counter()
        for _ in range(5):
            out = fn(*args)
        jax.block_until_ready(out)
        best = min(best, (time.perf_counter() - t) / 5)
    return round(1e3 * best, 4)


def gather_k(y, slot, w):
    return sum(w[:, k, None] * L._gather_rows(y, slot[:, k]) for k in range(slot.shape[1]))


def held_first(slot, w):
    """`(front, scale, m)`: a token's held rows and their weights moved to the
    front of its `K`, as `ops.layers._experts_forward` does."""
    front, place, m = L._held_first(slot)
    return front, jnp.sum(jnp.where(place, w[None], 0), axis=2), m


def gather_m(y, slot, w):
    front, scale, m = held_first(slot, w)
    return L._rows_summed(y, front, m, scale)


def loop_m(y, slot, w):
    """`gather_m`'s sum as a loop of `m` steps, one gather of `T` rows each."""
    front, scale, m = held_first(slot, w)
    return lax.fori_loop(
        0, m, lambda i, acc: acc + lax.dynamic_index_in_dim(scale, i, 0, keepdims=False)[:, None]
        * L._gather_rows(y, lax.dynamic_index_in_dim(front, i, 0, keepdims=False)),
        jnp.zeros((slot.shape[0], y.shape[1]), y.dtype))


def scatter_tiles(y, rows, w, n_tiles, tile, T):
    """A tile's rows added onto their tokens: within a tile (one expert) a
    token occurs once, ascending; padding rows point past the last token."""
    K, w_flat = w.shape[1], w.reshape(-1)

    def step(i, out):
        r = lax.dynamic_slice(rows, (i * tile,), (tile,))
        y_t = lax.dynamic_slice(y, (i * tile, 0), (tile, y.shape[1]))
        tok = jnp.where(r >= 0, r // K, T + jnp.arange(tile))
        return out.at[tok].add(L._gather_rows(w_flat, r)[:, None] * y_t, mode="drop",
                               indices_are_sorted=True, unique_indices=True)

    return lax.fori_loop(0, n_tiles, step, jnp.zeros((T, y.shape[1]), y.dtype))


def dispatch_parts(T, K, E, held, groups, D, F, body, seed):
    """One cell's rows of the table: milliseconds a part, full against compact."""
    tile = L.MOE_TILE if groups is None else expert_tile(T, K, E, groups)
    tile = min(tile, max(8, T * K // E * 2)) if T < 1024 else tile       # the tiny size
    n_rows = (T * K // tile + held) * tile
    cap = L.moe_capacity(held, tile, n_rows)
    keys = jax.random.split(jax.random.key(seed % (2 ** 31)), 8)
    h = jax.random.normal(keys[0], (T, D))
    sel, w = jax.jit(partial(L.moe_route, top_k=K, scaling=1.0))(
        h, jax.random.normal(keys[1], (D, E)) / D ** 0.5, None)
    n_mats = 3 if body is L.swiglu else 2
    experts = [jax.random.normal(k, (held, F, D) if i == n_mats - 1 else (held, D, F)) / D ** 0.5
               for i, k in enumerate(keys[2:2 + n_mats])]
    probe = jax.random.normal(keys[6], (T, D))
    local = sel.reshape(-1)
    is_held = local < held                                          # the first `held` experts
    e = jnp.where(is_held, local, held)
    full_index = jax.jit(partial(L._sorted_groups, held=held, tile=tile, n_rows=n_rows))
    _, slot, rows, tile_expert, ends = full_index(e, is_held)
    slot = slot.reshape(T, K)
    n_tiles, n_held = int(ends[-1]), int(is_held.sum())
    m = int(jnp.max(jnp.sum(slot >= 0, axis=1)))
    out = {"T": T, "K": K, "E": E, "held": held, "tile": tile, "D": D, "F": F, "n_rows": n_rows,
           "capacity": cap, "held_pairs": n_held, "tiles_in_use": n_tiles,
           "most_held_choices_of_a_token": m, "ms": {}}
    ms = out["ms"]

    def routed(h, w, experts):
        return L.moe_experts(h, sel, w, experts, 0, lambda x: x, tile=tile, body=body)

    def whole(tag):
        y, c = jax.jit(lambda *a: routed(*a))(h, w, experts)  # a new trace a build
        out[f"compact_counter.{tag}"] = c["compact"].tolist()
        ms[f"routed_part.fwd.{tag}"] = timed(jax.jit(lambda *a: routed(*a)[0]), h, w, experts)
        ms[f"routed_part.fwd+bwd.{tag}"] = timed(jax.jit(jax.grad(
            lambda *a: jnp.sum(routed(*a)[0] * probe), argnums=(0, 1, 2))), h, w, experts)
        return y

    ms["index.full"] = timed(full_index, e, is_held)
    ms["index.argsort_all_pairs"] = timed(jax.jit(lambda e: jnp.argsort(e, stable=True)), e)
    ms["zeros.full"] = timed(jax.jit(lambda: jnp.zeros((n_rows, D), jnp.float32)))
    y_rows = jax.random.normal(keys[7], (n_rows, D))
    ms["combine.gather_K.full"] = timed(jax.jit(gather_k), y_rows, slot, w)
    ms["dh.gather_K.full"] = timed(jax.jit(lambda y, s: sum(
        L._gather_rows(y, s[:, k]) for k in range(K))), y_rows, slot)
    y_c = whole("as_built")
    if cap is not None and n_tiles * tile <= cap:
        ms["index.compact"] = timed(jax.jit(partial(L._compact_groups, held=held, tile=tile,
                                                    n_rows=n_rows, cap=cap, K=K)), e, is_held)
        ms["zeros.compact"] = timed(jax.jit(lambda: jnp.zeros((cap, D), jnp.float32)))
        ms["combine.gather_K.compact"] = timed(jax.jit(gather_k), y_rows[:cap], slot, w)
        ms["combine.gather_m.compact"] = timed(jax.jit(gather_m), y_rows[:cap], slot, w)
        ms["combine.loop_m.compact"] = timed(jax.jit(loop_m), y_rows[:cap], slot, w)
        ms["combine.scatter_tiles.compact"] = timed(
            jax.jit(partial(scatter_tiles, tile=tile, T=T)), y_rows[:cap], rows, w, ends[-1])
        ms["dh.gather_m.compact"] = timed(
            jax.jit(lambda y, s: L._rows_summed(y, *L._held_first(s)[::2])), y_rows[:cap], slot)
        real = L.moe_capacity
        L.moe_capacity = lambda *a: None                            # the full dispatch alone
        try:
            y_f = whole("full_only")
        finally:
            L.moe_capacity = real
        out["compact_equals_full"] = bool(jnp.array_equal(y_c, y_f))
        out["largest_gap"] = float(jnp.max(jnp.abs(y_c - y_f)))
    return out


def share(cell_name, seed):
    """One round of the cell's own experiment with telemetry on: the experts'
    counters as `obs.split_probes` finishes them."""
    from benchmark import harness, weights
    from heterofl_tpu import config as C
    from heterofl_tpu.entry.common import FedExperiment, build_cli, cfg_from_args
    from heterofl_tpu.obs import split_probes

    cell, config = harness.load_cell(cell_name)
    with tempfile.TemporaryDirectory(prefix="moe_ab_") as work:
        data_dir, out_dir = os.path.join(work, "data"), os.path.join(work, "out")
        harness.load_module("data", config["data"]["writer"]).write(
            data_dir, config["data_name"], seed, config["data"]["sizes"])
        cfg = C.process_control(cfg_from_args(build_cli("moe_ab").parse_args(
            harness.experiment_argv(cell, config, seed, data_dir, out_dir))))
        cfg["telemetry"] = "on"
        exp = FedExperiment(cfg, cfg["init_seed"])
        exp.stage(*exp.make_splits())
        shapes = {k: tuple(v.shape) for k, v in
                  jax.eval_shape(exp.model.init, jax.random.key(0)).items()}
        params = weights.make_params(shapes, seed)
        epoch = harness.WINDOW_EPOCH
        _, ms = exp.engine.train_round(params, jax.random.fold_in(exp.host_key, epoch),
                                       exp.scheduler(epoch), exp.sample_users(epoch),
                                       exp.train_data)
        ms = {k: np.asarray(v) for k, v in ms.items()}
    _, rounds = split_probes(ms, exp.mesh.shape["clients"],
                             counters=exp.model.meta["counters"])
    rec = rounds[0]
    return {k: rec[k] for k in ("moe_compact", "moe_compact_share", "moe_assign",
                                "moe_held_share", "moe_dropped", "moe_tokens")}


def main(argv):
    seed = int(next((a for a in argv if a.isdigit()), 4900000101))
    named = [f for a in argv for f in a.split(",") if f in CELLS]
    cells = TINY if "tiny" in argv else {f: CELLS[f] for f in named or CELLS}
    out = {"seed": seed, "device": jax.devices()[0].device_kind}
    for family, (cell, *shape) in cells.items():
        if "share" in argv:
            out[family] = share(cell, seed)
        else:
            out[family] = dispatch_parts(*shape, seed)
        print(family, json.dumps(out[family]), flush=True)
    os.makedirs("chiprun_out", exist_ok=True)
    name = "moe_share.json" if "share" in argv else "moe_ab.json"
    with open(os.path.join("chiprun_out", name), "w", encoding="utf-8") as f:
        json.dump(out, f, indent=1)


if __name__ == "__main__":
    main(sys.argv[1:])
