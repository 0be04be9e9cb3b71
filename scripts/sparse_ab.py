"""The Keye cell's sparse-attention indexer on the chip, at the cell's shapes
(one row of 8,192 positions, hidden 2,048, 16 indexer heads of 64, top-2,048)
with the benchmark's seeded weights: how many (query, key) selections the
program (`models.keye.index_keys`: `ops.layers.select_keys`, the exact top-k
as counting passes) and the plain reference (`benchmark/reference/keye.py`:
`lax.top_k` on its own scores) disagree on, layer by layer on one input; what
the program's counters read; what the selection costs against a sort; and one
layer's SELECTED ATTENTION under layer 0's selection, the `jnp` block loop
(`ops.layers.blockwise_gq_attention(select=)`) against the fused kernels
(`ops/pallas_attention.py` `sel_attn_fwd` / `sel_attn_bwd`) at the tile the
rule gives (`sel_tile_for`) and at any others named, forward and forward +
backward, alone and inside the layer's attention BLOCK (`models.decoder.
gq_attention`: projections, head norms, turn, attention, output projection,
under `jax.checkpoint` as a layer runs it), which is where a kernel's operand
layout shows in its neighbours.  Four minutes.  `keep` instead times ONE
LAYER of the model (`make_model` at one layer: embedding, the layer, head and
loss), forward + backward, and reads its program's bytes with what the
layer's checkpoint keeps for the backward taken in turn as nothing but its
input / the indexer's choice / the choice and the kernels' output
(`models.keye.kept`, the model's own, is the last):

    chiprun -- python scripts/sparse_ab.py [seed] [256x512,128x512]   # on the chip
    chiprun -- python scripts/sparse_ab.py attn [seed] [tiles]        # the attention part alone
    chiprun -- python scripts/sparse_ab.py keep [seed]                # what a layer keeps
    JAX_PLATFORMS=cpu python scripts/sparse_ab.py tiny  # the same code at the tests' size

Every layer's indexer reads the SAME input here (the normed embedding of the
row), so that the two sides' choices differ by their own arithmetic alone and
not by what earlier layers handed on.  Milliseconds are host-clock over 5
calls, best of 3.  The numbers land in `chiprun_out/sparse_ab.json` (`keep`:
`sparse_keep.json`).
"""
import json
import os
import sys
import time
from functools import partial

os.environ.setdefault("TPU_LOG_DIR", "disabled")
sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402

from benchmark import harness, weights  # noqa: E402
from benchmark.reference import common, keye as ref  # noqa: E402
from heterofl_tpu import config as C  # noqa: E402
from heterofl_tpu.entry.common import build_cli, cfg_from_args  # noqa: E402
from heterofl_tpu.models import keye, make_model  # noqa: E402
from heterofl_tpu.models.decoder import gq_attention, layer_leaves  # noqa: E402
from heterofl_tpu.ops import layers as L  # noqa: E402
from heterofl_tpu.ops import pallas_attention as PA  # noqa: E402

NAME = "keye-vl-2-30b-a3b.fix-a1-e1.train-8k"


def timed(fn, *args):
    jax.block_until_ready(fn(*args))
    best = float("inf")
    for _ in range(3):
        t = time.perf_counter()
        for _ in range(5):
            out = fn(*args)
        jax.block_until_ready(out)
        best = min(best, (time.perf_counter() - t) / 5)
    return 1e3 * best


def attention_times(a, lp, h, select, block, tiles, interpret):
    """Milliseconds of one layer's selected attention at the cell's shapes
    under ``select``: alone (operands heads first, seeded) and in the layer's
    block on ``h``; the block loop, then the kernels at each of ``tiles``."""
    H, Hkv, d, eps = a["num_attention_heads"], a["num_key_value_heads"], a["head_dim"], \
        a["rms_norm_eps"]
    S, scale = h.shape[1], d ** -0.5
    q, k, v, probe = (jax.random.normal(key, (1, n, S, d)) for key, n in
                      zip(jax.random.split(jax.random.key(7), 4), (H, Hkv, Hkv, H)))
    w = jax.random.normal(jax.random.key(8), h.shape)

    forms = {"jnp": lambda q, k, v, scale: L.blockwise_gq_attention(q, k, v, scale, block, select)}
    forms.update({f"fused{tq}x{tk}": partial(PA.fused_selected_attention, select=select,
                                             block=block, block_q=tq, block_k=tk,
                                             interpret=interpret) for tq, tk in tiles})

    def alone(attend, grad):
        def loss(q, k, v):
            return jnp.sum(attend(q, k, v, scale) * probe)
        return jax.jit(jax.grad(loss, argnums=(0, 1, 2)) if grad else loss)

    def in_block(attend, grad):
        ones = jnp.ones((d,))

        @jax.checkpoint
        def layer(lp, h):
            return gq_attention(
                lp, h, heads=H, kv_heads=Hkv, head_dim=d, theta=a["rope_theta"], scale=scale,
                sc=lambda x: x, attend=attend,
                head_norm=lambda x, g: L.masked_rms_norm(x, g, ones, ones.size, eps))

        def loss(lp, h):
            return jnp.sum(layer(lp, h) * w)
        return jax.jit(jax.grad(loss, argnums=(0, 1)) if grad else loss)

    attn = {n: x for n, x in lp.items() if n.startswith("attn.")}
    out, first = {}, None
    for name, attend in forms.items():
        fb, blk = alone(attend, True), in_block(attend, True)
        out[name] = {"fwd": timed(alone(attend, False), q, k, v), "fwd_bwd": timed(fb, q, k, v),
                     "block_fwd": timed(in_block(attend, False), attn, h),
                     "block_fwd_bwd": timed(blk, attn, h)}
        got = jax.tree_util.tree_leaves((fb(q, k, v), blk(attn, h)))
        first = first or got
        out[name]["gap"] = max(float(jnp.abs(x - y).max() / jnp.abs(y).max())
                               for x, y in zip(got, first))
        print(f"selected attention {name}: " + json.dumps(out[name]), flush=True)
    return out


def keep_times(cfg, seed, tokens):
    """Milliseconds of loss and gradients of the model cut to one layer, and
    the bytes of that program (the compiler's own count: temporaries,
    arguments, output), under each of three policies of the layer's
    checkpoint; every leaf's gradient against the first policy's."""
    from heterofl_tpu.ops.pallas_attention import SEL_LSE, SEL_OUT

    cfg = dict(cfg, keye=dict(cfg["keye"], num_hidden_layers=1))
    save, model_policy = jax.checkpoint_policies.save_only_these_names, keye.kept
    policies = {"input": lambda: None, "choice": lambda: save(keye.CHOICE),
                "choice+kernel": lambda: save(keye.CHOICE, SEL_OUT, SEL_LSE)}
    out, first = {}, None
    try:
        for name, policy in policies.items():
            keye.kept = policy
            model = make_model(cfg)
            shapes = {k: tuple(v.shape)
                      for k, v in jax.eval_shape(model.init, jax.random.key(0)).items()}
            params = weights.make_params(shapes, seed)
            step = jax.jit(jax.value_and_grad(
                lambda p, t: model.apply(p, {"label": t}, train=True)[0]["loss"]))
            mem = step.lower(params, tokens).compile().memory_analysis()
            got = jax.tree_util.tree_leaves(step(params, tokens))
            first = first or got
            out[name] = {
                "fwd_bwd": timed(step, params, tokens),
                "temp_bytes": mem.temp_size_in_bytes, "argument_bytes": mem.argument_size_in_bytes,
                "output_bytes": mem.output_size_in_bytes,
                "differs": sum(bool(jnp.any(x != y)) for x, y in zip(got, first))}
            print(f"one layer, the checkpoint keeps {name}: " + json.dumps(out[name]), flush=True)
    finally:
        keye.kept = model_policy
    return out


def dump(name, out):
    os.makedirs("chiprun_out", exist_ok=True)
    with open(os.path.join("chiprun_out", name), "w", encoding="utf-8") as f:
        json.dump(out, f, indent=1)


def main(argv):
    tiny = "tiny" in argv
    tiles = [tuple(int(t) for t in pair.split("x"))
             for a_ in argv if "x" in a_ for pair in a_.split(",")]
    seed = int(next((a for a in argv if a.isdigit()), 3500000101))
    if tiny:
        from benchmark.tests import tiny_keye

        cfg = tiny_keye.program_cfg()
        model_cfg = tiny_keye.reference_model(cfg)
    else:
        cell, config = harness.load_cell(NAME)
        cfg = C.process_control(cfg_from_args(build_cli("sparse_ab").parse_args(
            harness.experiment_argv(cell, config, seed, "/tmp/x", "/tmp/y"))))
        cfg["num_tokens"] = cfg["classes_size"] = config["model"]["num_tokens"]
        model_cfg = config["model"]
    a, S, V = cfg["keye"], cfg["bptt"], cfg["num_tokens"]
    if "keep" in argv:
        tokens = jax.random.randint(jax.random.key(seed % (2 ** 31)), (1, S), 0, V)
        return dump("sparse_keep.json", {
            "seed": seed, "device": jax.devices()[0].device_kind, "positions": S,
            "keep": keep_times(cfg, seed, tokens)})
    block = min(keye.QUERY_BLOCK, a["index_topk"])  # the model's
    model = make_model(cfg)
    shapes = {k: tuple(v.shape) for k, v in jax.eval_shape(model.init, jax.random.key(0)).items()}
    params = weights.make_params(shapes, seed)
    tokens = jax.random.randint(jax.random.key(seed % (2 ** 31)), (1, S), 0, V)
    arch = ref.arch_of(model_cfg)
    eps = a["rms_norm_eps"]
    h = ref._rms(params["embedding.tok.w"][tokens], params["l0.norm1.g"], eps)
    causal = np.tril(np.ones((S, S), bool))

    @jax.jit
    def program(lp, h):
        ones = jnp.ones((a["index_head_dim"],))
        select, pairs = keye.index_keys(
            lp, h, heads=a["index_n_heads"], head_dim=a["index_head_dim"], theta=a["rope_theta"],
            topk=a["index_topk"], block=block,
            key_norm=lambda x, g, b: L.masked_layer_norm(x, g, b, ones, ones.size, eps))
        return [m for m in select if m is not None], pairs

    @jax.jit
    @common.highest
    def reference(lp, h):
        q_i, k_i, w_i = ref.indexer(lp, h, dict(arch))
        return jax.lax.map(lambda s: ref.selected(ref.index_scores(
            jax.lax.dynamic_slice_in_dim(q_i, s, block, 1), k_i,
            jax.lax.dynamic_slice_in_dim(w_i, s, block, 1), s + jnp.arange(block)),
            a["index_topk"]), jnp.arange(0, S, block))

    def compare(out):
        for i in range(a["num_hidden_layers"]):
            lp = layer_leaves(params, i, model.meta["held_experts"])
            masks, pairs = program(lp, h)
            mine = causal.copy()
            first = S - sum(m.shape[1] for m in masks)  # blocks before it keep every causal key
            for m in masks:
                rows = slice(first, first + m.shape[1])
                mine[rows, :m.shape[2]] &= np.asarray(m[0])
                first += m.shape[1]
            theirs = np.asarray(reference(lp, h))[:, 0].reshape(S, S) & causal
            differ = int(np.count_nonzero(mine != theirs))
            out["layers"].append({"layer": i, "selected": int(mine.sum()), "reference": int(theirs.sum()),
                                  "pairs_that_differ": differ,
                                  "queries_that_differ": int(np.count_nonzero((mine != theirs).any(1))),
                                  "program_counts": [float(p) for p in pairs]})
            print(f"layer {i}: program selects {mine.sum()} of {causal.sum()} causal pairs "
                  f"({mine.sum() / S:.1f} a query), reference {theirs.sum()}; they differ on {differ} "
                  f"pairs in {out['layers'][-1]['queries_that_differ']} queries", flush=True)

        # what one forward pass of the model counts (the obs_ counters' source)
        res, _ = jax.jit(lambda p, t: model.apply(p, {"label": t}, train=False))(params, tokens)
        c = {k: np.asarray(v, np.float64) for k, v in res["counters"].items()}
        for name in ("sparse_selected", "sparse_kept_share", "sparse_fused"):
            out[name] = float(c[name][0] / c[name][1])
        print(f"counters of a forward pass: sparse_selected {out['sparse_selected']:.4f} keys a "
              f"query, sparse_kept_share {out['sparse_kept_share']:.6f}, sparse_fused "
              f"{out['sparse_fused']:.1f} of the query tiles through the kernels", flush=True)

        # the selection against a sort, on the last (widest) block's scores
        lp = layer_leaves(params, 0, model.meta["held_experts"])
        q_i, k_i, w_i = jax.jit(common.highest(lambda lp, h: ref.indexer(lp, h, dict(arch))))(lp, h)
        scores = ref.index_scores(q_i[:, S - block:], k_i, w_i[:, S - block:], jnp.arange(S - block, S))
        k = a["index_topk"]
        out["ms"] = {
            "top_k_mask": timed(jax.jit(lambda x: L.top_k_mask(x, k)), scores),
            "lax.top_k": timed(jax.jit(lambda x: jax.lax.top_k(x, k)), scores),
            "reference.selected": timed(jax.jit(lambda x: ref.selected(x, k)), scores),
            "program.index_keys": timed(program, lp, h),
            "reference.indexer+selected": timed(reference, lp, h)}
        print("ms:", json.dumps(out["ms"]), flush=True)

    out = {"seed": seed, "device": jax.devices()[0].device_kind, "positions": S,
           "topk": a["index_topk"], "layers": []}
    if "attn" not in argv:
        compare(out)
    # one layer's selected attention under layer 0's selection
    lp = layer_leaves(params, 0, model.meta["held_experts"])
    select = jax.jit(lambda lp, h: keye.index_keys(
        lp, h, heads=a["index_n_heads"], head_dim=a["index_head_dim"], theta=a["rope_theta"],
        topk=a["index_topk"], block=block,
        key_norm=lambda x, g, b: L.masked_layer_norm(x, g, b, jnp.ones(x.shape[-1:]),
                                                     x.shape[-1], eps))[0])(lp, h)
    rule = PA.sel_tile_for(S, a["head_dim"], a["num_attention_heads"] // a["num_key_value_heads"])
    print(f"the rule's tile at {S} positions: {rule}", flush=True)
    tiles = ([] if rule is None else [rule]) + [t for t in tiles if t != rule]
    out["tile"], out["attention_ms"] = rule, attention_times(
        a, lp, h, select, block, tiles, interpret=jax.default_backend() != "tpu")
    dump("sparse_ab.json", out)


if __name__ == "__main__":
    main(sys.argv[1:])
