"""The Laguna cell's two kinds of attention layer on the chip, alone, at the
cell's shapes (one row of 8,192 positions on 8 key/value heads of 128): a
SLIDING layer (64 query heads, a window of 512) and a FULL layer (48 query
heads, every causal pair).  For each: the `jnp` block loop
(`ops.layers.blockwise_gq_attention`) against the band kernels
(`ops/pallas_attention.py` `band_attn_fwd` / `band_attn_bwd`) at the tiles
`gq_plan` gives and at any others named, each against the plain reference's
explicit boolean masks (`benchmark/reference/laguna.py` `_attention` at
"highest" precision) on the output and the three gradients, and the time of
each, forward and forward + backward, alone and inside the layer's attention
BLOCK (`models.laguna.gated_gq_attention`: projections, gate, turn, attention,
output projection, under `jax.checkpoint` as a layer runs it), which is where
a kernel's operand layout shows in its neighbours.

    chiprun -- python scripts/swa_ab.py [seed] [256x256,512x128]     # on the chip
    chiprun -- python scripts/swa_ab.py sliding [seed] [tiles]       # one kind of layer alone (or: full)
    JAX_PLATFORMS=cpu python scripts/swa_ab.py tiny   # the same code at the tests' size

Milliseconds are host-clock over 5 calls, best of 3.  The numbers land in
`chiprun_out/swa_ab.json`.
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

from benchmark.reference import common, laguna as ref  # noqa: E402
from heterofl_tpu.models.laguna import gated_gq_attention, rope_frequencies  # noqa: E402
from heterofl_tpu.obs.trace import scope  # noqa: E402
from heterofl_tpu.ops import layers as L  # noqa: E402
from heterofl_tpu.ops import pallas_attention as PA  # noqa: E402


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


def layer_kind(name, H, Hkv, S, d, D, window, tiles, seed, interpret):
    """One kind of layer: gaps from the reference's masks and milliseconds."""
    scale = d ** -0.5
    keys = jax.random.split(jax.random.key(seed), 12)
    q, k, v, probe = (jax.random.normal(kk, (1, n, S, d)) for kk, n in
                      zip(keys[:4], (H, Hkv, Hkv, H)))
    plan = PA.gq_plan(S, d, H // Hkv, window) if not interpret else None
    tiles = ([plan[1:]] if plan else []) + [t for t in tiles if plan is None or t != plan[1:]]
    forms = {"jnp": partial(L.blockwise_gq_attention, block=L.ATTN_BLOCK, window=window)}
    forms.update({f"band{tq}x{tk}": partial(PA.fused_band_attention, window=window, block_q=tq,
                                            block_k=tk, interpret=interpret)
                  for tq, tk in tiles})

    def ref_loss(q, k, v):  # the reference's layout [N, S, H, d], key/value heads repeated
        qt, kt, vt = (jnp.swapaxes(t, 1, 2) for t in (q, k, v))
        kt, vt = (jnp.repeat(t, H // Hkv, axis=2) for t in (kt, vt))
        o = jnp.swapaxes(ref._attention(qt, kt, vt, window), 1, 2)
        return jnp.sum(o * probe), o

    (_, ref_o), ref_g = jax.jit(jax.value_and_grad(common.highest(ref_loss), argnums=(0, 1, 2),
                                                   has_aux=True))(q, k, v)
    want = (ref_o,) + tuple(ref_g)

    # the layer's block: seeded leaves of the cell's widths, the kind's own turn
    rot = d if window is not None else d // 2
    rope = {"rope_theta": 1e4} if window is not None else {
        "rope_theta": 5e5, "rope_type": "yarn", "factor": 64.0, "beta_fast": 64.0,
        "beta_slow": 1.0, "original_max_position_embeddings": 4096,
        "attention_factor": 1.4158883083359672}
    freqs, factor = rope_frequencies(rope, rot)
    shapes = {"attn.q.r.w": (D, H * rot), "attn.k.r.w": (D, Hkv * rot), "attn.v.w": (D, Hkv * d),
              "attn.gate.w": (D, H), "attn.o.w": (H * d, D)}
    if rot < d:
        shapes.update({"attn.q.n.w": (D, H * (d - rot)), "attn.k.n.w": (D, Hkv * (d - rot))})
    lp = {n: jax.random.uniform(kk, s, jnp.float32, -s[0] ** -0.5, s[0] ** -0.5)
          for (n, s), kk in zip(sorted(shapes.items()), keys[4:11])}
    h = jax.random.normal(keys[11], (1, S, D))
    w = jax.random.normal(keys[3], (1, S, D))

    def alone(attend, grad):
        def loss(q, k, v):
            return jnp.sum(attend(q, k, v, scale) * probe)
        return jax.jit(jax.grad(loss, argnums=(0, 1, 2)) if grad else loss)

    def in_block(attend, grad):
        def scoped(*a):
            with scope("swa" if window is not None else "attn"):
                return attend(*a)

        @jax.checkpoint
        def layer(lp, h):
            return gated_gq_attention(lp, h, heads=H, kv_heads=Hkv, freqs=freqs, factor=factor,
                                      scale=scale, sc=lambda x: x, attend=scoped)

        def loss(lp, h):
            return jnp.sum(layer(lp, h) * w)
        return jax.jit(jax.grad(loss, argnums=(0, 1)) if grad else loss)

    out = {"plan": plan, "extent": {f"{tq}x{tk}": PA.band_extent(S, tq, tk, window)
                                    for tq, tk in tiles}}
    for form, attend in forms.items():
        o = jax.jit(lambda q, k, v: attend(q, k, v, scale))(q, k, v)
        fb = alone(attend, True)
        got = (o,) + tuple(fb(q, k, v))
        out[form] = {
            "gap_o_dq_dk_dv": [float(jnp.abs(x - y).max() / jnp.abs(y).max())
                               for x, y in zip(got, want)],
            "fwd": timed(alone(attend, False), q, k, v), "fwd_bwd": timed(fb, q, k, v),
            "block_fwd": timed(in_block(attend, False), lp, h),
            "block_fwd_bwd": timed(in_block(attend, True), lp, h)}
        print(f"{name} {form}: " + json.dumps(out[form]), flush=True)
    return out


def main(argv):
    tiny = argv[:1] == ["tiny"]
    args = argv[1:] if tiny else argv
    only = [a for a in args if a in ("sliding", "full")]
    seed = int(args[0]) if args and args[0].isdigit() else 0
    named = [tuple(int(x) for x in t.split("x")) for a in args if "x" in a for t in a.split(",")]
    if tiny:
        S, d, D, Hkv, window, heads = 256, 128, 64, 1, 128, {"sliding": 4, "full": 3}
        tiles = {"sliding": named or [(128, 128)], "full": named or [(128, 128)]}
    else:
        if jax.devices()[0].platform != "tpu":
            print("swa_ab needs a TPU (or the argument `tiny`)", file=sys.stderr)
            return 4
        S, d, D, Hkv, window, heads = 8192, 128, 2048, 8, 512, {"sliding": 64, "full": 48}
        tiles = {"sliding": named or [(512, 256), (256, 256), (512, 128), (256, 128)],
                 "full": named or [(256, 512), (512, 256)]}
    out = {"device": str(jax.devices()[0]), "seed": seed}
    for name, win in (("sliding", window), ("full", None)):
        if only and name not in only:
            continue
        out[name] = layer_kind(name, heads[name], Hkv, S, d, D, win, tiles[name], seed,
                               interpret=tiny)
    os.makedirs("chiprun_out", exist_ok=True)
    with open("chiprun_out/swa_ab.json", "w") as f:
        json.dump(out, f, indent=1)
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:]))
