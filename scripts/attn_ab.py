"""Attention alone on the chip: the score / softmax / value part of the
Kanana-2 cell (heads first, `[2, 32, 2048, 128 | 64 | 128]`, float32 in),
forward and forward + backward, the blockwise `jnp` form against the fused
kernels at several tiles and against the library's splash attention; and the
layer's whole latent-attention BLOCK (projections, rotary turn, attention,
output projection; `[2, 2048, 2048]` in and out), as the model writes it
(`block_heads_first`: `models.kanana2.latent_attention`) against the
formulation before PR 31 (`block_swapaxes`: `linear` to `[N, S, H * d]`,
`reshape`, the rotary pair swap on the activation, `swapaxes`), which is
where the layout work round the kernels shows: the attention-alone rows start
at the kernels' operands.  Two minutes:

    chiprun -- python scripts/attn_ab.py [form,form,...]   # times, on the chip
    chiprun -- python scripts/attn_ab.py ops form,form     # and each form's device operations
    JAX_PLATFORMS=cpu python scripts/attn_ab.py aot        # compiles for a described v5e

Milliseconds are host-clock over 5 calls, best of 3, layout work included;
a block's forward + backward runs under `jax.checkpoint` as a layer of the
model does (forward, rematerialised forward, backward; gradients of the
eight leaves and the input).  `gap` is the largest difference from the first
form of its kind (`jnp`, `block_swapaxes`) over the largest element, for the
output's probe sum and every gradient.
"""
import json
import os
import sys
import time

os.environ.setdefault("TPU_LOG_DIR", "disabled")
sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402

from heterofl_tpu.models.decoder import gq_attention  # noqa: E402
from heterofl_tpu.models.kanana2 import latent_attention, latent_attention_shapes  # noqa: E402
from heterofl_tpu.ops import layers as L  # noqa: E402
from heterofl_tpu.ops import pallas_attention as PA  # noqa: E402

N, S, H, DN, DR, DV = 2, 2048, 32, 128, 64, 128
SHAPES = [(N, H, S, DN), (N, H, S, DR), (N, H, S, DN), (N, S, DR), (N, H, S, DV), (N, H, S, DV)]
D, R, THETA = 2048, 512, 1e6  # the block: hidden size, latent width, rope_theta
LEAVES = latent_attention_shapes(D, H, DN, DR, DV, R)
HKV, HD = 8, 64  # the LFM2 cell's grouped-query heads
GQ_SHAPES = [(N, H, S, HD), (N, HKV, S, HD), (N, HKV, S, HD), (N, H, S, HD)]
GQ_LEAVES = {"attn.q.w": (D, H * HD), "attn.k.w": (D, HKV * HD), "attn.v.w": (D, HKV * HD),
             "attn.q_norm.g": (HD,), "attn.k_norm.g": (HD,), "attn.o.w": (H * HD, D)}


def fused(tile):
    return lambda *a: PA.fused_latent_attention(*a, block_q=tile, block_k=tile)


def splash(block, fused_bwd):
    from jax.experimental.pallas.ops.tpu.splash_attention import splash_attention_kernel as sk
    from jax.experimental.pallas.ops.tpu.splash_attention import splash_attention_mask as sm

    dq = {} if fused_bwd else dict(block_q_dq=block, block_kv_dq=block)
    sizes = sk.BlockSizes(block_q=block, block_kv=block, block_kv_compute=block,
                          block_q_dkv=block, block_kv_dkv=block, block_kv_dkv_compute=block,
                          use_fused_bwd_kernel=fused_bwd, **dq)
    kernel = sk.make_splash_mha(sm.MultiHeadMask([sm.CausalMask((S, S))] * H),
                                block_sizes=sizes, head_shards=1, q_seq_shards=1)

    def f(qn, qr, kn, kr, v, scale):
        q = jnp.concatenate([qn, qr], -1) * scale
        k = jnp.concatenate([kn, jnp.broadcast_to(kr[:, None], qr.shape)], -1)
        o = jax.vmap(kernel)(*(x.astype(jnp.bfloat16) for x in (q, k, v)))
        return o.astype(jnp.float32)
    return f


def _kv_norm(c, g):
    return L.masked_rms_norm(c, g, jnp.ones((R,)), jnp.float32(R))


def block_heads_first(lp, h, scale):
    return latent_attention(lp, h, heads=H, theta=THETA, scale=scale, sc=lambda x: x,
                            kv_norm=_kv_norm)


def block_swapaxes(lp, h, scale):
    """The block as `models/kanana2.py` wrote it before PR 31."""
    def heads_first(x, d):
        return jnp.swapaxes(x.reshape(N, S, H, d), 1, 2)

    qn, qr = L.linear(h, lp["attn.q.n.w"]), L.linear(h, lp["attn.q.r.w"])
    c, kr = L.linear(h, lp["attn.kv_a.c.w"]), L.linear(h, lp["attn.kv_a.r.w"])
    c = _kv_norm(c, lp["attn.kv_norm.g"])
    kn, v = L.linear(c, lp["attn.kv_b.k.w"]), L.linear(c, lp["attn.kv_b.v.w"])
    pos = jnp.arange(S)
    qr = qr.reshape(N, S, H, DR)  # the rotary turn's pair swap on the activation
    qr = L.rope_interleaved(qr, L.rope_swap(qr), pos, THETA).reshape(N, S, H * DR)
    kr = L.rope_interleaved(kr, L.rope_swap(kr), pos, THETA)
    o = L.causal_latent_attention(heads_first(qn, DN), heads_first(qr, DR), heads_first(kn, DN),
                                  kr, heads_first(v, DV), scale)
    return L.linear(jnp.swapaxes(o, 1, 2).reshape(N, S, H * DV), lp["attn.o.w"])


def gq_fused(tq, tk=None):
    return lambda *a: PA.fused_gq_attention(*a, block_q=tq, block_k=tk or tq)


def gq_block(tile):
    """`models.decoder.gq_attention` with the kernels at ``tile``, or with the
    block loop (None): the script answers the one question the layer asks."""
    def f(lp, h, scale):
        asked, PA.gq_tile_for = PA.gq_tile_for, lambda *a: tile
        try:
            return gq_attention(
                lp, h, heads=H, kv_heads=HKV, head_dim=HD, theta=THETA, scale=scale,
                sc=lambda x: x,
                head_norm=lambda x, g: L.masked_rms_norm(x, g, jnp.ones((HD,)), jnp.float32(HD)))
        finally:
            PA.gq_tile_for = asked
    return f


FORMS = {"jnp": L.blockwise_latent_attention, "fused256": fused(256), "fused512": fused(512),
         "fused1024": fused(1024), "splash512": splash(512, False),
         "splash1024f": splash(1024, True)}
BLOCKS = {"block_swapaxes": block_swapaxes, "block_heads_first": block_heads_first}
GQ_FORMS = {"gq_jnp": L.blockwise_gq_attention, "gq_fused512": gq_fused(512),
            "gq_fused256": gq_fused(256), "gq_fused128": gq_fused(128),
            "gq_fused256x512": gq_fused(256, 512), "gq_fused512x256": gq_fused(512, 256)}
GQ_BLOCKS = {"gq_block_jnp": gq_block(None), "gq_block512": gq_block(512),
             "gq_block256": gq_block(256), "gq_block128": gq_block(128)}
def fwd(f):
    return lambda *a: f(*a[:-2], a[-1])  # operands, probe, scale


def fwd_bwd(f):
    def g(*a):
        *ops, w, scale = a
        loss, grads = jax.value_and_grad(lambda *o: jnp.sum(f(*o, scale) * w),
                                         argnums=tuple(range(len(ops))))(*ops)
        return (loss,) + grads
    return g


def block_fwd(f):
    return lambda lp, h, w, scale: f(lp, h, scale)


def block_fwd_bwd(f):
    def g(lp, h, w, scale):
        loss, grads = jax.value_and_grad(
            lambda lp_, h_: jnp.sum(jax.checkpoint(f)(lp_, h_, scale) * w), argnums=(0, 1))(lp, h)
        return (loss, grads[1]) + tuple(grads[0][k] for k in sorted(grads[0]))
    return g


def device_ops(fn, args, calls=3):
    """(device ms a call, [(ms a call, operation)]) of ``fn`` by the device's
    own clock: the self times of a traced run's operations, largest first."""
    import glob
    import tempfile

    from benchmark.trace_reduce import load_xplane, self_times

    with tempfile.TemporaryDirectory() as d:
        with jax.profiler.trace(d):
            for _ in range(calls):
                r = fn(*args)
            jax.block_until_ready(r)
        trace = load_xplane(glob.glob(os.path.join(d, "plugins/profile/*/*.xplane.pb"))[0])
    events = [e for plane in trace["planes"] if plane["name"].startswith("/device:")
              for line in plane["lines"] for e in line["events"]]
    times = self_times(events, 0.0, float("inf"))
    rows = sorted(((ns / calls / 1e6, name) for name, ns in times.items()), reverse=True)
    return sum(ms for ms, _ in rows), rows


#: a kind of row: its forms (the first is the one the others' `gap` is taken from), its
#: (forward, forward + backward) wrappers, its operands' shapes or its block's leaves, and
#: the head dims of its softmax scale
KINDS = [(FORMS, (fwd, fwd_bwd), SHAPES, DN + DR),
         (BLOCKS, (block_fwd, block_fwd_bwd), LEAVES, DN + DR),
         (GQ_FORMS, (fwd, fwd_bwd), GQ_SHAPES, HD),
         (GQ_BLOCKS, (block_fwd, block_fwd_bwd), GQ_LEAVES, HD)]


def _asked(names, make, leaf, gain, scale):
    """(the names asked of a kind, its forms, its wrappers, its arguments) for
    every kind with a name asked: ``make(shape)`` makes an operand,
    ``leaf(shape)`` a weight, ``gain(shape)`` a norm's gain and
    ``scale(head_dims)`` the softmax scale (shapes alone under `aot`)."""
    for forms, wraps, shapes, width in KINDS:
        asked = [n for n in names if n in forms]
        if not asked:
            continue
        if isinstance(shapes, dict):  # a block: its leaves, the input, the probe
            args = [{n: (gain if n.endswith(".g") else leaf)(s) for n, s in shapes.items()},
                    make((N, S, D)), make((N, S, D))]
        else:
            args = [make(s) for s in shapes]
        yield asked, forms, wraps, args + [scale(width)]


def main(argv):
    aot, ops = argv[:1] == ["aot"], argv[:1] == ["ops"]
    names = argv[-1].split(",") if argv and argv[-1] not in ("aot", "ops") \
        else [name for forms, *_ in KINDS for name in forms]
    if aot:
        from jax.experimental import topologies
        from jax.sharding import SingleDeviceSharding

        one = SingleDeviceSharding(topologies.get_topology_desc(
            platform="tpu", topology_name="v5e:2x2").devices[0])

        def sds(shape):
            return jax.ShapeDtypeStruct(shape, jnp.float32, sharding=one)

        jax.default_backend = lambda: "tpu"  # the one question the attention functions ask
        for asked, forms, wraps, avals in _asked(names, sds, sds, sds, lambda width: sds(())):
            for name in asked:
                t = time.time()
                c = jax.jit(wraps[1](forms[name])).lower(*avals).compile()
                print(f"{name}: compiled in {time.time() - t:.1f}s, temporaries "
                      f"{c.memory_analysis().temp_size_in_bytes >> 20} MB, "
                      f"{c.as_text().count('tpu_custom_call')} custom calls", flush=True)
        return
    print(jax.devices(), flush=True)
    if jax.default_backend() != "tpu":
        raise SystemExit("attn_ab times the chip; without one, `aot` compiles for it")
    keys = iter(jax.random.split(jax.random.key(29), 64))

    def normal(shape):
        return jax.random.normal(next(keys), shape, jnp.float32)

    out = {}
    for asked, forms, wraps, args_ in _asked(
            names, normal, lambda s: normal(s) / np.sqrt(s[0]), lambda s: jnp.ones(s, jnp.float32),
            lambda width: jnp.float32(1.0 / np.sqrt(width))):
        ref = None
        for name in asked:
            rec = {}
            for kind, wrap in zip(("fwd", "fwdbwd"), wraps):
                fn = jax.jit(wrap(forms[name]))
                r = jax.block_until_ready(fn(*args_))
                best = []
                for _ in range(3):
                    t = time.time()
                    for _ in range(5):
                        r = fn(*args_)
                    jax.block_until_ready(r)
                    best.append((time.time() - t) / 5 * 1e3)
                rec[kind + "_ms"] = round(min(best), 3)
            if ops:  # of the forward + backward, the loop's last ``fn``
                total, rows = device_ops(fn, args_)
                rec["device_ms"], rec["device_ops"] = round(total, 3), rows
                print(f"{name}: forward + backward {total:.3f} device ms a call; largest operations:")
                for ms_, op in rows[:14]:
                    print(f"  {ms_:8.3f}  {op}")
            r = [np.asarray(x) for x in r]
            if name == next(iter(forms)):
                ref = r
            elif ref is not None:
                rec["gap"] = [float(np.max(np.abs(a - b)) / np.max(np.abs(b)))
                              for a, b in zip(r, ref)]
            out[name] = rec
            print(name, json.dumps(rec), flush=True)
    os.makedirs("chiprun_out", exist_ok=True)
    with open("chiprun_out/attn_ab.json", "w") as f:
        json.dump(out, f, indent=1)


if __name__ == "__main__":
    main(sys.argv[1:])
