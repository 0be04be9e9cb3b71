"""Attention alone on the chip: the score / softmax / value part of the
Kanana-2 cell (`[2, 2048, 32, 128 | 64 | 128]`, float32 in), forward and
forward + backward, the blockwise `jnp` form against the fused kernels at
several tiles and against the library's splash attention.  One minute:

    chiprun -- python scripts/attn_ab.py [form,form,...]   # times, on the chip
    JAX_PLATFORMS=cpu python scripts/attn_ab.py aot        # compiles for a described v5e

Milliseconds are host-clock over 5 calls, best of 3, layout work included;
`gap` is the largest difference from the `jnp` form over the largest element,
for the output's probe sum and the five gradients.
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

from heterofl_tpu.ops import layers as L  # noqa: E402
from heterofl_tpu.ops import pallas_attention as PA  # noqa: E402

N, S, H, DN, DR, DV = 2, 2048, 32, 128, 64, 128
SHAPES = [(N, S, H, DN), (N, S, H, DR), (N, S, H, DN), (N, S, DR), (N, S, H, DV), (N, S, H, DV)]


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
        def heads_first(x):
            return jnp.swapaxes(x, 1, 2).astype(jnp.bfloat16)

        q = jnp.concatenate([qn, qr], -1) * scale
        k = jnp.concatenate([kn, jnp.broadcast_to(kr[:, :, None, :], qr.shape)], -1)
        o = jax.vmap(kernel)(heads_first(q), heads_first(k), heads_first(v))
        return jnp.swapaxes(o, 1, 2).astype(jnp.float32)
    return f


FORMS = {"jnp": L.blockwise_latent_attention, "fused256": fused(256), "fused512": fused(512),
         "fused1024": fused(1024), "splash512": splash(512, False),
         "splash1024f": splash(1024, True)}


def fwd(f):
    return lambda qn, qr, kn, kr, v, w, scale: f(qn, qr, kn, kr, v, scale)


def fwd_bwd(f):
    def g(qn, qr, kn, kr, v, w, scale):
        loss, grads = jax.value_and_grad(lambda *a: jnp.sum(f(*a, scale) * w),
                                         argnums=(0, 1, 2, 3, 4))(qn, qr, kn, kr, v)
        return (loss,) + grads
    return g


def main(argv):
    aot = argv[:1] == ["aot"]
    names = argv[-1].split(",") if argv and argv[-1] != "aot" else list(FORMS)
    if aot:
        from jax.experimental import topologies
        from jax.sharding import SingleDeviceSharding

        one = SingleDeviceSharding(topologies.get_topology_desc(
            platform="tpu", topology_name="v5e:2x2").devices[0])
        avals = [jax.ShapeDtypeStruct(s, jnp.float32, sharding=one) for s in SHAPES + [()]]
        for name in names:
            t = time.time()
            c = jax.jit(fwd_bwd(FORMS[name])).lower(*avals).compile()
            print(f"{name}: compiled in {time.time() - t:.1f}s, temporaries "
                  f"{c.memory_analysis().temp_size_in_bytes >> 20} MB, "
                  f"{c.as_text().count('tpu_custom_call')} custom calls", flush=True)
        return
    print(jax.devices(), flush=True)
    if jax.default_backend() != "tpu":
        raise SystemExit("attn_ab times the chip; without one, `aot` compiles for it")
    args = [jax.random.normal(k, s, jnp.float32)
            for k, s in zip(jax.random.split(jax.random.key(29), 6), SHAPES)]
    args.append(jnp.float32(1.0 / np.sqrt(DN + DR)))
    out, ref = {}, None
    for name in names:
        rec = {}
        for kind, wrap in (("fwd", fwd), ("fwdbwd", fwd_bwd)):
            fn = jax.jit(wrap(FORMS[name]))
            r = jax.block_until_ready(fn(*args))
            best = []
            for _ in range(3):
                t = time.time()
                for _ in range(5):
                    r = fn(*args)
                jax.block_until_ready(r)
                best.append((time.time() - t) / 5 * 1e3)
            rec[kind + "_ms"] = round(min(best), 3)
        r = [np.asarray(x) for x in r]
        if name == "jnp":
            ref = r
        elif ref is not None:
            rec["gap"] = [float(np.max(np.abs(a - b)) / np.max(np.abs(b))) for a, b in zip(r, ref)]
        out[name] = rec
        print(name, json.dumps(rec), flush=True)
    os.makedirs("chiprun_out", exist_ok=True)
    with open("chiprun_out/attn_ab.json", "w") as f:
        json.dump(out, f, indent=1)


if __name__ == "__main__":
    main(sys.argv[1:])
