"""The Nemotron-H cell's chunked scan on the chip, alone, at the cell's shapes
(one row of 8,192 positions, 64 heads of 64 in 8 groups, a state of 128,
chunks of 128): the `jnp` form of `ops.layers.ssm_chunked_scan` against the
kernel pair it takes on a TPU (`ops/pallas_ssm.py` `ssm_scan_fwd` /
`ssm_scan_bwd`), each against the plain reference's position-by-position
`recurrence` at "highest" precision on the output and the five gradients, and
the time of each: forward, forward + backward, and as a layer under its bare
`jax.checkpoint` runs it (forward, forward again, backward).

    chiprun -- python scripts/ssm_ab.py [seed]        # on the chip
    JAX_PLATFORMS=cpu python scripts/ssm_ab.py tiny   # the same code at the tests' size (both forms are the jnp form there)

`x` is handed over lane-dense (`[N, S, H P]`, split into heads inside the
program), as the mixer's convolution hands it over: with a `[N, S, 64, 64]`
ARGUMENT the compiler pads the 64 to the 128 lanes and copies 134 MB in and out,
which is the argument's cost and not the scan's.  Milliseconds are host-clock
means over 20 calls.
"""
import os
import sys
import time

os.environ.setdefault("TPU_LOG_DIR", "disabled")
sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import jax
import jax.numpy as jnp

from benchmark.reference import nemotron_h as ref
from heterofl_tpu.ops import layers as L

argv = sys.argv[1:]
tiny = "tiny" in argv
N, S, H, P, G, Ns, Q = (1, 256, 4, 64, 2, 128, 128) if tiny else (1, 8192, 64, 64, 8, 128, 128)
seed = next((int(a) for a in argv if a.isdigit()), 7)
ks = jax.random.split(jax.random.key(seed), 6)
x = jax.random.normal(ks[0], (N, S, H * P))
dt = jax.nn.softplus(jax.random.normal(ks[1], (N, S, H)) - 4.6)   # about 0.01, the published middle
a = -jnp.exp(jax.random.uniform(ks[2], (H,), minval=0.4, maxval=2.4))
b, c = (jax.random.normal(k, (N, S, G, Ns)) for k in ks[3:5])
probe = jax.random.normal(ks[5], x.shape)
args = (x, dt, a, b, c)
NAMES = ("x", "dt", "a", "b", "c")


def planned(*o):
    return L.ssm_chunked_scan(*o, Q)[0]


def jnp_form(*o):
    plan, L.ssm_scan_plan = L.ssm_scan_plan, lambda *a: None
    try:
        return planned(*o)
    finally:
        L.ssm_scan_plan = plan


def dense(f):
    return lambda x, *o: f(x.reshape(N, S, H, P), *o).reshape(N, S, H * P)


def grads(f, wrap=lambda g: g):
    return jax.jit(jax.value_and_grad(lambda *o: jnp.sum(wrap(f)(*o) * probe),
                                      argnums=(0, 1, 2, 3, 4)))


def bench(name, fn, n=20):
    out = jax.block_until_ready(fn(*args))
    t = time.perf_counter()
    for _ in range(n):
        out = fn(*args)
    jax.block_until_ready(out)
    print(f"{name}: {(time.perf_counter() - t) / n * 1e3:.3f} ms", flush=True)
    return out


def rel(u, v):
    return float(jnp.abs(u - v).max() / jnp.abs(v).max())


def against(name, y, g, y_ref, g_ref):
    print(f"  {name}: y {rel(y, y_ref):.3e} " + " ".join(
        f"d{n} {rel(u, v):.3e}" for n, u, v in zip(NAMES, g[1], g_ref[1])), flush=True)


print(jax.devices()[0].device_kind, flush=True)
with jax.default_matmul_precision("highest"):
    y_ref = jax.jit(dense(ref.recurrence))(*args)
    g_ref = grads(dense(ref.recurrence))(*args)
outs = {}
for name, f in (("jnp form", dense(jnp_form)), ("kernels", dense(planned))):
    y = bench(f"{name} forward", jax.jit(f))
    g = bench(f"{name} forward + backward", grads(f))
    bench(f"{name} forward, forward again and backward (checkpoint)", grads(f, jax.checkpoint))
    against(f"{name} against the recurrence", y, g, y_ref, g_ref)
    outs[name] = (y, g)
against("kernels against the jnp form", *outs["kernels"], *outs["jnp form"])
with jax.default_matmul_precision("highest"):
    for name, f in (("jnp form", dense(jnp_form)), ("kernels", dense(planned))):
        y = bench(f"{name} forward, highest", jax.jit(f))
        against(f"{name} at highest against the recurrence", y, grads(f)(*args), y_ref, g_ref)
