"""The fused chunked scan (ops/pallas_ssm.py, ISSUE 47) against its two
oracles -- the ``jnp`` form ``ops.layers.ssm_chunked_scan`` runs off a TPU, and
the benchmark reference's position-by-position ``recurrence`` -- on the CPU in
interpret mode, which says that the grid, the carried state and its
cotangent, the heads' lanes and the hand-written backward are right; what
Mosaic accepts is tests/test_tpu_compile.py's.

At float32 products (``jax.default_matmul_precision("highest")``, which the
kernels obey as the ``jnp`` form does) the three differ by the order of their
float32 sums alone: 1e-5 of the largest entry, as tests/test_nemotron_h.py
holds the ``jnp`` form to the recurrence (a state lost at a chunk's edge is off
by 1e-1).  The carried state's precision has a test of its own: the comparison
that decides ``correct`` on the chip cannot see it (PERF.md section 7)."""

import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from heterofl_tpu.ops import layers as L
from heterofl_tpu.ops import pallas_ssm as PS

CHUNK = 128
NAMES = ("x", "dt", "a", "b", "c")


def _inputs(S, seed=0, N=1, H=4, P=64, G=2, Ns=128, active=None):
    """``x, dt, a, b, c`` as the mixer hands them to the scan; with ``active``
    the ``x`` channels of every head past that count are zero (a narrow
    client's under the masked engine)."""
    ks = jax.random.split(jax.random.key(seed), 5)
    x = jax.random.normal(ks[0], (N, S, H, P))
    if active is not None:
        x = jnp.where(jnp.arange(P) < active, x, 0.0)
    dt = jax.nn.softplus(jax.random.normal(ks[1], (N, S, H)) - 3.0)  # about 0.05
    a = -jnp.exp(jax.random.uniform(ks[2], (H,), minval=0.0, maxval=2.0))
    b, c = (jax.random.normal(k, (N, S, G, Ns)) for k in ks[3:])
    return x, dt, a, b, c


def _kernels(x, dt, a, b, c, chunk=CHUNK, **kw):
    """``ssm_chunked_scan``'s prologue, then the kernel pair in interpret mode."""
    return PS.fused_ssm_scan(x, dt, dt * a, b, c, chunk, interpret=True, **kw)


def _jnp_form(*args):
    return L.ssm_chunked_scan(*args, CHUNK)[0]


def _recurrence(*args):
    from benchmark.reference import nemotron_h as ref

    return ref.recurrence(*args)


def _out_and_grads(scan, args, probe):
    def loss(*a):
        y = scan(*a)
        return jnp.sum(y * probe), y

    grads, y = jax.grad(loss, argnums=tuple(range(5)), has_aux=True)(*args)
    return (y,) + grads


#: case -> (positions, what `_inputs` takes beside them)
CASES = {
    "three-chunks": (384, {}),
    "one-chunk": (128, {}),
    "two-rows": (256, dict(N=2)),
    "one-group": (256, dict(H=2, G=1)),
    "four-heads-a-lane-tile": (256, dict(H=8, P=32)),
    "whole-lane-heads": (256, dict(H=2, P=128)),
    "masked-channels": (256, dict(active=4)),
}


@pytest.mark.parametrize("oracle", [_jnp_form, _recurrence], ids=["jnp-form", "recurrence"])
@pytest.mark.parametrize("case", list(CASES))
def test_the_kernel_pair_is_the_chunked_scan(case, oracle):
    """Output and the gradient of every input (``x``, ``dt``, ``a``, ``B``,
    ``C``: the prologue differentiates by itself, the kernels return the
    cotangents of ``x dt``, of the cumulative log decay, of ``B`` and ``C``)
    against both oracles; on a narrow client's rows the masked channels'
    output and ``dx`` are exactly zero."""
    S, kw = CASES[case]
    args = _inputs(S, **kw)
    probe = jax.random.normal(jax.random.key(9), args[0].shape)
    if "active" in kw:  # what reads y there is masked too (the gated norm)
        probe = jnp.where(jnp.arange(probe.shape[-1]) < kw["active"], probe, 0.0)
    with jax.default_matmul_precision("highest"):
        got = _out_and_grads(_kernels, args, probe)
        want = _out_and_grads(oracle, args, probe)
    for g, w, name in zip(got, want, ("y",) + NAMES):
        assert g.shape == w.shape and g.dtype == w.dtype
        # a gradient of `a` or `dt` sums signed terms over every position of a head
        tol = 1e-5 if name == "y" else 5e-5
        np.testing.assert_allclose(g, w, rtol=0, atol=tol * float(jnp.abs(w).max()), err_msg=name)
    if "active" in kw:
        assert not np.asarray(got[0])[..., kw["active"]:].any()
        assert not np.asarray(got[1])[..., kw["active"]:].any()
        assert np.asarray(got[1])[..., :kw["active"]].all()


def test_at_the_default_precision_the_products_take_bfloat16_operands():
    """Off "highest" the four products and their backward twins round their
    operands to bfloat16 (2^-9 of a term), as the chip's default precision does
    to the ``jnp`` form's: the pair is then 1e-2 of the largest entry from the
    float32 recurrence, not 1e-5, and its program holds the casts."""
    args = _inputs(256)
    probe = jax.random.normal(jax.random.key(9), args[0].shape)
    got = _out_and_grads(_kernels, args, probe)
    with jax.default_matmul_precision("highest"):
        want = _out_and_grads(_recurrence, args, probe)
    for g, w, name in zip(got, want, ("y",) + NAMES):
        err = float(jnp.abs(g - w).max() / jnp.abs(w).max())
        assert 1e-5 < err < 2e-2, (name, err)
    text = str(jax.make_jaxpr(_kernels)(*args))
    assert "bf16" in text
    with jax.default_matmul_precision("highest"):
        assert "bf16" not in str(jax.make_jaxpr(_kernels)(*args))


def test_an_impulse_at_position_0_is_read_at_the_last_position_through_the_kernels():
    """``tests/test_nemotron_h.py``'s impulse through the kernel pair: one write
    at position 0 read by ``C`` four chunks on is ``C . B x dt_0 exp(sum_{t=1..}
    dt_t a)``, so the state in the VMEM scratch crosses every chunk boundary,
    decayed by each chunk's ``exp(l_last)``."""
    N, S, H, P, G, Ns = 1, 512, 2, 64, 1, 128
    x = jnp.zeros((N, S, H, P)).at[0, 0].set(jnp.arange(1.0, 1.0 + H * P).reshape(H, P) / P)
    b = jnp.zeros((N, S, G, Ns)).at[0, 0, 0, :4].set(jnp.array([1.0, -2.0, 0.5, 3.0]))
    c = jnp.ones((N, S, G, Ns))
    dt = jnp.full((N, S, H), 0.0025)
    a = jnp.array([-1.0, -3.0])
    with jax.default_matmul_precision("highest"):
        y = _kernels(x, dt, a, b, c)
    t = jnp.arange(S, dtype=jnp.float32)
    decay = jnp.exp(0.0025 * a[None, :] * t[:, None])                # exp(sum_{1..t} dt a)
    want = decay[None, :, :, None] * (x[:, :1] * 0.0025) * jnp.sum(b[0, 0, 0])
    np.testing.assert_allclose(y, want, rtol=1e-5, atol=1e-9)
    assert float(jnp.abs(y[0, -1]).min()) > 1e-5  # it arrived


def test_a_bfloat16_state_would_fail_where_the_float32_state_passes():
    """THE STATE'S PRECISION.  A row of slow heads (``dt a`` near -1e-3: a
    position keeps 99.9 % of the state) over sixteen chunks: what the state
    holds is the sum of two thousand positions' writes, and rounding it to
    bfloat16 once a chunk loses 2^-9 of that sum sixteen times.  The kernels
    with their float32 scratch are within the 1e-5 class of the recurrence; the
    same kernels with a bfloat16 scratch (``state=``, this test's alone) are a
    hundred times outside it -- which the chip's ``correct`` cannot see, because
    the products' bfloat16 operands hide it (PERF.md section 7 (f))."""
    N, S, H, P, G, Ns = 1, 2048, 2, 64, 1, 128
    ks = jax.random.split(jax.random.key(3), 4)
    x = jax.random.normal(ks[0], (N, S, H, P))
    dt = jnp.full((N, S, H), 1e-3) * (1.0 + 0.1 * jax.random.uniform(ks[1], (N, S, H)))
    a = jnp.array([-1.0, -1.1])
    b, c = (jax.random.normal(k, (N, S, G, Ns)) for k in ks[2:])
    args = (x, dt, a, b, c)
    with jax.default_matmul_precision("highest"):
        want = _recurrence(*args)
        sound = _kernels(*args)
        rounded = _kernels(*args, state=jnp.bfloat16)
    scale = float(jnp.abs(want).max())
    tol = 2e-5
    assert float(jnp.abs(sound - want).max()) < tol * scale
    assert float(jnp.abs(rounded - want).max()) > 100 * tol * scale


@pytest.mark.parametrize("shapes, block", [
    ((8192, 64, 64, 8, 128, 128), (128, 512)),    # the cell: 64 heads of 64 in 8 groups
    ((8192, 64, 32, 8, 128, 128), (128, 256)),    # a rate-1/2 client's own widths: four heads a tile
    ((8192, 16, 128, 8, 128, 256), (256, 256)),   # whole-lane heads, chunks of 256
    ((8000, 64, 64, 8, 128, 128), None),          # a ragged row
    ((8192, 64, 4, 8, 128, 128), None),           # a rate-1/16 client's: 32 lanes a group
    ((8192, 64, 8, 8, 128, 128), None),           # a rate-1/8 client's: 64 lanes a group
    ((8192, 64, 64, 8, 128, 16), None),           # chunks of 16
    ((8192, 64, 64, 8, 64, 128), None),           # a state of 64
    ((8192, 64, 48, 8, 128, 128), None),          # heads that do not divide a lane tile
    ((8192, 8, 256, 8, 128, 128), None),          # nor do heads of two
    ((8192, 64, 64, 2, 128, 128), None),          # 32 heads a group: 2,048 lanes side by side
], ids=["cell", "half-width", "whole-lane-heads", "ragged-row", "narrow-slice", "eighth-width",
        "chunk-16", "state-64", "heads-of-48", "heads-of-256", "wide-group"])
def test_the_plan_takes_whole_chunks_lanes_and_states(shapes, block):
    assert PS.ssm_plan(*shapes) == block


def _scan_calls(S, P, chunk, backend, monkeypatch):
    """Names of the ``pallas_call``s in ``ssm_chunked_scan``'s program, forward
    and gradient, when jax reports ``backend``."""
    monkeypatch.setattr(jax, "default_backend", lambda: backend)
    shapes = [(1, S, 8, P), (1, S, 8), (8,), (1, S, 2, 128), (1, S, 2, 128)]
    text = str(jax.make_jaxpr(jax.grad(lambda *a: jnp.sum(L.ssm_chunked_scan(*a, chunk)[0])))(
        *(jax.ShapeDtypeStruct(s, jnp.float32) for s in shapes)))
    assert text.count("pallas_call") == len(re.findall(r"name=ssm_scan_\w+", text))
    return re.findall(r"name=(ssm_scan_\w+)", text)


@pytest.mark.parametrize("S, P, chunk, backend, fused", [
    (512, 64, 128, "tpu", True),
    (512, 64, 128, "cpu", False),     # the CPU takes the jnp form
    (500, 64, 128, "tpu", False),     # a ragged row
    (512, 4, 128, "tpu", False),      # a rate-1/16 client's own width
    (512, 64, 16, "tpu", False),      # chunks of 16
], ids=["cell-dims", "cpu", "ragged-row", "narrow-slice", "chunk-16"])
def test_which_form_runs_is_decided_by_backend_and_shapes(S, P, chunk, backend, fused,
                                                          monkeypatch):
    """No key, flag or variable: ``ssm_chunked_scan`` takes the kernels on a
    TPU where ``ssm_plan`` finds a block, and its ``jnp`` form elsewhere."""
    calls = _scan_calls(S, P, chunk, backend, monkeypatch)
    assert calls == (["ssm_scan_fwd", "ssm_scan_bwd"] if fused else [])
