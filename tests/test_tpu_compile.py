"""The Pallas kernels, compiled by the chip's own compiler at real widths.

Interpret mode says nothing about what Mosaic accepts (scalar stores to
VMEM, shape casts, block tiling), so each kernel is AOT-compiled here, with
``interpret=False`` and shapes only, for a v5e that is described and not
attached.  A compile that passes is not a run: ``chip_smoke.py`` is the run.

Everything that describes the chip lives in the two fixtures below -- nothing
at import time, so every xdist worker collects the same tests and only the
worker given this file loads the TPU's library.  Keep these tests in this one
file, and compile in the test's own process.
"""

import functools
import math
import os
import re

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from heterofl_tpu import config as C
from heterofl_tpu.models import make_model
from heterofl_tpu.utils.compile_cache import no_persistent_cache

SLOTS = 10  # the flagship's active clients, all on one device


@pytest.fixture(scope="module")
def topo():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies

    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


@functools.lru_cache(maxsize=None)
def _flat_size(model_name):
    """Flat f32 size of a full-width model's param tree, from its factory."""
    cfg = C.default_cfg()
    cfg["control"] = C.parse_control_name(
        "1_100_0.1_iid_fix_a1-b1-c1-d1-e1_bn_1_1")
    cfg["model_name"] = model_name
    cfg["data_name"] = "WikiText2" if model_name == "transformer" else "CIFAR10"
    cfg = C.process_control(cfg)
    cfg["classes_size"] = 10
    cfg["num_tokens"] = 33278  # WikiText-2's vocabulary
    shapes = jax.eval_shape(make_model(cfg).init, jax.random.key(0))
    return sum(v.size for v in shapes.values())


def _compile(fn, *avals, kernels):
    """Compile for the described chip, and see that Mosaic did and that the
    kernels' ``name=`` reached the program (a device trace finds them by it)."""
    with no_persistent_cache():
        text = jax.jit(fn).lower(*avals).compile().as_text()
    assert "tpu_custom_call" in text  # no XLA stand-in, no interpreter
    for name in kernels:
        assert name in text, f"no instruction of the program carries {name!r}"
    return text


def test_quant_pack_kernel_compiles(one_chip):
    """The int8 codec's quantise+pack pass at ResNet-18's flat size."""
    from heterofl_tpu.ops.quant import quantize_pack

    total = _flat_size("resnet18")
    flat = jax.ShapeDtypeStruct((total,), jnp.float32, sharding=one_chip)
    key = jax.eval_shape(lambda: jax.random.key(0))
    key = jax.ShapeDtypeStruct(key.shape, key.dtype, sharding=one_chip)
    _compile(lambda x, s, k: quantize_pack(x, s, k, 63, 64, mode="pallas",
                                           interpret=False), flat, flat, key,
             kernels=("int8_pack",))


@pytest.mark.parametrize("n,h,c", [(10, 32, 64), (10, 4, 512)],
                         ids=["first-stage", "last-stage"])
def test_pallas_norm_compiles(one_chip, n, h, c):
    """Fused batch norm forward + backward at ResNet-18's first and last
    stage shapes for batch 10, bare and under ``vmap`` over client slots."""
    from heterofl_tpu.ops.pallas_norm import batch_norm_pallas

    def grads(x, g, b, sw):
        return jax.grad(
            lambda x_, g_, b_: jnp.sum(batch_norm_pallas(
                x_, g_, b_, sample_weight=sw, interpret=False) ** 2),
            argnums=(0, 1, 2))(x, g, b)

    def sds(*shape):
        return jax.ShapeDtypeStruct(shape, jnp.float32, sharding=one_chip)

    bn = ("masked_bn_fwd", "masked_bn_bwd")
    _compile(grads, sds(n, h, h, c), sds(c), sds(c), sds(n), kernels=bn)
    _compile(jax.vmap(grads), sds(SLOTS, n, h, h, c), sds(SLOTS, c),
             sds(SLOTS, c), sds(SLOTS, n), kernels=bn)


#: the Keye cell's selected attention: one row of 8,192 positions, 32 query heads on 4
#: key/value heads of 128, the indexer's top-2,048 in query blocks of 512
KEYE = dict(N=1, S=8192, H=32, Hkv=4, hd=128, topk=2048, block=512)


def _keye_selection(lead=()):
    """(per block None or the shape of its 0/1 choice, as ``select_keys`` gives
    them at the Keye cell's shape; ``rebuild(masks)`` the list with its Nones)."""
    N, S, topk, block = (KEYE[k] for k in ("N", "S", "topk", "block"))
    blocks = [None if end <= topk else lead + (N, block, end)
              for end in range(block, S + block, block)]

    def rebuild(masks):
        it = iter(masks)
        return [None if b is None else next(it) for b in blocks]

    return [b for b in blocks if b is not None], rebuild


@pytest.mark.parametrize("family, vmapped", [("latent", False), ("latent", True), ("gq", False),
                                             ("gq", True), ("sel", False), ("sel", True)],
                         ids=["cell", "vmap10", "gq-cell", "gq-vmap10", "sel-cell", "sel-vmap10"])
def test_attention_kernels_compile_under_attn(one_chip, family, vmapped, monkeypatch):
    """The fused causal attentions, forward and backward, as the models call
    them (the described chip is not the default backend, so the test steers
    the one question the functions ask), bare and under ``vmap`` over client
    slots with a per-client scale: latent attention at the Kanana-2 cell's
    shapes (2 rows x 2,048 positions, 32 heads, 128 | 64 | 128 head dims,
    heads first), grouped-query attention at the LFM2 cell's (32 query
    heads on 8 key/value heads of 64) and the selected attention at the Keye
    cell's REAL shape (:data:`KEYE`, a per-client selection: a backward that
    does not fit VMEM fails here, before any chip call); and both custom calls
    carry the ``attn`` scope, forward and transposed, by which the traced
    run's metrics find them."""
    from heterofl_tpu.ops.layers import (causal_gq_attention, causal_latent_attention,
                                         selected_gq_attention)

    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    N, S, H, dn, dr, dv, Hkv, hd = 2, 2048, 32, 128, 64, 128, 8, 64
    lead = (SLOTS,) if vmapped else ()
    masks, rebuild = _keye_selection()
    K = KEYE

    def selected(q, k, v, *rest):
        return selected_gq_attention(q, k, v, rest[-1], rebuild(rest[:-1]), K["block"])

    attention, shapes, widest = {
        "latent": (causal_latent_attention, ((N, H, S, dn), (N, H, S, dr), (N, H, S, dn),
                                             (N, S, dr), (N, H, S, dv)), max(dn, dv)),
        "gq": (causal_gq_attention, ((N, H, S, hd), (N, Hkv, S, hd), (N, Hkv, S, hd)), hd),
        "sel": (selected, ((K["N"], K["H"], K["S"], K["hd"]),)
                + ((K["N"], K["Hkv"], K["S"], K["hd"]),) * 2, K["hd"]),
    }[family]
    fwd, bwd = {"latent": ("latent_attn_fwd", "latent_attn_bwd"),
                "gq": ("gq_attn_fwd", "gq_attn_bwd"), "sel": ("sel_attn_fwd", "sel_attn_bwd")}[family]

    def grads(*a):
        ops, rest = a[:len(shapes)], a[len(shapes):]
        return jax.grad(lambda *o: jnp.sum(attention(*o, *rest) ** 2),
                        argnums=tuple(range(len(ops))))(*ops)

    avals = [jax.ShapeDtypeStruct(lead + s, jnp.float32, sharding=one_chip) for s in shapes] \
        + [jax.ShapeDtypeStruct(lead + m, bool, sharding=one_chip)
           for m in (masks if family == "sel" else ())] \
        + [jax.ShapeDtypeStruct(lead, jnp.float32, sharding=one_chip)]
    text = _compile(jax.vmap(grads) if vmapped else grads, *avals, kernels=(fwd, bwd))
    calls = [line for line in text.splitlines()
             if "custom-call(" in line and "tpu_custom_call" in line]
    op_names = sorted(re.search(r'op_name="([^"]*)"', line).group(1) for line in calls)
    assert len(op_names) == 2
    assert re.search(rf"jvp\(attn\)\)?/{fwd}/pallas_call$", op_names[0])
    assert re.search(rf"transpose\((vmap\()?jvp\(attn\)\)+/{bwd}/pallas_call$", op_names[1])
    # no [rows, heads, queries, keys] score block goes through HBM: nothing of 128 or
    # more queries is wider than ``widest``, the kernels' own results (a head's dims;
    # the grouped kernels' are [2, 32, 64, 2048], positions minor; the selected
    # kernels' [1, 32, 128, 8192], where what is held is a block of 512 queries)
    found = re.findall(r"f32\[(?:10,)?[12],(?:32|8,4|4,8),(\d+),(\d+)\]", text)
    assert found and not [m for m in found if int(m[0]) >= (512 if family == "sel" else 128)
                          and int(m[1]) > widest]


@pytest.mark.parametrize("vmapped", [False, True], ids=["cell", "vmap1"])
def test_gq_kernels_compile_at_heads_of_128_in_groups_of_one(one_chip, vmapped, monkeypatch):
    """The grouped-query kernels at the Ouro cell's shape (ISSUE 40): one row
    of 2,048 positions, 16 query heads on 16 key/value heads of 128, so a group
    is ONE query head and a tile ``[128, 512]`` where the LFM2 cell's is
    ``[64, 4 x 512]``; bare and under the ``vmap`` over the one client slot of
    a chunk, with a per-client scale.  Both custom calls carry the ``attn``
    scope, and no score block goes through HBM."""
    from heterofl_tpu.ops.layers import causal_gq_attention

    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    shape, lead = (1, 16, 2048, 128), ((1,) if vmapped else ())

    def grads(q, k, v, scale):
        return jax.grad(lambda *o: jnp.sum(causal_gq_attention(*o, scale) ** 2),
                        argnums=(0, 1, 2))(q, k, v)

    avals = [jax.ShapeDtypeStruct(lead + shape, jnp.float32, sharding=one_chip)] * 3 \
        + [jax.ShapeDtypeStruct(lead, jnp.float32, sharding=one_chip)]
    text = _compile(jax.vmap(grads) if vmapped else grads, *avals,
                    kernels=("gq_attn_fwd", "gq_attn_bwd"))
    calls = [line for line in text.splitlines()
             if "custom-call(" in line and "tpu_custom_call" in line]
    op_names = sorted(re.search(r'op_name="([^"]*)"', line).group(1) for line in calls)
    assert len(op_names) == 2
    assert re.search(r"jvp\(attn\)\)?/gq_attn_fwd/pallas_call$", op_names[0])
    assert re.search(r"transpose\((vmap\()?jvp\(attn\)\)+/gq_attn_bwd/pallas_call$", op_names[1])
    found = re.findall(r"f32\[(?:1,)?1,16,(\d+),(\d+)\]", text)
    assert found and not [m for m in found if int(m[0]) >= 128 and int(m[1]) > 2048]
    assert not re.search(r"f32\[(?:1,)?1,16,2048,2048\]", text)


@pytest.mark.parametrize("vmapped", [False, True], ids=["cell", "vmap1"])
@pytest.mark.parametrize("heads, window, scope_name", [(64, 512, "swa"), (48, None, "attn")],
                         ids=["sliding-G8", "full-G6"])
def test_band_kernels_compile_at_the_laguna_cells_two_layer_kinds(one_chip, heads, window,
                                                                  scope_name, vmapped, monkeypatch):
    """The band kernels at the Laguna cell's shapes (ISSUE 42): one row of
    8,192 positions on 8 key/value heads of 128, a sliding layer's 64 query
    heads under a window of 512 (groups of 8: `gq_attn_bwd`'s resident `dq`
    would be 34 MB) and a full layer's 48 under the diagonal alone (groups of
    6: 25 MB; `pallas_attention.gq_plan` hands both to the band pair); bare
    and under the ``vmap`` over the one client slot of a chunk, with a
    per-client scale.  Both custom calls carry the layer kind's scope, and no
    score block goes through HBM."""
    from heterofl_tpu.ops.layers import causal_gq_attention, sliding_gq_attention

    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    lead = (1,) if vmapped else ()
    attend = causal_gq_attention if window is None else \
        functools.partial(sliding_gq_attention, window=window)

    def grads(q, k, v, scale):
        return jax.grad(lambda *o: jnp.sum(attend(*o, scale) ** 2), argnums=(0, 1, 2))(q, k, v)

    avals = [jax.ShapeDtypeStruct(lead + (1, h, 8192, 128), jnp.float32, sharding=one_chip)
             for h in (heads, 8, 8)] \
        + [jax.ShapeDtypeStruct(lead, jnp.float32, sharding=one_chip)]
    text = _compile(jax.vmap(grads) if vmapped else grads, *avals,
                    kernels=("band_attn_fwd", "band_attn_bwd"))
    calls = [line for line in text.splitlines()
             if "custom-call(" in line and "tpu_custom_call" in line]
    op_names = sorted(re.search(r'op_name="([^"]*)"', line).group(1) for line in calls)
    assert len(op_names) == 2
    assert re.search(rf"jvp\({scope_name}\)\)?/band_attn_fwd/pallas_call$", op_names[0])
    assert re.search(rf"transpose\((vmap\()?jvp\({scope_name}\)\)+/band_attn_bwd/pallas_call$",
                     op_names[1])
    assert not re.search(rf"f32\[(?:1,)?1,{heads},\d+,8192\]", text.replace(
        f"f32[1,{heads},128,8192]", "").replace(f"f32[1,1,{heads},128,8192]", ""))


def test_band_kernels_compile_at_a_group_of_16(one_chip, monkeypatch):
    """The Nemotron-H cell's attention layer (ISSUE 46): one row of 8,192
    positions, 32 query heads on 2 key/value heads of 128, a group of 16
    (`gq_attn_bwd`'s resident `dq` would be 67 MB: `pallas_attention.gq_plan`
    gives the band pair at tiles 256 x 512), under the ``vmap`` over the one
    client slot of a chunk as the cell runs it; both custom calls under
    ``attn``, and no score block goes through HBM."""
    from heterofl_tpu.ops.layers import causal_gq_attention

    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")

    def grads(q, k, v, scale):
        return jax.grad(lambda *o: jnp.sum(causal_gq_attention(*o, scale) ** 2),
                        argnums=(0, 1, 2))(q, k, v)

    avals = [jax.ShapeDtypeStruct((1, 1, h, 8192, 128), jnp.float32, sharding=one_chip)
             for h in (32, 2, 2)] + [jax.ShapeDtypeStruct((1,), jnp.float32, sharding=one_chip)]
    text = _compile(jax.vmap(grads), *avals, kernels=("band_attn_fwd", "band_attn_bwd"))
    op_names = sorted(re.search(r'op_name="([^"]*)"', line).group(1) for line in text.splitlines()
                      if "custom-call(" in line and "tpu_custom_call" in line)
    assert len(op_names) == 2
    assert re.search(r"jvp\(attn\)\)?/band_attn_fwd/pallas_call$", op_names[0])
    assert re.search(r"transpose\(vmap\(jvp\(attn\)+/band_attn_bwd/pallas_call$", op_names[1])
    assert not re.search(r"f32\[(?:1,)?1,32,\d+,8192\]", text.replace(
        "f32[1,32,128,8192]", "").replace("f32[1,1,32,128,8192]", ""))


def test_the_chunked_scan_compiles_at_the_cells_shapes_and_keeps_no_state_a_position(one_chip):
    """`ops.layers.ssm_chunked_scan`, forward and gradient, at the Nemotron-H
    cell's REAL shapes (one row of 8,192 positions, 64 heads of 64 in 8 groups,
    a state of 128, chunks of 128) for the described chip: it fits beside the
    cell's state (a state a position would be 17 GB a layer; the compiled
    program's temporaries stay under 3 GB) and holds no `[.., 8192, .., 64,
    128]` array."""
    from heterofl_tpu.ops.layers import ssm_chunked_scan

    def grads(x, dt, a, b, c):
        return jax.grad(lambda *o: jnp.sum(ssm_chunked_scan(*o, 128)[0] ** 2),
                        argnums=(0, 1, 2, 3, 4))(x, dt, a, b, c)

    avals = [jax.ShapeDtypeStruct(s, jnp.float32, sharding=one_chip) for s in (
        (1, 8192, 64, 64), (1, 8192, 64), (64,), (1, 8192, 8, 128), (1, 8192, 8, 128))]
    with no_persistent_cache():
        compiled = jax.jit(grads).lower(*avals).compile()
    assert compiled.memory_analysis().temp_size_in_bytes < 3 << 30
    assert not re.search(r"f32\[[\d,]*8192,[\d,]*64,128\]", compiled.as_text())


@pytest.mark.parametrize("vmapped", [False, True], ids=["cell", "vmap1"])
def test_the_scan_kernels_compile_at_the_cells_shapes_under_ssm_scan(one_chip, vmapped,
                                                                      monkeypatch):
    """The fused pair (``ops/pallas_ssm.py``, ISSUE 47) through
    ``ssm_chunked_scan`` at the Nemotron-H cell's REAL shapes (one row of 8,192
    positions, 64 heads of 64 in 8 groups, a state of 128, chunks of 128),
    forward and gradient, plain and under the ``vmap`` over the one client
    slot of a chunk as the cell runs it: two custom calls, both under
    ``ssm/scan`` (the scope ``ssm_scan_ms.step`` and ``ssm_scan_roofline_pct``
    read, whatever implements it), no ``[.., 128, 128]`` float32 array a chunk
    and head -- the decay matrices and the decayed scores the ``jnp`` form
    writes, 64 x 64 of them a layer -- anywhere in the compiled program, the
    state before each chunk as the one float32 ``[64, 8, 128, 512]`` between the
    two kernels, and temporaries under 1 GB (the ``jnp`` form's line above is 3)."""
    from heterofl_tpu.ops.layers import ssm_chunked_scan

    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")

    def grads(x, dt, a, b, c):
        return jax.grad(lambda *o: jnp.sum(ssm_chunked_scan(*o, 128)[0] ** 2),
                        argnums=(0, 1, 2, 3, 4))(x, dt, a, b, c)

    lead = (1,) if vmapped else ()
    avals = [jax.ShapeDtypeStruct(lead + s, jnp.float32, sharding=one_chip) for s in (
        (1, 8192, 64, 64), (1, 8192, 64), (64,), (1, 8192, 8, 128), (1, 8192, 8, 128))]
    with no_persistent_cache():
        compiled = jax.jit(jax.vmap(grads) if vmapped else grads).lower(*avals).compile()
    text = compiled.as_text()
    op_names = sorted(re.search(r'op_name="([^"]*)"', line).group(1) for line in text.splitlines()
                      if "custom-call(" in line and "tpu_custom_call" in line)
    assert len(op_names) == 2, op_names
    assert re.search(r"jvp\(ssm/scan\)\)?/ssm_scan_fwd/pallas_call$", op_names[0])
    assert re.search(r"transpose\((vmap\()?jvp\(ssm/scan\)\)+/ssm_scan_bwd/pallas_call$",
                     op_names[1])
    # as the benchmark's reader files them: under the scope, the kernel's own component dropped
    from benchmark import scope_reduce

    monkeypatch.setattr(scope_reduce, "_PAIRS", scope_reduce._PAIRS | {("ssm", "scan")})
    assert [scope_reduce.scope_of(n) for n in op_names] == [("ssm/scan", "fwd"), ("ssm/scan", "bwd")]
    assert not re.search(r"f32\[[\d,]*64,[\d,]*128,128\]", text.replace(
        "f32[1,64,8,128,512]", "").replace("f32[1,1,64,8,128,512]", ""))
    assert "64,8,128,512]" in text  # the states between the kernels, and nothing else of their size
    assert compiled.memory_analysis().temp_size_in_bytes < 1 << 30


#: bytes of an element, for the shapes a relayout of the block can have
_ITEMSIZE = {"f32": 4, "bf16": 2}


def test_the_selective_scan_compiles_at_the_cells_shapes_and_keeps_no_state_a_position(one_chip):
    """`ops.layers.selective_scan`, forward and gradient, at the Phi-4-flash
    cell's REAL shapes (ISSUE 50: one row of 8,192 positions, 5,120 channels, a
    state of 16, chunks of 256 in blocks of 16) for the described chip: a state
    a position would be 2.7 GB a tensor; the compiled program's temporaries
    stay under 1.5 GB and it holds no `[.., 8192, 16, 5120]` array."""
    from heterofl_tpu.ops.layers import selective_scan

    def grads(x, dt, a, b, c):
        return jax.grad(lambda *o: jnp.sum(selective_scan(*o, 256, 16)[0] ** 2),
                        argnums=(0, 1, 2, 3, 4))(x, dt, a, b, c)

    avals = [jax.ShapeDtypeStruct(s, jnp.float32, sharding=one_chip) for s in (
        (1, 8192, 5120), (1, 8192, 5120), (5120, 16), (1, 8192, 16), (1, 8192, 16))]
    with no_persistent_cache():
        compiled = jax.jit(grads).lower(*avals).compile()
    assert compiled.memory_analysis().temp_size_in_bytes < 3 << 29
    text = compiled.as_text()
    assert not re.search(r"f32\[[\d,]*8192,16,5120\]|f32\[[\d,]*8192,5120,16\]", text)
    assert "tpu_custom_call" not in text  # plain jax.numpy: a kernel is a later PR's


@pytest.mark.parametrize("vmapped", [False, True], ids=["cell", "vmap1"])
def test_differential_attention_compiles_with_one_call_a_softmax(one_chip, vmapped, monkeypatch):
    """Differential attention at the Phi-4-flash cell's shapes (ISSUE 50): one
    row of 8,192 positions, 20 query pairs on 10 key pairs of 64 dims against
    ONE 128-wide value a pair; each softmax is one `gq_attn_fwd` /
    `gq_attn_bwd` call with the value's own width (`gq_plan(8192, 64, 2, None,
    128)`), bare and under the `vmap` over the one client slot of a chunk with
    a per-client scale; every call carries the `attn` scope and not the
    combine's, and no score block goes through HBM."""
    from heterofl_tpu.ops.layers import differential_attention

    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    lead = (1,) if vmapped else ()

    def grads(q1, q2, k1, k2, v, lam, g_sub, scale):
        return jax.grad(lambda *o: jnp.sum(differential_attention(
            *o, 0.79, g_sub, None, scale=scale, mask=jnp.ones(128), count=128.0) ** 2),
            argnums=(0, 1, 2, 3, 4, 5))(q1, q2, k1, k2, v, lam)

    shapes = [(1, 20, 8192, 64)] * 2 + [(1, 10, 8192, 64)] * 2 + [(1, 10, 8192, 128), (), (128,), ()]
    avals = [jax.ShapeDtypeStruct(lead + s, jnp.float32, sharding=one_chip) for s in shapes]
    text = _compile(jax.vmap(grads) if vmapped else grads, *avals,
                    kernels=("gq_attn_fwd", "gq_attn_bwd"))
    calls = [line for line in text.splitlines()
             if "custom-call(" in line and "tpu_custom_call" in line]
    op_names = sorted(re.search(r'op_name="([^"]*)"', line).group(1) for line in calls)
    assert len(op_names) == 4  # two softmaxes, forward and backward: no split by value halves
    assert all("attn" in n and "diff" not in n for n in op_names)
    assert not re.search(r"f32\[(?:1,)?1,\d+,8192,8192\]", text)


def _standalone_relayouts(text, floor=4 << 20):
    """(bytes written, instruction) of every ``copy`` / ``transpose`` of at
    least ``floor`` bytes that is an instruction of its own in the entry
    computation (one inside a fusion is that fusion's own read or write)."""
    found = []
    for line in text[text.index("\nENTRY"):].splitlines():
        m = re.match(r"\s*(?:ROOT )?\S+ = (\w+)\[([\d,]+)\]\S* (?:copy|transpose)\(", line)
        if m and m.group(1) in _ITEMSIZE:
            size = _ITEMSIZE[m.group(1)] * math.prod(int(d) for d in m.group(2).split(","))
            if size >= floor:
                found.append((size, line.strip()))
    return found


def test_latent_attention_block_feeds_the_kernels_without_relayouts(one_chip, monkeypatch):
    """A layer's whole latent attention (``models.kanana2.latent_attention``:
    projections, rotary turn, attention, output projection) at the Kanana-2
    cell's shapes, under ``jax.checkpoint`` and ``jax.grad`` as a layer of the
    model runs it: (a) both kernels are there under ``attn``; (b) the
    projections write ``qn``, ``qr``, ``kn``, ``v`` and read their gradients
    in the kernels' heads-first layout, so no activation of theirs is copied
    on its own, in the forward, the rematerialised forward or the backward.

    The formulation before (PR 29: ``linear`` to ``[N, S, H * d]``, ``reshape``,
    ``swapaxes``) compiled to 22 standalone copies of 4 MB or more writing
    788.5 MB a layer pass, every operand twice (``bf16[2,2048,4096]{1,2,0}``
    then ``bf16[2,2048,32,128]{3,1,2,0}``) in each of the three passes.  This
    one compiles to 17 writing 260.0 MB: the weights' bfloat16 and gradient
    relayouts (192.9 MB; 4-34 MB each; 33.5 MB of it the rotary query weight
    and its pair-swapped twin, without which it was 16 writing 226.5 MB) and
    ONE activation, the attention's float32 result ``o`` in the
    rematerialised forward (67.1 MB), which the output projection's weight
    gradient reads with the positions minor."""
    from heterofl_tpu.models.kanana2 import latent_attention, latent_attention_shapes
    from heterofl_tpu.ops.layers import masked_rms_norm

    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    N, S, D, H, dn, dr, dv, R = 2, 2048, 2048, 32, 128, 64, 128, 512
    shapes = latent_attention_shapes(D, H, dn, dr, dv, R)

    def sds(*shape):
        return jax.ShapeDtypeStruct(shape, jnp.float32, sharding=one_chip)

    @jax.checkpoint
    def block(lp, h, scale, rate):
        return latent_attention(
            lp, h, heads=H, theta=1e6, scale=scale, sc=lambda x: x / rate,
            kv_norm=lambda c, g: masked_rms_norm(c, g, jnp.ones((R,)), jnp.float32(R)))

    text = _compile(jax.grad(lambda *a: jnp.sum(block(*a) ** 2), argnums=(0, 1)),
                    {k: sds(*s) for k, s in shapes.items()}, sds(N, S, D), sds(), sds(),
                    kernels=("latent_attn_fwd", "latent_attn_bwd"))
    calls = [re.search(r'op_name="([^"]*)"', line).group(1) for line in text.splitlines()
             if "custom-call(" in line and "tpu_custom_call" in line]
    assert len(calls) == 3  # forward, rematerialised forward, backward
    assert all(re.search(r"attn\)*/latent_attn_(fwd|bwd)/pallas_call$", c) for c in calls), calls
    relayouts = _standalone_relayouts(text)
    listing = "\n".join(line for _, line in relayouts)
    assert sum(size for size, _ in relayouts) <= 265e6, listing
    # of the activations ([2, 32, 2048, d] or [2, 2048, 32 * d] in any order) only ``o``
    activations = [line for size, line in relayouts
                   if re.search(r"\[2,(32,2048|2048,32|2048),\d+\]", line)]
    assert len(activations) <= 1 and all("f32[2,32,2048,128]" in a for a in activations), listing


@pytest.mark.parametrize("mixer", ["shortconv", "gqa"])
def test_lfm2_mixers_compile_with_their_relayouts_listed(one_chip, monkeypatch, mixer):
    """A layer's mixer of the LFM2 cell (``models.lfm2.conv_mixer`` /
    ``gq_attention``) at the cell's shapes (2 rows x 2,048 positions, hidden
    2,048; 32 query heads on 8 key/value heads of 64), under ``jax.checkpoint``
    and ``jax.grad`` as a layer of the model runs it, for the described chip:
    counts, not times.  The standalone copies of 4 MB or more a layer pass
    (forward, rematerialised forward and backward together):

    - ``shortconv``: none.  The gates and the taps fuse into elementwise
      fusions around the four products; no ``[2, 2048, 2048]`` activation and
      no weight is copied on its own.
    - ``gqa``: 5 copies writing 41.9 MB, every one a weight's own (``q`` /
      ``o`` in bfloat16 for the rematerialised forward 2 x 8.4 MB, the three
      gradient relayouts 16.8 + 2 x 4.2 MB) and no activation: the fused
      kernels (PR 34) write ``dq`` whole, where the block loop's per-block
      pieces came back through a 33.6 MB ``attn/add_any`` copy of the grouped
      query's gradient (6 copies, 75.5 MB).  Three custom calls under
      ``attn`` (forward, rematerialised forward, backward) and no float32
      ``[..., 256, <= 2048]`` score block of the block loop in the program.
    """
    from heterofl_tpu.models.decoder import gq_attention
    from heterofl_tpu.models.lfm2 import conv_mixer
    from heterofl_tpu.ops.layers import masked_rms_norm

    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    N, S, D, H, Hkv, hd = 2, 2048, 2048, 32, 8, 64

    def sds(*shape):
        return jax.ShapeDtypeStruct(shape, jnp.float32, sharding=one_chip)

    if mixer == "shortconv":
        shapes = {**{f"conv.in.{m}.w": (D, D) for m in "bcu"},
                  "conv.taps.w": (3, D), "conv.out.w": (D, D)}

        def run(lp, h, scale, rate):
            return conv_mixer(lp, h, sc=lambda x: x / rate)
    else:
        shapes = {"attn.q.w": (D, H * hd), "attn.k.w": (D, Hkv * hd), "attn.v.w": (D, Hkv * hd),
                  "attn.q_norm.g": (hd,), "attn.k_norm.g": (hd,), "attn.o.w": (H * hd, D)}

        def run(lp, h, scale, rate):
            return gq_attention(
                lp, h, heads=H, kv_heads=Hkv, head_dim=hd, theta=1e6, scale=scale,
                sc=lambda x: x / rate,
                head_norm=lambda x, g: masked_rms_norm(x, g, jnp.ones((hd,)), jnp.float32(hd)))

    block = jax.checkpoint(run)
    with no_persistent_cache():
        text = jax.jit(jax.grad(lambda *a: jnp.sum(block(*a) ** 2), argnums=(0, 1))).lower(
            {k: sds(*s) for k, s in shapes.items()}, sds(N, S, D), sds(), sds()).compile().as_text()
    names = re.findall(r'op_name="([^"]*)"', text)
    for scope in {"shortconv": ("shortconv/linear", "shortconv/shortconv/gate"),
                  "gqa": ("gqa/linear", "gqa/norm", "rope", "attn")}[mixer]:
        assert any(f"/{scope}/" in "/" + n for n in names), scope
    relayouts = _standalone_relayouts(text)
    listing = "\n".join(f"{size / 1e6:.1f} MB  {line[:160]}" for size, line in relayouts)
    count, written = {"shortconv": (0, 0), "gqa": (5, 42e6)}[mixer]
    assert len(relayouts) <= count and sum(s for s, _ in relayouts) <= written, listing
    if mixer == "shortconv":  # no activation of the gated convolution on its own
        assert not [line for _, line in relayouts if re.search(r"\[2,2048,2048\]", line)], listing
    else:
        calls = [re.search(r'op_name="([^"]*)"', line).group(1) for line in text.splitlines()
                 if "custom-call(" in line and "tpu_custom_call" in line]
        assert len(calls) == 3 and all(
            re.search(r"attn\)*/gq_attn_(fwd|bwd)/pallas_call$", c) for c in calls), calls
        assert not re.search(r"f32\[2,8,4,256,\d+\]", text)  # the block loop's scores
        assert not [line for _, line in relayouts if "/attn/" in line], listing  # add_any: gone


def test_keye_attention_layer_compiles_with_the_selected_kernels(one_chip, monkeypatch):
    """A layer's indexer and selected attention of the Keye cell
    (``models.keye.index_keys``, then ``models.lfm2.gq_attention`` with
    ``selected_gq_attention``) at the cell's REAL shapes (:data:`KEYE`; hidden
    2,048, an indexer of 16 heads of 64), under ``jax.checkpoint`` and
    ``jax.grad`` as a layer of the model runs it, for the described chip: three
    custom calls under ``attn`` (forward, rematerialised forward, backward),
    ``sel_attn_fwd`` / ``sel_attn_bwd``, and none of the block loop's float32
    score blocks ``[.., 512, k]`` with ``k`` > 512 under ``attn`` (the
    indexer's own, under ``sparse/index``, stay: they decide the choice)."""
    from functools import partial

    from heterofl_tpu.models.decoder import gq_attention
    from heterofl_tpu.models.keye import index_keys
    from heterofl_tpu.ops.layers import masked_layer_norm, masked_rms_norm, selected_gq_attention

    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    K, D, Hi, di = KEYE, 2048, 16, 64
    H, Hkv, hd = K["H"], K["Hkv"], K["hd"]
    shapes = {"attn.q.w": (D, H * hd), "attn.k.w": (D, Hkv * hd), "attn.v.w": (D, Hkv * hd),
              "attn.q_norm.g": (hd,), "attn.k_norm.g": (hd,), "attn.o.w": (H * hd, D),
              "idx.q.w": (D, Hi * di), "idx.k.w": (D, di), "idx.w.w": (D, Hi),
              "idx.k_norm.g": (di,), "idx.k_norm.b": (di,)}

    def sds(*shape):
        return jax.ShapeDtypeStruct(shape, jnp.float32, sharding=one_chip)

    @jax.checkpoint
    def block(lp, h, scale, rate):
        select, _ = index_keys(
            lp, h, heads=Hi, head_dim=di, theta=1e7, topk=K["topk"], block=K["block"],
            key_norm=lambda x, g, b: masked_layer_norm(x, g, b, jnp.ones((di,)), jnp.float32(di)))
        return gq_attention(
            lp, h, heads=H, kv_heads=Hkv, head_dim=hd, theta=1e7, scale=scale,
            sc=lambda x: x / rate,
            head_norm=lambda x, g: masked_rms_norm(x, g, jnp.ones((hd,)), jnp.float32(hd)),
            attend=partial(selected_gq_attention, select=select, block=K["block"]))

    text = _compile(jax.grad(lambda *a: jnp.sum(block(*a) ** 2), argnums=(0, 1)),
                    {k: sds(*s) for k, s in shapes.items()}, sds(K["N"], K["S"], D), sds(), sds(),
                    kernels=("sel_attn_fwd", "sel_attn_bwd"))
    calls = [re.search(r'op_name="([^"]*)"', line).group(1) for line in text.splitlines()
             if "custom-call(" in line and "tpu_custom_call" in line]
    assert len(calls) == 3 and all(
        re.search(r"attn\)*/sel_attn_(fwd|bwd)/pallas_call$", c) for c in calls), calls
    blocks = [line.strip()[:200] for line in text.splitlines()
              if re.search(r"= f32\[[\d,]*512,(\d+)\]", line)
              and int(re.search(r"= f32\[[\d,]*512,(\d+)\]", line).group(1)) > 512
              and "attn" in line and "sparse/" not in line]
    assert not blocks, "\n".join(blocks)
