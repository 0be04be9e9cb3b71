"""Phi-4-mini-flash-reasoning (``model_type: phi4flash``) with HeteroFL width
scaling.

The published block (huggingface.co/microsoft/Phi-4-mini-flash-reasoning
``config.json``; the SambaY decoder-hybrid-decoder of arXiv:2507.06607 --
Samba's Mamba + sliding-window layers as a self-decoder, YOCO's
cross-decoder, arXiv:2405.05254, with half of its cross-attention layers
replaced by gated memory units -- with the differential attention of
arXiv:2410.05258 in every attention layer): every layer ``i`` is ``x <- x +
mixer_i(LN1_i(x))``, then ``x <- x + (silu(g) * u) W2`` with ``[g | u] =
LN2_i(x) W1``; ``LN`` a LayerNorm WITH a bias; a final ``LN`` and the tied head;
no position encoding (the Mamba layers carry the order); next-token loss.  The
mixer's kind by the published rule (:func:`layer_types`: ``mb_per_layer`` 2,
half = ``num_hidden_layers`` / 2), ``h`` the normed input ``[T, D]``:

  mamba  (even layers <= half)   [xs | z] = h W_in;  xs = silu(conv(xs) + b_conv)  (depthwise, causal)
                                 [r | B | C] = xs W_x;  dt = softplus(r W_dt + b_dt) [E];  A = -exp(A_log) [E, N]
                                 over a row from a zero state [E, N]:
                                     H_t = exp(dt_t[:, None] * A) * H_{t-1} + (dt_t * xs_t)[:, None] * B_t[None, :]
                                     y_t = H_t C_t + D * xs_t
                                 out = (y * silu(z)) W_out;   THE LAST ONE'S ``y`` IS KEPT, the memory ``m``
  sliding (odd layers < half)    differential attention under a window
  full   (layer half + 1)        differential attention, causal;  ITS ``k1, k2, v`` ARE KEPT
  gmu    (even layers > half)    out = (m * silu(h W1)) W2  on the kept memory
  cross  (odd layers > half + 1) differential attention, its own queries on the kept ``k1, k2, v``

  differential attention: [q | k | v] = h W_qkv + b_qkv;  q -> [T, H/2, 2, d]: q1, q2;  k -> [T, Hkv/2, 2, d]:
      k1, k2;  v -> [T, Hkv/2, 2 d] (a pair's two value heads side by side);  query pair p reads pair p // 2
      a_j = softmax_mask(q_j k_j^T / sqrt(d)) v;   lam = exp(lq1 . lk1) - exp(lq2 . lk2) + lam0_i
      lam0_i = 0.8 - 0.6 exp(-0.3 i)  (i the PUBLISHED layer index);   o = RMSNorm_2d(a_1 - lam a_2; g_sub) (1 - lam0_i)
      out = o.reshape(T, H d) W_o + b_o

The layout reproduces the published parameter count: 9 x 119.90 M (``mamba``) +
9 x 98.32 M (``sliding``, ``full``) + 7 x 104.87 M (``gmu``) + 7 x 91.77 M
(``cross``) + 200,064 x 2,560 = 3.85 B, the published "3.8B" (tested).

A LAYER HANDS VALUES FORWARD BESIDE ``x``.  The layers take and return ``(x,
side)`` (``decoder.run_layers``): the last ``mamba`` layer before the first
``gmu`` adds ``side["m"]``, the last ``full`` layer before the first ``cross``
adds ``side["k1"]``, ``["k2"]``, ``["v"]``; a ``layer_types`` that puts a
consumer before its producer raises where the model is built.  Every layer is
under ``jax.checkpoint`` (:func:`kept`) and is given only what it reads, so a
side value is a residual of its producer once.  No two neighbours of the
published pattern are alike, and a layer's constant follows its index: every
layer is a lone layer.

STORED FORMS (so that a leaf drawn by the package's one rule for a matrix
lands where the published initialisation does; a slope of one, so every
gradient is the published parameter's): the published ``in_proj``, ``Wqkv``
and the feed-forward's first matrix are column leaves (``ssm.in.{x,z}``,
``attn.{q,k,v}``, ``mlp.{g,u}``), each block under its own width group;
``A_log = ssm.a_log.w + log(1..N)`` (a leaf near zero is the S4D-real start
``A`` = -(1..N)); ``b_dt = ssm.dt.b[0] +`` :data:`DT_BIAS_SHIFT` with the leaf
``[1, E]``; ``D`` the gain ``ssm.skip.g`` (1); each ``l*`` vector a ``[d, 1]``
leaf (drawn uniform at ``1 / sqrt(d)``; a 1-D leaf would start at zero, a
saddle at which none of the four ever gets a gradient).

HeteroFL slicing (the paper defines none for this family; stated in the
benchmark configuration's ``assumed``): ``emb`` prefix of the hidden size
(embedding columns, every norm's gain and bias, every matrix's model-side
axis); ``inner``, a prefix of the ``E`` inner channels, ONE group for every
``mamba`` and ``gmu`` layer (``xs``, ``z``, the convolution's taps and bias,
``W_x``'s rows, ``W_dt``'s columns and ``b_dt``, ``A_log``'s rows, ``D``,
``W_out``'s rows; a ``gmu``'s ``W1`` columns and ``W2`` rows), so that a masked
channel of ``m`` meets a masked channel of the gate; per-head prefixes of the
``d`` dims of the query, key and value heads, equal for all (one family; the
sub-norm's gain holds two heads' dims and counts the active ones); a prefix of
the feed-forward's width; never sliced: ``d_state``, ``dt_rank``, ``B``, ``C``,
the number of heads, the ``l*`` vectors, the window, the vocabulary.  A masked
channel of ``xs`` stays zero through the convolution (its taps and bias are
zero), the scan (its state is never written), the skip and the gate.  Softmax
scale ``1 / sqrt(active dims of a head)``; a Scaler after every sliced linear
except ``W_x -> W_dt`` (a time step read by a softplus and an exponential,
where a division by the rate changes a decision's temperature and not a
feature's size) and the head; none after the depthwise taps.
"""

from __future__ import annotations

import math
from functools import partial
from typing import Dict, List

import jax
import jax.numpy as jnp

from ..obs.trace import scope
from ..ops.layers import (causal_conv_silu, differential_attention,
                          differential_attention_planned, gated_memory_unit, heads_linear,
                          linear as _linear, linear_heads, selective_scan, swiglu)
from .base import ModelDef
from .decoder import Leaves, alike_runs, decoder, layer_leaves, run_layers
from .spec import Group

KINDS = ("mamba", "sliding", "full", "gmu", "cross")
#: what a kind reads of ``side``
READS = {"gmu": ("m",), "cross": ("k1", "k2", "v")}

#: ``b_dt = ssm.dt.b[0] + DT_BIAS_SHIFT`` (``nemotron_h``'s shift):
#: ``softplus(-4.6) = 0.01``, the geometric middle of the published
#: initialisation's [0.001, 0.1], and a leaf uniform in [-1, 1] gives a bias
#: whose softplus lies in [0.0037, 0.027]
DT_BIAS_SHIFT = -4.6

#: positions of a row ``ops.layers.selective_scan`` keeps ONE state for (the
#: backward's memory: 32 states of 328 KB a row and layer at 8,192 positions)
#: and, inside such a chunk, positions a block runs one after another while the
#: chunk's 16 blocks run side by side (a step then works on ``[16, 16, 5120]``,
#: 5 MB: long enough to fill the chip, short enough that a chunk's residuals,
#: a few times ``[256, 16, 5120]`` float32, stay under half a GB)
SCAN_CHUNK, SCAN_BLOCK = 256, 16


def layer_types(num_hidden_layers: int, mb_per_layer: int = 2) -> List[str]:
    """The published rule: which mixer each layer has."""
    half = num_hidden_layers // 2
    kinds = []
    for i in range(num_hidden_layers):
        if i % mb_per_layer == 0:
            kinds.append("mamba" if i <= half else "gmu")
        elif i < half:
            kinds.append("sliding")
        else:
            kinds.append("full" if i == half + 1 else "cross")
    return kinds


def lam0_of(layer: int) -> float:
    """Differential attention's constant at PUBLISHED layer ``layer``."""
    return 0.8 - 0.6 * math.exp(-0.3 * layer)


def kept():
    """What a layer's ``jax.checkpoint`` keeps for the backward beside its
    input: nothing (None: a bare checkpoint).  The attention's kernel pair
    names its results (``pallas_attention.GQ_OUT`` / ``GQ_LSE`` / ``GQ_OPS``)
    for a policy that would keep them, as ``ouro``'s layer does."""
    return None


def producers(kinds) -> Dict[str, int]:
    """The layers whose values later layers read: ``{"m": the last mamba
    layer before the first gmu, "kv": the last full layer before the first
    cross}`` (absent where nothing reads it); raises where a consumer has no
    producer before it."""
    out = {}
    for reader, writer, key in (("gmu", "mamba", "m"), ("cross", "full", "kv")):
        if reader not in kinds:
            continue
        before = [i for i in range(kinds.index(reader)) if kinds[i] == writer]
        if not before:
            raise ValueError(f"Not valid layer_types: the {reader!r} layer {kinds.index(reader)} "
                             f"reads what a {writer!r} layer before it keeps, and none is")
        out[key] = before[-1]
    return out


def mamba_mixer(lp, h, *, rank: int, state: int, sc, compute_dtype=None):
    """A layer's Mamba-1 mixer on the normed ``h`` ``[N, S, D]``: ``(out [N, S,
    D], m [N, S, E], keep [2])``, ``m`` the scan's output with its skip, before
    the gate (what a gated memory unit reads), ``keep``
    :func:`~..ops.layers.selective_scan`'s count."""
    linear = partial(_linear, compute_dtype=compute_dtype)
    with scope("ssm"):
        xs, z = sc(linear(h, lp["ssm.in.x.w"])), sc(linear(h, lp["ssm.in.z.w"]))
        xs = causal_conv_silu(xs, lp["ssm.conv.w"], lp["ssm.conv.b"])
        rbc = linear(xs, lp["ssm.x.w"])
        # a time step: no Scaler (the module's note); B and C are features
        r, b, c = rbc[..., :rank], sc(rbc[..., rank:rank + state]), sc(rbc[..., rank + state:])
        dt = jax.nn.softplus(linear(r, lp["ssm.dt.w"]) + (lp["ssm.dt.b"][0] + DT_BIAS_SHIFT))
        a = -jnp.exp(lp["ssm.a_log.w"] + jnp.log(jnp.arange(1, state + 1, dtype=jnp.float32)))
        y, keep = selective_scan(xs, dt, a, b, c, SCAN_CHUNK, SCAN_BLOCK)
        m = y + lp["ssm.skip.g"] * xs
        return sc(linear(m * jax.nn.silu(z), lp["ssm.out.w"])), m, keep


def diff_mixer(lp, h, side, *, pairs: int, kv_pairs: int, lam0: float, window, scale, sc, mask,
               count, eps: float, compute_dtype=None):
    """A layer's differential attention on the normed ``h`` ``[N, S, D]``,
    heads first: ``(out [N, S, D], (k1, k2, v), lam)``.  ``side`` None: the
    layer's own keys and value (``attn.{k,v}``); else another layer's ``(k1,
    k2, v)``.  ``pairs`` / ``kv_pairs``: query and key/value head PAIRS."""
    D = h.shape[-1]

    def half(name, n, j):  # head ``j`` of each of the ``n`` adjacent pairs: (weight, bias)
        w, b = lp[f"attn.{name}.w"], lp[f"attn.{name}.b"]
        return (w.reshape(D, n, 2, -1)[:, :, j].reshape(D, -1), b.reshape(n, 2, -1)[:, j, None])

    def heads(w, b, n):
        return sc(linear_heads(h, w, n, compute_dtype) + b)

    with scope("gqa"):
        q1, q2 = (heads(*half("q", pairs, j), pairs) for j in (0, 1))
        if side is None:
            k1, k2 = (heads(*half("k", kv_pairs, j), kv_pairs) for j in (0, 1))
            v = heads(lp["attn.v.w"], lp["attn.v.b"].reshape(kv_pairs, 1, -1), kv_pairs)
            side = (k1, k2, v)
        k1, k2, v = side
        lam = jnp.exp(jnp.sum(lp["attn.lq1.w"] * lp["attn.lk1.w"])) \
            - jnp.exp(jnp.sum(lp["attn.lq2.w"] * lp["attn.lk2.w"])) + lam0
    if compute_dtype is not None:
        q1, q2, k1, k2, v = (t.astype(compute_dtype) for t in (q1, q2, k1, k2, v))
    o = differential_attention(q1, q2, k1, k2, v, lam, lam0, lp["attn.sub.g"], window,
                               scale=scale, mask=mask, count=count, eps=eps)
    with scope("gqa"):
        return sc(heads_linear(o, lp["attn.o.w"], compute_dtype) + lp["attn.o.b"]), side, lam


def make_phi4flash(num_tokens: int, arch: Dict, model_rate: float = 1.0, *,
                   mask: bool = True, compute_dtype=None) -> ModelDef:
    """``arch``: ``cfg['phi4flash']`` (config.process_control) at the GLOBAL
    widths; ``model_rate`` builds the dense sub-model a client at that rate
    holds (the sliced strategy and the equivalence tests)."""
    leaves = Leaves(model_rate)
    cw, add = leaves.cw, leaves.add
    D, L = cw(arch["hidden_size"]), int(arch["num_hidden_layers"])
    kinds, offset = [str(k) for k in arch["layer_types"]], int(arch["layer_offset"])
    H, Hkv = int(arch["num_attention_heads"]), int(arch["num_key_value_heads"])
    hd = cw(int(arch["hidden_size"]) // H)
    E, F = cw(int(arch["expand"]) * int(arch["hidden_size"])), cw(arch["intermediate_size"])
    Ns, taps, R = int(arch["d_state"]), int(arch["d_conv"]), int(arch["dt_rank"])
    window = int(arch["sliding_window"])
    eps = float(arch["layer_norm_eps"])  # staticcheck: allow(no-float-coercion): build-time config scalar
    if len(kinds) != L or set(kinds) - set(KINDS):
        raise ValueError(f"Not valid layer_types: {kinds!r} (one of {'|'.join(KINDS)} for each "
                         f"of the {L} layers)")
    if H % 2 or Hkv % 2 or H % Hkv:
        raise ValueError(f"{H} query heads on {Hkv} key/value heads: differential attention "
                         f"pairs adjacent heads, and the query pairs divide over the key pairs")
    writes = producers(kinds)
    pairs, kv_pairs = H // 2, Hkv // 2

    def heads(name, n):
        return Group(name, n * hd, kind="per_head", num_heads=n, coupled=False, family="head")

    groups = {"emb": Group("emb", D), "inner": Group("inner", E), "ffn": Group("ffn", F),
              "q_head": heads("q_head", H), "kv_head": heads("kv_head", Hkv),
              "sub": heads("sub", 2)}
    leaves.stem(num_tokens, D, tied=True)
    add("norm.b", (D,), {0: "emb"})
    for i, kind in enumerate(kinds):
        p = f"l{i}"
        for n in ("norm1", "norm2"):
            add(f"{p}.{n}.g", (D,), {0: "emb"})
            add(f"{p}.{n}.b", (D,), {0: "emb"})
        leaves.add_ffn(f"{p}.mlp", F, "ffn")
        if kind == "mamba":
            add(f"{p}.ssm.in.x.w", (D, E), {0: "emb", 1: "inner"})
            add(f"{p}.ssm.in.z.w", (D, E), {0: "emb", 1: "inner"})
            add(f"{p}.ssm.conv.w", (taps, E), {1: "inner"})
            add(f"{p}.ssm.conv.b", (E,), {0: "inner"})
            add(f"{p}.ssm.x.w", (E, R + 2 * Ns), {0: "inner"})
            add(f"{p}.ssm.dt.w", (R, E), {1: "inner"})
            add(f"{p}.ssm.dt.b", (1, E), {1: "inner"})
            add(f"{p}.ssm.a_log.w", (E, Ns), {0: "inner"})
            add(f"{p}.ssm.skip.g", (E,), {0: "inner"})
            add(f"{p}.ssm.out.w", (E, D), {0: "inner", 1: "emb"})
        elif kind == "gmu":
            add(f"{p}.gmu.in.w", (D, E), {0: "emb", 1: "inner"})
            add(f"{p}.gmu.out.w", (E, D), {0: "inner", 1: "emb"})
        else:
            for m, group, n in (("q", "q_head", H),) + ((("k", "kv_head", Hkv), ("v", "kv_head", Hkv))
                                                       if kind != "cross" else ()):
                add(f"{p}.attn.{m}.w", (D, n * hd), {0: "emb", 1: group})
                add(f"{p}.attn.{m}.b", (n * hd,), {0: group})
            add(f"{p}.attn.o.w", (H * hd, D), {0: "q_head", 1: "emb"})
            add(f"{p}.attn.o.b", (D,), {0: "emb"})
            for m in ("lq1", "lk1", "lq2", "lk2"):
                add(f"{p}.attn.{m}.w", (int(arch["hidden_size"]) // H, 1), {})
            add(f"{p}.attn.sub.g", (2 * hd,), {0: "sub"})

    # summed over the layers: `ssm_keep` = (sum of exp(dt A), its count); `ssm_chunks`
    # = chunks scanned; `diff_lambda` = (sum of lam over the attention layers, their
    # number); `diff_fused` = (softmaxes a fused kernel pair took, softmaxes);
    # `side_reads` = layers that read another layer's value
    counters = {"keep": ("ssm_keep", (2,), "mean"), "chunks": ("ssm_chunks", (1,), "sum"),
                "lam": ("diff_lambda", (2,), "mean"), "fused": ("diff_fused", (2,), "ratio"),
                "reads": ("side_reads", (1,), "sum")}

    def body(c, params):
        N, S, sc, ln = c.N, c.S, c.sc, c.layer_norm
        mamba = partial(mamba_mixer, rank=R, state=Ns, sc=sc, compute_dtype=compute_dtype)
        attention = partial(diff_mixer, pairs=pairs, kv_pairs=kv_pairs,
                            scale=1.0 / jnp.sqrt(c.count["kv_head"]), sc=sc, mask=c.mask["sub"],
                            count=c.count["sub"], eps=eps, compute_dtype=compute_dtype)
        zero = c.zeros()
        chunks = jnp.full((1,), N * -(-S // SCAN_CHUNK), jnp.float32)
        one = jnp.ones((1,), jnp.float32)

        def layer_of(i):
            """Layer ``i`` as ``((x, side), leaves) -> ((x, side), counters)``
            (``decoder.run_layers``)."""
            kind, reads = kinds[i], READS.get(kinds[i], ())
            win = window if kind == "sliding" else None
            fused = jnp.stack([2.0 * differential_attention_planned(S, hd, pairs // kv_pairs,
                                                                    2 * hd, win), 2.0])

            @partial(jax.checkpoint, policy=kept())
            def inner(x, side, lp):
                h, new = ln(lp["norm1.g"], lp["norm1.b"], x), {}
                if kind == "mamba":
                    y, m, keep = mamba(lp, h)
                    if writes.get("m") == i:
                        new["m"] = m
                    counted = dict(zero, keep=keep, chunks=chunks)
                elif kind == "gmu":
                    y = gated_memory_unit(h, side["m"], lp["gmu.in.w"], lp["gmu.out.w"], sc,
                                          compute_dtype)
                    counted = dict(zero, reads=one)
                else:
                    y, kv, lam = attention(lp, h, tuple(side[k] for k in reads) or None,
                                           lam0=lam0_of(offset + i), window=win)
                    if writes.get("kv") == i:
                        new.update(zip(READS["cross"], kv))
                    counted = dict(zero, lam=jnp.stack([lam, 1.0]), fused=fused,
                                   reads=one * (kind == "cross"))
                x = x + y
                h = ln(lp["norm2.g"], lp["norm2.b"], x)
                return x + swiglu(h, lp["mlp.g.w"], lp["mlp.u.w"], lp["mlp.d.w"], sc,
                                  compute_dtype), new, counted

            def layer(carry, lp):
                x, side = carry
                x, new, counted = inner(x, {k: side[k] for k in reads}, lp)
                return (x, {**side, **new}), counted
            return layer

        # a layer's constant and what it reads and writes follow its index: every layer lone
        runs = alike_runs(L, lambda i: i, lambda i: layer_leaves(params, i), layer_of)
        (x, _), counted = run_layers((c.embed(), {}), runs, zero)
        return c.finish(x, counted)

    return decoder(
        "phi4flash", num_tokens, arch, leaves, groups, body, eps=eps, mask=mask,
        compute_dtype=compute_dtype, counts=(("kv_head", Hkv), "sub"), masks=("sub",),
        counters=counters, norm=lambda c, x: c.layer_norm(c.params["norm.g"], c.params["norm.b"], x),
        # a site: two softmaxes a query pair, 64-wide scores against a 128-wide value
        profile={"attention": {f"l{i}.attn": (H, hd, 2 * hd) + ((window,) if k == "sliding" else ())
                               for i, k in enumerate(kinds) if k in ("sliding", "full", "cross")}})
