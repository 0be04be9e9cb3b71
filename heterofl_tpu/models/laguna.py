"""Laguna-XS.2 (``model_type: laguna``) with HeteroFL width scaling.

The published block (huggingface.co/poolside/Laguna-XS.2 ``config.json``):
pre-norm decoder layers of GROUPED-QUERY ATTENTION on ``Hkv`` key/value heads
whose KIND is the layer's (``layer_types``): a ``full_attention`` layer sees
every causal key, a ``sliding_attention`` layer the last ``sliding_window``
(itself and the 511 before it); the number of query heads is the layer's too
(``num_attention_heads_per_layer``: 48 | 64), each kind has its own RoPE
(``rope_parameters``: the sliding layers the default turn over the whole
head, the full layers YaRN over the first half of a head), and a per-head
sigmoid gate sits on the attention's output (``gating``).  The feed-forward
is, by ``mlp_layer_types``, a dense SwiGLU or ``num_experts`` SwiGLU experts
(sigmoid router, top-k, renormalised and scaled weights) beside one shared
expert that every token takes; RMSNorm, no biases, no dropout, untied
embedding and head; next-token loss.  ``x`` is ``[T, D]``, ``rms(x, g) = x /
sqrt(mean(x^2) + eps) * g``, layer ``l`` of kind ``t``:

  h = rms(x, g1);  q = h Wq -> [T, H_t, d];  k, v = h Wk, h Wv -> [T, Hkv, d]
  q, k = rope_t(., pos);   allowed_full(i, j) = j <= i
  allowed_sliding(i, j) = j <= i and i - j < window
  a_h = softmax_{j allowed}(q_h k_{h // G_t}^T / sqrt(d)) v_{h // G_t}
  gate = sigmoid(h Wgate) -> [T, H_t];  x = x + concat_h(gate_h * a_h) Wo
  h = rms(x, g2);  dense: x = x + (silu(h Wg) * (h Wu)) Wd
  sparse: s = sigmoid(h Wr);  sel = top_k(s);  w = s[sel] / sum(s[sel]) * scale
          x = x + shared(h) + sum_{e in sel, held} w_e expert_e(h)
  logits = rms(x, g_f) W_head

``rope_t``: half-split pairs over the first ``partial_rotary_factor`` of a
head, the rest untouched; ``default``: pair ``i`` turns by ``pos *
theta^(-2i/r)``; ``yarn`` (:func:`rope_frequencies`): the blend of that table
and the table divided by ``factor``, cos and sin times ``attention_factor``.

The expert layer is told what it holds (``expert_share = (index, of)``, as
``kanana2``); with ``of == 1`` the model is the published one.

HeteroFL slicing (the paper defines none for this family; stated in the
benchmark configuration's ``assumed``): ``emb`` prefix of the hidden size
(embedding columns, head rows, every norm gain, every matrix's model-side
axis, router and gate rows); per-head prefixes of a head's ROTARY dims (whole
pairs) and of its pass-through dims, each a family of its own, so a layer's
``q_proj`` / ``k_proj`` are held as column-split leaves (``q.r`` | ``q.n``,
``k.r`` | ``k.n``; a kind whose whole head turns has no ``.n``), the rotary
ones STORED with each head's pairs adjacent as in ``lfm2`` (stored ``2i`` =
published ``i``, stored ``2i + 1`` = published ``i + r/2``); per-head prefixes
of the value heads' dims and of the output projection's rows; ``ffn`` /
``shared`` / ``expert`` prefixes of the three feed-forward widths; never
sliced: the expert axis, the router's columns, the gate's ``H_t`` columns
(heads are not sliced), the vocabulary, the window.  Softmax scale
``1/sqrt(active dims of a head)``; a Scaler after every sliced linear except
the router, the gate and the head: their outputs are squashed or categorical
(a sigmoid, a sigmoid read by a top-k, a softmax), where a division by the
rate changes the temperature of a decision and not the size of a feature.

Consecutive layers alike in kind, head count and feed-forward run as one
``lax.scan`` over their stacked leaves, a lone layer as itself; each layer
under a ``jax.checkpoint`` that keeps, beside the layer's input, what the band
kernels name (:func:`kept`): ``BAND_OUT`` and ``BAND_LSE``, ``band_attn_fwd``'s
``o`` ``[N, H, d, S]`` float32 and log-sum-exp, and ``BAND_OPS``, the kernels'
own operands (``q`` scaled, ``k``, ``v`` in bfloat16 with the positions minor:
half the bytes of the float32 heads they are cast from).  They are the three
residuals of ``band_attn_bwd``, so a layer's backward runs no second
``band_attn_fwd`` and, nothing else in it reading them, no second ``q`` / ``k``
/ ``v`` product, turn, concatenation or cast; the rest of the layer is
computed again from its input as before.  BOTH KINDS KEEP ALL THREE, lone or
scanned: in the benchmark's cell (two lone full layers of 48 heads, a scan of
three sliding ones of 64; 337 and 438 MB a layer and client) a round read
3.613 s with nothing kept, 3.489 with the full layers' ``o`` and log-sum-exp,
3.417 with their operands too, 3.436 with the sliding layers' operands as
well and **3.235** with everything (PR 44's search, one seed; ``PERF.md`` §6):
a scanned layer that keeps its operands and not its ``o`` runs its forward
kernel again from them and gains nothing.  The attention's projected output
kept too read worse (3.251); the dense layer's SwiGLU output is the layer's
last value, which no backward reads.
"""

from __future__ import annotations

import math
from functools import partial
from typing import Dict

import jax
import jax.numpy as jnp
import numpy as np

from ..obs.trace import scope
from ..ops.layers import (band_attention_planned, causal_gq_attention, heads_linear,
                          linear as _linear, linear_heads, moe_experts, moe_route,
                          rope_interleaved, rope_swap, sliding_attention_tiles,
                          sliding_gq_attention, swiglu)
from .base import ModelDef
from .decoder import (Leaves, alike_runs, decoder, expert_tile, held_experts, layer_leaves,
                      moe_counters, run_layers)
from .spec import Group

KINDS = ("full_attention", "sliding_attention")


def kept():
    """What a layer's ``jax.checkpoint`` keeps for the backward beside its
    input: the three residuals of ``band_attn_bwd``, named where the kernel
    pair is called (``band_attn_fwd``'s output and log-sum-exp and the
    kernels' bfloat16 operands; the block loop names nothing).  Both kinds of
    layer keep all three, a lone layer and a scanned run alike (the rows that
    decided it: the module docstring).  None: a bare checkpoint, the input
    alone."""
    from ..ops.pallas_attention import BAND_LSE, BAND_OPS, BAND_OUT  # Pallas, imported where a Laguna model is built

    return jax.checkpoint_policies.save_only_these_names(BAND_OUT, BAND_LSE, BAND_OPS)


def rope_frequencies(rope: Dict, rotary_dim: int):
    """``(per-pair frequencies [rotary_dim / 2], cos/sin factor or None)`` of
    one kind's ``rope_parameters`` entry.  ``default``: ``theta^(-2i/r)``.
    ``yarn`` (the published ``_compute_yarn_parameters``): with ``e_i`` that
    table, ``dim(b) = r ln(original / (2 pi b)) / (2 ln theta)``, ``low =
    floor(dim(beta_fast))``, ``high = ceil(dim(beta_slow))`` and ``ramp_i =
    clip((i - low) / (high - low), 0, 1)``: ``e_i (1 - ramp_i) + e_i / factor
    * ramp_i``; cos and sin times ``attention_factor`` (absent: ``0.1 ln
    factor + 1``).  Static numbers, float64 until the model casts them."""
    # staticcheck: allow(no-float-coercion): build-time config scalar
    theta = float(rope["rope_theta"])
    i = np.arange(rotary_dim // 2, dtype=np.float64)
    e = theta ** (-2.0 * i / rotary_dim)
    kind = rope.get("rope_type", "default")
    if kind == "default":
        return e, None
    if kind != "yarn":
        raise ValueError(f"Not valid rope_type: {kind!r} ('default' | 'yarn')")
    # staticcheck: allow(no-float-coercion): build-time config scalars
    factor, original = float(rope["factor"]), float(rope["original_max_position_embeddings"])

    def dim(beta):
        return rotary_dim * math.log(original / (2 * math.pi * beta)) / (2 * math.log(theta))

    low = max(math.floor(dim(rope["beta_fast"])), 0)
    high = min(math.ceil(dim(rope["beta_slow"])), rotary_dim - 1)
    ramp = np.clip((i - low) / max(high - low, 1e-3), 0.0, 1.0)
    # staticcheck: allow(no-float-coercion): build-time config scalar
    scale = float(rope.get("attention_factor") or 0.1 * math.log(factor) + 1.0)
    return e * (1.0 - ramp) + e / factor * ramp, scale


def gated_gq_attention(lp, h, *, heads: int, kv_heads: int, freqs, factor, scale, sc, attend,
                       compute_dtype=None):
    """A layer's gated grouped-query attention on the normed ``h`` ``[N, S,
    D]``, heads first from the projections to the output projection.  ``lp``
    holds ``attn.{q,k}.r.w`` (the rotary dims of every head, pairs adjacent)
    and, where a head turns in part, ``attn.{q,k}.n.w`` (the rest);
    ``freqs`` / ``factor`` the kind's rotary table of the GLOBAL head (a
    sliced sub-model's pairs keep their frequencies); ``attend(q, k, v,
    scale)`` the kind's score / softmax / value part."""
    linear = partial(_linear, compute_dtype=compute_dtype)
    q_heads = partial(linear_heads, heads=heads, compute_dtype=compute_dtype)
    kv = partial(linear_heads, heads=kv_heads, compute_dtype=compute_dtype)
    pos = jnp.arange(h.shape[1])
    turn = partial(rope_interleaved, pos=pos, theta=None, axis=2, freqs=freqs, factor=factor)
    with scope("gqa"):
        q, k = sc(q_heads(h, lp["attn.q.r.w"])), sc(kv(h, lp["attn.k.r.w"]))
        v = sc(kv(h, lp["attn.v.w"]))
        # squashed: no Scaler (the module's note)
        gate = jax.nn.sigmoid(linear(h, lp["attn.gate.w"]))          # [N, S, H]
        rest = [sc(f(h, lp[f"attn.{m}.n.w"])) for m, f in (("q", q_heads), ("k", kv))] \
            if "attn.q.n.w" in lp else None
    q, k = turn(q, rope_swap(q)), turn(k, rope_swap(k))
    if rest is not None:
        q, k = jnp.concatenate([q, rest[0]], axis=-1), jnp.concatenate([k, rest[1]], axis=-1)
    if compute_dtype is not None:
        q, k, v = (t.astype(compute_dtype) for t in (q, k, v))
    o = attend(q, k, v, scale)
    with scope("gqa"):
        o = o.astype(jnp.float32) * jnp.swapaxes(gate, 1, 2)[..., None]
        return sc(heads_linear(o, lp["attn.o.w"], compute_dtype))


def make_laguna(num_tokens: int, arch: Dict, model_rate: float = 1.0, *,
                mask: bool = True, compute_dtype=None) -> ModelDef:
    """``arch``: ``cfg['laguna']`` (config.process_control) at the GLOBAL
    widths; ``model_rate`` builds the dense sub-model a client at that rate
    holds (the sliced strategy and the equivalence tests)."""
    leaves = Leaves(model_rate)
    cw, add, add_ffn = leaves.cw, leaves.add, leaves.add_ffn
    D, L = cw(arch["hidden_size"]), int(arch["num_hidden_layers"])
    kinds, mlps = list(arch["layer_types"]), list(arch["mlp_layer_types"])
    layer_heads = [int(n) for n in arch["num_attention_heads_per_layer"]]
    F, Fe = cw(arch["intermediate_size"]), cw(arch["moe_intermediate_size"])
    Fs = cw(arch["shared_expert_intermediate_size"])
    E, K = int(arch["num_experts"]), int(arch["num_experts_per_tok"])
    Hkv, hd_full = int(arch["num_key_value_heads"]), int(arch["head_dim"])
    hd, window = cw(hd_full, 2), int(arch["sliding_window"])
    # staticcheck: allow(no-float-coercion): build-time config scalars
    eps, scaling = float(arch["rms_norm_eps"]), float(arch["moe_routed_scaling_factor"])
    held = held_experts(arch["expert_share"], E)
    if not (len(kinds) == len(mlps) == len(layer_heads) == L) or set(kinds) - set(KINDS) \
            or set(mlps) - {"dense", "sparse"}:
        raise ValueError(
            f"Not valid layer lists: layer_types {kinds!r} (of {KINDS}), mlp_layer_types "
            f"{mlps!r} ('dense' | 'sparse'), num_attention_heads_per_layer {layer_heads!r}: "
            f"one entry for each of the {L} layers")
    if any(n % Hkv for n in layer_heads):
        raise ValueError(f"query heads {layer_heads!r} do not divide over {Hkv} key/value heads")

    # a kind's rotary part: the GLOBAL head's dims that turn and their table
    rotary = {}
    for t in set(kinds):
        rope = arch["rope_parameters"][t]
        r = int(hd_full * rope.get("partial_rotary_factor", 1.0))
        if r % 2 or not 0 < r <= hd_full:
            raise ValueError(f"rotary width {r} of a {t} head is not whole pairs of {hd_full}")
        rotary[t] = (r,) + rope_frequencies(rope, r)

    def short(kind):  # "full" | "sliding": a kind in a group's name
        return kind.split("_")[0]

    groups = {
        "emb": Group("emb", D), "ffn": Group("ffn", F), "shared": Group("shared", Fs),
        "expert": Group("expert", Fe), "router": Group("router", E, kind="full"),
        "v_head": Group("v_head", Hkv * hd, kind="per_head", num_heads=Hkv, multiple=2,
                        coupled=False, family="head"),
    }

    def heads(name, n, width, multiple, family):
        groups[name] = Group(name, n * width, kind="per_head", num_heads=n, multiple=multiple,
                             coupled=False, family=family)
        return name

    leaves.stem(num_tokens, D)
    widths = {}  # kind -> (rotary dims, the rest) of a head at this model_rate
    for i in range(L):
        p, H, kd = f"l{i}", layer_heads[i], short(kinds[i])
        t = f"{kd}{H}"  # the groups of a layer's query heads: its kind's and its head count's
        r_full = rotary[kinds[i]][0]
        r, n = cw(r_full, 2), (cw(hd_full - r_full) if r_full < hd_full else 0)
        widths[kinds[i]] = (r, n)
        add(f"{p}.norm1.g", (D,), {0: "emb"})
        for m, who, count in (("q", t, H), ("k", kd, Hkv)):
            add(f"{p}.attn.{m}.r.w", (D, count * r),
                {0: "emb", 1: heads(f"{who}.{m}_rope", count, r, 2, f"{kd}.rope")})
            if n:
                add(f"{p}.attn.{m}.n.w", (D, count * n),
                    {0: "emb", 1: heads(f"{who}.{m}_nope", count, n, 1, f"{kd}.nope")})
        add(f"{p}.attn.v.w", (D, Hkv * hd), {0: "emb", 1: "v_head"})
        groups[f"{t}.gate"] = Group(f"{t}.gate", H, kind="full")
        add(f"{p}.attn.gate.w", (D, H), {0: "emb", 1: f"{t}.gate"})
        add(f"{p}.attn.o.w", (H * hd, D), {0: heads(f"{t}.o_head", H, hd, 2, "head"), 1: "emb"})
        add(f"{p}.norm2.g", (D,), {0: "emb"})
        if mlps[i] == "dense":
            add_ffn(f"{p}.mlp", F, "ffn")
        else:
            add(f"{p}.moe.router.w", (D, E), {0: "emb", 1: "router"})
            add_ffn(f"{p}.moe.shared", Fs, "shared")
            for j in held:
                add_ffn(f"{p}.moe.e{j}", Fe, "expert")


    def body(c, params):
        N, S, T, sc, rms, width_rate = c.N, c.S, c.T, c.sc, c.rms, c.width_rate

        def head_dims(kind):  # the active dims of one head of a layer of this kind
            names = [f"{short(kind)}.k_rope"] + ([f"{short(kind)}.k_nope"] if widths[kind][1] else [])
            return sum(groups[g].active_count(width_rate) for g in names).astype(
                jnp.float32) / Hkv

        tile = expert_tile(T, K, E)
        zero = c.zeros()

        def swa_counters(H):
            """What a sliding layer adds, (numerator, denominator) pairs: query
            tiles the kernel pair took over query tiles, band pairs over causal
            pairs, key tiles visited over key tiles on or under the diagonal."""
            fused, visited, causal = sliding_attention_tiles(S, hd, H // Hkv, window)
            w = min(window, S)
            band = w * (w + 1) // 2 + (S - w) * w

            def pair(a, b):
                return jnp.stack([jnp.float32(N * a), jnp.float32(N * b)])

            return {"fused": pair(int(fused), 1), "pairs": pair(band, S * (S + 1) // 2),
                    "tiles": pair(visited, causal)}

        policy = kept()

        def layer_of(i):
            """Layer ``i``'s kind as ``(x, leaves) -> (x, counters)``: a
            ``lax.scan`` body, and a plain call for a lone layer.  For the
            backward it keeps its input and what :func:`kept` names."""
            kind, H, sparse = kinds[i], layer_heads[i], mlps[i] == "sparse"
            _, freqs, factor = rotary[kind]
            # (the layer keeps its kernels' results, its attention is on the
            # kernels that name them): `band_kept`
            on_band = band_attention_planned(
                S, hd, H // Hkv, window if kind == "sliding_attention" else None)
            band = jnp.array([on_band and policy is not None, on_band], jnp.float32)
            attend = causal_gq_attention if kind == "full_attention" else \
                partial(sliding_gq_attention, window=window)
            attention = partial(
                gated_gq_attention, heads=H, kv_heads=Hkv, freqs=freqs, factor=factor,
                scale=1.0 / jnp.sqrt(head_dims(kind)), sc=sc, attend=attend,
                compute_dtype=compute_dtype)

            @partial(jax.checkpoint, policy=policy)
            def layer(x, lp):
                x = x + attention(lp, rms(lp["norm1.g"], x))
                h = rms(lp["norm2.g"], x)
                counters = dict(zero, kept=band)
                if kind == "sliding_attention":
                    counters.update(swa_counters(H))
                if not sparse:
                    return x + swiglu(h, lp["mlp.g.w"], lp["mlp.u.w"], lp["mlp.d.w"],
                                      sc, compute_dtype), counters
                hf = h.reshape(T, D)
                sel, w = moe_route(hf, lp["moe.router.w"], None, K, scaling)
                y, moe = moe_experts(hf, sel, w, [lp[f"moe.e.{m}.w"] for m in "gud"],
                                     held[0], sc, compute_dtype, tile=tile)
                with scope("moe/shared"):
                    y = y + swiglu(hf, lp["moe.shared.g.w"], lp["moe.shared.u.w"],
                                   lp["moe.shared.d.w"], sc, compute_dtype)
                counters.update(moe)
                return x + y.reshape(N, S, D), counters
            return layer

        # alike in kind, head count and feed-forward
        runs = alike_runs(L, lambda i: (kinds[i], layer_heads[i], mlps[i]),
                          lambda i: layer_leaves(params, i, held if mlps[i] == "sparse" else None),
                          layer_of)
        return c.finish(*run_layers(c.embed(), runs, zero))

    # summed over the layers; the three swa ones: query tiles the kernel pair
    # took over query tiles, band pairs over causal pairs, key tiles visited
    # over key tiles on or under the diagonal; `band_kept`: layers whose
    # checkpoint kept their band kernels' results over layers on the band kernels
    counters = {"kept": ("band_kept", (2,), "ratio")}
    if "sparse" in mlps:
        counters.update(moe_counters(held))
    if "sliding_attention" in kinds:
        counters.update({k: (f"swa_{k}", (2,), "ratio") for k in ("fused", "pairs", "tiles")})
    return decoder(
        "laguna", num_tokens, arch, leaves, groups, body, eps=eps, mask=mask,
        compute_dtype=compute_dtype, held=held, counters=counters,
        # a site's heads, its two products' widths and its window (None: every
        # causal pair)
        profile={"routed_share": K / E,
                 "attention": {f"l{i}.attn": (
                     layer_heads[i], sum(widths[kinds[i]]), hd,
                     window if kinds[i] == "sliding_attention" else None)
                     for i in range(L)}})
