"""LFM2-8B-A1B (``model_type: lfm2_moe``) with HeteroFL width scaling.

The published block (huggingface.co/LiquidAI/LFM2-8B-A1B ``config.json``):
pre-norm decoder layers whose sequence mixer is, by ``layer_types``, a GATED
SHORT CONVOLUTION (``conv``: one ``D -> 3D`` projection split into two gates
and a stream, a depthwise causal convolution of ``conv_L_cache`` taps, one
``D -> D`` projection) or GROUPED-QUERY ATTENTION (``full_attention``: ``H``
query heads on ``Hkv`` key/value heads, RMSNorm on every query and key head,
half-split RoPE over the whole head); the feed-forward is a dense SwiGLU in
the first ``num_dense_layers`` layers and, in the rest, ``num_experts`` SwiGLU
experts (sigmoid router, top-k, a selection bias read by top-k only,
normalised weights, NO shared expert); RMSNorm, no biases, no dropout, the
embedding tied to the head; next-token loss.  ``x`` is ``[T, D]``, ``rms(x,
g) = x / sqrt(mean(x^2) + eps) * g``:

  every layer:  x = x + mixer(rms(x, g_op));  x = x + ff(rms(x, g_ffn))
  conv:         [b | c | u] = h W_in;  z = b * u
                y[t] = sum_j taps[j] * z[t - (L - 1) + j];  out = (c * y) W_out
  attention:    q = h Wq -> [T, H, d];  k, v = h Wk, h Wv -> [T, Hkv, d]
                q, k = rms_head(q, g_q), rms_head(k, g_k);  q, k = rope(., pos)
                out = concat_heads(softmax_causal(q k_group^T / sqrt(d)) v_group) Wo
  dense ff:     (silu(h Wg) * (h Wu)) Wd
  expert ff:    s = sigmoid(h Wr); sel = top_k(s + b); w = s[sel] / (sum + 1e-6) * scale
                sum_{e in sel, held} w_e expert_e(h)
  logits = rms(x, g_f) E^T      (E the embedding)

The expert layer is told what it holds (``expert_share = (index, of)``, as
``kanana2``); with ``of == 1`` the model is the published one.

HeteroFL slicing (the paper defines none for this family; stated in the
benchmark configuration's ``assumed``): ``emb`` prefix of the hidden size
(embedding columns, every norm gain, every matrix's model-side axis, router
rows); ``conv`` prefix of the conv channels, so the published ``in_proj`` is
held as three column leaves (``conv.in.b`` | ``conv.in.c`` | ``conv.in.u``)
beside the taps ``[L, channels]`` and ``out_proj``'s rows; per-head prefixes
of the head's dims in whole rotary pairs, equal for the query heads, the
key/value heads and the two head norms' gains (one ``family`` of groups:
``spec.Group.family``).  Half-split RoPE pairs dim ``i`` with dim ``i + d/2``,
which no prefix keeps whole, so ``q``, ``k`` and the head norms' gains are
STORED under the fixed permutation of a head's columns that makes the pairs
adjacent (stored ``2i`` = published ``i``, stored ``2i + 1`` = published ``i +
d/2``): a dot product, a norm over the head and a rotation within pairs are
all unchanged by it, ``per_head`` with ``multiple=2`` keeps whole pairs and
``rope_interleaved`` turns them, as for Kanana-2's rotary leaves.  ``ffn`` /
``expert`` prefixes of the two feed-forward widths; the expert axis (one leaf
per expert), the router's columns, its selection bias and the vocabulary are
never sliced; the tied leaf ``tok.w`` (embedding rows = head columns) carries
the label axis on its rows for both of its uses.  Softmax scale ``1/sqrt(active head dims)``; a Scaler after every
sliced linear except the router and the head (categorical outputs), none
after the depthwise taps (a channel reads only itself) or the look-up.

Consecutive layers of one kind (mixer and feed-forward alike) run as one
``lax.scan`` over their stacked leaves, a lone layer as itself; each layer
under ``jax.checkpoint``.
"""

from __future__ import annotations

from functools import partial
from typing import Dict

import jax
import jax.numpy as jnp

from ..obs.trace import scope
from ..ops.layers import (linear as _linear, masked_rms_norm, moe_experts, moe_route,
                          short_conv, swiglu)
from .base import ModelDef
from .decoder import (Leaves, alike_runs, decoder, expert_tile, gq_attention, held_experts,
                      layer_leaves, moe_counters, run_layers)
from .spec import Group

#: what ``lfm2_moe`` adds to the sum of the chosen scores before dividing
ROUTE_SUM_EPS = 1e-6


def conv_mixer(lp, h, *, sc, compute_dtype=None):
    """A layer's gated short convolution on the normed ``h`` ``[N, S, D]``."""
    linear = partial(_linear, compute_dtype=compute_dtype)
    with scope("shortconv"):
        b, c, u = (sc(linear(h, lp[f"conv.in.{m}.w"])) for m in "bcu")
        return sc(linear(short_conv(b, c, u, lp["conv.taps.w"]), lp["conv.out.w"]))


def make_lfm2(num_tokens: int, arch: Dict, model_rate: float = 1.0, *,
              mask: bool = True, compute_dtype=None) -> ModelDef:
    """``arch``: ``cfg['lfm2']`` (config.process_control) at the GLOBAL widths;
    ``model_rate`` builds the dense sub-model a client at that rate holds
    (the sliced strategy and the equivalence tests)."""
    leaves = Leaves(model_rate)
    cw, add, add_ffn = leaves.cw, leaves.add, leaves.add_ffn
    D, Dc = cw(arch["hidden_size"]), cw(arch["conv_dim"])
    L, L_dense = int(arch["num_hidden_layers"]), int(arch["num_dense_layers"])
    kinds = list(arch["layer_types"])
    F, Fe = cw(arch["intermediate_size"]), cw(arch["moe_intermediate_size"])
    E, K = int(arch["num_experts"]), int(arch["num_experts_per_tok"])
    H, Hkv = int(arch["num_attention_heads"]), int(arch["num_key_value_heads"])
    hd, taps = cw(arch["head_dim"], 2), int(arch["conv_L_cache"])
    # staticcheck: allow(no-float-coercion): build-time config scalars
    theta, eps = float(arch["rope_theta"]), float(arch["norm_eps"])
    # staticcheck: allow(no-float-coercion): build-time config scalar
    scaling = float(arch["routed_scaling_factor"])
    held = held_experts(arch["expert_share"], E)
    if len(kinds) != L or set(kinds) - {"conv", "full_attention"}:
        raise ValueError(f"Not valid layer_types: {kinds!r} (one of 'conv' | "
                         f"'full_attention' for each of the {L} layers)")
    if H % Hkv:
        raise ValueError(f"{H} query heads do not divide over {Hkv} key/value heads")

    def heads(name, n):
        return Group(name, n * hd, kind="per_head", num_heads=n, multiple=2,
                     coupled=False, family="head")

    groups = {
        "emb": Group("emb", D),
        "conv": Group("conv", Dc),
        "q_head": heads("q_head", H),
        "kv_head": heads("kv_head", Hkv),
        "head": heads("head", 1),
        "ffn": Group("ffn", F),
        "expert": Group("expert", Fe),
        "router": Group("router", E, kind="full"),
    }
    leaves.stem(num_tokens, D, tied=True)
    for i, kind in enumerate(kinds):
        p = f"l{i}"
        add(f"{p}.norm1.g", (D,), {0: "emb"})
        if kind == "conv":
            for m in "bcu":
                add(f"{p}.conv.in.{m}.w", (D, Dc), {0: "emb", 1: "conv"})
            add(f"{p}.conv.taps.w", (taps, Dc), {1: "conv"})
            add(f"{p}.conv.out.w", (Dc, D), {0: "conv", 1: "emb"})
        else:
            leaves.add_gq_attention(p, H, Hkv, hd)
        add(f"{p}.norm2.g", (D,), {0: "emb"})
        if i < L_dense:
            add_ffn(f"{p}.mlp", F, "ffn")
        else:
            add(f"{p}.moe.router.w", (D, E), {0: "emb", 1: "router"})
            add(f"{p}.moe.router.b", (E,), {0: "router"})
            for j in held:
                add_ffn(f"{p}.moe.e{j}", Fe, "expert")

    def body(c, params):
        N, S, T, sc, rms = c.N, c.S, c.T, c.sc, c.rms
        head_act, head_mask = c.count["head"], c.mask["head"]
        mixers = {
            "conv": partial(conv_mixer, sc=sc, compute_dtype=compute_dtype),
            "full_attention": partial(
                gq_attention, heads=H, kv_heads=Hkv, head_dim=int(arch["head_dim"]), theta=theta,
                scale=1.0 / jnp.sqrt(head_act), sc=sc, compute_dtype=compute_dtype,
                head_norm=lambda x, g: masked_rms_norm(x, g, head_mask, head_act, eps)),
        }

        tile = expert_tile(T, K, E)
        zero_counters = c.zeros() if L > L_dense else None

        def layer_of(i):
            """Layer ``i``'s kind as ``(x, leaves) -> (x, counters)``
            (``decoder.run_layers``).  It keeps only its input for the
            backward."""
            kind, dense = kinds[i], i < L_dense

            @jax.checkpoint
            def layer(x, lp):
                x = x + mixers[kind](lp, rms(lp["norm1.g"], x))
                h = rms(lp["norm2.g"], x)
                if dense:
                    return x + swiglu(h, lp["mlp.g.w"], lp["mlp.u.w"], lp["mlp.d.w"],
                                      sc, compute_dtype), zero_counters
                hf = h.reshape(T, D)
                sel, w = moe_route(hf, lp["moe.router.w"], lp["moe.router.b"], K, scaling,
                                   ROUTE_SUM_EPS)
                y, counters = moe_experts(hf, sel, w, [lp[f"moe.e.{m}.w"] for m in "gud"],
                                          held[0], sc, compute_dtype, tile=tile)
                return x + y.reshape(N, S, D), counters
            return layer

        # alike in mixer and feed-forward
        runs = alike_runs(L, lambda i: (kinds[i], i < L_dense),
                          lambda i: layer_leaves(params, i, held if i >= L_dense else None),
                          layer_of)
        return c.finish(*run_layers(c.embed(), runs, zero_counters))

    return decoder(
        "lfm2", num_tokens, arch, leaves, groups, body, eps=eps, mask=mask,
        compute_dtype=compute_dtype, counts=("head",), masks=("head",), held=held,
        # summed over the expert layers
        counters=moe_counters(held) if L > L_dense else None,
        profile={"routed_share": K / E,
                 "attention": {f"l{i}.attn": (H, hd, hd) for i, kind in enumerate(kinds)
                               if kind == "full_attention"}})
