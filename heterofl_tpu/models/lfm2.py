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
from itertools import groupby
from typing import Dict

import jax
import jax.numpy as jnp

from ..obs.trace import scope
from ..ops.layers import (causal_gq_attention, embed, heads_linear,
                          linear as _linear, linear_heads, masked_logits, masked_rms_norm,
                          moe_experts, moe_route, next_token_loss, rope_interleaved,
                          rope_swap, scaler, short_conv, swiglu)
from .base import ModelDef, expert_tile, held_experts, layer_leaves, normal_init, uniform_fan_in
from .spec import Group, ParamSpec

#: what ``lfm2_moe`` adds to the sum of the chosen scores before dividing
ROUTE_SUM_EPS = 1e-6


def conv_mixer(lp, h, *, sc, compute_dtype=None):
    """A layer's gated short convolution on the normed ``h`` ``[N, S, D]``."""
    linear = partial(_linear, compute_dtype=compute_dtype)
    with scope("shortconv"):
        b, c, u = (sc(linear(h, lp[f"conv.in.{m}.w"])) for m in "bcu")
        return sc(linear(short_conv(b, c, u, lp["conv.taps.w"]), lp["conv.out.w"]))


def gq_attention(lp, h, *, heads: int, kv_heads: int, head_dim: int, theta: float, scale,
                 sc, head_norm=None, compute_dtype=None, attend=causal_gq_attention):
    """A layer's grouped-query attention on the normed ``h`` ``[N, S, D]``,
    heads first from the projections to the output projection; ``head_norm(x,
    g)`` the RMSNorm over each head's dims (None: the family has none, and
    the layer no ``attn.q_norm.g`` / ``attn.k_norm.g``: models/ouro.py),
    ``head_dim`` the GLOBAL model's (the rotary frequencies' denominator at
    every width), ``attend(q, k, v, scale)``."""
    q_heads = partial(linear_heads, heads=heads, compute_dtype=compute_dtype)
    kv = partial(linear_heads, heads=kv_heads, compute_dtype=compute_dtype)
    pos = jnp.arange(h.shape[1])
    with scope("gqa"):
        # (each norm straight after its product, the order the LFM2 and Keye
        # programs were traced and measured in)
        q = sc(q_heads(h, lp["attn.q.w"]))
        if head_norm is not None:
            q = head_norm(q, lp["attn.q_norm.g"])
        k = sc(kv(h, lp["attn.k.w"]))
        if head_norm is not None:
            k = head_norm(k, lp["attn.k_norm.g"])
        v = sc(kv(h, lp["attn.v.w"]))
    # the norm sits between the product and the turn, so the pair swap is
    # taken on the activations (kanana2 takes its rotary query's on the weight)
    q = rope_interleaved(q, rope_swap(q), pos, theta, axis=2, full=head_dim)
    k = rope_interleaved(k, rope_swap(k), pos, theta, axis=2, full=head_dim)
    if compute_dtype is not None:
        q, k, v = (t.astype(compute_dtype) for t in (q, k, v))
    o = attend(q, k, v, scale)
    with scope("gqa"):
        return sc(heads_linear(o.astype(jnp.float32), lp["attn.o.w"], compute_dtype))


def make_lfm2(num_tokens: int, arch: Dict, model_rate: float = 1.0, *,
              mask: bool = True, compute_dtype=None) -> ModelDef:
    """``arch``: ``cfg['lfm2']`` (config.process_control) at the GLOBAL widths;
    ``model_rate`` builds the dense sub-model a client at that rate holds
    (the sliced strategy and the equivalence tests)."""
    from ..config import ceil_width

    def cw(n, multiple=1):
        k = ceil_width(n, model_rate)
        return -(-k // multiple) * multiple

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

    # the tied leaf: looked up by row and multiplied as the head, one label
    # axis (its rows) for both uses.  Not named ``embedding.*``: normal(0, 1)
    # rows (the initialisers' rule for that name) read as a head give logits
    # of the hidden size's scale
    specs: Dict[str, ParamSpec] = {
        "tok.w": ParamSpec({1: "emb"}, label_axis=0),
        "norm.g": ParamSpec({0: "emb"}),
    }
    shapes: Dict[str, tuple] = {"tok.w": (num_tokens, D), "norm.g": (D,)}

    def add(name, shape, axis_groups):
        shapes[name] = shape
        specs[name] = ParamSpec(axis_groups)

    def add_ffn(prefix, width, group):
        add(f"{prefix}.g.w", (D, width), {0: "emb", 1: group})
        add(f"{prefix}.u.w", (D, width), {0: "emb", 1: group})
        add(f"{prefix}.d.w", (width, D), {0: group, 1: "emb"})

    for i, kind in enumerate(kinds):
        p = f"l{i}"
        add(f"{p}.norm1.g", (D,), {0: "emb"})
        if kind == "conv":
            for m in "bcu":
                add(f"{p}.conv.in.{m}.w", (D, Dc), {0: "emb", 1: "conv"})
            add(f"{p}.conv.taps.w", (taps, Dc), {1: "conv"})
            add(f"{p}.conv.out.w", (Dc, D), {0: "conv", 1: "emb"})
        else:
            add(f"{p}.attn.q.w", (D, H * hd), {0: "emb", 1: "q_head"})
            add(f"{p}.attn.k.w", (D, Hkv * hd), {0: "emb", 1: "kv_head"})
            add(f"{p}.attn.v.w", (D, Hkv * hd), {0: "emb", 1: "kv_head"})
            add(f"{p}.attn.q_norm.g", (hd,), {0: "head"})
            add(f"{p}.attn.k_norm.g", (hd,), {0: "head"})
            add(f"{p}.attn.o.w", (H * hd, D), {0: "q_head", 1: "emb"})
        add(f"{p}.norm2.g", (D,), {0: "emb"})
        if i < L_dense:
            add_ffn(f"{p}.mlp", F, "ffn")
        else:
            add(f"{p}.moe.router.w", (D, E), {0: "emb", 1: "router"})
            add(f"{p}.moe.router.b", (E,), {0: "router"})
            for j in held:
                add_ffn(f"{p}.moe.e{j}", Fe, "expert")

    def init(key: jax.Array) -> Dict[str, jnp.ndarray]:
        names = sorted(shapes)
        params = {}
        for name, k in zip(names, jax.random.split(key, len(names))):
            shape = shapes[name]
            if len(shape) == 1:  # norm gains 1; the selection bias 0
                params[name] = (jnp.ones if name.endswith(".g") else jnp.zeros)(shape)
            elif name == "tok.w":  # read as the head too: small, as a head's columns
                params[name] = normal_init(k, shape, 0.02)
            else:  # the taps [L, channels]: a channel's fan-in is its L taps
                params[name] = uniform_fan_in(k, shape, shape[0])
        return params

    linear = partial(_linear, compute_dtype=compute_dtype)

    def apply(params, batch, *, train: bool, width_rate=1.0, scaler_rate=1.0,
              label_mask=None, bn_mode: str = "batch", bn_state=None,
              sample_weight=None, rng=None, bn_axis=None, attn_override=None):
        if "pos_offset" in batch or attn_override is not None:
            raise ValueError("lfm2 has no sequence-sharded path (mesh "
                             "'data' axis must be 1)")
        labels = batch["label"]
        N, S = labels.shape
        T = N * S
        emb_act = groups["emb"].active_count(width_rate).astype(jnp.float32)
        head_act = groups["head"].active_count(width_rate).astype(jnp.float32)
        emb_mask, head_mask = groups["emb"].mask(width_rate), groups["head"].mask(width_rate)

        def sc(x):
            return scaler(x, scaler_rate, train)

        def rms(g, x):
            return masked_rms_norm(x, g, emb_mask, emb_act, eps)

        mixers = {
            "conv": partial(conv_mixer, sc=sc, compute_dtype=compute_dtype),
            "full_attention": partial(
                gq_attention, heads=H, kv_heads=Hkv, head_dim=int(arch["head_dim"]), theta=theta,
                scale=1.0 / jnp.sqrt(head_act), sc=sc, compute_dtype=compute_dtype,
                head_norm=lambda x, g: masked_rms_norm(x, g, head_mask, head_act, eps)),
        }

        tile = expert_tile(T, K, E)

        zero_counters = {"tokens": jnp.zeros((len(held),), jnp.float32),
                         "assign": jnp.zeros((3,), jnp.float32)}

        def layer_of(kind, dense):
            """One layer of a kind as ``(x, leaves) -> (x, counters)``: a
            ``lax.scan`` body, and a plain call for a lone layer.  It keeps
            only its input for the backward."""
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

        def leaves(i):
            return layer_leaves(params, i, held if i >= L_dense else None)

        counters = zero_counters
        x = embed(params["tok.w"], labels)
        for (kind, dense), run in groupby(range(L), key=lambda i: (kinds[i], i < L_dense)):
            run = [leaves(i) for i in run]
            if len(run) == 1:
                x, c = layer_of(kind, dense)(x, run[0])
            else:
                # alike layers: one scan over their stacked leaves, so the
                # program holds one layer's code however long the run
                x, c = jax.lax.scan(layer_of(kind, dense), x,
                                    {k: jnp.stack([lp[k] for lp in run]) for k in run[0]})
                c = jax.tree_util.tree_map(lambda v: jnp.sum(v, axis=0), c)
            counters = jax.tree_util.tree_map(jnp.add, counters, c)
        xn = rms(params["norm.g"], x)

        def head(x_):  # the tied head: the embedding's rows as columns
            return masked_logits(linear(x_, params["tok.w"].T), label_mask, mask)

        # the logits [N, S, V] a caller may read (training does not: then the
        # compiler drops them); the loss takes the head in blocks of positions
        res = {"score": head(xn), "loss": next_token_loss(xn, labels, head, sample_weight)}
        if L > L_dense:
            res["counters"] = {f"moe_{k}": v for k, v in counters.items()}
        return res, {}

    meta = {"bn_sizes": {}, "kind": "lfm2", "num_tokens": num_tokens,
            "arch": dict(arch), "held_experts": list(held), "shapes": dict(shapes),
            # what analysis.summary.module_table cannot read off the leaves
            "profile": {"routed_share": K / E, "tied_head": "tok.w",
                        "attention": {f"l{i}.attn": (H, hd, hd) for i, kind in enumerate(kinds)
                                      if kind == "full_attention"}}}
    if L > L_dense:
        # what apply's "counters" holds (summed over the expert layers); the
        # engines carry them as obs_ probes when telemetry is on
        meta["counters"] = {"moe_tokens": (len(held),), "moe_assign": (3,)}
    return ModelDef("lfm2", init, apply, specs, groups, [], meta)
