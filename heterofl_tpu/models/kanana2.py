"""Kanana-2-30B-A3B (``model_type: deepseek_v3``) with HeteroFL width scaling.

The published block (huggingface.co/kakaocorp/kanana-2-30b-a3b-instruct-2601
``config.json``): pre-norm decoder layers of multi-head LATENT attention
(no query bottleneck; keys and values up-projected from a 512-wide latent
with its own RMSNorm; 128 no-position + 64 rotary query/key dims a head, one
rotary key head shared by all heads, interleaved RoPE) and a feed-forward
that is a dense SwiGLU in the first ``first_k_dense_replace`` layers and, in
the rest, ``n_routed_experts`` SwiGLU experts (sigmoid router, top-k,
``noaux_tc`` selection bias, normalised and scaled weights) plus one SwiGLU
of ``n_shared_experts * moe_intermediate_size`` that every token takes;
RMSNorm, no biases, no dropout, untied embedding and head; next-token loss.
``x`` is ``[T, D]``, ``rms(x, g) = x / sqrt(mean(x^2) + eps) * g``:

  h = rms(x, g1); q = h Wq -> [T, H, dn + dr]; c, k_r = h Wkv_a -> [512], [dr]
  c = rms(c, g_kv); [k_n | v] = c Wkv_b -> [T, H, dn + dv]
  q_r, k_r = rope(., pos); scores = (q_n k_n^T + q_r k_r^T) / sqrt(dn + dr)
  x = x + concat_heads(softmax_causal(scores) v) Wo
  h = rms(x, g2); dense: x = x + (silu(h Wg) * (h Wu)) Wd
  experts: s = sigmoid(h Wr); sel = top_k(s + b); w = s[sel] / sum * scale
           x = x + sum_{e in sel, held} w_e expert_e(h) + shared(h)
  logits = rms(x, g_f) W_head

The expert layer is told what it holds (``expert_share = (index, of)``:
experts ``[index * n/of, (index + 1) * n/of)``); it routes over all ``n`` and
computes its own experts' part (``ops.layers.moe_experts``).  With
``of == 1`` the model is the published one.

HeteroFL slicing (the paper defines none for this family; stated in the
benchmark configuration's ``assumed``): ``emb`` prefix of the hidden size
(embedding columns, every norm gain, every matrix's model-side axis, router
rows); per-head prefixes of the no-position, rotary (whole pairs) and value
dims, so the published ``q_proj`` / ``kv_a_proj_with_mqa`` / ``kv_b_proj``
are held as column-split leaves (``q.n`` | ``q.r``, ``kv_a.c`` | ``kv_a.r``,
``kv_b.k`` | ``kv_b.v``: a column permutation of the published matrices);
``kv_lora`` prefix of the latent with its masked norm; ``ffn`` / ``shared`` /
``expert`` prefixes of the three feed-forward widths; the expert axis (one
leaf per expert), the router's columns, its selection bias and the
vocabulary are never sliced; embedding rows and head columns carry the label
axis.  Softmax scale ``1/sqrt(active dn + active dr)``; a Scaler after every
sliced linear except the router and the head (categorical outputs); none on
the embedding look-up.

Every expert is its own three leaves (``l{i}.moe.e{j}.{g,u,d}.w``), so a
fan-in initialiser (``init`` below, ``benchmark/weights.py``) sees each
expert's true fan-in.  ``apply`` stacks a layer's held experts for
``moe_experts`` and the expert layers, which are alike, for one ``lax.scan``
over them: the program holds one expert layer's code whatever the depth.
"""

from __future__ import annotations

from functools import partial
from typing import Dict

import jax
import jax.numpy as jnp

from ..obs.trace import scope
from ..ops.layers import (causal_latent_attention, heads_linear, linear as _linear,
                          linear_heads, masked_rms_norm, moe_experts, moe_route,
                          rope_interleaved, rope_swap, swiglu)
from .base import ModelDef
from .decoder import (Leaves, alike_runs, decoder, held_experts, layer_leaves, moe_counters,
                      run_layers)
from .spec import Group


def latent_attention_shapes(D: int, H: int, dn: int, dr: int, dv: int, R: int) -> Dict[str, tuple]:
    """The leaves :func:`latent_attention` reads, a layer's ``attn.*``: hidden
    size ``D``, ``H`` heads of ``dn`` no-position, ``dr`` rotary and ``dv``
    value dims, latent width ``R``."""
    return {"attn.q.n.w": (D, H * dn), "attn.q.r.w": (D, H * dr),
            "attn.kv_a.c.w": (D, R), "attn.kv_a.r.w": (D, dr), "attn.kv_norm.g": (R,),
            "attn.kv_b.k.w": (R, H * dn), "attn.kv_b.v.w": (R, H * dv), "attn.o.w": (H * dv, D)}


def latent_attention(lp, h, *, heads: int, theta: float, scale, sc, kv_norm,
                     compute_dtype=None, rope_dim=None):
    """A layer's latent attention on the normed ``h`` ``[N, S, D]``; ``lp`` the
    layer's leaves, ``sc`` the Scaler, ``kv_norm(c, g)`` the latent's norm,
    ``rope_dim`` the GLOBAL model's rotary width (None: the leaves' own; a
    sliced sub-model's pairs keep the global frequencies).

    Heads first from end to end: the per-head projections write ``[N, H, S,
    d]`` (``linear_heads`` on the stored ``[K, H * d]`` leaves), the rotary
    turn, the attention and the output projection read it, so no activation
    is transposed or sliced between ``mla`` and the attention kernels,
    forward or backward.  The one rotary key head ``kr`` stays ``[N, S, dr]``."""
    linear = partial(_linear, compute_dtype=compute_dtype)
    per_head = partial(linear_heads, heads=heads, compute_dtype=compute_dtype)
    pos = jnp.arange(h.shape[1])
    with scope("mla"):
        qn = sc(per_head(h, lp["attn.q.n.w"]))
        # the rotary query and its pair swap: the products of the weight and
        # of the swapped weight (ops.layers.rope_swap says why)
        qr = sc(per_head(h, lp["attn.q.r.w"]))
        qr_swapped = sc(per_head(h, rope_swap(lp["attn.q.r.w"])))
        c = sc(linear(h, lp["attn.kv_a.c.w"]))
        kr = sc(linear(h, lp["attn.kv_a.r.w"]))
        c = kv_norm(c, lp["attn.kv_norm.g"])
        kn = sc(per_head(c, lp["attn.kv_b.k.w"]))
        v = sc(per_head(c, lp["attn.kv_b.v.w"]))
    qr = rope_interleaved(qr, qr_swapped, pos, theta, axis=2, full=rope_dim)
    # one key head: its swap is cheap where it is, a second product reads ``h`` again
    kr = rope_interleaved(kr, rope_swap(kr), pos, theta, full=rope_dim)
    if compute_dtype is not None:
        qn, qr, kn, kr, v = (t.astype(compute_dtype) for t in (qn, qr, kn, kr, v))
    o = causal_latent_attention(qn, qr, kn, kr, v, scale)
    with scope("mla"):
        return sc(heads_linear(o.astype(jnp.float32), lp["attn.o.w"], compute_dtype))


def make_kanana2(num_tokens: int, arch: Dict, model_rate: float = 1.0, *,
                 mask: bool = True, compute_dtype=None) -> ModelDef:
    """``arch``: ``cfg['kanana2']`` (config.process_control) at the GLOBAL widths;
    ``model_rate`` builds the dense sub-model a client at that rate holds
    (the sliced strategy and the equivalence tests)."""
    leaves = Leaves(model_rate)
    cw, add, add_ffn = leaves.cw, leaves.add, leaves.add_ffn
    D = cw(arch["hidden_size"])
    L, L_dense = int(arch["num_hidden_layers"]), int(arch["first_k_dense_replace"])
    F, Fe = cw(arch["intermediate_size"]), cw(arch["moe_intermediate_size"])
    Fs = cw(arch["moe_intermediate_size"] * arch["n_shared_experts"])
    E, K = int(arch["n_routed_experts"]), int(arch["num_experts_per_tok"])
    H = int(arch["num_attention_heads"])
    dn, dv = cw(arch["qk_nope_head_dim"]), cw(arch["v_head_dim"])
    dr = cw(arch["qk_rope_head_dim"], 2)
    R = cw(arch["kv_lora_rank"])
    # staticcheck: allow(no-float-coercion): build-time config scalars
    theta, eps = float(arch["rope_theta"]), float(arch["rms_norm_eps"])
    # staticcheck: allow(no-float-coercion): build-time config scalar
    scaling = float(arch["routed_scaling_factor"])
    held = held_experts(arch["expert_share"], E)
    if dr % 2:
        raise ValueError(f"rotary width {dr} is not whole pairs")

    groups = {
        "emb": Group("emb", D),
        "q_nope": Group("q_nope", H * dn, kind="per_head", num_heads=H, coupled=False),
        "q_rope": Group("q_rope", H * dr, kind="per_head", num_heads=H,
                        multiple=2, coupled=False),
        "k_rope": Group("k_rope", dr, kind="per_head", num_heads=1,
                        multiple=2, coupled=False),
        "v_head": Group("v_head", H * dv, kind="per_head", num_heads=H, coupled=False),
        "kv_lora": Group("kv_lora", R),
        "ffn": Group("ffn", F),
        "shared": Group("shared", Fs),
        "expert": Group("expert", Fe),
        "router": Group("router", E, kind="full"),
        "vocab": Group("vocab", num_tokens, kind="full"),
    }

    leaves.stem(num_tokens, D)
    attn_groups = {
        "attn.q.n.w": {0: "emb", 1: "q_nope"}, "attn.q.r.w": {0: "emb", 1: "q_rope"},
        "attn.kv_a.c.w": {0: "emb", 1: "kv_lora"}, "attn.kv_a.r.w": {0: "emb", 1: "k_rope"},
        "attn.kv_norm.g": {0: "kv_lora"},
        "attn.kv_b.k.w": {0: "kv_lora", 1: "q_nope"}, "attn.kv_b.v.w": {0: "kv_lora", 1: "v_head"},
        "attn.o.w": {0: "v_head", 1: "emb"}}
    for i in range(L):
        p = f"l{i}"
        add(f"{p}.norm1.g", (D,), {0: "emb"})
        for name, shape in latent_attention_shapes(D, H, dn, dr, dv, R).items():
            add(f"{p}.{name}", shape, attn_groups[name])
        add(f"{p}.norm2.g", (D,), {0: "emb"})
        if i < L_dense:
            add_ffn(f"{p}.mlp", F, "ffn")
        else:
            add(f"{p}.moe.router.w", (D, E), {0: "emb", 1: "router"})
            add(f"{p}.moe.router.b", (E,), {0: "router"})
            add_ffn(f"{p}.moe.shared", Fs, "shared")
            for j in held:
                add_ffn(f"{p}.moe.e{j}", Fe, "expert")

    def body(c, params):
        N, S, T, sc, rms, act = c.N, c.S, c.T, c.sc, c.rms, c.count
        lora_mask = c.mask["kv_lora"]
        scale = 1.0 / jnp.sqrt((act["q_nope"] + act["q_rope"]) / H)

        attention = partial(
            latent_attention, heads=H, theta=theta, scale=scale, sc=sc,
            kv_norm=lambda c, g: masked_rms_norm(c, g, lora_mask, act["kv_lora"], eps),
            compute_dtype=compute_dtype, rope_dim=int(arch["qk_rope_head_dim"]))

        def ffn(lp, prefix, h):
            return swiglu(h, lp[f"{prefix}.g.w"], lp[f"{prefix}.u.w"], lp[f"{prefix}.d.w"],
                          sc, compute_dtype)

        # a layer keeps only its input for the backward: the model is sized
        # so that parameters, not activations, fill the chip
        @jax.checkpoint
        def dense_layer(x, lp):
            x = x + attention(lp, rms(lp["norm1.g"], x))
            return x + ffn(lp, "mlp", rms(lp["norm2.g"], x)), None

        @jax.checkpoint
        def expert_layer(x, lp):
            x = x + attention(lp, rms(lp["norm1.g"], x))
            hf = rms(lp["norm2.g"], x).reshape(T, D)
            sel, w = moe_route(hf, lp["moe.router.w"], lp["moe.router.b"], K, scaling)
            y, counters = moe_experts(hf, sel, w, [lp[f"moe.e.{m}.w"] for m in "gud"],
                                      held[0], sc, compute_dtype)
            with scope("moe/shared"):
                y = y + ffn(lp, "moe.shared", hf)
            return x + y.reshape(N, S, D), counters

        # the expert layers are alike (and the dense ones): one scan over
        # their stacked leaves, so the program holds one layer's code whatever
        # the depth
        runs = alike_runs(L, lambda i: i < L_dense,
                          lambda i: layer_leaves(params, i, held if i >= L_dense else None),
                          lambda i: dense_layer if i < L_dense else expert_layer)
        return c.finish(*run_layers(c.embed(), runs))

    return decoder(
        "kanana2", num_tokens, arch, leaves, groups, body, eps=eps, mask=mask,
        compute_dtype=compute_dtype, counts=("kv_lora", "q_nope", "q_rope"), masks=("kv_lora",),
        # summed over the expert layers
        held=held, counters=moe_counters(held) if L > L_dense else None,
        profile={"routed_share": K / E,
                 "attention": {f"l{i}.attn": (H, dn + dr, dv) for i in range(L)}})
