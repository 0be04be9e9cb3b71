"""Keye-VL-2.0-30B-A3B's language model (``model_type: KeyeVL2``) with
HeteroFL width scaling.

The published block (huggingface.co/Kwai-Keye/Keye-VL-2.0-30B-A3B
``config.json``): pre-norm decoder layers, all alike, of GROUPED-QUERY
ATTENTION (``H`` query heads on ``Hkv`` key/value heads, RMSNorm on every
query and key head, half-split RoPE over the whole head) whose softmax runs
over the keys a LEARNED INDEXER chooses for each query (``sa_config``: ``Hi``
small heads against ONE shared key head, ``topk`` keys a query, as
DeepSeek-V3.2's sparse attention), and ``num_experts`` SwiGLU experts
(softmax router over all of them, top-k, renormalised, NO shared expert and
no selection bias); RMSNorm, no biases, no dropout, untied embedding and
head; next-token loss.  ``x`` is ``[S, D]`` a row, ``rms(x, g) = x /
sqrt(mean(x^2) + eps) * g``:

  h  = rms(x, g1)
  indexer (no gradient enters or leaves it):
      qI = rope(h Wq_I) -> [S, Hi, di];  kI = rope(layernorm(h Wk_I)) -> [S, di]
      wI = (h Ww_I) * Hi^-1/2 * di^-1/2 -> [S, Hi]
      I[t, s] = sum_j wI[t, j] * relu(qI[t, j] . kI[s])       for s <= t
      S_t = the min(t + 1, topk) keys of largest I[t, :]      (ties to the lower position)
  attention:  q = rope(rms_head(h Wq)), k = rope(rms_head(h Wk)), v = h Wv
      o[t, a] = sum_{s in S_t} softmax_{s in S_t}(q[t, a] . k[s, g(a)] / sqrt(d)) v[s, g(a)]
      x = x + concat_a(o[t, a]) Wo
  experts:  y = rms(x, g2);  p = softmax(y Wr);  sel = top_k(p);  w = p[sel] / sum(p[sel])
      x = x + sum_{e in sel, held} w_e (silu(y Wg_e) * (y Wu_e)) Wd_e
  logits = rms(x, g_f) W_head

On text the three position streams of the published ``mrope_section`` are
equal and the turn is half-split RoPE, which is what is built; the vision
tower is not part of this model.  A row no longer than ``topk`` selects every
causal key: the model then takes ``causal_gq_attention`` and runs no indexer
(a static shape test, the same mathematics).

The expert layer is told what it holds (``expert_share = (index, of)``, as
``kanana2``); with ``of == 1`` the model is the published one.

HeteroFL slicing (the paper defines none for this family; stated in the
benchmark configuration's ``assumed``): ``emb`` prefix of the hidden size;
per-head prefixes in whole rotary pairs of the ``d`` dims of the query heads,
the key/value heads and the two head norms' gains (one ``family`` of groups,
``head``), and of the ``di`` dims of the indexer's query heads, its key head
and that head's LayerNorm (a second family, ``index``); as in ``lfm2`` the
rotary leaves are STORED with each head's pairs adjacent (stored ``2i`` =
published ``i``, stored ``2i + 1`` = published ``i + d/2``) and turned by
``rope_interleaved``; ``expert`` prefix of an expert's width; never sliced:
the expert axis, the router's columns, the indexer's ``Hi`` per-head weights,
``topk``, the vocabulary.  Softmax scale ``1/sqrt(active head dims)``; a
Scaler after every sliced linear except the router, the head and the
indexer's three (their outputs are read by a top-k only, which a positive
factor does not move).  The indexer's leaves get no gradient by construction
(the pre-training term that trains a published indexer is not in
``config.json``) and are sliced, carried, decayed and aggregated like every
leaf.

The layers are alike, so the whole depth is one ``lax.scan`` over their
stacked leaves, each layer under ``jax.checkpoint`` with a save-by-name policy
(:func:`kept`): for its backward a layer keeps its input, the indexer's 0/1
choice (frozen and discrete: a second pass could only repeat it) and, where
the fused kernels run, their forward's output and log-sum-exp, which are the
backward kernel's residuals.  Everything else it recomputes.
"""

from __future__ import annotations

from functools import partial
from typing import Dict

import jax
import jax.numpy as jnp
from jax import lax
from jax.ad_checkpoint import checkpoint_name

from ..obs.trace import scope
from ..ops.layers import (causal_gq_attention, masked_layer_norm, masked_rms_norm, moe_experts,
                          moe_route, rope_interleaved, rope_swap, select_keys,
                          selected_attention_tile, selected_gq_attention)
from .base import ModelDef
from .decoder import (Leaves, alike_runs, decoder, expert_tile, gq_attention, held_experts,
                      layer_leaves, moe_counters, run_layers)
from .spec import Group

#: query rows a block of the selection and of the selected attention takes,
#: where ``topk`` is not smaller: the published kernel's ``q_chunk_size``
#: (memory, not mathematics; the blocks before ``topk`` then carry no mask)
QUERY_BLOCK = 512

#: the name an indexer's 0/1 blocks carry (``checkpoint_name``; :func:`kept`)
CHOICE = "sparse_choice"


def kept():
    """What a layer's ``jax.checkpoint`` keeps for the backward beside its
    input: the values that carry one of three names, the indexer's choice
    (booleans, a bit of a layer's activations) and the selected-attention
    kernel's two results.  Where nothing carries a name (a row no longer than
    ``topk`` runs no indexer, the block loop no kernel) that is the input
    alone."""
    from ..ops.pallas_attention import SEL_LSE, SEL_OUT  # Pallas, imported where a Keye model is built

    return jax.checkpoint_policies.save_only_these_names(CHOICE, SEL_OUT, SEL_LSE)


def index_keys(lp, h, *, heads: int, head_dim: int, theta: float, topk: int, block: int,
               key_norm):
    """A layer's indexer on the normed ``h`` ``[N, S, D]``: the per-block
    choice of keys and its counts (``ops.layers.select_keys``).  Float32 at
    "highest" matmul precision throughout, as a router's product is: the
    scores decide a discrete set.  ``key_norm(x, g, b)`` the LayerNorm over
    the key head's dims, ``head_dim`` the GLOBAL model's."""
    h = lax.stop_gradient(h.astype(jnp.float32))
    w = {k: lax.stop_gradient(lp[f"idx.{k}"].astype(jnp.float32))
         for k in ("q.w", "k.w", "w.w", "k_norm.g", "k_norm.b")}
    pos = jnp.arange(h.shape[1])
    highest = lax.Precision.HIGHEST
    with scope("sparse/index"):
        qi = jnp.einsum("nsk,khd->nhsd", h, w["q.w"].reshape(h.shape[-1], heads, -1),
                        precision=highest)
        ki = key_norm(jnp.einsum("nsk,kd->nsd", h, w["k.w"], precision=highest),
                      w["k_norm.g"], w["k_norm.b"])
        wi = jnp.einsum("nsk,kh->nhs", h, w["w.w"], precision=highest) \
            * (heads ** -0.5 * head_dim ** -0.5)
        qi = rope_interleaved(qi, rope_swap(qi), pos, theta, axis=2, full=head_dim)
        ki = rope_interleaved(ki, rope_swap(ki), pos, theta, axis=1, full=head_dim)
    return select_keys(qi, ki, wi, topk, block)


def make_keye(num_tokens: int, arch: Dict, model_rate: float = 1.0, *,
              mask: bool = True, compute_dtype=None) -> ModelDef:
    """``arch``: ``cfg['keye']`` (config.process_control) at the GLOBAL widths;
    ``model_rate`` builds the dense sub-model a client at that rate holds
    (the sliced strategy and the equivalence tests)."""
    leaves = Leaves(model_rate)
    cw, add, add_ffn = leaves.cw, leaves.add, leaves.add_ffn
    D, Fe = cw(arch["hidden_size"]), cw(arch["moe_intermediate_size"])
    L = int(arch["num_hidden_layers"])
    E, K = int(arch["num_experts"]), int(arch["num_experts_per_tok"])
    H, Hkv = int(arch["num_attention_heads"]), int(arch["num_key_value_heads"])
    Hi, topk = int(arch["index_n_heads"]), int(arch["index_topk"])
    hd, di = cw(arch["head_dim"], 2), cw(arch["index_head_dim"], 2)
    # staticcheck: allow(no-float-coercion): build-time config scalars
    theta, eps = float(arch["rope_theta"]), float(arch["rms_norm_eps"])
    held = held_experts(arch["expert_share"], E)
    if H % Hkv:
        raise ValueError(f"{H} query heads do not divide over {Hkv} key/value heads")

    def heads(name, n, width, family):
        return Group(name, n * width, kind="per_head", num_heads=n, multiple=2,
                     coupled=False, family=family)

    groups = {
        "emb": Group("emb", D),
        "q_head": heads("q_head", H, hd, "head"),
        "kv_head": heads("kv_head", Hkv, hd, "head"),
        "head": heads("head", 1, hd, "head"),
        "iq_head": heads("iq_head", Hi, di, "index"),
        "ik_head": heads("ik_head", 1, di, "index"),
        "index": Group("index", Hi, kind="full"),
        "expert": Group("expert", Fe),
        "router": Group("router", E, kind="full"),
    }
    leaves.stem(num_tokens, D)
    for i in range(L):
        p = f"l{i}"
        add(f"{p}.norm1.g", (D,), {0: "emb"})
        leaves.add_gq_attention(p, H, Hkv, hd)
        add(f"{p}.idx.q.w", (D, Hi * di), {0: "emb", 1: "iq_head"})
        add(f"{p}.idx.k.w", (D, di), {0: "emb", 1: "ik_head"})
        add(f"{p}.idx.k_norm.g", (di,), {0: "ik_head"})
        add(f"{p}.idx.k_norm.b", (di,), {0: "ik_head"})
        add(f"{p}.idx.w.w", (D, Hi), {0: "emb", 1: "index"})
        add(f"{p}.norm2.g", (D,), {0: "emb"})
        add(f"{p}.moe.router.w", (D, E), {0: "emb", 1: "router"})
        for j in held:
            add_ffn(f"{p}.moe.e{j}", Fe, "expert")

    def body(c, params):
        N, S, T, sc, rms, act, masks = c.N, c.S, c.T, c.sc, c.rms, c.count, c.mask
        tile, block = expert_tile(T, K, E), min(QUERY_BLOCK, topk)
        attention = partial(
            gq_attention, heads=H, kv_heads=Hkv, head_dim=int(arch["head_dim"]), theta=theta,
            scale=1.0 / jnp.sqrt(act["head"]), sc=sc, compute_dtype=compute_dtype,
            head_norm=lambda x, g: masked_rms_norm(x, g, masks["head"], act["head"], eps))
        indexer = partial(
            index_keys, heads=Hi, head_dim=int(arch["index_head_dim"]), theta=theta, topk=topk,
            block=block,
            key_norm=lambda x, g, b: masked_layer_norm(x, g, b, masks["ik_head"],
                                                       act["ik_head"], eps))
        # floats, so that they ride the metrics beside the experts' counters
        all_pairs = jnp.full((2,), N * (S * (S + 1) // 2), jnp.float32)
        # query tiles of the selected attention, and those the fused kernels took
        tiles = N * -(-S // block) if S > topk else 0
        fused = tiles if selected_attention_tile(S, hd, H // Hkv) is not None else 0
        # query blocks that end after topk: those whose keys the indexer chooses
        selecting = N * sum(min(s + block, S) > topk for s in range(0, S, block))

        @partial(jax.checkpoint, policy=kept())
        def layer(x, lp):
            """``(x, leaves) -> (x, counters)``, the scan's body; for the
            backward it keeps its input and what :func:`kept` names."""
            h = rms(lp["norm1.g"], x)
            saved = 0
            if S > topk:
                select, pairs = indexer(lp, h)
                select = [m if m is None else checkpoint_name(m, CHOICE) for m in select]
                saved = N * sum(m is not None for m in select)
                x = x + attention(lp, h, attend=partial(selected_gq_attention, select=select,
                                                        block=block))
            else:  # every causal key is among the topk: no choice to make
                pairs = all_pairs
                x = x + attention(lp, h, attend=causal_gq_attention)
            hf = rms(lp["norm2.g"], x).reshape(T, D)
            sel, w = moe_route(hf, lp["moe.router.w"], None, K, 1.0, softmax=True)
            y, counters = moe_experts(hf, sel, w, [lp[f"moe.e.{m}.w"] for m in "gud"],
                                      held[0], sc, compute_dtype, tile=tile)
            counters["selected"] = jnp.stack([pairs[0], jnp.float32(T)])
            counters["kept_share"] = pairs
            counters["fused"] = jnp.stack([jnp.float32(fused), jnp.float32(tiles)])
            counters["saved"] = jnp.stack([jnp.float32(saved), jnp.float32(selecting)])
            return x + y.reshape(N, S, D), counters

        # the layers are alike: the whole depth is one run
        runs = alike_runs(L, lambda i: 0, lambda i: layer_leaves(params, i, held), lambda i: layer)
        return c.finish(*run_layers(c.embed(), runs))

    return decoder(
        "keye", num_tokens, arch, leaves, groups, body, eps=eps, mask=mask,
        compute_dtype=compute_dtype, counts=("head", "ik_head"), masks=("head", "ik_head"),
        held=held,
        # summed over the layers.  The four sparse ones finish as selected keys
        # a query, selected over causal pairs, the share of the selected
        # attention's query tiles that the fused kernels took, and the share of
        # the selecting query blocks whose choice the layer kept for its backward
        counters={**moe_counters(held),
                  "selected": ("sparse_selected", (2,), "ratio"),
                  "kept_share": ("sparse_kept_share", (2,), "ratio"),
                  "fused": ("sparse_fused", (2,), "ratio"),
                  "saved": ("sparse_saved", (2,), "ratio")},
        # (the attention's two products at every causal pair: an upper bound
        # where the indexer selects)
        profile={"routed_share": K / E,
                 "attention": {f"l{i}.attn": (H, hd, hd) for i in range(L)}})
