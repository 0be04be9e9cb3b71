"""Masked-LM Transformer encoder with HeteroFL width scaling.

Parity: ``src/models/transformer.py`` -- learned positional embedding over
``bptt`` positions (transformer.py:11-20), custom multi-head attention with
separate q/k/v/o projections each followed by a Scaler (transformer.py:54-85,
Scaler is unconditional here, unlike the vision models), post-norm encoder
layers with exact GELU (transformer.py:88-119), 2-layer decoder head
(transformer.py:122-133), Bernoulli(mask_rate) token corruption to an extra
``<mask>`` id = num_tokens applied in *every* forward incl. eval
(transformer.py:148-151), CE over all positions vs. uncorrupted labels.

Slicing rules mirror ``src/fed.py:104-156``: embeddings sliced on the
embedding (column) axis, q/k/v sliced *per head* (fed.py:124-131), decoder
output kept full-width and label-restricted at aggregation (fed.py:263-274 --
token-embedding rows likewise).  Scores are returned class-LAST ``[N, S, V]``
(the reference permutes to ``[N, V, S]`` for torch's CE layout).

Divergence: each encoder layer is initialised independently; torch's
``nn.TransformerEncoder`` deep-copies one layer so all reference layers start
identical (transformer.py:141-142) -- an artifact, not a feature.
"""

from __future__ import annotations

from functools import partial
from typing import Dict, Optional

import jax
import jax.numpy as jnp

from ..obs.trace import scope
from ..ops.layers import cross_entropy, embed, linear as _linear, masked_layer_norm, masked_logits, scaler
from .base import ModelDef, normal_init, uniform_fan_in
from .spec import Group, ParamSpec


def make_transformer(num_tokens: int, embedding_size: int, num_heads: int,
                     hidden_size: int, num_layers: int, dropout: float, bptt: int,
                     mask_rate: float, *, mask: bool = True, compute_dtype=None,
                     attn_impl=None, remat: bool = False) -> ModelDef:
    E, H, F = embedding_size, num_heads, hidden_size

    groups = {
        "emb": Group("emb", E),
        "qkv": Group("qkv", E, kind="per_head", num_heads=H),
        "ffn": Group("ffn", F),
        "vocab": Group("vocab", num_tokens, kind="full"),
    }

    specs: Dict[str, ParamSpec] = {
        "embedding.tok.w": ParamSpec({1: "emb"}, label_axis=0),
        "embedding.pos.w": ParamSpec({1: "emb"}),
        "embedding.norm.g": ParamSpec({0: "emb"}),
        "embedding.norm.b": ParamSpec({0: "emb"}),
        "dec.l1.w": ParamSpec({0: "emb", 1: "emb"}),
        "dec.l1.b": ParamSpec({0: "emb"}),
        "dec.norm.g": ParamSpec({0: "emb"}),
        "dec.norm.b": ParamSpec({0: "emb"}),
        "dec.l2.w": ParamSpec({0: "emb"}, label_axis=1),
        "dec.l2.b": ParamSpec({}, label_axis=0),
    }
    for i in range(num_layers):
        p = f"enc{i}"
        for h in ("q", "k", "v"):
            specs[f"{p}.mha.{h}.w"] = ParamSpec({0: "emb", 1: "qkv"})
            specs[f"{p}.mha.{h}.b"] = ParamSpec({0: "qkv"})
        specs[f"{p}.mha.o.w"] = ParamSpec({0: "qkv", 1: "emb"})
        specs[f"{p}.mha.o.b"] = ParamSpec({0: "emb"})
        for n in ("norm1", "norm2"):
            specs[f"{p}.{n}.g"] = ParamSpec({0: "emb"})
            specs[f"{p}.{n}.b"] = ParamSpec({0: "emb"})
        specs[f"{p}.ff.l1.w"] = ParamSpec({0: "emb", 1: "ffn"})
        specs[f"{p}.ff.l1.b"] = ParamSpec({0: "ffn"})
        specs[f"{p}.ff.l2.w"] = ParamSpec({0: "ffn", 1: "emb"})
        specs[f"{p}.ff.l2.b"] = ParamSpec({0: "emb"})

    def init(key: jax.Array) -> Dict[str, jnp.ndarray]:
        params: Dict[str, jnp.ndarray] = {}
        keys = iter(jax.random.split(key, 4 + 6 * num_layers + 2))
        params["embedding.tok.w"] = normal_init(next(keys), (num_tokens + 1, E), 1.0)
        params["embedding.pos.w"] = normal_init(next(keys), (bptt, E), 1.0)
        params["embedding.norm.g"] = jnp.ones(E); params["embedding.norm.b"] = jnp.zeros(E)
        for i in range(num_layers):
            p = f"enc{i}"
            for h in ("q", "k", "v", "o"):
                params[f"{p}.mha.{h}.w"] = uniform_fan_in(next(keys), (E, E), E)
                params[f"{p}.mha.{h}.b"] = jnp.zeros(E)  # ref models/utils.py:8
            params[f"{p}.ff.l1.w"] = normal_init(next(keys), (E, F), 0.02)  # ref transformer.py:104
            params[f"{p}.ff.l1.b"] = jnp.zeros(F)
            params[f"{p}.ff.l2.w"] = normal_init(next(keys), (F, E), 0.02)
            params[f"{p}.ff.l2.b"] = jnp.zeros(E)
            for n in ("norm1", "norm2"):
                params[f"{p}.{n}.g"] = jnp.ones(E); params[f"{p}.{n}.b"] = jnp.zeros(E)
        params["dec.l1.w"] = uniform_fan_in(next(keys), (E, E), E)
        params["dec.l1.b"] = jnp.zeros(E)
        params["dec.norm.g"] = jnp.ones(E); params["dec.norm.b"] = jnp.zeros(E)
        params["dec.l2.w"] = uniform_fan_in(next(keys), (E, num_tokens), E)
        params["dec.l2.b"] = jnp.zeros(num_tokens)
        return params

    apply = _make_apply(num_tokens, E, H, F, num_layers, dropout, bptt, mask_rate, mask, groups, specs,
                        compute_dtype=compute_dtype, attn_impl=attn_impl, remat=remat)

    meta = {"bn_sizes": {}, "kind": "transformer", "num_tokens": num_tokens,
            "embedding_size": E, "num_heads": H, "hidden_size": F,
            "num_layers": num_layers, "bptt": bptt}
    return ModelDef("transformer", init, apply, specs, groups, [], meta)


def _make_apply(num_tokens, E, H, F, num_layers, dropout_rate, bptt, mask_rate, mask_flag,
                groups, specs, compute_dtype=None, attn_impl=None, remat=False):
    linear = partial(_linear, compute_dtype=compute_dtype)
    head_dim = E // H

    def apply(params, batch, *, train: bool, width_rate=1.0, scaler_rate=1.0,
              label_mask=None, bn_mode: str = "batch", bn_state=None,
              sample_weight=None, rng=None, bn_axis=None, attn_override=None):
        assert rng is not None, "transformer apply needs an rng (token corruption)"
        labels = batch["label"]
        N, S = labels.shape
        # Sequence-sharded execution: ``pos_offset`` is this shard's global
        # position and ``seq_full`` the full window length; corruption is
        # drawn over the FULL window on every shard and sliced locally, so a
        # sharded run corrupts exactly like an unsharded one.
        off = batch.get("pos_offset", 0)
        S_full = batch.get("seq_full", S)
        emb_mask = groups["emb"].mask(width_rate)
        k_emb = groups["emb"].active_count(width_rate).astype(jnp.float32)
        temp = jnp.sqrt(jnp.floor(k_emb / H))

        corrupt_key = jax.random.fold_in(rng, 0)
        # dropout keys are derived per site id (NOT an iterator) so remat's
        # replay of a layer block regenerates identical masks; shards of a
        # sequence-sharded window are decorrelated via their position offset
        drop_base = jax.random.fold_in(rng, 1)
        if S_full != S:
            drop_base = jax.random.fold_in(drop_base, off)

        def dropout(x, site: int):
            if not train or dropout_rate == 0.0:
                return x
            key = jax.random.fold_in(drop_base, site)
            keep = jax.random.bernoulli(key, 1.0 - dropout_rate, x.shape)
            return jnp.where(keep, x / (1.0 - dropout_rate), 0.0)

        def sc(x):
            return scaler(x, scaler_rate, train)

        def ln(site, x):
            return masked_layer_norm(x, params[f"{site}.g"], params[f"{site}.b"], emb_mask, k_emb)

        corrupt = jax.random.bernoulli(corrupt_key, mask_rate, (N, S_full))
        if S_full != S:
            corrupt = jax.lax.dynamic_slice(corrupt, (0, off), (N, S))
        src_ids = jnp.where(corrupt, num_tokens, labels)

        # Embedding: scaler(tok) + scaler(pos), LayerNorm, dropout
        # (ref transformer.py:34-37).  ``pos_offset`` supports sequence-
        # sharded execution (each shard embeds its global positions).
        pos = jax.lax.dynamic_slice_in_dim(params["embedding.pos.w"], off, S, axis=0)
        x = sc(embed(params["embedding.tok.w"], src_ids)) + sc(pos)[None, :, :]
        x = dropout(ln("embedding.norm", x), 0)

        def heads_split(t):  # [N,S,E] -> [N,H,S,hd]
            return t.reshape(N, S, H, head_dim).transpose(0, 2, 1, 3)

        def layer_block(x, i):
            p = f"enc{i}"
            q = sc(linear(x, params[f"{p}.mha.q.w"], params[f"{p}.mha.q.b"]))
            k = sc(linear(x, params[f"{p}.mha.k.w"], params[f"{p}.mha.k.b"]))
            v = sc(linear(x, params[f"{p}.mha.v.w"], params[f"{p}.mha.v.b"]))
            attn_fn = attn_override if attn_override is not None else attn_impl
            with scope("attn"):
                q, k, v = heads_split(q), heads_split(k), heads_split(v)
                if compute_dtype is not None:
                    q, k, v = (t.astype(compute_dtype) for t in (q, k, v))
                if attn_fn is not None:
                    o = attn_fn(q, k, v, temp).astype(jnp.float32)
                else:
                    scores = jnp.einsum("nhqd,nhkd->nhqk", q, k).astype(jnp.float32) / temp
                    attn = jax.nn.softmax(scores, axis=-1)
                    if compute_dtype is not None:
                        attn = attn.astype(compute_dtype)
                    o = jnp.einsum("nhqk,nhkd->nhqd", attn, v).astype(jnp.float32)
                o = o.transpose(0, 2, 1, 3).reshape(N, S, E)
            o = sc(linear(o, params[f"{p}.mha.o.w"], params[f"{p}.mha.o.b"]))
            x = ln(f"{p}.norm1", x + dropout(o, 1 + 3 * i))
            h = dropout(jax.nn.gelu(sc(linear(x, params[f"{p}.ff.l1.w"], params[f"{p}.ff.l1.b"])),
                                    approximate=False), 2 + 3 * i)
            h = sc(linear(h, params[f"{p}.ff.l2.w"], params[f"{p}.ff.l2.b"]))
            x = ln(f"{p}.norm2", x + dropout(h, 3 + 3 * i))
            return x

        block = jax.checkpoint(layer_block, static_argnums=(1,)) if remat else layer_block
        for i in range(num_layers):
            x = block(x, i)

        # Decoder head (ref transformer.py:131-133).
        d = jax.nn.gelu(sc(linear(x, params["dec.l1.w"], params["dec.l1.b"])), approximate=False)
        d = ln("dec.norm", d)
        out = linear(d, params["dec.l2.w"], params["dec.l2.b"])  # [N,S,V]
        out = masked_logits(out, label_mask, mask_flag)
        loss = cross_entropy(out, labels, sample_weight)
        return {"score": out, "loss": loss}, {}

    return apply
