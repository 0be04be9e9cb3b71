"""The decoder skeleton: what every decoder-only language-model family of
this package (a row of ``config.DECODER_FAMILIES``) does alike, once.  A
family's file keeps the published block and its slicing, its groups and leaves,
its mixers and router, its layer body, what its checkpoint keeps and its
counters, and takes from here:

* :class:`Leaves`: widths at the model's rate, the ``shapes`` / ``specs``
  pair and the ``init`` built from it;
* :func:`decoder`: a family's ``body(c, params)`` closed into a
  :class:`~.base.ModelDef` -- ``apply``'s prologue (:class:`Call`) and tail
  (``Call.finish``) and the ``meta`` every reader of a model expects;
* :func:`alike_runs` / :func:`run_layers`: consecutive alike layers as ONE
  ``lax.scan`` over their stacked leaves, a lone layer as itself.  WHAT A
  LAYER HANDS FORWARD: ``run_layers`` threads whatever a family's layers take
  and return in ``x``'s place, so a family whose later layers read what an
  earlier one computed (``phi4flash``: a scan's output as memory, a layer's
  keys and values) makes it a pair ``(x, side)``, ``side`` a dict the
  producing layer adds to; the FAMILY checks, where it builds its model, that
  every consumer follows its producer, and a run that a ``lax.scan`` takes
  must leave the pair's structure as it found it;
* :func:`gq_attention`, the block three families share, and the expert
  layers' :func:`held_experts`, :func:`expert_tile`, :func:`layer_leaves`.

A family declares its counters where it builds its model: ``{key: (name,
shape, fold)}``, ``key`` what the layer body calls it, ``name`` what it rides
the metrics by, ``fold`` how the host finishes the per-device sums
(``obs.split_probes``): ``"sum"``, ``"ratio"`` of a (numerator, denominator)
pair, or ``"mean"`` of sums with their count last.
"""

from __future__ import annotations

from functools import partial
from itertools import groupby
from types import SimpleNamespace
from typing import Callable, Dict, Optional

import jax
import jax.numpy as jnp

from ..config import ceil_width
from ..obs.trace import scope
from ..ops.layers import (causal_gq_attention, embed, heads_linear, linear as _linear,
                          linear_heads, masked_layer_norm, masked_logits, masked_rms_norm,
                          next_token_loss, rope_interleaved, rope_swap, scaler)
from .base import ModelDef, normal_init, uniform_fan_in
from .spec import ParamSpec


def held_experts(expert_share, n: int) -> range:
    """The routed experts a share ``(index, of)`` of an ``of``-way
    expert-parallel layer of ``n`` experts holds: ``[index * n/of, (index +
    1) * n/of)``."""
    index, of = (int(v) for v in expert_share)
    if of < 1 or n % of or not 0 <= index < of:
        raise ValueError(f"Not valid expert_share: {list(expert_share)!r} "
                         f"(index, of) with of dividing the {n} routed experts")
    return range(index * (n // of), (index + 1) * (n // of))


def expert_tile(tokens: int, top_k: int, experts: int, groups: int = 2) -> int:
    """Rows a step of the expert loop (``ops.layers.moe_experts``) takes:
    ``groups`` times an expert's expected group (``tokens * top_k / experts``
    pairs), in whole ``MOE_TILE``s; twice unless the family says otherwise.
    An expert is then one step a pass unless its load doubles: its float32
    weights are read once, and the loop's trip count stops following the
    seed's routing (at 256 rows, half an expected group of the cell it was
    sized in, two seeds' rounds lay 2.7 % apart on the chip and 0.4 % at
    1,024, no slower; PERF.md, PR 32)."""
    from ..ops.layers import MOE_TILE

    return MOE_TILE * max(1, -(-groups * tokens * top_k // (experts * MOE_TILE)))


def moe_counters(held) -> Dict[str, tuple]:
    """The declaration of what ``ops.layers.moe_experts`` counts: tokens a
    held expert, (pairs routed, pairs on held experts, held pairs not
    computed), and (layer applications whose dispatch was the compact one,
    layer applications)."""
    return {"tokens": ("moe_tokens", (len(held),), "sum"), "assign": ("moe_assign", (3,), "sum"),
            "compact": ("moe_compact", (2,), "sum")}


def layer_leaves(params: Dict[str, jnp.ndarray], i: int, held=None,
                 parts: str = "gud") -> Dict[str, jnp.ndarray]:
    """Layer ``i``'s leaves (``l{i}.*``) without their prefix; with ``held``
    (an expert layer), its held experts' ``moe.e{j}.{m}.w`` for ``m`` of
    ``parts`` (SwiGLU's gate, up and down; a two-matrix expert's ``"ud"``)
    stacked on a leading axis as ``moe.e.{m}.w``, in that order."""
    pre = f"l{i}."
    lp = {k[len(pre):]: v for k, v in params.items()
          if k.startswith(pre) and ".moe.e" not in k}
    if held is not None:
        for m in parts:
            lp[f"moe.e.{m}.w"] = jnp.stack([params[f"{pre}moe.e{j}.{m}.w"] for j in held])
    return lp


def gq_attention(lp, h, *, heads: int, kv_heads: int, head_dim: int, theta: float, scale,
                 sc, head_norm=None, compute_dtype=None, attend=causal_gq_attention):
    """A layer's grouped-query attention on the normed ``h`` ``[N, S, D]``,
    heads first from the projections to the output projection; ``head_norm(x,
    g)`` the RMSNorm over each head's dims (None: the family has none, and
    the layer no ``attn.q_norm.g`` / ``attn.k_norm.g``), ``head_dim`` the
    GLOBAL model's (the rotary frequencies' denominator at every width),
    ``attend(q, k, v, scale)``; ``theta`` None: the family's attention has no
    position encoding, and nothing turns."""
    q_heads = partial(linear_heads, heads=heads, compute_dtype=compute_dtype)
    kv = partial(linear_heads, heads=kv_heads, compute_dtype=compute_dtype)
    pos = jnp.arange(h.shape[1])
    with scope("gqa"):
        # (each norm straight after its product, the order the programs that
        # take this block were traced and measured in)
        q = sc(q_heads(h, lp["attn.q.w"]))
        if head_norm is not None:
            q = head_norm(q, lp["attn.q_norm.g"])
        k = sc(kv(h, lp["attn.k.w"]))
        if head_norm is not None:
            k = head_norm(k, lp["attn.k_norm.g"])
        v = sc(kv(h, lp["attn.v.w"]))
    # the norm sits between the product and the turn, so the pair swap is
    # taken on the activations (a latent-attention layer takes its rotary
    # query's on the weight)
    if theta is not None:
        q = rope_interleaved(q, rope_swap(q), pos, theta, axis=2, full=head_dim)
        k = rope_interleaved(k, rope_swap(k), pos, theta, axis=2, full=head_dim)
    if compute_dtype is not None:
        q, k, v = (t.astype(compute_dtype) for t in (q, k, v))
    o = attend(q, k, v, scale)
    with scope("gqa"):
        return sc(heads_linear(o.astype(jnp.float32), lp["attn.o.w"], compute_dtype))


class Leaves:
    """A model's leaves at ``model_rate``: ``shapes`` and ``specs`` as the
    family ``add``s them, and the ``init`` that follows from them."""

    def __init__(self, model_rate: float):
        self.model_rate = model_rate
        self.shapes: Dict[str, tuple] = {}
        self.specs: Dict[str, ParamSpec] = {}

    def cw(self, n, multiple: int = 1) -> int:
        """Width ``n`` at the model's rate, in whole ``multiple``s."""
        k = ceil_width(n, self.model_rate)
        return -(-k // multiple) * multiple

    def add(self, name: str, shape, axis_groups, label_axis=None):
        self.shapes[name] = tuple(shape)
        self.specs[name] = ParamSpec(axis_groups, label_axis=label_axis)

    def stem(self, num_tokens: int, D: int, tied: bool = False):
        """The leaves round the layers, at hidden size ``D``: the embedding,
        the final norm and the head.  ``tied``: ONE leaf, looked up by row and
        multiplied as the head, one label axis (its rows) for both uses; not
        named ``embedding.*``: normal(0, 1) rows (the rule for that name)
        read as a head give logits of the hidden size's scale, so it starts
        small, as a head's columns."""
        self.D, self.tied = D, tied
        self.embedding, self.embedding_std = ("tok.w", 0.02) if tied else ("embedding.tok.w", 1.0)
        self.add(self.embedding, (num_tokens, D), {1: "emb"}, label_axis=0)
        self.add("norm.g", (D,), {0: "emb"})
        if not tied:
            self.add("head.w", (D, num_tokens), {0: "emb"}, label_axis=1)

    def add_ffn(self, prefix: str, width: int, group: str):
        """A SwiGLU's gate, up and down projections of ``width``."""
        self.add(f"{prefix}.g.w", (self.D, width), {0: "emb", 1: group})
        self.add(f"{prefix}.u.w", (self.D, width), {0: "emb", 1: group})
        self.add(f"{prefix}.d.w", (width, self.D), {0: group, 1: "emb"})

    def add_gq_attention(self, p: str, heads: int, kv_heads: int, hd: int, head_norm: bool = True):
        """The leaves :func:`gq_attention` reads, layer ``p``'s ``attn.*``, on
        the groups ``q_head`` / ``kv_head`` (and ``head``, the head norms')."""
        self.add(f"{p}.attn.q.w", (self.D, heads * hd), {0: "emb", 1: "q_head"})
        self.add(f"{p}.attn.k.w", (self.D, kv_heads * hd), {0: "emb", 1: "kv_head"})
        self.add(f"{p}.attn.v.w", (self.D, kv_heads * hd), {0: "emb", 1: "kv_head"})
        if head_norm:
            self.add(f"{p}.attn.q_norm.g", (hd,), {0: "head"})
            self.add(f"{p}.attn.k_norm.g", (hd,), {0: "head"})
        self.add(f"{p}.attn.o.w", (heads * hd, self.D), {0: "q_head", 1: "emb"})

    def init(self, key: jax.Array) -> Dict[str, jnp.ndarray]:
        names = sorted(self.shapes)
        params = {}
        for name, k in zip(names, jax.random.split(key, len(names))):
            shape = self.shapes[name]
            if len(shape) == 1:  # norm gains 1; a bias 0
                params[name] = (jnp.ones if name.endswith(".g") else jnp.zeros)(shape)
            elif name == self.embedding:
                params[name] = normal_init(k, shape, self.embedding_std)
            else:  # a fan-in of the leading axis (a short convolution's taps
                # [L, channels]: a channel's fan-in is its L taps)
                params[name] = uniform_fan_in(k, shape, shape[0])
        return params


def alike_runs(L: int, alike: Callable, leaves: Callable, layer_of: Callable, unroll: int = 1):
    """The ``L`` layers as runs of consecutive layers with equal ``alike(i)``:
    yields ``(layer, leaves, scanned)``, ``layer = layer_of(first of the run)``
    the run's kind as ``(x, leaves) -> (x, counters or None)``.  A run of at
    most ``unroll`` layers comes as the list of its layers' ``leaves(i)``, a
    longer one as ONE dict of them stacked, for one ``lax.scan``: the program
    then holds one layer's code however long the run.  Lazy, so a run's
    leaves are gathered where :func:`run_layers` applies it."""
    for _, run in groupby(range(L), key=alike):
        run = list(run)
        lps = [leaves(i) for i in run]
        layer = layer_of(run[0])
        if len(run) <= unroll:
            yield layer, lps, False
        else:
            yield layer, {k: jnp.stack([lp[k] for lp in lps]) for k in lps[0]}, True


def run_layers(x, runs, counters=None):
    """Apply :func:`alike_runs`' runs to ``x``: a stacked run as a
    ``lax.scan`` of its layer, the others layer by layer.  Returns ``(x,
    counters)``, the layers' counters summed onto ``counters`` (None: nothing
    to start from, and nothing if no layer counts).  ``x`` is the layers' own:
    the hidden state, or any pytree that holds it beside what a layer hands
    the layers after it (the module docstring); the order of producers and
    consumers is the family's to check, not this loop's."""
    for layer, lps, scanned in runs:
        if scanned:
            x, c = jax.lax.scan(layer, x, lps)
            c = jax.tree_util.tree_map(lambda v: jnp.sum(v, axis=0), c)
            counters = _add(counters, c)
        else:
            for lp in lps:
                x, c = layer(x, lp)
                counters = _add(counters, c)
    return x, counters


def _add(counters, c):
    if counters is None or c is None:
        return c if counters is None else counters
    return jax.tree_util.tree_map(jnp.add, counters, c)


class Call:
    """One ``apply`` of a decoder: what every family's layers read.
    ``labels`` ``[N, S]`` the token rows, ``T = N * S``; ``count[g]`` /
    ``mask[g]`` the float32 active dims and the 0/1 mask of group ``g`` at the
    client's ``width_rate`` (``emb`` and what the family asked for; a ``(g,
    n)`` pair asks for the dims of ONE of ``g``'s ``n`` heads); ``sc`` the
    Scaler, ``rms(g, x)`` the RMSNorm over the hidden size, ``head`` the
    logits of a normed state."""

    def __init__(self, d, params, batch, attn_override, train, width_rate, scaler_rate,
                 label_mask, sample_weight):
        if "pos_offset" in batch or attn_override is not None:
            raise ValueError(f"{d.name} has no sequence-sharded path (mesh "
                             "'data' axis must be 1)")
        self.d, self.params, self.train, self.width_rate = d, params, train, width_rate
        self.labels, self.sample_weight = batch["label"], sample_weight
        self.N, self.S = self.labels.shape
        self.T = self.N * self.S
        self.count, self.mask = {}, {}
        for g in ("emb",) + tuple(d.counts):
            g, n = g if isinstance(g, tuple) else (g, None)
            act = d.groups[g].active_count(width_rate).astype(jnp.float32)
            self.count[g] = act if n is None else act / n
        for g in ("emb",) + tuple(d.masks):
            self.mask[g] = d.groups[g].mask(width_rate)
        self.sc = lambda x: scaler(x, scaler_rate, train)

        def head(x_):  # a tied head: the embedding's rows as columns
            w = params[d.leaves.embedding].T if d.leaves.tied else params["head.w"]
            return masked_logits(d.linear(x_, w), label_mask, d.mask)

        self.head = head

    def rms(self, g, x):
        return masked_rms_norm(x, g, self.mask["emb"], self.count["emb"], self.d.eps)

    def layer_norm(self, g, b, x):
        """LayerNorm with a bias over the hidden size's active dims."""
        return masked_layer_norm(x, g, b, self.mask["emb"], self.count["emb"], self.d.eps)

    def embed(self):
        return embed(self.params[self.d.leaves.embedding], self.labels)

    def zeros(self):
        """The declared counters at zero, under the layer body's keys."""
        return {k: jnp.zeros(shape, jnp.float32) for k, (_, shape, _) in self.d.counters.items()}

    def result(self, score, loss, counters=None):
        """``apply``'s first result; the counters, where the model declares
        any, under the names they ride the metrics by."""
        res = {"score": score, "loss": loss}
        if self.d.counters:
            res["counters"] = {self.d.counters[k][0]: v for k, v in counters.items()}
        return res

    def finish(self, x, counters=None):
        """The tail: the final norm, the logits ``[N, S, V]`` a caller may
        read (training does not: then the compiler drops them) and the
        next-token loss, which takes the head in blocks of positions."""
        xn = self.d.norm(self, x) if self.d.norm else self.rms(self.params["norm.g"], x)
        return self.result(self.head(xn),
                           next_token_loss(xn, self.labels, self.head, self.sample_weight),
                           counters)


def decoder(name: str, num_tokens: int, arch: Dict, leaves: Leaves, groups: Dict, body: Callable,
            *, eps: float, mask: bool, compute_dtype=None, counts=(), masks=(),
            counters: Optional[Dict[str, tuple]] = None, profile: Dict, held=None,
            norm: Optional[Callable] = None) -> ModelDef:
    """The family ``name``'s model: ``body(c, params)`` (``c`` the
    :class:`Call`) runs from the embedding to ``c.finish``; ``counts`` /
    ``masks`` the groups beside ``emb`` whose active dims / masks it reads;
    ``counters`` its counters' declaration (the module docstring), ``profile``
    what ``analysis.summary.module_table`` cannot read off the leaves,
    ``held`` the experts an expert family holds; ``norm(c, x)`` the family's
    final norm (None: RMSNorm by ``norm.g``)."""
    d = SimpleNamespace(name=name, groups=groups, leaves=leaves, eps=eps, mask=mask, counts=counts,
                        masks=masks, counters=counters, norm=norm,
                        linear=partial(_linear, compute_dtype=compute_dtype))

    def apply(params, batch, *, train: bool, width_rate=1.0, scaler_rate=1.0,
              label_mask=None, bn_mode: str = "batch", bn_state=None,
              sample_weight=None, rng=None, bn_axis=None, attn_override=None):
        return body(Call(d, params, batch, attn_override, train, width_rate, scaler_rate,
                         label_mask, sample_weight), params), {}

    meta = {"bn_sizes": {}, "kind": name, "num_tokens": num_tokens, "arch": dict(arch),
            "shapes": dict(leaves.shapes), "profile": dict(profile)}
    if leaves.tied:
        meta["profile"]["tied_head"] = leaves.embedding
    if held is not None:
        meta["held_experts"] = list(held)
    if counters:
        # what apply's "counters" holds (summed over the layers): name ->
        # (shape, fold); the engines carry them as obs_ probes when telemetry
        # is on and obs.split_probes finishes each by its fold
        meta["counters"] = {n: (shape, fold) for n, shape, fold in counters.values()}
    return ModelDef(name, leaves.init, apply, leaves.specs, groups, [], meta)
