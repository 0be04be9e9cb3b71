"""Ouro-2.6B (``model_type: ouro``, the looped language model of "Scaling
Latent Reasoning via Looped Language Models", arXiv:2510.25741) with HeteroFL
width scaling.

The published block (huggingface.co/ByteDance/Ouro-2.6B ``config.json``):
decoder layers, all alike, of plain multi-head attention (``H`` query heads
on ``H`` key/value heads, half-split RoPE over the whole head, no head norm,
no bias) and a dense SwiGLU, with TWO RMSNorms round each sub-block (one on
its input, one on its output before the residual add: a sandwich); untied
embedding and head.  THE WHOLE STACK RUNS ``R = total_ut_steps`` TIMES on its
own output and the SAME weights; after every pass the final norm, a
one-column exit gate and the head read the state, and training minimises the
loss expected under the exit distribution, less ``beta`` times that
distribution's entropy.  ``x`` is ``[T, D]``, ``rms(x, g) = x / sqrt(mean(x^2)
+ eps) * g``, ``N`` the layers held:

  layer l:   x = x + rms(attn(rms(x, g1_l)), g2_l);  x = x + rms(swiglu(rms(x, g3_l)), g4_l)
  attn:      q, k, v = h Wq, h Wk, h Wv -> [T, H, d];  q, k = rope(., pos)
             out = concat_heads(softmax_causal(q k^T / sqrt(d)) v) Wo
  pass t:    for l in 0..N-1: x = layer_l(x);  h_t = rms(x, g_f);  x = h_t
             z_t = h_t W_head (logits);  lam_t = sigmoid(h_t w_gate + b_gate)
  exit:      p_t = lam_t * prod_{j<t}(1 - lam_j) for t < R;  p_R = prod_{j<R}(1 - lam_j)
  loss:      mean over target positions i of  sum_t p_t[i] nll(z_t[i], y[i+1]) - beta H(p[i])
             H(p) = -sum_t p_t log p_t

``R`` = 1 is a plain sandwich-norm decoder under the plain next-token loss
(``p_1`` = 1, ``H`` = 0; the gate then gets no gradient).  Out of training
the published exit rule reads, a token, the first pass at which the exit
distribution's running sum reaches ``early_exit_threshold`` (the last pass if
none does: at the published 1 always the last); ``loss`` is then that pass's
negative log-likelihood and ``score`` its logits.  In training every pass
runs and ``score`` is the last pass's.

HeteroFL slicing (the paper defines none for this family; stated in the
benchmark configuration's ``assumed``): ``emb`` prefix of the hidden size
(embedding columns, head rows, every norm gain, every matrix's model-side
axis, the gate's rows); per-head prefixes in whole rotary pairs of the ``d``
dims of the query heads and the key/value heads (one ``family``, ``head``); as
in ``lfm2`` the rotary leaves are STORED with each head's pairs adjacent
(stored ``2i`` = published ``i``, stored ``2i + 1`` = published ``i + d/2``)
and turned by ``rope_interleaved``; ``ffn`` prefix of the SwiGLU's width;
never sliced: the vocabulary, the gate's one column, ``total_ut_steps``.
Softmax scale ``1/sqrt(active head dims)``; a Scaler after every sliced linear
except the head and the exit gate (a categorical and a Bernoulli output:
their logits are read by a softmax and a sigmoid, not by a further layer whose
input statistics the Scaler is there to keep).  A leaf used ``R`` times a step
is sliced, carried, decayed and counted like any leaf; its gradient is the sum
over its uses.

The passes are a ``lax.scan``; the shared weights are its invariants, so a
leaf's gradient is the sum over its ``R`` uses.  INSIDE a pass a stack of at
most :data:`UNROLL_LAYERS` layers is a Python loop over the layers' own leaves
(ISSUE 43), a longer one an inner ``lax.scan`` over their stacks (``lfm2``'s
run of alike layers); every layer APPLICATION is under ``jax.checkpoint``
either way.  What the loop spares is not mathematics but the inner scan's own
traffic, a fifth of a round at four layers of the published widths (PERF.md, PR
41): no leaf is stacked ``[N, ...]``, so no application slices its weights
out, no stacked gradient is zero-filled and added to the passes' carry a whole
stack at a time (the sum over the passes rides each weight-gradient product
instead), and a value kept for the backward is copied TWICE -- into the passes'
``[R, ...]`` stack and back -- where two nested scans copy it four times (into
the inner ``[N, ...]``, that into ``[R, N, ...]``, and twice back).  What it
costs is ``N`` layer bodies of code where the scan has one.  The rule is read
off ``num_hidden_layers`` alone.  The round program compiled for a v5e at the
published widths (code / its compile-cache entry, MB; PERF.md, PR 43): 4 layers
86.6 / 17.5 unrolled against 31.8 / 6.3 scanned, 5 layers 104.7 / 21.3, 6
layers 39.6 / 9.9, 8 layers 51.7 / 12.8 against 30.8 / 7.5 -- every one far
inside the 192 MiB the chip machine's cache holds, and 8 layers are as deep a
stage of these widths as one chip's memory trains (8 x 51.4 M parameters at 24
B); beyond, nothing is measured, so the published 48 layers scan as before.

For the backward an application keeps its input (``R x N`` states a step) and,
by name (:func:`kept`), what costs more to compute again than to write once
and read back: where the fused kernels run, the three residuals of
``gq_attn_bwd`` -- ``gq_attn_fwd``'s output, its log-sum-exp and the kernels'
bfloat16 operands, so that the backward runs no second forward kernel and no
second ``q`` / ``k`` / ``v`` product, turn or cast -- and the SwiGLU's
down-projected output, the input of ``norm4``, so that it runs no second
down-projection.  NOT the gate and up pre-activations nor the attention's
projected output: chosen on the chip through the two scans, where those cost
more in traffic than their products do again (PERF.md, PR 41).
"""

from __future__ import annotations

from functools import partial
from typing import Dict

import jax
import jax.numpy as jnp
from jax import lax
from jax.ad_checkpoint import checkpoint_name

from ..obs.trace import scope
from ..ops.layers import exit_log_probs, gq_attention_tile, pass_token_nll, swiglu
from .base import ModelDef
from .decoder import Leaves, alike_runs, decoder, gq_attention, layer_leaves, run_layers
from .spec import Group

#: the name a layer application's SwiGLU output carries (``checkpoint_name``;
#: :func:`kept`): the down-projected ``y``, the input of the sandwich's ``norm4``
MLP_OUT = "mlp_out"

#: the longest layer stack a pass applies as a Python loop; a longer one is a
#: ``lax.scan`` over its stacked leaves (why, and the compiled sizes behind the
#: number: the module docstring)
UNROLL_LAYERS = 8


def kept():
    """What a layer application's ``jax.checkpoint`` keeps for the backward
    beside its input: the values that carry one of four names, the three
    residuals of the attention's backward kernel (``gq_attn_fwd``'s output and
    log-sum-exp and the kernels' bfloat16 operands, named where the kernel is
    called; the block loop names nothing) and the SwiGLU's down-projected
    output.  None: a bare checkpoint, the input alone."""
    from ..ops.pallas_attention import GQ_LSE, GQ_OPS, GQ_OUT  # Pallas, imported where an Ouro model is built

    return jax.checkpoint_policies.save_only_these_names(GQ_OUT, GQ_LSE, GQ_OPS, MLP_OUT)


def make_ouro(num_tokens: int, arch: Dict, model_rate: float = 1.0, *,
              mask: bool = True, compute_dtype=None) -> ModelDef:
    """``arch``: ``cfg['ouro']`` (config.process_control) at the GLOBAL widths;
    ``model_rate`` builds the dense sub-model a client at that rate holds
    (the sliced strategy and the equivalence tests)."""
    leaves = Leaves(model_rate)
    cw, add, add_ffn = leaves.cw, leaves.add, leaves.add_ffn
    D, F = cw(arch["hidden_size"]), cw(arch["intermediate_size"])
    L, R = int(arch["num_hidden_layers"]), int(arch["total_ut_steps"])
    H, Hkv = int(arch["num_attention_heads"]), int(arch["num_key_value_heads"])
    hd = cw(arch["head_dim"], 2)
    # staticcheck: allow(no-float-coercion): build-time config scalars
    theta, eps = float(arch["rope_theta"]), float(arch["rms_norm_eps"])
    # staticcheck: allow(no-float-coercion): build-time config scalars
    beta, threshold = float(arch["exit_entropy_beta"]), float(arch["early_exit_threshold"])
    if H % Hkv:
        raise ValueError(f"{H} query heads do not divide over {Hkv} key/value heads")
    if R < 1 or L < 1:
        raise ValueError(f"Not valid total_ut_steps / num_hidden_layers: {R} / {L} (each >= 1)")

    def heads(name, n):
        return Group(name, n * hd, kind="per_head", num_heads=n, multiple=2,
                     coupled=False, family="head")

    groups = {
        "emb": Group("emb", D),
        "q_head": heads("q_head", H),
        "kv_head": heads("kv_head", Hkv),
        "ffn": Group("ffn", F),
        "gate": Group("gate", 1, kind="full"),
    }
    leaves.stem(num_tokens, D)
    add("exit.w", (D, 1), {0: "emb", 1: "gate"})
    add("exit.b", (1,), {0: "gate"})
    for i in range(L):
        p = f"l{i}"
        for j in (1, 2, 3, 4):  # the sandwich: in and out of the attention, in and out of the SwiGLU
            add(f"{p}.norm{j}.g", (D,), {0: "emb"})
        leaves.add_gq_attention(p, H, Hkv, hd, head_norm=False)
        add_ffn(f"{p}.mlp", F, "ffn")

    def body(c, params):
        N, S, train, sc, rms, head_act = c.N, c.S, c.train, c.sc, c.rms, c.count["q_head"]
        attention = partial(
            gq_attention, heads=H, kv_heads=Hkv, head_dim=int(arch["head_dim"]), theta=theta,
            scale=1.0 / jnp.sqrt(head_act), sc=sc, compute_dtype=compute_dtype)
        policy = kept()
        # layer applications a step, and those whose attention runs the kernel
        # that names its results under a policy that keeps them
        applied = R * L
        named = applied if policy is not None and gq_attention_tile(S, hd) is not None else 0

        @partial(jax.checkpoint, policy=policy)
        def layer(x, lp):
            """``(x, leaves) -> (x, None)``, a scan's body whether or not one
            runs it; for the backward it keeps its input and what
            :func:`kept` names."""
            x = x + rms(lp["norm2.g"], attention(lp, rms(lp["norm1.g"], x)))
            h = rms(lp["norm3.g"], x)
            y = swiglu(h, lp["mlp.g.w"], lp["mlp.u.w"], lp["mlp.d.w"], sc, compute_dtype)
            return x + rms(lp["norm4.g"], checkpoint_name(y, MLP_OUT)), None

        # the layers are alike: one run, gathered (and, where it is scanned,
        # stacked) once, outside the passes
        runs = list(alike_runs(L, lambda i: 0, lambda i: layer_leaves(params, i), lambda i: layer,
                               unroll=UNROLL_LAYERS))
        unrolled = not runs[0][2]

        def one_pass(x, _):
            """The whole stack once on the shared weights, then the final
            norm: the normed state is what the head and the gate read and
            what the next pass starts from."""
            with scope("loop/pass"):
                x, _ = run_layers(x, runs)
            with scope("loop/exit"):
                h = rms(params["norm.g"], x)
            return h, h

        _, hs = lax.scan(one_pass, c.embed(), None, length=R)
        with scope("loop/head"):
            # [R, N, S] and the targets' weights [N, S]
            nll, wt = pass_token_nll(hs, c.labels, c.head, c.sample_weight)
        with scope("loop/exit"):
            # no Scaler: the gate's logit is read by a sigmoid alone.  One
            # column: float32 at "highest" precision costs nothing, and the
            # exit distribution is then as exact as the reference's
            gate = jnp.einsum("rnsd,d->rns", hs, params["exit.w"][:, 0],
                              precision=lax.Precision.HIGHEST) + params["exit.b"][0]
            logp = exit_log_probs(gate)
            if train:
                p, last = jnp.exp(logp), jnp.full((N, S), R - 1)
                entropy = -jnp.sum(p * logp, axis=0)
            else:
                # the published exit rule: the first pass at which the
                # running sum of p reaches the threshold, else the last
                reached = jnp.cumsum(jnp.exp(logp[:-1]), axis=0) >= threshold
                last = jnp.argmax(jnp.concatenate([reached, jnp.ones((1, N, S), bool)]), axis=0)
                p = jax.nn.one_hot(last, R, axis=0, dtype=jnp.float32)
                entropy = jnp.zeros((N, S), jnp.float32)
            count = jnp.sum(wt)
            loss = jnp.sum((jnp.sum(p * nll, axis=0) - beta * entropy) * wt) \
                / jnp.maximum(count, 1e-12)
            # sums over the target positions, each with its count last
            # (obs.split_probes divides): the exit distribution's mean, each
            # pass's mean negative log-likelihood, the expected pass
            ranks = jnp.arange(1, R + 1, dtype=jnp.float32)[:, None, None]
            counters = {
                "exit_share": jnp.append(jnp.sum(p * wt, axis=(1, 2)), count),
                "pass_nll": jnp.append(jnp.sum(nll * wt, axis=(1, 2)), count),
                "passes": jnp.stack([jnp.sum(ranks * p * wt), count]),
                "kept": jnp.array([named, applied], jnp.float32),
                "unrolled": jnp.array([applied if unrolled else 0, applied], jnp.float32)}
        read = jnp.take_along_axis(hs, last[None, :, :, None], axis=0)[0]
        # the logits [N, S, V] a caller may read (training does not: then the
        # compiler drops them)
        return c.result(c.head(read), loss, counters)

    return decoder(
        "ouro", num_tokens, arch, leaves, groups, body, eps=eps, mask=mask,
        compute_dtype=compute_dtype, counts=(("q_head", H),),
        # sums over the target positions with their count last: the exit
        # distribution's mean a pass (sums to 1), each pass's mean negative
        # log-likelihood; pairs: the expected pass (sum of t * p_t), the layer
        # applications whose attention kernel's results the layer kept for its
        # backward over the layer applications, and those run from an unrolled
        # stack over the layer applications
        counters={"exit_share": ("loop_exit_share", (R + 1,), "mean"),
                  "pass_nll": ("loop_pass_nll", (R + 1,), "mean"),
                  "passes": ("loop_passes", (2,), "ratio"),
                  "kept": ("loop_kept", (2,), "ratio"),
                  "unrolled": ("loop_unrolled", (2,), "ratio")},
        # every layer leaf, the final norm, the gate and the head are used
        # once a pass, the embedding once a step
        profile={"routed_share": 1.0, "passes": R,
                 "attention": {f"l{i}.attn": (H, hd, hd) for i in range(L)}})
