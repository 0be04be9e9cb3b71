"""NVIDIA-Nemotron-3-Nano-30B-A3B (``model_type: nemotron_h``) with HeteroFL
width scaling.

The published block (huggingface.co/nvidia/NVIDIA-Nemotron-3-Nano-30B-A3B-BF16
``config.json``; the Nemotron-H hybrid of arXiv:2504.03624 with its
feed-forwards made sparse): by ``hybrid_override_pattern`` EACH LAYER IS ONE
SUB-BLOCK, ``x <- x + f(rms(x, g))``, of three kinds: ``M`` a Mamba-2
state-space mixer, ``*`` grouped-query attention with NO position encoding,
``E`` ``n_routed_experts`` two-matrix squared-``relu`` experts (sigmoid router,
top-k by the scores plus a selection bias, renormalised and scaled weights)
beside one shared expert of the same form that every token takes; RMSNorm, no
bias on any linear, untied embedding and head; next-token loss.  ``h = rms(x,
g)`` ``[T, D]``, ``rms(x, g) = x / sqrt(mean(x^2) + eps) * g``:

  M:  z, xs, B, C, dt = h W_z, h W_x, h W_B, h W_C, h W_dt    (the published in_proj's blocks)
      xs, B, C = silu(conv(.) + b_conv)     (depthwise, causal, ``conv_kernel`` taps)
      dt = softplus(dt + dt_bias) [H];  A = -exp(A_log) [H]
      per head h of group g = h // (H / G), over a row from a zero state [P, Ns]:
          S_t = exp(dt_t A) S_{t-1} + dt_t xs_t B_t^T;   y_t = S_t C_t + D_h xs_t
      out = (rms_groups(y * silu(z)) * g_norm) W_out    (mean square over each group's channels)
  *:  q = h Wq -> [T, Hq, d];  k, v = h Wk, h Wv -> [T, Hkv, d]
      out = concat_heads(softmax_causal(q k_group^T / sqrt(d)) v_group) Wo
  E:  s = sigmoid(h Wr);  sel = top_k(s + b);  w = s[sel] / sum(s[sel]) * scale
      out = relu(h Wu_s)^2 Wd_s + sum_{e in sel, held} w_e relu(h Wu_e)^2 Wd_e
  logits = rms(x, g_f) W_head

The expert layer is told what it holds (``expert_share = (index, of)``, as
``kanana2``); with ``of == 1`` the model is the published one.

STORED FORMS.  The published ``in_proj`` (``[D, 2 H P + 2 G Ns + H]``) is five
column leaves (``ssm.in.{z,x,b,c,dt}``) and the convolution over ``[xs | B |
C]`` three (``ssm.conv.{x,b,c}`` taps ``[L, channels]`` and biases), because
the five blocks belong to three different width groups; the reference
concatenates them back.  ``A_log`` and ``dt_bias`` are held as ``[1, H]``
leaves SHIFTED by a constant, ``A_log = ssm.a_log.w +`` :data:`A_LOG_SHIFT`,
``dt_bias = ssm.dt_bias.w +`` :data:`DT_BIAS_SHIFT`: a slope of one, so every
gradient is the published parameter's, and a leaf drawn by the package's one
rule for a matrix (uniform at ``1 / sqrt(fan-in)`` = 1 here) lands where the
published initialisation puts it (``A`` in [1, 16], ``dt`` in [0.001, 0.1]: the
two constants' notes); ``D`` is the gain ``ssm.skip.g``, 1 as published.

HeteroFL slicing (the paper defines none for this family; stated in the
benchmark configuration's ``assumed``): ``emb`` prefix of the hidden size
(embedding columns, head rows, every norm gain, every matrix's model-side
axis, router rows); ``ssm_head``, a per-head prefix of the ``P`` dims of each
of the ``H`` state-space heads (``z``, ``xs``, the convolution's ``xs``
channels, the gated norm's gain, ``W_out``'s rows); per-head prefixes of the
attention heads' dims, equal for query and key/value heads (one family);
``expert`` / ``shared`` prefixes of the two feed-forward widths; never sliced:
the state size, ``B``, ``C``, ``dt``, ``A_log``, ``D``, ``dt_bias``, the number
of heads of either kind, the expert axis, the router's columns, its selection
bias and the vocabulary.  A masked channel of ``xs`` stays zero through the
convolution (its taps and bias are zero), the scan (its state rows are never
written) and the gated norm, whose count is the ACTIVE channels of a group.
Softmax scale ``1 / sqrt(active dims of a head)``; a Scaler after every sliced
linear except the router, the head and ``dt``: their outputs are squashed or
categorical (a sigmoid read by a top-k, a softmax, a time step read by a
softplus and an exponential), where a division by the rate changes the
temperature of a decision and not the size of a feature; none after the
depthwise taps.

Consecutive layers of one kind run as one ``lax.scan`` over their stacked
leaves and a lone layer as itself (``decoder.alike_runs``): in the published
pattern no two neighbours are alike, so every layer is a lone layer (seven
lone layers in the benchmark's cell and not two interleaved stacks: a short
stack gains nothing from a scan and pays for the stacking, ``PERF.md`` PR 43);
each layer under ``jax.checkpoint`` (:func:`kept`).
"""

from __future__ import annotations

import math
from functools import partial
from typing import Dict

import jax
import jax.numpy as jnp

from ..obs.trace import scope
from ..ops.layers import (causal_conv_silu, gated_group_rms_norm, linear as _linear, moe_experts,
                          moe_route, relu2_ffn, ssm_chunked_scan, ssm_scan_plan)
from .base import ModelDef
from .decoder import (Leaves, alike_runs, decoder, expert_tile, gq_attention, held_experts,
                      layer_leaves, moe_counters, run_layers)
from .spec import Group

#: pattern letter -> kind
KINDS = {"M": "ssm", "*": "attention", "E": "experts"}

#: ``A_log = ssm.a_log.w + A_LOG_SHIFT``: a leaf uniform in [-1, 1] gives ``A =
#: -exp(A_log)`` in -[4 / e, 4 e] = -[1.47, 10.9], inside the published
#: initialisation's [1, 16]
A_LOG_SHIFT = math.log(4.0)
#: ``dt_bias = ssm.dt_bias.w + DT_BIAS_SHIFT``: ``softplus(-4.6) = 0.01``, the
#: geometric middle of ``time_step_min`` 0.001 and ``time_step_max`` 0.1, and
#: a leaf uniform in [-1, 1] gives a bias whose softplus lies in [0.0037, 0.027]
DT_BIAS_SHIFT = -4.6


#: expected groups a step of the expert loop holds (``decoder.expert_tile``;
#: the other families' 2).  This family's first expert layer can read the raw
#: embeddings, whose routing follows the token's identity: on a Zipf corpus an
#: expert's load then reaches 2-4 times its expected group (of 120 seeded
#: draws at the benchmark cell's shapes one held expert in sixteen passes 2x
#: and one in two thousand 4x; a CPU count), and at 2 the loop's trip count,
#: 8 or 9 tiles a layer by the seed, spread ``round_s`` by 0.57 % over six
#: seeds on the chip (``PERF.md``, PR 46)
TILE_GROUPS = 4


def kept():
    """What a layer's ``jax.checkpoint`` keeps for the backward beside its
    input: nothing (None: a bare checkpoint).  The attention layer's band
    kernels name their results (``pallas_attention.BAND_OUT`` / ``BAND_LSE`` /
    ``BAND_OPS``) for a policy that would keep them, as ``laguna.kept`` does;
    the state-space mixer names nothing yet."""
    return None


def ssm_mixer(lp, h, *, heads: int, groups: int, state: int, chunk: int, sc, mask, count,
              eps: float, compute_dtype=None):
    """A layer's Mamba-2 mixer on the normed ``h`` ``[N, S, D]``: ``(out [N,
    S, D], keep [2])``, ``keep`` :func:`~..ops.layers.ssm_chunked_scan`'s
    count.  ``mask`` / ``count``: the 0/1 mask of the inner channels and the
    active channels of ONE group."""
    linear = partial(_linear, compute_dtype=compute_dtype)
    N, S, _ = h.shape
    with scope("ssm"):
        z, x, b, c = (sc(linear(h, lp[f"ssm.in.{m}.w"])) for m in "zxbc")
        dt = linear(h, lp["ssm.in.dt.w"])  # a time step: no Scaler (the module's note)
        x, b, c = (causal_conv_silu(t, lp[f"ssm.conv.{m}.w"], lp[f"ssm.conv.{m}.b"])
                   for t, m in ((x, "x"), (b, "b"), (c, "c")))
        dt = jax.nn.softplus(dt + (lp["ssm.dt_bias.w"][0] + DT_BIAS_SHIFT))
        a = -jnp.exp(lp["ssm.a_log.w"][0] + A_LOG_SHIFT)
        xh = x.reshape(N, S, heads, -1)
        y, keep = ssm_chunked_scan(xh, dt, a, b.reshape(N, S, groups, state),
                                   c.reshape(N, S, groups, state), chunk)
        y = (y + lp["ssm.skip.g"][:, None] * xh).reshape(x.shape)
        y = gated_group_rms_norm(y, z, lp["ssm.norm.g"], mask, count, groups, eps)
        return sc(linear(y, lp["ssm.out.w"])), keep


def make_nemotron_h(num_tokens: int, arch: Dict, model_rate: float = 1.0, *,
                    mask: bool = True, compute_dtype=None) -> ModelDef:
    """``arch``: ``cfg['nemotron_h']`` (config.process_control) at the GLOBAL
    widths; ``model_rate`` builds the dense sub-model a client at that rate
    holds (the sliced strategy and the equivalence tests)."""
    leaves = Leaves(model_rate)
    cw, add = leaves.cw, leaves.add
    D, L = cw(arch["hidden_size"]), int(arch["num_hidden_layers"])
    pattern = str(arch["hybrid_override_pattern"])
    Hs, P = int(arch["mamba_num_heads"]), cw(arch["mamba_head_dim"])
    G, Ns = int(arch["n_groups"]), int(arch["ssm_state_size"])
    taps, chunk = int(arch["conv_kernel"]), int(arch["chunk_size"])
    H, Hkv, hd = int(arch["num_attention_heads"]), int(arch["num_key_value_heads"]), \
        cw(arch["head_dim"])
    Fe, Fs = cw(arch["moe_intermediate_size"]), cw(arch["moe_shared_expert_intermediate_size"])
    E, K = int(arch["n_routed_experts"]), int(arch["num_experts_per_tok"])
    # staticcheck: allow(no-float-coercion): build-time config scalars
    eps, scaling = float(arch["layer_norm_epsilon"]), float(arch["routed_scaling_factor"])
    held = held_experts(arch["expert_share"], E)
    if len(pattern) != L or set(pattern) - set(KINDS):
        raise ValueError(f"Not valid hybrid_override_pattern: {pattern!r} (one of "
                         f"{'|'.join(KINDS)} for each of the {L} layers)")
    if H % Hkv or Hs % G:
        raise ValueError(f"{H} query heads on {Hkv} key/value heads, {Hs} state-space heads "
                         f"in {G} groups: neither divides")
    kinds = [KINDS[ch] for ch in pattern]
    inner = Hs * P

    def heads(name, n, width, family):
        return Group(name, n * width, kind="per_head", num_heads=n, coupled=False, family=family)

    def at(axis, group):  # a leaf's axis under a group, or under none
        return {axis: group} if group else {}

    groups = {
        "emb": Group("emb", D),
        "ssm_head": heads("ssm_head", Hs, P, "ssm"),
        "q_head": heads("q_head", H, hd, "head"),
        "kv_head": heads("kv_head", Hkv, hd, "head"),
        "expert": Group("expert", Fe),
        "shared": Group("shared", Fs),
        "router": Group("router", E, kind="full"),
    }
    # the published in_proj's five column blocks (the convolution runs over three
    # of them): block -> (width, the group that slices it or None)
    blocks = {"z": (inner, "ssm_head"), "x": (inner, "ssm_head"), "b": (G * Ns, None),
              "c": (G * Ns, None), "dt": (Hs, None)}
    leaves.stem(num_tokens, D)
    for i, kind in enumerate(kinds):
        p = f"l{i}"
        add(f"{p}.norm.g", (D,), {0: "emb"})
        if kind == "ssm":
            for m, (width, group) in blocks.items():
                add(f"{p}.ssm.in.{m}.w", (D, width), {0: "emb", **at(1, group)})
            for m in "xbc":
                width, group = blocks[m]
                add(f"{p}.ssm.conv.{m}.w", (taps, width), at(1, group))
                add(f"{p}.ssm.conv.{m}.b", (width,), at(0, group))
            add(f"{p}.ssm.a_log.w", (1, Hs), {})
            add(f"{p}.ssm.dt_bias.w", (1, Hs), {})
            add(f"{p}.ssm.skip.g", (Hs,), {})
            add(f"{p}.ssm.norm.g", (inner,), {0: "ssm_head"})
            add(f"{p}.ssm.out.w", (inner, D), {0: "ssm_head", 1: "emb"})
        elif kind == "attention":
            leaves.add_gq_attention(p, H, Hkv, hd, head_norm=False)
        else:
            add(f"{p}.moe.router.w", (D, E), {0: "emb", 1: "router"})
            add(f"{p}.moe.router.b", (E,), {0: "router"})
            for q, width, group in [("shared", Fs, "shared")] + [(f"e{j}", Fe, "expert")
                                                                 for j in held]:
                add(f"{p}.moe.{q}.u.w", (D, width), {0: "emb", 1: group})
                add(f"{p}.moe.{q}.d.w", (width, D), {0: group, 1: "emb"})

    # summed over the layers: `ssm_keep` = (sum of exp(dt A), its count): the
    # share of the state a position keeps; `ssm_chunks` = chunks scanned;
    # `ssm_fused` = (state-space layers whose scan the fused kernels took,
    # state-space layers)
    counters = {}
    if "ssm" in kinds:
        counters.update(keep=("ssm_keep", (2,), "mean"), chunks=("ssm_chunks", (1,), "sum"),
                        fused=("ssm_fused", (2,), "ratio"))
    if "experts" in kinds:
        counters.update(moe_counters(held))

    def body(c, params):
        N, S, T, sc, rms = c.N, c.S, c.T, c.sc, c.rms
        ssm = partial(ssm_mixer, heads=Hs, groups=G, state=Ns, chunk=chunk, sc=sc,
                      mask=c.mask["ssm_head"], count=c.count["ssm_head"], eps=eps,
                      compute_dtype=compute_dtype)
        attention = partial(gq_attention, heads=H, kv_heads=Hkv, head_dim=None, theta=None,
                            scale=1.0 / jnp.sqrt(c.count["kv_head"]), sc=sc,
                            compute_dtype=compute_dtype)
        tile = expert_tile(T, K, E, groups=TILE_GROUPS)
        zero = c.zeros() if counters else None
        chunks = jnp.full((1,), N * -(-S // chunk), jnp.float32)
        fused = jnp.stack([jnp.float32(ssm_scan_plan(S, Hs, P, G, Ns, chunk) is not None),
                           jnp.float32(1.0)])

        def layer_of(i):
            """Layer ``i``'s kind as ``(x, leaves) -> (x, counters)``
            (``decoder.run_layers``)."""
            kind = kinds[i]

            @partial(jax.checkpoint, policy=kept())
            def layer(x, lp):
                h = rms(lp["norm.g"], x)
                if kind == "ssm":
                    y, keep = ssm(lp, h)
                    return x + y, dict(zero, keep=keep, chunks=chunks, fused=fused)
                if kind == "attention":
                    return x + attention(lp, h), zero
                hf = h.reshape(T, D)
                sel, w = moe_route(hf, lp["moe.router.w"], lp["moe.router.b"], K, scaling)
                y, moe = moe_experts(hf, sel, w, [lp["moe.e.u.w"], lp["moe.e.d.w"]], held[0], sc,
                                     compute_dtype, tile=tile, body=relu2_ffn)
                with scope("moe/shared"):
                    y = y + relu2_ffn(hf, lp["moe.shared.u.w"], lp["moe.shared.d.w"], sc,
                                      compute_dtype)
                return x + y.reshape(N, S, D), dict(zero, **moe)
            return layer

        runs = alike_runs(L, lambda i: kinds[i],
                          lambda i: layer_leaves(params, i, held if kinds[i] == "experts" else None,
                                                 parts="ud"),
                          layer_of)
        return c.finish(*run_layers(c.embed(), runs, zero))

    return decoder(
        "nemotron_h", num_tokens, arch, leaves, groups, body, eps=eps, mask=mask,
        compute_dtype=compute_dtype, counts=(("ssm_head", G), ("kv_head", Hkv)),
        masks=("ssm_head",), held=held, counters=counters or None,
        profile={"routed_share": K / E,
                 "attention": {f"l{i}.attn": (H, hd, hd) for i, kind in enumerate(kinds)
                               if kind == "attention"}})
