"""FLOPs one local SGD step of one client needs, from shapes: the matrix
multiplications of the dense sub-model the client holds (2 per multiply-add),
forward once and backward twice, by part.  Counted: a Mamba layer's
projections (``D -> 2 E``, ``E -> D``), its depthwise taps, ``W_x`` (``E -> 160
+ 16 + 16``) and ``W_dt`` (``160 -> E``); a gated memory unit's two matrices;
an attention layer's ``W_qkv`` (a cross layer's ``W_q``) and ``W_o``;
DIFFERENTIAL ATTENTION AT ITS LEAST FORM -- for each of the 20 query pairs two
score products of 64 and two value products of 128 over the pairs a query
sees (the causal ones; a sliding layer's band) --; the feed-forwards' three
matrices a layer; the head.  Not counted: the embedding look-up, LayerNorms,
softmax, softplus, silu, the sub-norm, lam, THE SCAN (no product in it: its
elementwise multiply-adds are `scan_forward_flops`, 6 a (position, channel,
state), held against its roofline alone and left out of a step's FLOPs as
every other elementwise operation is), the optimizer, recomputation under
`jax.checkpoint`, a fused kernel's masked half-tiles on the diagonal, a second
score product where a softmax is split by value halves, and channels the
client does not hold (the masked engine computes them as zeros: the
`executed_*` functions give a step as the chip runs it, at full width).

`scan_forward_bytes`: the bytes the scan cannot avoid, float32: it reads ``xs``
and ``dt`` ``[S, E]``, ``B`` and ``C`` ``[S, 16]`` and writes ``y`` ``[S, E]``;
the ``[S, E, 16]`` decays and states need not leave the chip.
"""

import math


def _w(n, rate):
    return int(math.ceil(n * rate))


def _count(model, *kinds):
    return sum(k in kinds for k in model["layer_types"])


def _dims(model, rate):
    """(hidden, inner channels, feed-forward width, dims a head)."""
    d = model["hidden_size"]
    return (_w(d, rate), _w(model["expand"] * d, rate), _w(model["intermediate_size"], rate),
            _w(d // model["num_attention_heads"], rate))


def seen_pairs(model, kind):
    """(query, key) pairs a row's queries see: the causal ones, or a sliding
    layer's band."""
    s = model["bptt"]
    w = min(model["sliding_window"], s) if kind == "sliding" else s
    return w * (w + 1) // 2 + (s - w) * w


def mamba_projection_forward_flops(model, rate):
    """The Mamba layers' five products and their depthwise taps, one row."""
    d, e, _, _ = _dims(model, rate)
    rank, ns = model["dt_rank"], model["d_state"]
    per_token = 2 * d * 2 * e + 2 * e * d + 2 * model["d_conv"] * e \
        + 2 * e * (rank + 2 * ns) + 2 * rank * e
    return _count(model, "mamba") * model["bptt"] * per_token


def scan_forward_flops(model, rate):
    """The scan's elementwise multiply-adds, one row through every Mamba
    layer: the decay's argument, the decay on the state, the input's outer
    product, their sum, and the read by ``C`` (a product and a sum)."""
    _, e, _, _ = _dims(model, rate)
    return _count(model, "mamba") * 6 * model["bptt"] * e * model["d_state"]


def scan_forward_bytes(model, rate):
    """The scan's reads and writes, one row through every Mamba layer."""
    _, e, _, _ = _dims(model, rate)
    return _count(model, "mamba") * 4 * model["bptt"] * (3 * e + 2 * model["d_state"])


def gmu_forward_flops(model, rate):
    d, e, _, _ = _dims(model, rate)
    return _count(model, "gmu") * model["bptt"] * 2 * 2 * d * e


def attn_projection_forward_flops(model, rate):
    """``W_qkv`` (a cross layer's ``W_q``) and ``W_o``, one row."""
    d, _, _, hd = _dims(model, rate)
    q, kv = model["num_attention_heads"] * hd, model["num_key_value_heads"] * hd
    own = _count(model, "sliding", "full") * (2 * d * (q + 2 * kv) + 2 * q * d)
    return model["bptt"] * (own + _count(model, "cross") * (2 * d * q + 2 * q * d))


def diff_attn_forward_flops(model, rate):
    """Differential attention at its least form, one row through every
    attention layer: a query pair's two score products of a head's dims and
    two value products of twice that, over the pairs a query sees."""
    _, _, _, hd = _dims(model, rate)
    per_pair = (model["num_attention_heads"] // 2) * 2 * (2 * hd + 2 * 2 * hd)
    return sum(seen_pairs(model, k) * per_pair for k in model["layer_types"]
               if k in ("sliding", "full", "cross"))


def ffn_forward_flops(model, rate):
    d, _, f, _ = _dims(model, rate)
    return len(model["layer_types"]) * model["bptt"] * 3 * 2 * d * f


def head_forward_flops(model, rate):
    return model["bptt"] * 2 * _dims(model, rate)[0] * model["num_tokens"]


def forward_flops(model, rate):
    """One row of ``bptt`` tokens through the sub-model."""
    return (mamba_projection_forward_flops(model, rate) + gmu_forward_flops(model, rate)
            + attn_projection_forward_flops(model, rate) + diff_attn_forward_flops(model, rate)
            + ffn_forward_flops(model, rate) + head_forward_flops(model, rate))


def step_flops(config, rate):
    return 3 * config["federation"]["rows_per_user"] * forward_flops(config["model"], rate)


def _executed(config, fn):
    return 3 * config["federation"]["rows_per_user"] * fn(config["model"], 1.0)


def executed_step_flops(config):
    """A step as the masked engine runs it: every client at full width."""
    return step_flops(config, 1.0)


def executed_scan_step_flops(config):
    """The scan's elementwise work: `phi4_scan_roofline_pct`'s compute side
    (held against the bf16 peak it is 0.07 ms a step: it never binds)."""
    return _executed(config, scan_forward_flops)


def executed_scan_step_bytes(config):
    """The scan's bytes, forward once and backward twice (the backward reads
    what the forward read and ``dy``, and writes a cotangent for every
    input): `phi4_scan_roofline_pct`'s memory side."""
    return _executed(config, scan_forward_bytes)


def executed_diff_attn_step_flops(config):
    """Differential attention's least form: what `diff_attn_roofline_pct`
    holds `diff_attn_ms.step` against, whatever form ran."""
    return _executed(config, diff_attn_forward_flops)


def executed_ffn_step_flops(config):
    """The feed-forwards' part of :func:`executed_step_flops`."""
    return _executed(config, ffn_forward_flops)
