"""FLOPs one local SGD step of one client needs, from shapes: the matrix
multiplications of the dense sub-model the client holds (2 per multiply-add),
forward once and backward twice.  Counted: the conv mixers' two projections
and their depthwise taps (3 multiply-adds a channel and token), the attention
layers' projections and causal attention (each query against the keys up to
itself), the dense feed-forward, the router, the routed experts at the
EXPECTED number of (token, held expert) pairs -- `num_experts_per_tok * held /
num_experts` a token, what a router that spreads its choices evenly sends this
share -- and the tied head.  Not counted: the embedding look-up, norms, the
gates' two products, RoPE, softmax, top-k, the dispatch's gathers, the
optimizer, recomputation under `jax.checkpoint`, rows of an expert's last tile
that hold no token, and channels the client does not hold (the masked engine
computes them as zeros all the same: the `executed_*` functions give a step as
the chip runs it, at full width).
"""

import math


def _w(n, rate, multiple=1):
    k = int(math.ceil(n * rate))
    return -(-k // multiple) * multiple


def _held(model):
    return model["num_experts"] // model["expert_share"][1]


def _layers(model):
    """(conv layers, attention layers, dense layers, expert layers)."""
    kinds = model["layer_types"]
    dense = model["num_dense_layers"]
    return (kinds.count("conv"), kinds.count("full_attention"), dense, len(kinds) - dense)


def shortconv_forward_flops(model, rate):
    """The conv mixers' two projections (``D -> 3 x channels`` as three
    leaves, ``channels -> D``), one row of ``bptt`` tokens through every conv
    layer."""
    d, dc = _w(model["hidden_size"], rate), _w(model["conv_dim"], rate)
    return _layers(model)[0] * model["bptt"] * 4 * 2 * d * dc


def routed_forward_flops(model, rate):
    """The routed experts' three matmuls, one row of ``bptt`` tokens through
    every expert layer, at the expected pairs a token."""
    d, fe = _w(model["hidden_size"], rate), _w(model["moe_intermediate_size"], rate)
    pairs = model["num_experts_per_tok"] * _held(model) / model["num_experts"]
    return _layers(model)[3] * model["bptt"] * pairs * 3 * 2 * d * fe


def forward_flops(model, rate):
    """One window of ``bptt`` tokens of one row through the sub-model."""
    s, h, hkv = model["bptt"], model["num_attention_heads"], model["num_key_value_heads"]
    d, dc = _w(model["hidden_size"], rate), _w(model["conv_dim"], rate)
    hd, f = _w(model["head_dim"], rate, 2), _w(model["intermediate_size"], rate)
    conv, attn, dense, expert = _layers(model)
    total = shortconv_forward_flops(model, rate) + conv * s * 2 * model["conv_L_cache"] * dc
    pairs = s * (s + 1) // 2                       # (query, key) pairs of a causal row
    total += attn * (s * (2 * 2 * d * h * hd + 2 * 2 * d * hkv * hd) + 2 * 2 * pairs * h * hd)
    total += dense * s * 3 * 2 * d * f
    total += expert * s * 2 * d * model["num_experts"]
    total += routed_forward_flops(model, rate)
    return total + s * 2 * d * model["num_tokens"]


def step_flops(config, rate):
    rows = config["federation"]["rows_per_user"]
    return 3 * rows * forward_flops(config["model"], rate)


def executed_step_flops(config):
    """A step as the masked engine runs it: every client at full width."""
    return step_flops(config, 1.0)


def executed_routed_step_flops(config):
    """The routed experts' part of :func:`executed_step_flops`: what
    `lfm2_experts_roofline_pct` holds `lfm2_experts_ms.step` against."""
    rows = config["federation"]["rows_per_user"]
    return 3 * rows * routed_forward_flops(config["model"], 1.0)


def executed_shortconv_step_flops(config):
    """The conv mixers' projections' part of :func:`executed_step_flops`: what
    `shortconv_roofline_pct` holds `shortconv_ms.step` against."""
    rows = config["federation"]["rows_per_user"]
    return 3 * rows * shortconv_forward_flops(config["model"], 1.0)
