"""FLOPs one local SGD step of one client needs, from shapes: the matrix
multiplications of the dense sub-model the client holds (2 per multiply-add),
forward once and backward twice.  Counted: the attention projections, causal
attention (each query against the keys up to itself), the dense feed-forward,
the router, the shared experts, the routed experts at the EXPECTED number of
(token, held expert) pairs -- `num_experts_per_tok * held / n_routed_experts` a
token, what a router that spreads its choices evenly sends this share -- and
the head.  Not counted: the embedding look-up, norms, RoPE, softmax, top-k,
the dispatch's gathers, the optimizer, recomputation under `jax.checkpoint`,
rows of an expert's last tile that hold no token, and channels the client does
not hold (the masked engine computes them as zeros all the same: the
`executed_*` functions give a step as the chip runs it, at full width).
"""

import math


def _w(n, rate, multiple=1):
    k = int(math.ceil(n * rate))
    return -(-k // multiple) * multiple


def _held(model):
    return model["n_routed_experts"] // model["expert_share"][1]


def routed_forward_flops(model, rate):
    """The routed experts' three matmuls, one row of ``bptt`` tokens through
    every expert layer, at the expected pairs a token."""
    d, fe = _w(model["hidden_size"], rate), _w(model["moe_intermediate_size"], rate)
    pairs = model["num_experts_per_tok"] * _held(model) / model["n_routed_experts"]
    layers = model["num_hidden_layers"] - model["first_k_dense_replace"]
    return layers * model["bptt"] * pairs * 3 * 2 * d * fe


def forward_flops(model, rate):
    """One window of ``bptt`` tokens of one row through the sub-model."""
    s, h = model["bptt"], model["num_attention_heads"]
    d = _w(model["hidden_size"], rate)
    dn, dv = _w(model["qk_nope_head_dim"], rate), _w(model["v_head_dim"], rate)
    dr, r = _w(model["qk_rope_head_dim"], rate, 2), _w(model["kv_lora_rank"], rate)
    f = _w(model["intermediate_size"], rate)
    fs = _w(model["moe_intermediate_size"] * model["n_shared_experts"], rate)
    proj = 2 * d * h * (dn + dr) + 2 * d * (r + dr) + 2 * r * h * (dn + dv) + 2 * h * dv * d
    pairs = s * (s + 1) // 2                       # (query, key) pairs of a causal row
    attn = 2 * pairs * h * (dn + dr) + 2 * pairs * h * dv
    dense = model["first_k_dense_replace"]
    expert = model["num_hidden_layers"] - dense
    total = model["num_hidden_layers"] * (s * proj + attn)
    total += dense * s * 3 * 2 * d * f
    total += expert * s * (2 * d * model["n_routed_experts"] + 3 * 2 * d * fs)
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
    `experts_roofline_pct` holds `experts_ms.step` against."""
    rows = config["federation"]["rows_per_user"]
    return 3 * rows * routed_forward_flops(config["model"], 1.0)
