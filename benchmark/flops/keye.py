"""FLOPs one local SGD step of one client needs, from shapes: the matrix
multiplications of the dense sub-model the client holds (2 per multiply-add),
forward once and backward twice -- the indexer forward only, it has no
backward.  Counted: the attention's projections and its two products over the
SELECTED (query, key) pairs (query t reads min(t + 1, topk) keys), the
indexer's three projections and its score of every causal pair (none where a
row is no longer than ``topk``: it is not run), the router, the routed experts
at the EXPECTED number of (token, held expert) pairs -- `num_experts_per_tok *
held / num_experts` a token -- and the head.  Not counted: the embedding
look-up, norms, RoPE, `relu` and the weighted sum over the indexer's heads,
the top-k, softmax, the dispatch's gathers, the optimizer, recomputation under
`jax.checkpoint`, causal pairs the selection masks out (a dense-masked form
computes them all the same), rows of an expert's last tile that hold no token,
and channels the client does not hold (the masked engine computes them as
zeros: the `executed_*` functions give a step as the chip runs it, at full
width).
"""

import math


def _w(n, rate, multiple=1):
    k = int(math.ceil(n * rate))
    return -(-k // multiple) * multiple


def _held(model):
    return model["num_experts"] // model["expert_share"][1]


def selected_pairs(model):
    """(query, key) pairs of one row the attention reads: query t its
    min(t + 1, topk) selected keys."""
    s, k = model["bptt"], min(model["index_topk"], model["bptt"])
    return k * (k + 1) // 2 + (s - k) * k


def causal_pairs(model):
    return model["bptt"] * (model["bptt"] + 1) // 2


def sparse_attn_forward_flops(model, rate):
    """The two products of the selected attention, one row through every
    layer."""
    hd = _w(model["head_dim"], rate, 2)
    return model["num_hidden_layers"] * 2 * 2 * selected_pairs(model) \
        * model["num_attention_heads"] * hd


def index_forward_flops(model, rate):
    """The indexer, one row through every layer: its projections and the
    score of every causal pair; nothing where the row is no longer than
    ``topk``."""
    if model["bptt"] <= model["index_topk"]:
        return 0
    d, di, hi = _w(model["hidden_size"], rate), _w(model["index_head_dim"], rate, 2), \
        model["index_n_heads"]
    return model["num_hidden_layers"] * (
        model["bptt"] * 2 * d * (hi * di + di + hi) + 2 * causal_pairs(model) * hi * di)


def routed_forward_flops(model, rate):
    """The routed experts' three matmuls, one row of ``bptt`` tokens through
    every layer, at the expected pairs a token."""
    d, fe = _w(model["hidden_size"], rate), _w(model["moe_intermediate_size"], rate)
    pairs = model["num_experts_per_tok"] * _held(model) / model["num_experts"]
    return model["num_hidden_layers"] * model["bptt"] * pairs * 3 * 2 * d * fe


def trained_forward_flops(model, rate):
    """One row of ``bptt`` tokens through everything a gradient passes."""
    s, h, hkv = model["bptt"], model["num_attention_heads"], model["num_key_value_heads"]
    d, hd = _w(model["hidden_size"], rate), _w(model["head_dim"], rate, 2)
    per_layer = s * (2 * 2 * d * h * hd + 2 * 2 * d * hkv * hd) + s * 2 * d * model["num_experts"]
    return model["num_hidden_layers"] * per_layer + sparse_attn_forward_flops(model, rate) \
        + routed_forward_flops(model, rate) + s * 2 * d * model["num_tokens"]


def step_flops(config, rate):
    rows, m = config["federation"]["rows_per_user"], config["model"]
    return rows * (3 * trained_forward_flops(m, rate) + index_forward_flops(m, rate))


def executed_step_flops(config):
    """A step as the masked engine runs it: every client at full width."""
    return step_flops(config, 1.0)


def executed_sparse_attn_step_flops(config):
    """The selected attention's part of :func:`executed_step_flops`: what
    `sparse_attn_roofline_pct` holds the `attn` scope's time against."""
    rows = config["federation"]["rows_per_user"]
    return 3 * rows * sparse_attn_forward_flops(config["model"], 1.0)


def executed_index_step_flops(config):
    """The indexer's part of :func:`executed_step_flops` (forward only)."""
    return config["federation"]["rows_per_user"] * index_forward_flops(config["model"], 1.0)


def executed_routed_step_flops(config):
    """The routed experts' part of :func:`executed_step_flops`: what
    `keye_experts_roofline_pct` holds `keye_experts_ms.step` against."""
    rows = config["federation"]["rows_per_user"]
    return 3 * rows * routed_forward_flops(config["model"], 1.0)
