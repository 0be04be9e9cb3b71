"""FLOPs one local SGD step of one client needs, from shapes: the matrix
multiplications of the dense sub-model the client holds (2 per multiply-add),
forward once and backward twice.  Counted: every layer's attention projections
and its gate at the layer's own head count, the attention's two products over
the pairs the layer's KIND allows (a full layer every causal pair, a sliding
layer the band: query t reads min(t + 1, window) keys), the dense SwiGLU, the
router, the shared expert, the routed experts at the EXPECTED number of
(token, held expert) pairs -- `num_experts_per_tok * held / num_experts` a
token -- and the head.  Not counted: the embedding look-up, norms, RoPE,
softmax, the gate's sigmoid and product, top-k, the dispatch's gathers, the
optimizer, recomputation under `jax.checkpoint`, masked-out pairs inside a
tile the kernels visit (a tile of t keys on a band of 512 holds band pairs
for about 512 / (512 + t) of its pairs: 1/2 at 512, 2/3 at 256, 4/5 at 128),
rows of an expert's last tile that hold no token, and channels the client
does not hold (the masked engine computes them as zeros: the `executed_*`
functions give a step as the chip runs it, at full width).
"""

import math


def _w(n, rate, multiple=1):
    k = int(math.ceil(n * rate))
    return -(-k // multiple) * multiple


def _held(model):
    return model["num_experts"] // model["expert_share"][1]


def causal_pairs(model):
    return model["bptt"] * (model["bptt"] + 1) // 2


def band_pairs(model):
    """(query, key) pairs of one row a sliding layer reads: query t itself and
    the window - 1 keys before it."""
    s, w = model["bptt"], min(model["sliding_window"], model["bptt"])
    return w * (w + 1) // 2 + (s - w) * w


def _head_dims(model, kind, rate):
    """(dims of a head in the score product, dims in the value product)."""
    hd = model["head_dim"]
    r = int(hd * model["rope_parameters"][kind].get("partial_rotary_factor", 1.0))
    return _w(r, rate, 2) + (_w(hd - r, rate) if r < hd else 0), _w(hd, rate, 2)


def attn_forward_flops(model, rate, kind):
    """The two products of every layer of ``kind``, one row."""
    pairs = causal_pairs(model) if kind == "full_attention" else band_pairs(model)
    dq, dv = _head_dims(model, kind, rate)
    return sum(2 * pairs * h * (dq + dv)
               for t, h in zip(model["layer_types"], model["num_attention_heads_per_layer"])
               if t == kind)


def routed_forward_flops(model, rate):
    """The routed experts' three matmuls, one row of ``bptt`` tokens through
    every expert layer, at the expected pairs a token."""
    d, fe = _w(model["hidden_size"], rate), _w(model["moe_intermediate_size"], rate)
    pairs = model["num_experts_per_tok"] * _held(model) / model["num_experts"]
    return model["mlp_layer_types"].count("sparse") * model["bptt"] * pairs * 3 * 2 * d * fe


def forward_flops(model, rate):
    """One row of ``bptt`` tokens through the sub-model."""
    s, hkv = model["bptt"], model["num_key_value_heads"]
    d = _w(model["hidden_size"], rate)
    f, fs = _w(model["intermediate_size"], rate), _w(model["shared_expert_intermediate_size"], rate)
    total = 0
    for kind, h, mlp in zip(model["layer_types"], model["num_attention_heads_per_layer"],
                            model["mlp_layer_types"]):
        dq, dv = _head_dims(model, kind, rate)
        total += s * 2 * d * (h * dq + hkv * dq + hkv * dv + h + h * dv)  # q, k, v, gate, o
        total += s * 3 * 2 * d * f if mlp == "dense" else \
            s * (2 * d * model["num_experts"] + 3 * 2 * d * fs)
    total += sum(attn_forward_flops(model, rate, kind) for kind in set(model["layer_types"]))
    return total + routed_forward_flops(model, rate) + s * 2 * d * model["num_tokens"]


def step_flops(config, rate):
    return 3 * config["federation"]["rows_per_user"] * forward_flops(config["model"], rate)


def executed_step_flops(config):
    """A step as the masked engine runs it: every client at full width."""
    return step_flops(config, 1.0)


def executed_window_step_flops(config):
    """The sliding layers' two products over the BAND pairs: what
    `swa_roofline_pct` holds the `swa` scope's time against."""
    rows = config["federation"]["rows_per_user"]
    return 3 * rows * attn_forward_flops(config["model"], 1.0, "sliding_attention")


def executed_full_attn_step_flops(config):
    """The full layers' two products over the causal pairs: what
    `laguna_full_attn_roofline_pct` holds the `attn` scope's time against."""
    rows = config["federation"]["rows_per_user"]
    return 3 * rows * attn_forward_flops(config["model"], 1.0, "full_attention")


def executed_routed_step_flops(config):
    """The routed experts' part of :func:`executed_step_flops`: what
    `laguna_experts_roofline_pct` holds `laguna_experts_ms.step` against."""
    rows = config["federation"]["rows_per_user"]
    return 3 * rows * routed_forward_flops(config["model"], 1.0)
