"""FLOPs one local SGD step of one client needs, from shapes: the matrix
multiplications of the dense sub-model the client holds (2 per multiply-add),
forward once and backward twice.  A looped model uses every layer leaf, the
exit gate and the head `total_ut_steps` times a step, so each is counted that
many times: the attention's four projections and its two products over the
causal (query, key) pairs, the SwiGLU's three, the gate's one column and the
head over the whole vocabulary, a pass each.  The embedding is looked up
once, before the first pass, and a look-up multiplies nothing.  Not counted:
norms, RoPE, softmax, the exit distribution and its entropy, the optimizer,
recomputation under `jax.checkpoint`, pairs above the diagonal, and channels
the client does not hold (the masked engine computes them as zeros all the
same: the `executed_*` functions give a step as the chip runs it, at full
width).
"""

import math


def _w(n, rate, multiple=1):
    k = int(math.ceil(n * rate))
    return -(-k // multiple) * multiple


def causal_pairs(model):
    return model["bptt"] * (model["bptt"] + 1) // 2


def applications(model):
    """Layer applications a step: every layer held, every pass."""
    return model["total_ut_steps"] * model["num_hidden_layers"]


def attn_forward_flops(model, rate):
    """The attention's two products over the causal pairs, one row through
    every layer application."""
    hd = _w(model["head_dim"], rate, 2)
    return applications(model) * 2 * 2 * causal_pairs(model) * model["num_attention_heads"] * hd


def head_forward_flops(model, rate):
    """The head over the whole vocabulary, one row, every pass."""
    return model["total_ut_steps"] * model["bptt"] * 2 * _w(model["hidden_size"], rate) \
        * model["num_tokens"]


def forward_flops(model, rate):
    """One window of ``bptt`` tokens of one row through the sub-model, all
    passes."""
    s, h, hkv = model["bptt"], model["num_attention_heads"], model["num_key_value_heads"]
    d, hd = _w(model["hidden_size"], rate), _w(model["head_dim"], rate, 2)
    f = _w(model["intermediate_size"], rate)
    layer = s * (2 * 2 * d * h * hd + 2 * 2 * d * hkv * hd + 3 * 2 * d * f)
    gate = model["total_ut_steps"] * s * 2 * d
    return applications(model) * layer + attn_forward_flops(model, rate) \
        + head_forward_flops(model, rate) + gate


def step_flops(config, rate):
    rows = config["federation"]["rows_per_user"]
    return 3 * rows * forward_flops(config["model"], rate)


def executed_step_flops(config):
    """A step as the masked engine runs it: every client at full width."""
    return step_flops(config, 1.0)


def executed_attn_step_flops(config):
    """The causal attention's part of :func:`executed_step_flops`: what
    `ouro_attn_roofline_pct` holds the `attn` scope's time against."""
    rows = config["federation"]["rows_per_user"]
    return 3 * rows * attn_forward_flops(config["model"], 1.0)
