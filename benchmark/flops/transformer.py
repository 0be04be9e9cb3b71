"""FLOPs one local SGD step of one client needs, from shapes: the matrix
multiplications of the dense sub-model the client holds (2 per multiply-add),
forward once and backward twice.  Embedding look-ups, norms, softmax, the
optimizer and channels the client does not hold are not counted.
"""

import math


def forward_flops(model, rate):
    """One window of ``bptt`` tokens of one row through the sub-model."""
    s = model["bptt"]
    e = int(math.ceil(model["embedding_size"] * rate))
    f = int(math.ceil(model["hidden_size"] * rate))
    hd = int(math.ceil(model["embedding_size"] // model["num_heads"] * rate))
    a = hd * model["num_heads"]
    layer = 3 * 2 * s * e * a          # q, k, v
    layer += 2 * 2 * s * s * a         # q k^T and attention x v
    layer += 2 * s * a * e             # output projection
    layer += 2 * 2 * s * e * f         # feed-forward
    head = 2 * s * e * e + 2 * s * e * model["num_tokens"]
    return model["num_layers"] * layer + head


def step_flops(config, rate):
    rows = config["federation"]["rows_per_user"]
    return 3 * rows * forward_flops(config["model"], rate)
