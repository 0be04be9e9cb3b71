"""FLOPs one local SGD step of one client needs, from shapes: the matrix
multiplications of the dense sub-model the client holds (convolutions and the
classifier; 2 per multiply-add with a real input, the zero border of a
padded convolution not counted), forward once and backward twice (gradients of
the input and of the kernel).  Norms, activations, the optimizer and channels
the client does not hold are not counted.
"""

import math


def taps(n, stride):
    """(output position, kernel tap) pairs of a 3-wide, pad-1 convolution
    over ``n`` inputs that meet a real input, not the zero border."""
    return sum(1 for o in range(0, n, stride) for k in (-1, 0, 1) if 0 <= o + k < n)


def forward_flops(model, data_shape, rate):
    """One image through the sub-model at ``rate``."""
    h, w, c_in = data_shape
    width = [int(math.ceil(x * rate)) for x in model["hidden_size"]]
    flops = 2 * taps(h, 1) * taps(w, 1) * c_in * width[0]
    planes = width[0]
    for s, blocks in enumerate(model["num_blocks"]):
        for b in range(blocks):
            stride = 2 if (s > 0 and b == 0) else 1
            flops += 2 * taps(h, stride) * taps(w, stride) * planes * width[s]  # conv1
            h, w = h // stride, w // stride
            if stride != 1 or planes != width[s]:
                flops += 2 * h * w * planes * width[s]                # 1x1 shortcut
            flops += 2 * taps(h, 1) * taps(w, 1) * width[s] * width[s]  # conv2
            planes = width[s]
    return flops + 2 * planes * model["classes"]


def step_flops(config, rate):
    model = dict(config["model"], classes=config["data"]["sizes"]["classes"])
    batch = config["federation"]["batch_size"]
    return 3 * batch * forward_flops(model, config["data"]["shape"], rate)
