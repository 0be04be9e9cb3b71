"""Plain reference: HeteroFL's pre-activation ResNet-18 (reference code
src/models/resnet.py) as the dense sub-model a client at one level holds,
with its loss, gradients and local SGD.  float32, `jax.numpy` matmuls at
"highest" precision (a convolution is written out as its taps), no kernels, no client vmap, its own random
numbers for shuffling and augmentation.

Block: out = relu(bn(scaler(x))); shortcut = 1x1 conv(out) where the shape
changes, else x; out = conv3x3(out); out = conv3x3(relu(bn(scaler(out))));
x = out + shortcut.  Head: relu(bn(scaler(x))), global average pool, linear,
logits of labels the client lacks set to zero, cross entropy.  `scaler`
divides by the client's rate in training.  BN uses batch statistics
(momentum=None, track_running_stats=False in training).

Leaves are named and laid out as the program's are (NHWC activations, HWIO
kernels, [in, out] linear), which is the interface, not the program's code.
"""

import functools

import jax
import jax.numpy as jnp
import numpy as np

from . import common

LABEL_AXES = {"linear.w": 1, "linear.b": 0}


def _stage_of(name):
    """Stage (0-3) whose width the leaf's output axis follows."""
    return int(name.split(".")[0][len("layer"):])


def index(shapes, model, rate):
    """Which entries of every leaf a client at ``rate`` holds: the first
    ceil(rate * width) channels of each stage, chained through the network;
    the input image channels and the classes are never cut."""
    hidden = model["hidden_size"]
    width = [common.prefix(h, rate) for h in hidden]
    out = {}
    for name, shape in shapes.items():
        if name == "conv1.w":
            ax = (shape[0], shape[1], shape[2], width[0])
        elif name.startswith("linear."):
            ax = (width[-1], shape[1]) if name == "linear.w" else (shape[0],)
        elif name.startswith("n4."):
            ax = (width[-1],)
        else:
            s, block = _stage_of(name), int(name.split(".")[1])
            w_in = width[s] if block > 0 or s == 0 else width[s - 1]
            leaf = name.split(".", 2)[2]
            if leaf in ("n1.g", "n1.b"):
                ax = (w_in,)
            elif leaf in ("n2.g", "n2.b"):
                ax = (width[s],)
            elif leaf in ("conv1.w", "shortcut.w"):
                ax = (shape[0], shape[1], w_in, width[s])
            elif leaf == "conv2.w":
                ax = (shape[0], shape[1], width[s], width[s])
            else:
                raise ValueError(f"resnet18 reference: unknown leaf {name!r}")
        out[name] = tuple(np.arange(n) for n in ax)
    return out


def _conv(x, w, stride, pad):
    """Convolution from its definition: for each kernel tap, the input shifted
    by that tap (zero border of ``pad``) times the tap's [in, out] matrix.
    (`lax.conv` at "highest" precision sent the TPU's compiler past 20 GB of
    host memory on the 4-channel level: my AOT compile, PR 25.)"""
    kh, kw = w.shape[:2]
    h, wd = x.shape[1:3]
    ho = (h + 2 * pad - kh) // stride + 1
    wo = (wd + 2 * pad - kw) // stride + 1
    xp = jnp.pad(x, ((0, 0), (pad, pad), (pad, pad), (0, 0)))
    out = 0.0
    for i in range(kh):
        for j in range(kw):
            out = out + xp[:, i:i + stride * (ho - 1) + 1:stride,
                           j:j + stride * (wo - 1) + 1:stride, :] @ w[i, j]
    return out


def _bn(x, g, b, eps=1e-5):
    mean = jnp.mean(x, axis=(0, 1, 2), keepdims=True)
    var = jnp.mean((x - mean) ** 2, axis=(0, 1, 2), keepdims=True)
    return (x - mean) / jnp.sqrt(var + eps) * g + b


def forward(p, img, rate, num_blocks):
    """Logits of the sub-model ``p`` in training mode."""
    x = _conv(img, p["conv1.w"], 1, 1)
    for s, blocks in enumerate(num_blocks):
        for b in range(blocks):
            pre = f"layer{s}.{b}"
            stride = 2 if (s > 0 and b == 0) else 1
            out = jax.nn.relu(_bn(x / rate, p[f"{pre}.n1.g"], p[f"{pre}.n1.b"]))
            short = (_conv(out, p[f"{pre}.shortcut.w"], stride, 0)
                     if f"{pre}.shortcut.w" in p else x)
            out = _conv(out, p[f"{pre}.conv1.w"], stride, 1)
            out = jax.nn.relu(_bn(out / rate, p[f"{pre}.n2.g"], p[f"{pre}.n2.b"]))
            x = _conv(out, p[f"{pre}.conv2.w"], 1, 1) + short
    x = jax.nn.relu(_bn(x / rate, p["n4.g"], p["n4.b"]))
    x = jnp.mean(x, axis=(1, 2))
    return x @ p["linear.w"] + p["linear.b"]


def loss_fn(p, img, label, label_mask, rate, num_blocks):
    logits = forward(p, img, rate, num_blocks)
    logits = jnp.where(label_mask > 0, logits, 0.0)
    logp = jax.nn.log_softmax(logits, axis=-1)
    return -jnp.mean(jnp.take_along_axis(logp, label[:, None], axis=-1))


def _augment(key, x):
    """RandomCrop(32, padding=4) and RandomHorizontalFlip, per image."""
    n = x.shape[0]
    k1, k2 = jax.random.split(key)
    xp = jnp.pad(x, ((0, 0), (4, 4), (4, 4), (0, 0)))
    dy, dx = jax.random.randint(k1, (2, n), 0, 9)
    rows = dy[:, None] + jnp.arange(32)[None, :]
    cols = dx[:, None] + jnp.arange(32)[None, :]
    out = xp[jnp.arange(n)[:, None, None], rows[:, :, None], cols[:, None, :]]
    flip = jax.random.bernoulli(k2, 0.5, (n,))
    return jnp.where(flip[:, None, None, None], out[:, :, ::-1], out)


@functools.partial(jax.jit, static_argnames=("rate", "num_blocks", "batch",
                                             "epochs", "hp"))
def _train(p, x, y, label_mask, lr, key, *, rate, num_blocks, batch, epochs, hp):
    mean, std, momentum, weight_decay = hp
    n = x.shape[0]
    steps = n // batch
    perms = jnp.concatenate([jax.random.permutation(k, n)[: steps * batch]
                             for k in jax.random.split(key, epochs)])
    grad = jax.value_and_grad(common.highest(loss_fn))

    def step(carry, t):
        p, buf, total = carry
        ids = jax.lax.dynamic_slice(perms, (t * batch,), (batch,))
        img = _augment(jax.random.fold_in(key, t), x[ids]).astype(jnp.float32)
        img = (img / 255.0 - jnp.asarray(mean)) / jnp.asarray(std)
        loss, g = grad(p, img, y[ids], label_mask, rate, num_blocks)
        p, buf = common.sgd_step(p, g, buf, lr, momentum, weight_decay)
        return (p, buf, total + loss), None

    buf = {k: jnp.zeros_like(v) for k, v in p.items()}
    (p, _, total), _ = jax.lax.scan(step, (p, buf, jnp.zeros(())),
                                    jnp.arange(epochs * steps))
    return p, total / (epochs * steps)


def local_train(sub, client, config, lr, key):
    """A client's local training: ``epochs`` passes over its images in
    shuffled batches.  Returns its trained sub-model and the mean of its
    batch losses (what the reference's logger reports, batches being equal)."""
    opt, norm = config["optimizer"], config["data"]["normalize"]
    classes = config["data"]["sizes"]["classes"]
    label_mask = np.zeros(classes, np.float32)
    label_mask[np.asarray(client["labels"])] = 1.0
    return _train(
        sub, jnp.asarray(client["x"]), jnp.asarray(client["y"], jnp.int32),
        jnp.asarray(label_mask), jnp.float32(lr), key,
        rate=float(client["rate"]), num_blocks=tuple(config["model"]["num_blocks"]),
        batch=int(config["federation"]["batch_size"]), epochs=int(client["epochs"]),
        hp=(tuple(norm["mean"]), tuple(norm["std"]), float(opt["momentum"]),
            float(opt["weight_decay"])))
