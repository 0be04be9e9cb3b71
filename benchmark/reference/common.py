"""What the plain references share: the HeteroFL sub-model slice, one masked
SGD step, and the counted average, written from the paper (ICLR 2021,
arXiv:2010.01264, section 3 and Algorithm 1) and the reference training loop
(clip the gradient's global norm to 1, then SGD with momentum and weight
decay).  Plain `jax.numpy`, float32, no kernels, one client at a time.
Nothing here imports the program.
"""

import math

import jax
import jax.numpy as jnp
import numpy as np


def prefix(n, rate):
    """Channels a client at ``rate`` holds of a width-``n`` layer."""
    return int(math.ceil(n * rate))


def take(params, index):
    """The sub-model: each leaf cut to its index (a tuple of slices or index
    arrays, one per axis)."""
    return {k: np.asarray(v)[np.ix_(*index[k])] for k, v in params.items()}


def sgd_step(p, g, buf, lr, momentum, weight_decay, max_norm=1.0):
    """torch's clip_grad_norm_ then SGD(momentum, weight_decay), dampening 0,
    buffers starting at zero."""
    total = jnp.sqrt(sum(jnp.sum(x * x) for x in g.values()))
    coef = jnp.minimum(max_norm / (total + 1e-6), 1.0)
    new_p, new_buf = {}, {}
    for k in p:
        d = g[k] * coef + weight_decay * p[k]
        new_buf[k] = momentum * buf[k] + d
        new_p[k] = p[k] - lr * new_buf[k]
    return new_p, new_buf


def counted_average(global_params, trained, held, shared):
    """Algorithm 1's aggregation: every element of the global model becomes
    the mean over the clients that hold it; elements nobody holds stay.

    Per client: ``trained`` its sub-model after local training, ``held`` the
    index it was cut with, ``shared`` the part of that index it sends back
    (output rows only for the labels the client has, section 3 "masking
    trick"); both are tuples of index arrays, one per axis."""
    out = {}
    for k, v in global_params.items():
        v = np.asarray(v, np.float32)
        total = np.zeros(v.shape, np.float32)
        count = np.zeros(v.shape, np.float32)
        for sub, h, s in zip(trained, held, shared):
            local = [np.searchsorted(full, part) for full, part
                     in zip(h[k], s[k])]
            total[np.ix_(*s[k])] += np.asarray(sub[k], np.float32)[np.ix_(*local)]
            count[np.ix_(*s[k])] += 1.0
        out[k] = np.where(count > 0, total / np.maximum(count, 1.0), v)
    return out


def restrict(index, label_axes, labels):
    """``index`` with each output leaf's label axis cut to ``labels``."""
    out = dict(index)
    for k, axis in label_axes.items():
        axes = list(index[k])
        axes[axis] = np.intersect1d(axes[axis], labels)
        out[k] = tuple(axes)
    return out


def run_round(ref, config, global_params, clients, lr, seed):
    """One federated round by the plain reference.

    ``clients``: per active client a dict with ``rate``, ``labels`` (the
    label ids it holds), ``copies`` (how many slots of the round hold this
    client; each counts in the average) and the model's own data fields.
    Returns the new global parameters and each client's mean training loss,
    in order.
    """
    global_params = {k: np.asarray(v, np.float32) for k, v in global_params.items()}
    shapes = {k: v.shape for k, v in global_params.items()}
    trained, held, shared, losses = [], [], [], []
    for i, c in enumerate(clients):
        index = ref.index(shapes, config["model"], c["rate"])
        sub = take(global_params, index)
        key = jax.random.key((int(seed) * 1000003 + i) % (2 ** 63))
        new, loss = ref.local_train(sub, c, config, lr, key)
        for _ in range(int(c.get("copies", 1))):
            trained.append({k: np.asarray(v, np.float32) for k, v in new.items()})
            held.append(index)
            shared.append(restrict(index, ref.LABEL_AXES, c["labels"]))
        losses.append(float(loss))
    return counted_average(global_params, trained, held, shared), losses


def highest(fn):
    """Trace ``fn`` with float32 matmuls at full precision: on a TPU a float32
    matmul otherwise runs in bf16 passes."""
    def wrapped(*a, **kw):
        with jax.default_matmul_precision("highest"):
            return fn(*a, **kw)
    return wrapped
