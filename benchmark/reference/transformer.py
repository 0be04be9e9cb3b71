"""Plain reference: HeteroFL's masked-language-model Transformer encoder
(reference code src/models/transformer.py) as the dense sub-model a client at
one level holds, with its loss, gradients and local SGD.  float32,
`jax.numpy` at "highest" matmul precision, no kernels, no client vmap, its
own random numbers for token corruption and dropout.

Embedding: scaler(token) + scaler(position), LayerNorm, dropout.  Encoder
layer (post-norm): q, k, v = scaler(linear(x)) split into heads, softmax(q k^T
/ sqrt(head size)) v, scaler(linear_o), x = LN(x + dropout(.)); feed-forward
scaler(linear), exact GELU, dropout, scaler(linear), x = LN(x + dropout(.)).
Head: exact GELU(scaler(linear)), LayerNorm, linear to the vocabulary; logits
of tokens the client lacks set to zero; cross entropy of every position
against the uncorrupted token.  Input tokens become the extra `<mask>` id with
probability `mask_rate` in every forward.  `scaler` divides by the client's
rate.  A client at rate r holds the first ceil(r * width) embedding and
feed-forward channels and the first ceil(r * head size) channels of each head.

Leaves are named and laid out as the program's are ([in, out] matrices), which
is the interface, not the program's code.
"""

import functools

import jax
import jax.numpy as jnp
import numpy as np

from . import common

LABEL_AXES = {"embedding.tok.w": 0, "dec.l2.w": 1, "dec.l2.b": 0}


def index(shapes, model, rate):
    emb, heads = model["embedding_size"], model["num_heads"]
    hd = emb // heads
    e = np.arange(common.prefix(emb, rate))
    f = np.arange(common.prefix(model["hidden_size"], rate))
    per_head = np.concatenate([h * hd + np.arange(common.prefix(hd, rate))
                               for h in range(heads)])
    out = {}
    for name, shape in shapes.items():
        leaf = name.split(".", 1)[1] if name.startswith("enc") else name
        if name in ("embedding.tok.w", "embedding.pos.w"):
            ax = (np.arange(shape[0]), e)
        elif name == "dec.l1.w":
            ax = (e, e)
        elif name == "dec.l2.w":
            ax = (e, np.arange(shape[1]))
        elif name == "dec.l2.b":
            ax = (np.arange(shape[0]),)
        elif leaf in ("mha.q.w", "mha.k.w", "mha.v.w"):
            ax = (e, per_head)
        elif leaf in ("mha.q.b", "mha.k.b", "mha.v.b"):
            ax = (per_head,)
        elif leaf == "mha.o.w":
            ax = (per_head, e)
        elif leaf == "ff.l1.w":
            ax = (e, f)
        elif leaf == "ff.l1.b":
            ax = (f,)
        elif leaf == "ff.l2.w":
            ax = (f, e)
        elif len(shape) == 1 and shape[0] == emb:
            ax = (e,)  # every remaining bias and LayerNorm leaf is emb-wide
        else:
            raise ValueError(f"transformer reference: unknown leaf {name!r}")
        out[name] = ax
    return out


def _ln(x, g, b, eps=1e-5):
    mean = jnp.mean(x, axis=-1, keepdims=True)
    var = jnp.mean((x - mean) ** 2, axis=-1, keepdims=True)
    return (x - mean) / jnp.sqrt(var + eps) * g + b


def forward(p, tokens, rate, key, *, heads, layers, dropout, mask_rate, mask_id):
    """Logits [rows, positions, vocabulary] of the sub-model in training."""
    n, s = tokens.shape
    keys = iter(jax.random.split(key, 2 + 3 * layers))

    def drop(x):
        keep = jax.random.bernoulli(next(keys), 1.0 - dropout, x.shape)
        return jnp.where(keep, x / (1.0 - dropout), 0.0)

    corrupt = jax.random.bernoulli(next(keys), mask_rate, tokens.shape)
    src = jnp.where(corrupt, mask_id, tokens)
    x = p["embedding.tok.w"][src] / rate + p["embedding.pos.w"][:s][None] / rate
    x = drop(_ln(x, p["embedding.norm.g"], p["embedding.norm.b"]))
    for i in range(layers):
        pre = f"enc{i}"

        def proj(h, x_in=x, pre=pre):
            y = (x_in @ p[f"{pre}.mha.{h}.w"] + p[f"{pre}.mha.{h}.b"]) / rate
            return y.reshape(n, s, heads, -1).transpose(0, 2, 1, 3)

        q, k, v = proj("q"), proj("k"), proj("v")
        scores = jnp.einsum("nhqd,nhkd->nhqk", q, k) / jnp.sqrt(float(q.shape[-1]))
        o = jnp.einsum("nhqk,nhkd->nhqd", jax.nn.softmax(scores, axis=-1), v)
        o = o.transpose(0, 2, 1, 3).reshape(n, s, -1)
        o = (o @ p[f"{pre}.mha.o.w"] + p[f"{pre}.mha.o.b"]) / rate
        x = _ln(x + drop(o), p[f"{pre}.norm1.g"], p[f"{pre}.norm1.b"])
        h = (x @ p[f"{pre}.ff.l1.w"] + p[f"{pre}.ff.l1.b"]) / rate
        h = drop(jax.nn.gelu(h, approximate=False))
        h = (h @ p[f"{pre}.ff.l2.w"] + p[f"{pre}.ff.l2.b"]) / rate
        x = _ln(x + drop(h), p[f"{pre}.norm2.g"], p[f"{pre}.norm2.b"])
    d = jax.nn.gelu((x @ p["dec.l1.w"] + p["dec.l1.b"]) / rate, approximate=False)
    d = _ln(d, p["dec.norm.g"], p["dec.norm.b"])
    return d @ p["dec.l2.w"] + p["dec.l2.b"]


def loss_fn(p, tokens, label_mask, rate, key, arch):
    logits = forward(p, tokens, rate, key, **arch)
    logits = jnp.where(label_mask > 0, logits, 0.0)
    logp = jax.nn.log_softmax(logits, axis=-1)
    return -jnp.mean(jnp.take_along_axis(logp, tokens[..., None], axis=-1))


@functools.partial(jax.jit, static_argnames=("rate", "bptt", "epochs", "arch",
                                             "hp"))
def _train(p, rows, label_mask, lr, key, *, rate, bptt, epochs, arch, hp):
    momentum, weight_decay = hp
    windows = rows.shape[1] // bptt
    grad = jax.value_and_grad(common.highest(
        lambda p_, t_, k_: loss_fn(p_, t_, label_mask, rate, k_, dict(arch))))

    def step(carry, t):
        p, buf, total = carry
        w = t % windows
        tokens = jax.lax.dynamic_slice(rows, (0, w * bptt), (rows.shape[0], bptt))
        loss, g = grad(p, tokens, jax.random.fold_in(key, t))
        p, buf = common.sgd_step(p, g, buf, lr, momentum, weight_decay)
        return (p, buf, total + loss), None

    buf = {k: jnp.zeros_like(v) for k, v in p.items()}
    (p, _, total), _ = jax.lax.scan(step, (p, buf, jnp.zeros(())),
                                    jnp.arange(epochs * windows))
    return p, total / (epochs * windows)


def local_train(sub, client, config, lr, key):
    """A client's local training: its token rows, window by window in order.
    Returns its trained sub-model and the mean of its window losses."""
    m, opt = config["model"], config["optimizer"]
    label_mask = np.zeros(m["num_tokens"], np.float32)
    label_mask[np.asarray(client["labels"])] = 1.0
    rows = np.asarray(client["rows"])
    if rows.shape[1] % m["bptt"]:
        raise ValueError("the reference handles whole windows only")
    arch = (("heads", m["num_heads"]), ("layers", m["num_layers"]),
            ("dropout", float(m["dropout"])), ("mask_rate", float(m["mask_rate"])),
            ("mask_id", m["num_tokens"]))
    return _train(sub, jnp.asarray(rows, jnp.int32), jnp.asarray(label_mask),
                  jnp.float32(lr), key, rate=float(client["rate"]),
                  bptt=int(m["bptt"]), epochs=int(client["epochs"]), arch=arch,
                  hp=(float(opt["momentum"]), float(opt["weight_decay"])))
