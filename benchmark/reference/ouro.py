"""Plain reference: Ouro-2.6B's looped decoder (``model_type: ouro``,
huggingface.co/ByteDance/Ouro-2.6B ``config.json``; the LoopLM of "Scaling
Latent Reasoning via Looped Language Models", arXiv:2510.25741) as the dense
sub-model a client at one level holds, with its expected loss over the
passes, gradients and local SGD.  float32, `jax.numpy` at "highest" matmul
precision, no kernels, no client vmap.  The ``R`` passes and the ``N`` layers
are two Python loops (:func:`passes_unrolled`: the ``R x N`` layer
applications one after another, each reading the SAME leaves); what the chip
compiles is the same two loops as two `lax.scan`s (:func:`passes`; see the
departures).

``x`` ``[T, D]``, ``rms(x, g) = x / sqrt(mean(x^2) + eps) * g``, ``s(.)`` =
HeteroFL's Scaler (divide by the client's rate):

  layer l:   h = rms(x, g1_l)
             q, k, v = s(h Wq), s(h Wk), s(h Wv) -> [T, 16, d]
             q, k = q cos + rotate_half(q) sin, likewise k   (dim i with dim i
                    + d/2, angle pos * theta^(-2i/128))
             x = x + rms(s((softmax_causal(q k^T / sqrt(d)) v) Wo), g2_l)
             x = x + rms(s((silu(s(h' Wg)) * s(h' Wu)) Wd), g4_l),  h' = rms(x, g3_l)
  pass t:    for l in 0..N-1: x = layer_l(x);  h_t = rms(x, g_f);  x = h_t
             z_t = h_t W_head, logits of tokens the client lacks set to zero
             lam_t = sigmoid(h_t w_gate + b_gate)
  exit:      p_t = lam_t prod_{j<t}(1 - lam_j) for t < R;  p_R = prod_{j<R}(1 - lam_j)
  loss:      mean over target positions i (position i against token i + 1
             inside a row) of  sum_t p_t[i] nll(z_t[i], y[i+1]) - beta H(p[i]),
             H(p) = -sum_t p_t log p_t

Departures from the published code, none of which changes a value at rate 1:

- ``q_proj`` and ``k_proj`` are STORED with each head's columns permuted so
  that a rotary pair is adjacent (stored ``2i`` = published ``i``, stored ``2i
  + 1`` = published ``i + d/2``), so that a per-head prefix keeps whole
  pairs.  This file turns them back (:func:`_published_order`) and applies the
  published half-split RoPE; a sliced head of ``d`` dims holds pairs ``0 ..
  d/2 - 1`` with their full-width frequencies.
- The width slice, the Scaler and zero-filled logits are HeteroFL's.
- Memory only: attention runs in query blocks and the head in blocks of
  positions (`lax.map`, each block under ``jax.checkpoint``), and every layer
  application under ``jax.checkpoint``.
- Compile size only: the two loops are compiled as two `lax.scan`s.  Unrolled,
  ONE level's program is 787 MB of device code for the chip (814 MB
  serialized; a layer application at "highest" precision is 16 MB forward and
  backward at the narrowest level and 50 MB at the widest), over the chip
  machine's 192 MiB compile cache: an entry over the cap is dropped and takes
  the cell's other entries with it, and every run then compiled everything
  again, 200-290 s of reference a run (PERF.md, PR 40, my chip call 2; the
  expert references scan their experts for the same reason, PR 28).  With the
  layers alone scanned a level is 23-46 MB compressed, 170 MB the five: still
  over.  `benchmark/tests/test_ouro.py` holds the scanned form to the Python
  loops at the tiny size, value and gradients.

Not in ``config.json`` and stated in the benchmark configuration's
``assumed``: ``beta`` (``exit_entropy_beta``), the gate's bias, and that the
normed state is what the next pass reads.

Leaves are named and laid out as the program's are ([in, out] matrices), which
is the interface, not the program's code.
"""

import concurrent.futures
import functools

import jax
import jax.numpy as jnp
import numpy as np

from . import common

LABEL_AXES = {"embedding.tok.w": 0, "head.w": 1}

ATTN_BLOCK = 512
HEAD_BLOCK = 1024


def _widths(model, rate):
    p = common.prefix
    d = p(model["head_dim"], rate)
    return {"D": p(model["hidden_size"], rate), "d": d + d % 2,
            "F": p(model["intermediate_size"], rate)}


def index(shapes, model, rate):
    w = _widths(model, rate)
    e, f = np.arange(w["D"]), np.arange(w["F"])

    def heads(n):
        return np.concatenate([h * model["head_dim"] + np.arange(w["d"]) for h in range(n)])

    q, kv = heads(model["num_attention_heads"]), heads(model["num_key_value_heads"])
    out = {}
    for name, shape in shapes.items():
        leaf = name.split(".", 1)[1] if name[0] == "l" and name[1].isdigit() else name
        if name == "embedding.tok.w":
            ax = (np.arange(shape[0]), e)
        elif name == "head.w":
            ax = (e, np.arange(shape[1]))
        elif name == "exit.w":
            ax = (e, np.arange(1))
        elif name == "exit.b":
            ax = (np.arange(1),)
        elif leaf == "attn.q.w":
            ax = (e, q)
        elif leaf in ("attn.k.w", "attn.v.w"):
            ax = (e, kv)
        elif leaf == "attn.o.w":
            ax = (q, e)
        elif leaf in ("mlp.g.w", "mlp.u.w"):
            ax = (e, f)
        elif leaf == "mlp.d.w":
            ax = (f, e)
        elif leaf in ("norm.g", "norm1.g", "norm2.g", "norm3.g", "norm4.g"):
            ax = (e,)
        else:
            raise ValueError(f"ouro reference: unknown leaf {name!r}")
        out[name] = ax
    return out


def _rms(x, g, eps):
    return x / jnp.sqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) * g


def _published_order(t):
    """The last axis (a head's stored dims, rotary pairs adjacent) back in the
    published order: first halves of all pairs, then second halves."""
    d = t.shape[-1]
    return jnp.swapaxes(t.reshape(t.shape[:-1] + (d // 2, 2)), -1, -2).reshape(t.shape)


def _rope_half(x, theta, full):
    """Half-split RoPE of ``x`` ``[N, S, H, d]``: dim i turns with dim i +
    d/2 by ``pos * theta^(-2i/full)``, ``d`` a client's share of ``full``."""
    s, d = x.shape[1], x.shape[-1]
    freq = theta ** (-2.0 * jnp.arange(d // 2, dtype=jnp.float32) / full)
    ang = jnp.arange(s, dtype=jnp.float32)[:, None] * freq[None, :]
    ang = jnp.concatenate([ang, ang], axis=-1)[None, :, None, :]
    rotated = jnp.concatenate([-x[..., d // 2:], x[..., :d // 2]], axis=-1)
    return x * jnp.cos(ang) + rotated * jnp.sin(ang)


def _attention(q, k, v):
    """Causal softmax attention ``[N, S, H, d]``, query block by query block
    against every key (the keys after a query masked out); scores of one
    block only are alive at a time (and recomputed for the backward)."""
    n, s_len = q.shape[:2]
    size = ATTN_BLOCK if s_len % ATTN_BLOCK == 0 else s_len
    scale = 1.0 / np.sqrt(q.shape[-1])

    def block(xs):
        q_b, start = xs
        sc = jnp.einsum("nqhd,nkhd->nhqk", q_b, k) * scale
        q_pos = start + jnp.arange(size)
        sc = jnp.where(q_pos[:, None] >= jnp.arange(s_len)[None, :], sc, -jnp.inf)
        return jnp.einsum("nhqk,nkhd->nqhd", jax.nn.softmax(sc, axis=-1), v)

    blocks = jnp.moveaxis(q.reshape((n, s_len // size, size) + q.shape[2:]), 1, 0)
    out = jax.lax.map(jax.checkpoint(block), (blocks, jnp.arange(0, s_len, size)))
    return jnp.moveaxis(out, 0, 1).reshape((n, s_len) + out.shape[3:])


def attention_mixer(lp, h, rate, arch):
    n, s, _ = h.shape
    heads, kv_heads = arch["heads"], arch["kv_heads"]
    q = (h @ lp["attn.q.w"] / rate).reshape(n, s, heads, -1)
    k = (h @ lp["attn.k.w"] / rate).reshape(n, s, kv_heads, -1)
    v = (h @ lp["attn.v.w"] / rate).reshape(n, s, kv_heads, -1)
    q, k = (_rope_half(_published_order(t), arch["theta"], arch["head_dim"]) for t in (q, k))
    k, v = (jnp.repeat(t, heads // kv_heads, axis=2) for t in (k, v))
    return _attention(q, k, v).reshape(n, s, -1) @ lp["attn.o.w"] / rate


def layer(lp, x, rate, arch):
    """One application of a decoder layer on ``x`` [N, S, D]: both sub-blocks
    normed on their way in and on their way out.  ``lp``: the layer's leaves
    without their ``l{i}.`` prefix."""
    arch = dict(arch)
    eps = arch["eps"]
    x = x + _rms(attention_mixer(lp, _rms(x, lp["norm1.g"], eps), rate, arch), lp["norm2.g"], eps)
    h = _rms(x, lp["norm3.g"], eps)
    gate = jax.nn.silu(h @ lp["mlp.g.w"] / rate)
    y = (gate * (h @ lp["mlp.u.w"] / rate)) @ lp["mlp.d.w"] / rate
    return x + _rms(y, lp["norm4.g"], eps)


def _layer_leaves(p, i):
    pre = f"l{i}."
    return {k[len(pre):]: v for k, v in p.items() if k.startswith(pre)}


def passes_unrolled(p, tokens, rate, arch):
    """The final normed state after each pass, ``R`` arrays ``[rows,
    positions, D]``, as two Python loops: ``R x N`` layer applications one
    after another, each reading the SAME leaves.  The statement of the
    mathematics; the tests hold :func:`passes` to it."""
    a = dict(arch)
    x = p["embedding.tok.w"][tokens]
    states = []
    for _ in range(a["passes"]):
        for i in range(a["layers"]):
            x = layer(_layer_leaves(p, i), x, rate, arch)
        x = _rms(x, p["norm.g"], a["eps"])
        states.append(x)
    return states


def passes(p, tokens, rate, arch):
    """:func:`passes_unrolled` as it is compiled: the two loops as two
    `lax.scan`s over the stacked leaves, each layer application under
    ``jax.checkpoint``; ``[R, rows, positions, D]``."""
    a = dict(arch)
    stack = [_layer_leaves(p, i) for i in range(a["layers"])]
    stacked = {k: jnp.stack([lp[k] for lp in stack]) for k in stack[0]}
    one = jax.checkpoint(layer, static_argnums=(2, 3))

    def one_pass(x, _):
        x, _ = jax.lax.scan(lambda x_, lp: (one(lp, x_, rate, arch), None), x, stacked)
        x = _rms(x, p["norm.g"], a["eps"])
        return x, x

    return jax.lax.scan(one_pass, p["embedding.tok.w"][tokens], None, length=a["passes"])[1]


def _nll(p, h, targets, label_mask):
    """Negative log-likelihood ``[rows, positions]`` of ``targets`` under one
    pass's states ``h``, the head a block of positions at a time."""
    n, s, d = h.shape
    size = HEAD_BLOCK if (n * s) % HEAD_BLOCK == 0 else n * s

    def block(xs):
        h_b, t_b = xs
        logits = jnp.where(label_mask > 0, h_b @ p["head.w"], 0.0)
        return -jnp.take_along_axis(jax.nn.log_softmax(logits, axis=-1), t_b[:, None], axis=-1)[:, 0]

    out = jax.lax.map(jax.checkpoint(block), (h.reshape(-1, size, d), targets.reshape(-1, size)))
    return out.reshape(n, s)


def pass_losses(p, states, tokens, label_mask):
    """(nll ``[R, rows, positions - 1]``, exit distribution likewise) over the
    target positions, from the ``R`` passes' final normed ``states``."""
    nll, lam = [], []
    # position i against token i + 1; the last position of a window has no
    # target: it is scored against a filler and dropped
    targets = jnp.concatenate([tokens[:, 1:], tokens[:, :1]], axis=1)
    for h in states:
        nll.append(_nll(p, h, targets, label_mask)[:, :-1])
        lam.append(jax.nn.sigmoid(h[:, :-1] @ p["exit.w"] + p["exit.b"])[..., 0])
    stay, probs = 1.0, []
    for lam_t in lam[:-1]:
        probs.append(lam_t * stay)
        stay = stay * (1.0 - lam_t)
    probs.append(stay * jnp.ones_like(lam[-1]))  # the last pass takes what is left
    return jnp.stack(nll), jnp.stack(probs)


def loss_fn(p, tokens, label_mask, rate, arch, states_of=passes):
    nll, probs = pass_losses(p, states_of(p, tokens, rate, arch), tokens, label_mask)
    entropy = -jnp.sum(probs * jnp.log(probs), axis=0)
    return jnp.mean(jnp.sum(probs * nll, axis=0) - dict(arch)["beta"] * entropy)


def arch_of(model):
    """The static description :func:`passes` takes (hashable)."""
    return (("heads", model["num_attention_heads"]),
            ("kv_heads", model["num_key_value_heads"]), ("head_dim", model["head_dim"]),
            ("layers", model["num_hidden_layers"]), ("passes", model["total_ut_steps"]),
            ("beta", float(model["exit_entropy_beta"])),
            ("eps", float(model["rms_norm_eps"])), ("theta", float(model["rope_theta"])))


@functools.partial(jax.jit, static_argnames=("rate", "bptt", "epochs", "arch", "hp"))
def _train(p, rows, label_mask, lr, *, rate, bptt, epochs, arch, hp):
    momentum, weight_decay = hp
    windows = rows.shape[1] // bptt
    grad = jax.value_and_grad(common.highest(
        lambda p_, t_: loss_fn(p_, t_, label_mask, rate, arch)))

    def step(carry, t):
        p, buf, total = carry
        w = t % windows
        tokens = jax.lax.dynamic_slice(rows, (0, w * bptt), (rows.shape[0], bptt))
        loss, g = grad(p, tokens)
        p, buf = common.sgd_step(p, g, buf, lr, momentum, weight_decay)
        return (p, buf, total + loss), None

    buf = {k: jnp.zeros_like(v) for k, v in p.items()}
    (p, _, total), _ = jax.lax.scan(step, (p, buf, jnp.zeros(())),
                                    jnp.arange(epochs * windows))
    return p, total / (epochs * windows)


_POOL = concurrent.futures.ThreadPoolExecutor(8)
_PROGRAMS = {}


def _program(shapes, rows_shape, model, hp, rate, epochs):
    """:func:`_train` compiled for a level's sub-model (a future).  ``shapes``
    may be any level's: only the axes no level slices are read from it."""
    key = (rate, rows_shape, epochs, hp)
    if key not in _PROGRAMS:
        sub = {k: jax.ShapeDtypeStruct(tuple(len(a) for a in axes), jnp.float32)
               for k, axes in index(shapes, model, rate).items()}
        lowered = _train.lower(
            sub, jax.ShapeDtypeStruct(rows_shape, jnp.int32),
            jax.ShapeDtypeStruct((model["num_tokens"],), jnp.float32),
            jax.ShapeDtypeStruct((), jnp.float32), rate=rate, bptt=int(model["bptt"]),
            epochs=epochs, arch=arch_of(model), hp=hp)
        _PROGRAMS[key] = _POOL.submit(lowered.compile)
    return _PROGRAMS[key]


def local_train(sub, client, config, lr, key):
    """A client's local training: its token rows, window by window in order.
    Returns its trained sub-model and the mean of its window losses.  (No
    dropout and no token corruption: ``key`` is not used.)

    The first call starts the compilation of every level's program side by
    side (a level's program takes the chip's compiler minutes at "highest"
    precision, and a cohort holds up to five levels)."""
    m, opt = config["model"], config["optimizer"]
    label_mask = np.zeros(m["num_tokens"], np.float32)
    label_mask[np.asarray(client["labels"])] = 1.0
    rows = np.asarray(client["rows"], np.int32)
    if rows.shape[1] % m["bptt"]:
        raise ValueError("the reference handles whole windows only")
    shapes = {k: np.shape(v) for k, v in sub.items()}
    rate = float(client["rate"])
    for r in sorted({rate, *map(float, m.get("level_rates", {}).values())}):
        program = _program(shapes, rows.shape, m, (float(opt["momentum"]),
                           float(opt["weight_decay"])), r, int(client["epochs"]))
        if r == rate:
            mine = program
    return mine.result()(sub, rows, label_mask, np.float32(lr))
