"""Plain reference: LFM2-8B-A1B's decoder (``model_type: lfm2_moe``,
huggingface.co/LiquidAI/LFM2-8B-A1B ``config.json``; the equations are those
of transformers' ``modeling_lfm2_moe.py``) as the dense sub-model a client at
one level holds, with its next-token loss, gradients and local SGD.  float32,
`jax.numpy` at "highest" matmul precision, no kernels, no client vmap, the
layers one after another as a Python loop, one expert at a time over ALL
tokens.

Layer ``i``, ``x`` ``[T, D]``, ``rms(x, g) = x / sqrt(mean(x^2) + eps) * g``,
``s(.)`` = HeteroFL's Scaler (divide by the client's rate):

  h = rms(x, g_op)
  layer_types[i] == "conv":
      b, c, u = s(h W_b), s(h W_c), s(h W_u);  z = b * u
      y[t] = w[0] z[t-2] + w[1] z[t-1] + w[2] z[t]   (per row; nothing before a row's start)
      x = x + s((c * y) W_out)
  layer_types[i] == "full_attention":
      q = s(h Wq) -> [T, 32, d];  k, v = s(h Wk), s(h Wv) -> [T, 8, d]
      q, k = rms(q, g_q), rms(k, g_k) over each head's d dims
      q, k = q cos + rotate_half(q) sin, likewise k   (dim i with dim i + d/2,
             angle pos * theta^(-2i/64))
      k, v repeated 4x over the heads; p = softmax_causal(q k^T / sqrt(d));  x = x + s((p v) Wo)
  h = rms(x, g_ffn)
  i < num_dense_layers:  x = x + s((silu(s(h Wg)) * s(h Wu)) Wd)
  else:  sc = sigmoid(h Wr) over all 32;  sel = top4(sc + b)
         w = sc[sel] / (sum(sc[sel]) + 1e-6) * routed_scaling_factor
         x = x + sum_{e in sel, e held} w_e ffn_e(h)          (no shared expert)
  logits = rms(x, g_f) E^T with E the embedding (tied); logits of tokens the
  client lacks set to zero; loss = mean cross entropy of position t against
  token t + 1 inside a row.

Departures from the published code, none of which changes a value at rate 1
on one full share:

- ``in_proj`` is held as three leaves (``conv.in.b`` | ``conv.in.c`` |
  ``conv.in.u``: its three column blocks in the published order), so that
  each has a prefix to slice; the depthwise ``conv.weight[d, 0, j]`` as
  ``conv.taps.w[j, d]``.
- ``q_proj``, ``k_proj`` and the two head norms' gains are STORED with each
  head's columns permuted so that a rotary pair is adjacent (stored ``2i`` =
  published ``i``, stored ``2i + 1`` = published ``i + d/2``), so that a
  per-head prefix keeps whole pairs.  This file turns them back
  (:func:`_published_order`) and applies the published half-split RoPE; a
  sliced head of ``d`` dims holds pairs ``0 .. d/2 - 1`` with their
  full-width frequencies.
- Only the experts this share holds exist (``expert_share`` = index, of): the
  router still scores all 32, and what an absent expert would add is left
  out.  The published layer is the sum over all shares (tested on the CPU).
- The width slice, the Scaler and zero-filled logits are HeteroFL's.
- Memory and compile time only: attention runs in query blocks (`lax.map`),
  every layer under ``jax.checkpoint``, and a layer's held experts as a
  `lax.scan` over their stacked leaves (one expert at a time over all
  tokens, as a Python loop would, but compiled once: five levels of a fully
  unrolled expert model took the chip's compiler 8 minutes, PERF.md PR 28).

Leaves are named and laid out as the program's are ([in, out] matrices), which
is the interface, not the program's code.
"""

import concurrent.futures
import functools

import jax
import jax.numpy as jnp
import numpy as np

from . import common

LABEL_AXES = {"tok.w": 0}

ATTN_BLOCK = 512
ROUTE_SUM_EPS = 1e-6


def _held(model):
    index, of = model["expert_share"]
    n = model["num_experts"] // of
    return list(range(index * n, (index + 1) * n))


def _widths(model, rate):
    p = common.prefix
    d = p(model["head_dim"], rate)
    return {"D": p(model["hidden_size"], rate), "Dc": p(model["conv_dim"], rate),
            "d": d + d % 2, "F": p(model["intermediate_size"], rate),
            "Fe": p(model["moe_intermediate_size"], rate)}


def index(shapes, model, rate):
    w = _widths(model, rate)
    e, conv = np.arange(w["D"]), np.arange(w["Dc"])

    def heads(n):
        return np.concatenate([h * model["head_dim"] + np.arange(w["d"]) for h in range(n)])

    q, kv = heads(model["num_attention_heads"]), heads(model["num_key_value_heads"])
    out = {}
    for name, shape in shapes.items():
        leaf = name.split(".", 1)[1] if name[0] == "l" and name[1].isdigit() else name
        if name == "tok.w":
            ax = (np.arange(shape[0]), e)
        elif leaf in ("conv.in.b.w", "conv.in.c.w", "conv.in.u.w"):
            ax = (e, conv)
        elif leaf == "conv.taps.w":
            ax = (np.arange(shape[0]), conv)
        elif leaf == "conv.out.w":
            ax = (conv, e)
        elif leaf == "attn.q.w":
            ax = (e, q)
        elif leaf in ("attn.k.w", "attn.v.w"):
            ax = (e, kv)
        elif leaf in ("attn.q_norm.g", "attn.k_norm.g"):
            ax = (np.arange(w["d"]),)
        elif leaf == "attn.o.w":
            ax = (q, e)
        elif leaf == "moe.router.w":
            ax = (e, np.arange(shape[1]))
        elif leaf == "moe.router.b":
            ax = (np.arange(shape[0]),)
        elif leaf.endswith((".g.w", ".u.w", ".d.w")):
            f = np.arange(w["F"] if leaf.startswith("mlp.") else w["Fe"])
            ax = (f, e) if leaf.endswith(".d.w") else (e, f)
        elif leaf in ("norm.g", "norm1.g", "norm2.g"):
            ax = (e,)
        else:
            raise ValueError(f"lfm2 reference: unknown leaf {name!r}")
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


def _ffn(p, prefix, h, rate):
    gate = jax.nn.silu(h @ p[f"{prefix}.g.w"] / rate)
    return (gate * (h @ p[f"{prefix}.u.w"] / rate)) @ p[f"{prefix}.d.w"] / rate


def conv_mixer(lp, h, rate):
    """The gated short convolution on ``h`` [N, S, D]."""
    b, c, u = (h @ lp[f"conv.in.{m}.w"] / rate for m in "bcu")
    z, taps = b * u, lp["conv.taps.w"]
    n_taps = taps.shape[0]
    y = 0.0
    for j in range(n_taps):
        back = n_taps - 1 - j  # tap j reads the position ``back`` steps before
        shifted = z if back == 0 else jnp.concatenate(
            [jnp.zeros_like(z[:, :back]), z[:, :-back]], axis=1)
        y = y + taps[j] * shifted
    return (c * y) @ lp["conv.out.w"] / rate


def attention_mixer(lp, h, rate, arch):
    n, s, _ = h.shape
    heads, kv_heads, eps = arch["heads"], arch["kv_heads"], arch["eps"]
    q = (h @ lp["attn.q.w"] / rate).reshape(n, s, heads, -1)
    k = (h @ lp["attn.k.w"] / rate).reshape(n, s, kv_heads, -1)
    v = (h @ lp["attn.v.w"] / rate).reshape(n, s, kv_heads, -1)
    q = _rms(_published_order(q), _published_order(lp["attn.q_norm.g"]), eps)
    k = _rms(_published_order(k), _published_order(lp["attn.k_norm.g"]), eps)
    q, k = (_rope_half(t, arch["theta"], arch["head_dim"]) for t in (q, k))
    k, v = (jnp.repeat(t, heads // kv_heads, axis=2) for t in (k, v))
    return _attention(q, k, v).reshape(n, s, -1) @ lp["attn.o.w"] / rate


def routing(p, prefix, h, top_k, scaling):
    """(chosen experts [T, k], their weights [T, k]) of the published router."""
    score = jax.nn.sigmoid(h @ p[f"{prefix}.router.w"])
    _, sel = jax.lax.top_k(score + p[f"{prefix}.router.b"], top_k)
    w = jnp.take_along_axis(score, sel, axis=-1)
    return sel, w / (jnp.sum(w, axis=-1, keepdims=True) + ROUTE_SUM_EPS) * scaling


def layer(lp, x, rate, arch, kind, dense):
    """One decoder layer on ``x`` [N, S, D].  ``lp``: the layer's leaves
    without their ``l{i}.`` prefix; in an expert layer each expert leaf
    ``moe.e.{g,u,d}.w`` is the held experts' leaves stacked on a leading
    axis."""
    arch = dict(arch)
    n, s, _ = x.shape
    h = _rms(x, lp["norm1.g"], arch["eps"])
    x = x + (conv_mixer(lp, h, rate) if kind == "conv" else attention_mixer(lp, h, rate, arch))
    h = _rms(x, lp["norm2.g"], arch["eps"])
    if dense:
        return x + _ffn(lp, "mlp", h, rate)
    flat = h.reshape(n * s, -1)
    sel, w = routing(lp, "moe", flat, arch["top_k"], arch["scaling"])

    def one_expert(y, xs):  # a held expert over ALL tokens, weighted
        e, leaves = xs
        w_e = jnp.sum(jnp.where(sel == e, w, 0.0), axis=-1)
        return y + w_e[:, None] * _ffn(leaves, "moe.e", flat, rate), None

    experts = {k: lp[k] for k in ("moe.e.g.w", "moe.e.u.w", "moe.e.d.w")}
    y, _ = jax.lax.scan(one_expert, jnp.zeros_like(flat), (jnp.asarray(arch["held"]), experts))
    return x + y.reshape(n, s, -1)


def _layer_leaves(p, i, held=None):
    """Layer ``i``'s leaves without the prefix; with ``held``, its experts'
    leaves stacked in that order."""
    pre = f"l{i}."
    lp = {k[len(pre):]: v for k, v in p.items()
          if k.startswith(pre) and not k.startswith(pre + "moe.e")}
    if held is not None:
        for m in "gud":
            lp[f"moe.e.{m}.w"] = jnp.stack([p[f"{pre}moe.e{e}.{m}.w"] for e in held])
    return lp


def forward(p, tokens, rate, arch):
    """Logits [rows, positions, vocabulary] of the sub-model in training."""
    a = dict(arch)
    x = p["tok.w"][tokens]
    for i, kind in enumerate(a["layer_types"]):
        dense = i < a["dense_layers"]
        x = jax.checkpoint(layer, static_argnums=(2, 3, 4, 5))(
            _layer_leaves(p, i, None if dense else a["held"]), x, rate, arch, kind, dense)
    return _rms(x, p["norm.g"], a["eps"]) @ p["tok.w"].T


def loss_fn(p, tokens, label_mask, rate, arch):
    logits = forward(p, tokens, rate, arch)
    logits = jnp.where(label_mask > 0, logits, 0.0)
    logp = jax.nn.log_softmax(logits[:, :-1], axis=-1)
    return -jnp.mean(jnp.take_along_axis(logp, tokens[:, 1:, None], axis=-1))


def arch_of(model):
    """The static description :func:`forward` takes (hashable)."""
    return (("heads", model["num_attention_heads"]),
            ("kv_heads", model["num_key_value_heads"]), ("head_dim", model["head_dim"]),
            ("layer_types", tuple(model["layer_types"])),
            ("dense_layers", model["num_dense_layers"]),
            ("eps", float(model["norm_eps"])), ("theta", float(model["rope_theta"])),
            ("top_k", model["num_experts_per_tok"]),
            ("scaling", float(model["routed_scaling_factor"])),
            ("held", tuple(_held(model))))


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
