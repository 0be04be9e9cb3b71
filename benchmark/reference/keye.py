"""Plain reference: the language model of Keye-VL-2.0-30B-A3B (``model_type:
KeyeVL2``, huggingface.co/Kwai-Keye/Keye-VL-2.0-30B-A3B ``config.json``) as
the dense sub-model a client at one level holds, with its next-token loss,
gradients and local SGD.  The decoder is the family's (grouped-query
attention with RMSNorm on every query and key head, softmax-routed SwiGLU
experts, pre-norm blocks); the sparse attention follows DeepSeek-V3.2-Exp's
public description of its indexer, which ``sa_config`` names the sizes of.
float32, `jax.numpy` at "highest" matmul precision, no kernels, no client
vmap, `lax.top_k` for the selection, one expert at a time over ALL tokens.

Layer, ``x`` ``[T, D]`` (``T`` the positions of one row), ``rms(x, g) = x /
sqrt(mean(x^2) + eps) * g``, ``s(.)`` = HeteroFL's Scaler (divide by the
client's rate):

  h = rms(x, g1)
  indexer, on stop_gradient(h), 16 heads of 64 on one key head:
      qI = rope(h Wq_I) -> [T, 16, 64];  kI = rope(layernorm(h Wk_I)) -> [T, 64]
      wI = (h Ww_I) * 16^-1/2 * 64^-1/2 -> [T, 16]
      I[t, s] = sum_j wI[t, j] * relu(qI[t, j] . kI[s])   for s <= t, -inf above the diagonal
      S_t = top_2048(I[t, :]) met with the causal keys    (every causal key while t < 2048)
  q = s(h Wq) -> [T, 32, d];  k, v = s(h Wk), s(h Wv) -> [T, 4, d]
  q, k = rms(q, g_q), rms(k, g_k) over each head's d dims;  q, k = rope(q), rope(k)
  k, v repeated 8x over the heads;  p = softmax over S_t of q k^T / sqrt(d)
  x = x + s((p v) Wo)
  h = rms(x, g2);  pr = softmax(h Wr) over all 128;  sel = top8(pr);  w = pr[sel] / sum(pr[sel])
  x = x + sum_{e in sel, e held} w_e s((silu(s(h Wg_e)) * s(h Wu_e)) Wd_e)     (no shared expert)
  logits = rms(x, g_f) W_head; logits of tokens the client lacks set to zero;
  loss = mean cross entropy of position t against token t + 1 inside a row.

``rope`` is half-split (dim i turns with dim i + d/2 by ``pos * theta^(-2i /
d)``) over the whole head, at theta 1e7.

Departures from the published model, none of which changes a value at rate 1
on one full share:

- The published ``rope_scaling.mrope_section`` [16, 24, 24] takes a head's 64
  frequencies from three position streams (time, height, width).  On text
  the three are equal and the turn is exactly the half-split RoPE built here;
  there is no second or third stream.
- The vision tower is not built (the catalog's ``config`` holds no size of
  it): the model reads token ids only.
- The indexer is a pure function of the layer's input with no gradient in or
  out: DeepSeek-V3.2 trains its indexer by a KL term against the attention's
  distribution on detached inputs; nothing in ``config.json`` states this
  model's recipe, so the indexer's leaves get no gradient at all here.
- ``q_chunk_size`` / ``kv_chunk_size`` 512 are read as the published
  kernel's tile sizes: the selection is per token, over all causal keys.
- ``q_proj``, ``k_proj``, the two head norms' gains, the indexer's ``wq``,
  ``wk`` and its LayerNorm's gain and bias are STORED with each head's
  columns permuted so that a rotary pair is adjacent (stored ``2i`` =
  published ``i``, stored ``2i + 1`` = published ``i + d/2``), so that a
  per-head prefix keeps whole pairs.  This file turns them back
  (:func:`_published_order`) and applies the published half-split RoPE; a
  sliced head of ``d`` dims holds pairs ``0 .. d/2 - 1`` with their
  full-width frequencies.
- Only the experts this share holds exist (``expert_share`` = index, of): the
  router still scores all 128, and what an absent expert would add is left
  out.  The published layer is the sum over all shares (tested on the CPU).
- The width slice, the Scaler (none inside the indexer, whose outputs only a
  top-k reads) and zero-filled logits are HeteroFL's; the indexer's two
  scale factors keep the published 16 and 64 at every width.
- Memory and compile time only: the index scores and the attention run in
  query blocks (`lax.map`), each recomputed for the backward; the head and
  the loss in blocks of positions; every layer under ``jax.checkpoint``; the
  layers as one `lax.scan` over their stacked leaves and a layer's held
  experts as a `lax.scan` inside it (one expert at a time over all tokens,
  as a Python loop would, but compiled once).

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

QUERY_BLOCK = 256
LOSS_BLOCK = 1024


def _held(model):
    index, of = model["expert_share"]
    n = model["num_experts"] // of
    return list(range(index * n, (index + 1) * n))


def _pairs(n, rate):
    d = common.prefix(n, rate)
    return d + d % 2


def _widths(model, rate):
    p = common.prefix
    return {"D": p(model["hidden_size"], rate), "d": _pairs(model["head_dim"], rate),
            "di": _pairs(model["index_head_dim"], rate),
            "Fe": p(model["moe_intermediate_size"], rate)}


def index(shapes, model, rate):
    w = _widths(model, rate)
    e = np.arange(w["D"])

    def heads(n, full, kept):
        return np.concatenate([h * full + np.arange(kept) for h in range(n)])

    q = heads(model["num_attention_heads"], model["head_dim"], w["d"])
    kv = heads(model["num_key_value_heads"], model["head_dim"], w["d"])
    iq = heads(model["index_n_heads"], model["index_head_dim"], w["di"])
    out = {}
    for name, shape in shapes.items():
        leaf = name.split(".", 1)[1] if name[0] == "l" and name[1].isdigit() else name
        if name == "embedding.tok.w":
            ax = (np.arange(shape[0]), e)
        elif name == "head.w":
            ax = (e, np.arange(shape[1]))
        elif leaf == "attn.q.w":
            ax = (e, q)
        elif leaf in ("attn.k.w", "attn.v.w"):
            ax = (e, kv)
        elif leaf in ("attn.q_norm.g", "attn.k_norm.g"):
            ax = (np.arange(w["d"]),)
        elif leaf == "attn.o.w":
            ax = (q, e)
        elif leaf == "idx.q.w":
            ax = (e, iq)
        elif leaf == "idx.k.w":
            ax = (e, np.arange(w["di"]))
        elif leaf in ("idx.k_norm.g", "idx.k_norm.b"):
            ax = (np.arange(w["di"]),)
        elif leaf in ("idx.w.w", "moe.router.w"):
            ax = (e, np.arange(shape[1]))
        elif leaf.endswith((".g.w", ".u.w", ".d.w")):
            f = np.arange(w["Fe"])
            ax = (f, e) if leaf.endswith(".d.w") else (e, f)
        elif leaf in ("norm.g", "norm1.g", "norm2.g"):
            ax = (e,)
        else:
            raise ValueError(f"keye reference: unknown leaf {name!r}")
        out[name] = ax
    return out


def _rms(x, g, eps):
    return x / jnp.sqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) * g


def _layer_norm(x, g, b, eps):
    mean = jnp.mean(x, axis=-1, keepdims=True)
    var = jnp.mean((x - mean) ** 2, axis=-1, keepdims=True)
    return (x - mean) / jnp.sqrt(var + eps) * g + b


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


def index_scores(q_i, k_i, w_i, q_pos):
    """``I[t, s]`` of the queries at positions ``q_pos`` against every key,
    -inf above the diagonal: ``q_i`` ``[N, q, 16, di]``, ``k_i`` ``[N, S,
    di]``, ``w_i`` ``[N, q, 16]`` -> ``[N, q, S]``."""
    dots = jnp.einsum("nqjd,nkd->nqjk", q_i, k_i)
    score = jnp.sum(w_i[..., None] * jax.nn.relu(dots), axis=2)
    causal = q_pos[:, None] >= jnp.arange(k_i.shape[1])[None, :]
    return jnp.where(causal, score, -jnp.inf)


def selected(scores, topk):
    """The 0/1 set ``lax.top_k`` picks in each row of ``scores`` ``[N, q, S]``
    (among them keys above the diagonal while a query has fewer than ``topk``
    causal keys: the caller meets the set with the causal mask)."""
    _, idx = jax.lax.top_k(scores, topk)
    return jnp.any(idx[..., None] == jnp.arange(scores.shape[-1]), axis=-2)


def _attention(q, k, v, q_i, k_i, w_i, topk):
    """Causal softmax attention ``[N, S, H, d]`` over the keys the indexer
    selects for each query, query block by query block against every key;
    scores of one block only are alive at a time (and recomputed for the
    backward)."""
    n, s_len = q.shape[:2]
    size = QUERY_BLOCK if s_len % QUERY_BLOCK == 0 else s_len
    scale = 1.0 / np.sqrt(q.shape[-1])

    def block(xs):
        q_b, qi_b, wi_b, start = xs
        q_pos = start + jnp.arange(size)
        keep = (q_pos[:, None] >= jnp.arange(s_len)[None, :])[None]
        if s_len > topk:  # else every causal key is among the topk
            keep = keep & selected(index_scores(qi_b, k_i, wi_b, q_pos), topk)
        sc = jnp.einsum("nqhd,nkhd->nhqk", q_b, k) * scale
        sc = jnp.where(keep[:, None], sc, -jnp.inf)
        return jnp.einsum("nhqk,nkhd->nqhd", jax.nn.softmax(sc, axis=-1), v)

    def blocks(t):  # [n, s, ...] -> [s / size, n, size, ...]
        return jnp.moveaxis(t.reshape((n, s_len // size, size) + t.shape[2:]), 1, 0)

    out = jax.lax.map(jax.checkpoint(block),
                      (blocks(q), blocks(q_i), blocks(w_i), jnp.arange(0, s_len, size)))
    return jnp.moveaxis(out, 0, 1).reshape((n, s_len) + out.shape[3:])


def indexer(lp, h, arch):
    """(qI [N, S, 16, di], kI [N, S, di], wI [N, S, 16]) of the layer's
    normed input ``h`` [N, S, D]; no gradient enters or leaves."""
    n, s, _ = h.shape
    h = jax.lax.stop_gradient(h)
    lp = {k: jax.lax.stop_gradient(lp[k]) for k in lp if k.startswith("idx.")}
    hi, di, theta = arch["index_heads"], arch["index_head_dim"], arch["theta"]
    q_i = _published_order((h @ lp["idx.q.w"]).reshape(n, s, hi, -1))
    k_i = _layer_norm(_published_order(h @ lp["idx.k.w"]), _published_order(lp["idx.k_norm.g"]),
                      _published_order(lp["idx.k_norm.b"]), arch["eps"])
    w_i = (h @ lp["idx.w.w"]) * (hi ** -0.5 * di ** -0.5)
    return (_rope_half(q_i, theta, di), _rope_half(k_i[:, :, None], theta, di)[:, :, 0], w_i)


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
    o = _attention(q, k, v, *indexer(lp, h, arch), arch["topk"])
    return o.reshape(n, s, -1) @ lp["attn.o.w"] / rate


def routing(lp, h, top_k):
    """(chosen experts [T, k], their weights [T, k]): softmax over all
    experts, top-k, renormalised (``norm_topk_prob``)."""
    prob = jax.nn.softmax(h @ lp["moe.router.w"], axis=-1)
    w, sel = jax.lax.top_k(prob, top_k)
    return sel, w / jnp.sum(w, axis=-1, keepdims=True)


def _ffn(p, prefix, h, rate):
    gate = jax.nn.silu(h @ p[f"{prefix}.g.w"] / rate)
    return (gate * (h @ p[f"{prefix}.u.w"] / rate)) @ p[f"{prefix}.d.w"] / rate


def layer(lp, x, rate, arch):
    """One decoder layer on ``x`` [N, S, D].  ``lp``: the layer's leaves
    without their ``l{i}.`` prefix, each expert leaf ``moe.e.{g,u,d}.w`` the
    held experts' leaves stacked on a leading axis."""
    arch = dict(arch)
    n, s, _ = x.shape
    x = x + attention_mixer(lp, _rms(x, lp["norm1.g"], arch["eps"]), rate, arch)
    flat = _rms(x, lp["norm2.g"], arch["eps"]).reshape(n * s, -1)
    sel, w = routing(lp, flat, arch["top_k"])

    def one_expert(y, xs):  # a held expert over ALL tokens, weighted
        e, leaves = xs
        w_e = jnp.sum(jnp.where(sel == e, w, 0.0), axis=-1)
        return y + w_e[:, None] * _ffn(leaves, "moe.e", flat, rate), None

    experts = {k: lp[k] for k in ("moe.e.g.w", "moe.e.u.w", "moe.e.d.w")}
    y, _ = jax.lax.scan(one_expert, jnp.zeros_like(flat), (jnp.asarray(arch["held"]), experts))
    return x + y.reshape(n, s, -1)


def _layer_leaves(p, i, held):
    """Layer ``i``'s leaves without the prefix, its held experts' leaves
    stacked in that order."""
    pre = f"l{i}."
    lp = {k[len(pre):]: v for k, v in p.items()
          if k.startswith(pre) and not k.startswith(pre + "moe.e")}
    for m in "gud":
        lp[f"moe.e.{m}.w"] = jnp.stack([p[f"{pre}moe.e{e}.{m}.w"] for e in held])
    return lp


def hidden(p, tokens, rate, arch):
    """The final normed states [rows, positions, D] of the sub-model."""
    a = dict(arch)
    layers = [_layer_leaves(p, i, a["held"]) for i in range(a["layers"])]
    stacked = {k: jnp.stack([lp[k] for lp in layers]) for k in layers[0]}
    one = jax.checkpoint(lambda x, lp: (layer(lp, x, rate, arch), None))
    x, _ = jax.lax.scan(one, p["embedding.tok.w"][tokens], stacked)
    return _rms(x, p["norm.g"], a["eps"])


def forward(p, tokens, rate, arch):
    """Logits [rows, positions, vocabulary] of the sub-model in training."""
    return hidden(p, tokens, rate, arch) @ p["head.w"]


def loss_fn(p, tokens, label_mask, rate, arch):
    """Mean cross entropy of position t against token t + 1 inside a row, the
    head and the loss a block of positions at a time."""
    x = hidden(p, tokens, rate, arch)[:, :-1]
    n, s, d = x.shape
    size = LOSS_BLOCK if (s + 1) % LOSS_BLOCK == 0 else s + 1
    pad = (-s) % size  # the row's last position has no target
    x = jnp.pad(x, ((0, 0), (0, pad), (0, 0))).reshape(n, -1, size, d)
    tgt = jnp.pad(tokens[:, 1:], ((0, 0), (0, pad))).reshape(n, -1, size)
    live = jnp.pad(jnp.ones((n, s)), ((0, 0), (0, pad))).reshape(n, -1, size)

    def block(xs):
        x_b, t_b, w_b = xs
        logits = jnp.where(label_mask > 0, x_b @ p["head.w"], 0.0)
        logp = jax.nn.log_softmax(logits, axis=-1)
        return -jnp.sum(jnp.take_along_axis(logp, t_b[..., None], axis=-1)[..., 0] * w_b)

    sums = jax.lax.map(jax.checkpoint(block), tuple(jnp.moveaxis(t, 1, 0) for t in (x, tgt, live)))
    return jnp.sum(sums) / (n * s)


def arch_of(model):
    """The static description :func:`hidden` takes (hashable)."""
    return (("heads", model["num_attention_heads"]),
            ("kv_heads", model["num_key_value_heads"]), ("head_dim", model["head_dim"]),
            ("index_heads", model["index_n_heads"]),
            ("index_head_dim", model["index_head_dim"]), ("topk", model["index_topk"]),
            ("layers", model["num_hidden_layers"]),
            ("eps", float(model["rms_norm_eps"])), ("theta", float(model["rope_theta"])),
            ("top_k", model["num_experts_per_tok"]), ("held", tuple(_held(model))))


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
