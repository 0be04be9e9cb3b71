"""Plain reference: Kanana-2-30B-A3B's decoder (``model_type: deepseek_v3``,
huggingface.co/kakaocorp/kanana-2-30b-a3b-instruct-2601 ``config.json``; the
equations are those of transformers' ``modeling_deepseek_v3.py``) as the dense
sub-model a client at one level holds, with its next-token loss, gradients and
local SGD.  float32, `jax.numpy` at "highest" matmul precision, no kernels, no
client vmap, one expert at a time over ALL tokens.

Layer, ``x`` ``[T, D]``, ``rms(x, g) = x / sqrt(mean(x^2) + eps) * g``,
``s(.)`` = HeteroFL's Scaler (divide by the client's rate):

  h = rms(x, g1); q_n, q_r = s(h Wq_n), s(h Wq_r)      -> [T, H, dn], [T, H, dr]
  c, k_r = s(h Wkv_c), s(h Wkv_r); c = rms(c, g_kv)    -> [T, R], [T, dr]
  k_n, v = s(c Wkv_bk), s(c Wkv_bv)                    -> [T, H, dn], [T, H, dv]
  q_r, k_r = rope(., pos): pairs (2i, 2i+1), angle pos * theta^(-2i/64)
  p = softmax_causal((q_n k_n^T + q_r k_r^T) / sqrt(dn + dr)); x = x + s((p v) Wo)
  h = rms(x, g2)
  dense layer:  x = x + s((silu(s(h Wg)) * s(h Wu)) Wd)
  expert layer: sc = sigmoid(h Wr) over all 128; sel = top6(sc + b)
                w = sc[sel] / sum(sc[sel]) * 2.448
                x = x + sum_{e in sel, e held} w_e ffn_e(h) + ffn_shared(h)
  logits = rms(x, g_f) W_head; logits of tokens the client lacks set to zero;
  loss = mean cross entropy of position t against token t + 1 inside a row.

Departures from the published code, none of which changes a value at rate 1
on one full share:

- ``q_proj``, ``kv_a_proj_with_mqa`` and ``kv_b_proj`` are held as two leaves
  each (``q.n`` | ``q.r``: per head the 128 no-position and the 64 rotary
  columns; ``kv_a.c`` | ``kv_a.r``: latent and rotary key; ``kv_b.k`` |
  ``kv_b.v``: per head the key and the value columns): a fixed permutation of
  the published matrices' columns, so that each part has a prefix to slice.
- Only the experts this share holds exist (``expert_share`` = index, of): the
  router still scores all 128, and what an absent expert would add is left
  out.  The published layer is the sum over all shares (tested on the CPU).
- RoPE rotates interleaved pairs in place; the published code de-interleaves
  first and rotates halves, the same dot products q_r . k_r.
- The width slice, the Scaler and zero-filled logits are HeteroFL's.
- Attention runs in query blocks and every layer under ``jax.checkpoint``
  (memory only); the expert layers run as a `lax.scan` over their stacked
  leaves and the held experts as a `lax.scan` inside it (one expert at a
  time over all tokens, as a Python loop would, but compiled once: five
  levels of an unrolled model took the chip's compiler 8 minutes, PR 28).

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


def _held(model):
    index, of = model["expert_share"]
    n = model["n_routed_experts"] // of
    return list(range(index * n, (index + 1) * n))


def _widths(model, rate):
    p = common.prefix
    dr = p(model["qk_rope_head_dim"], rate)
    return {"D": p(model["hidden_size"], rate), "dn": p(model["qk_nope_head_dim"], rate),
            "dr": dr + dr % 2, "dv": p(model["v_head_dim"], rate),
            "R": p(model["kv_lora_rank"], rate), "F": p(model["intermediate_size"], rate),
            "Fs": p(model["moe_intermediate_size"] * model["n_shared_experts"], rate),
            "Fe": p(model["moe_intermediate_size"], rate)}


def index(shapes, model, rate):
    w, H = _widths(model, rate), model["num_attention_heads"]
    e, lora = np.arange(w["D"]), np.arange(w["R"])

    def heads(full, kept):
        return np.concatenate([h * full + np.arange(kept) for h in range(H)])

    nope = heads(model["qk_nope_head_dim"], w["dn"])
    rope = heads(model["qk_rope_head_dim"], w["dr"])
    val = heads(model["v_head_dim"], w["dv"])
    out = {}
    for name, shape in shapes.items():
        leaf = name.split(".", 1)[1] if name[0] == "l" and name[1].isdigit() else name
        if name == "embedding.tok.w":
            ax = (np.arange(shape[0]), e)
        elif name == "head.w":
            ax = (e, np.arange(shape[1]))
        elif leaf == "attn.q.n.w":
            ax = (e, nope)
        elif leaf == "attn.q.r.w":
            ax = (e, rope)
        elif leaf == "attn.kv_a.c.w":
            ax = (e, lora)
        elif leaf == "attn.kv_a.r.w":
            ax = (e, np.arange(w["dr"]))
        elif leaf == "attn.kv_norm.g":
            ax = (lora,)
        elif leaf == "attn.kv_b.k.w":
            ax = (lora, nope)
        elif leaf == "attn.kv_b.v.w":
            ax = (lora, val)
        elif leaf == "attn.o.w":
            ax = (val, e)
        elif leaf == "moe.router.w":
            ax = (e, np.arange(shape[1]))
        elif leaf == "moe.router.b":
            ax = (np.arange(shape[0]),)
        elif leaf.endswith((".g.w", ".u.w", ".d.w")):
            width = w["F"] if leaf.startswith("mlp.") else \
                w["Fs"] if leaf.startswith("moe.shared.") else w["Fe"]
            f = np.arange(width)
            ax = (f, e) if leaf.endswith(".d.w") else (e, f)
        elif leaf in ("norm.g", "norm1.g", "norm2.g"):
            ax = (e,)
        else:
            raise ValueError(f"kanana2 reference: unknown leaf {name!r}")
        out[name] = ax
    return out


def _rms(x, g, eps):
    return x / jnp.sqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) * g


def _rope(x, theta, full):
    """x [N, S, ..., d] with d a prefix of whole pairs of the ``full`` rotary
    dims; pair i turns by ``pos * theta^(-2i/full)``."""
    n, s, d = x.shape[0], x.shape[1], x.shape[-1]
    freq = theta ** (-2.0 * jnp.arange(d // 2, dtype=jnp.float32) / full)
    ang = jnp.arange(s, dtype=jnp.float32)[:, None] * freq[None, :]
    ang = ang.reshape((1, s) + (1,) * (x.ndim - 3) + (d // 2,))
    pairs = x.reshape(x.shape[:-1] + (d // 2, 2))
    a, b = pairs[..., 0], pairs[..., 1]
    turned = jnp.stack([a * jnp.cos(ang) - b * jnp.sin(ang),
                        a * jnp.sin(ang) + b * jnp.cos(ang)], axis=-1)
    return turned.reshape(x.shape)


def _attention(qn, qr, kn, kr, v):
    """Causal softmax attention, query block by query block against every
    key (the keys after a query masked out); scores of one block only are
    alive at a time (and recomputed for the backward).  The blocks are alike,
    so they run as one `lax.map`: compiled once."""
    n, s_len = qn.shape[:2]
    size = ATTN_BLOCK if s_len % ATTN_BLOCK == 0 else s_len
    scale = 1.0 / np.sqrt(qn.shape[-1] + qr.shape[-1])

    def block(xs):
        qn_b, qr_b, start = xs
        sc = (jnp.einsum("nqhd,nkhd->nhqk", qn_b, kn)
              + jnp.einsum("nqhd,nkd->nhqk", qr_b, kr)) * scale
        q_pos = start + jnp.arange(size)
        sc = jnp.where(q_pos[:, None] >= jnp.arange(s_len)[None, :], sc, -jnp.inf)
        return jnp.einsum("nhqk,nkhd->nqhd", jax.nn.softmax(sc, axis=-1), v)

    def blocks(t):  # [n, s, ...] -> [s / size, n, size, ...]
        return jnp.moveaxis(t.reshape((n, s_len // size, size) + t.shape[2:]), 1, 0)

    out = jax.lax.map(jax.checkpoint(block),
                      (blocks(qn), blocks(qr), jnp.arange(0, s_len, size)))
    return jnp.moveaxis(out, 0, 1).reshape((n, s_len) + out.shape[3:])


def _ffn(p, prefix, h, rate):
    gate = jax.nn.silu(h @ p[f"{prefix}.g.w"] / rate)
    return (gate * (h @ p[f"{prefix}.u.w"] / rate)) @ p[f"{prefix}.d.w"] / rate


def routing(p, prefix, h, top_k, scaling):
    """(chosen experts [T, k], their weights [T, k]) of the published router."""
    score = jax.nn.sigmoid(h @ p[f"{prefix}.router.w"])
    _, sel = jax.lax.top_k(score + p[f"{prefix}.router.b"], top_k)
    w = jnp.take_along_axis(score, sel, axis=-1)
    return sel, w / jnp.sum(w, axis=-1, keepdims=True) * scaling


def layer(lp, x, rate, arch, dense):
    """One decoder layer on ``x`` [N, S, D].  ``lp``: the layer's leaves
    without their ``l{i}.`` prefix; in an expert layer each expert leaf
    ``moe.e.{g,u,d}.w`` is the held experts' leaves stacked on a leading
    axis."""
    arch = dict(arch)
    heads, eps = arch["heads"], arch["eps"]
    n, s, _ = x.shape

    def split(t):
        return t.reshape(n, s, heads, -1)

    h = _rms(x, lp["norm1.g"], eps)
    qn = split(h @ lp["attn.q.n.w"] / rate)
    qr = split(h @ lp["attn.q.r.w"] / rate)
    c = _rms(h @ lp["attn.kv_a.c.w"] / rate, lp["attn.kv_norm.g"], eps)
    kr = h @ lp["attn.kv_a.r.w"] / rate
    kn = split(c @ lp["attn.kv_b.k.w"] / rate)
    v = split(c @ lp["attn.kv_b.v.w"] / rate)
    qr, kr = _rope(qr, arch["theta"], arch["rope_full"]), _rope(kr, arch["theta"], arch["rope_full"])
    o = _attention(qn, qr, kn, kr, v).reshape(n, s, -1)
    x = x + o @ lp["attn.o.w"] / rate
    h = _rms(x, lp["norm2.g"], eps)
    if dense:
        return x + _ffn(lp, "mlp", h, rate)
    flat = h.reshape(n * s, -1)
    sel, w = routing(lp, "moe", flat, arch["top_k"], arch["scaling"])

    def one_expert(y, xs):  # a held expert over ALL tokens, weighted
        e, leaves = xs
        w_e = jnp.sum(jnp.where(sel == e, w, 0.0), axis=-1)
        return y + w_e[:, None] * _ffn(leaves, "moe.e", flat, rate), None

    experts = {k: lp[k] for k in ("moe.e.g.w", "moe.e.u.w", "moe.e.d.w")}
    y, _ = jax.lax.scan(one_expert, _ffn(lp, "moe.shared", flat, rate),
                        (jnp.asarray(arch["held"]), experts))
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
    """Logits [rows, positions, vocabulary] of the sub-model in training.
    The expert layers are alike, so they run as one `lax.scan` over their
    stacked leaves (and the held experts as one inside it): the same
    operations in the same order as a Python loop, compiled once."""
    a = dict(arch)
    x = p["embedding.tok.w"][tokens]
    for i in range(a["dense_layers"]):
        x = jax.checkpoint(layer, static_argnums=(2, 3, 4))(
            _layer_leaves(p, i), x, rate, arch, True)
    rest = [_layer_leaves(p, i, a["held"]) for i in range(a["dense_layers"], a["layers"])]
    if rest:
        stacked = {k: jnp.stack([lp[k] for lp in rest]) for k in rest[0]}
        x, _ = jax.lax.scan(
            lambda x_, lp: (jax.checkpoint(layer, static_argnums=(2, 3, 4))(
                lp, x_, rate, arch, False), None), x, stacked)
    return _rms(x, p["norm.g"], a["eps"]) @ p["head.w"]


def loss_fn(p, tokens, label_mask, rate, arch):
    logits = forward(p, tokens, rate, arch)
    logits = jnp.where(label_mask > 0, logits, 0.0)
    logp = jax.nn.log_softmax(logits[:, :-1], axis=-1)
    return -jnp.mean(jnp.take_along_axis(logp, tokens[:, 1:, None], axis=-1))


def arch_of(model):
    """The static description :func:`forward` takes (hashable)."""
    return (("heads", model["num_attention_heads"]), ("layers", model["num_hidden_layers"]),
            ("dense_layers", model["first_k_dense_replace"]),
            ("eps", float(model["rms_norm_eps"])), ("theta", float(model["rope_theta"])),
            ("rope_full", model["qk_rope_head_dim"]),
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
    side (a level's program takes the chip's compiler one to three minutes
    at "highest" precision, and a cohort holds up to five levels)."""
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
