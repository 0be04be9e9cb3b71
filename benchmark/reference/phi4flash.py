"""Plain reference: Phi-4-mini-flash-reasoning (``model_type: phi4flash``,
huggingface.co/microsoft/Phi-4-mini-flash-reasoning ``config.json``; the
SambaY decoder-hybrid-decoder of arXiv:2507.06607 with the differential
attention of arXiv:2410.05258 and the Mamba-1 mixer of arXiv:2312.00752) as
the dense sub-model a client at one level holds, with its next-token loss,
gradients and local SGD.  float32, `jax.numpy` at "highest" matmul precision,
no kernels, no client vmap, a Python loop over the layers, THE RECURRENCE
POSITION BY POSITION (a `lax.scan` over a row's positions on the published
``[E, N]`` state: no chunks, no blocks side by side), differential attention
as its formulas (TWO plain softmaxes a head pair against the pair's one
128-wide value), LayerNorm by its definition.

``LN(x; g, b) = (x - mean(x)) / sqrt(var(x) + eps) * g + b`` (biased variance),
``s(.)`` = HeteroFL's Scaler (divide by the client's rate).  Layer ``i`` of the
cut is the PUBLISHED layer ``layer_offset + i``, of the kind ``layer_types[i]``:

    x = x + mixer(LN(x; g1, b1));   x = x + s((silu(s(h Wg)) * s(h Wu)) Wd),  h = LN(x; g2, b2)

  "mamba":   [xs | z] = s(h W_in);  xs = silu(conv4(xs) + b_conv)  (depthwise, causal,
             nothing before a row's start);  [r | B | C] = xs W_x  (B, C through s(.), r not)
             dt = softplus(r W_dt + b_dt) [E];  A = -exp(A_log) [E, N]
             from H_0 = 0 [E, N]:  H_t = exp(dt_t[:, None] A) H_{t-1} + (dt_t xs_t)[:, None] B_t[None, :]
             y_t = H_t C_t + D xs_t;   m = y (kept for the gated memory units)
             f = s((y * silu(z)) W_out)
  "sliding", "full":  [q | k | v] = s(h W_qkv + b_qkv);  q -> [S, 20, 2, d]: q1 = q[:, :, 0], q2 = q[:, :, 1];
             k -> [S, 10, 2, d]: k1, k2 likewise;  v -> [S, 10, 2 d];  query pair p reads pair p // 2
             a_j = softmax_mask(q_j k_j^T / sqrt(d)) v  (causal; "sliding": and t - s < window)
             lam = exp(lq1 . lk1) - exp(lq2 . lk2) + lam0,  lam0 = 0.8 - 0.6 exp(-0.3 (layer_offset + i))
             o = (a_1 - lam a_2) / sqrt(mean over the 2 d dims of (.)^2 + eps) * g_sub * (1 - lam0)
             f = s(o.reshape(S, 40 d) W_o + b_o);   a "full" layer's k1, k2, v are kept
  "cross":   the same with q = s(h W_q + b_q) alone on the kept k1, k2, v (causal, no window)
  "gmu":     f = s((m * silu(s(h W1))) W2)
  logits = LN(x; g_f, b_f) E^T with E the embedding (tied); logits of tokens the client lacks
  set to zero; loss = mean cross entropy of position t against token t + 1 inside a row.

Departures from the published code, none of which changes a value at rate 1:

- The published ``in_proj`` ``[D, 2 E]`` is two column leaves (``ssm.in.{x,z}``),
  ``Wqkv`` three (``attn.{q,k,v}``) with their biases and the feed-forward's
  ``[D, 2 F]`` two (``mlp.{g,u}``), so that each block has its own rule to
  slice by; this file concatenates the first two back (:func:`_published`)
  and runs ONE product each.
- STORED FORMS, undone here (a slope of one: every gradient is the published
  parameter's): ``A_log = ssm.a_log.w + log(1..N)`` (the S4D-real start at a
  leaf near zero), ``b_dt = ssm.dt.b[0] +`` :data:`DT_BIAS_SHIFT`, ``D`` the
  gain ``ssm.skip.g``, each ``l*`` vector a ``[d, 1]`` leaf.
- The width slice, the Scaler and zero-filled logits are HeteroFL's.  A sliced
  head holds a prefix of its ``d`` dims (the softmax scale and the sub-norm's
  mean follow the kept dims), the inner channels ``E`` a prefix shared by every
  Mamba and gated-memory layer; the ``l*`` vectors, ``N`` and ``dt_rank`` are
  never sliced.
- Memory and compile time only: the recurrence runs in blocks of
  :data:`SCAN_BLOCK` positions, each block under ``jax.checkpoint`` (the
  backward then holds a state a block and a block's positions, not 8,192
  states); attention runs in query blocks (`lax.map`); every layer under
  ``jax.checkpoint``; the training step runs a layer's half at a time from the
  host (the note above :func:`loss_and_grads`).

Leaves are named and laid out as the program's are ([in, out] matrices), which
is the interface, not the program's code.
"""

import concurrent.futures
import functools
import math

import jax
import jax.numpy as jnp
import numpy as np

from . import common

LABEL_AXES = {"tok.w": 0}

ATTN_BLOCK = 512
SCAN_BLOCK = 128
DT_BIAS_SHIFT = -4.6
KINDS = ("mamba", "sliding", "full", "gmu", "cross")


def lam0_of(layer):
    """The constant of differential attention at PUBLISHED layer ``layer``."""
    return 0.8 - 0.6 * math.exp(-0.3 * layer)


def index(shapes, model, rate):
    p = common.prefix
    e = np.arange(p(model["hidden_size"], rate))
    inner = np.arange(p(model["expand"] * model["hidden_size"], rate))
    f = np.arange(p(model["intermediate_size"], rate))
    hd = model["hidden_size"] // model["num_attention_heads"]
    kept = p(hd, rate)

    def heads(n):
        return np.concatenate([h * hd + np.arange(kept) for h in range(n)])

    q, kv, sub = heads(model["num_attention_heads"]), heads(model["num_key_value_heads"]), heads(2)
    out = {}
    for name, shape in shapes.items():
        leaf = name.split(".", 1)[1] if name[0] == "l" and name[1].isdigit() else name
        whole = tuple(np.arange(n) for n in shape)
        if name == "tok.w":
            ax = (whole[0], e)
        elif leaf in ("norm.g", "norm.b", "norm1.g", "norm1.b", "norm2.g", "norm2.b", "attn.o.b"):
            ax = (e,)
        elif leaf in ("ssm.in.x.w", "ssm.in.z.w", "gmu.in.w"):
            ax = (e, inner)
        elif leaf in ("ssm.out.w", "gmu.out.w"):
            ax = (inner, e)
        elif leaf in ("ssm.conv.w", "ssm.dt.w", "ssm.dt.b"):
            ax = (whole[0], inner)
        elif leaf in ("ssm.x.w", "ssm.a_log.w"):
            ax = (inner, whole[1])
        elif leaf in ("ssm.conv.b", "ssm.skip.g"):
            ax = (inner,)
        elif leaf == "attn.q.w":
            ax = (e, q)
        elif leaf in ("attn.k.w", "attn.v.w"):
            ax = (e, kv)
        elif leaf == "attn.q.b":
            ax = (q,)
        elif leaf in ("attn.k.b", "attn.v.b"):
            ax = (kv,)
        elif leaf == "attn.o.w":
            ax = (q, e)
        elif leaf == "attn.sub.g":
            ax = (sub,)
        elif leaf in ("attn.lq1.w", "attn.lk1.w", "attn.lq2.w", "attn.lk2.w"):
            ax = whole
        elif leaf in ("mlp.g.w", "mlp.u.w"):
            ax = (e, f)
        elif leaf == "mlp.d.w":
            ax = (f, e)
        else:
            raise ValueError(f"phi4flash reference: unknown leaf {name!r}")
        out[name] = ax
    return out


def _ln(x, g, b, eps):
    mean = jnp.mean(x, axis=-1, keepdims=True)
    var = jnp.mean((x - mean) ** 2, axis=-1, keepdims=True)
    return (x - mean) / jnp.sqrt(var + eps) * g + b


def _published(lp):
    """The published ``in_proj`` ``[D, 2 E]``, ``A_log`` ``[E, N]`` and the
    time step's bias ``[E]`` from the stored leaves."""
    a_log = lp["ssm.a_log.w"]
    return (jnp.concatenate([lp["ssm.in.x.w"], lp["ssm.in.z.w"]], axis=1),
            a_log + jnp.log(jnp.arange(1, a_log.shape[1] + 1, dtype=jnp.float32)),
            lp["ssm.dt.b"][0] + DT_BIAS_SHIFT)


def recurrence(xs, dt, a, b, c):
    """``y_t = H_t C_t`` with ``H_t = exp(dt_t[:, None] a) H_{t-1} + (dt_t
    xs_t)[:, None] B_t[None, :]`` from a zero state, position by position.
    ``xs`` / ``dt`` ``[N, S, E]``, ``a`` ``[E, Ns]``, ``b`` / ``c`` ``[N, S, Ns]``."""
    n, s, e = xs.shape
    size = SCAN_BLOCK if s % SCAN_BLOCK == 0 else s

    def position(state, at):
        x_t, dt_t, b_t, c_t = at                                   # [N, E] x 2, [N, Ns] x 2
        state = jnp.exp(dt_t[..., None] * a) * state + (dt_t * x_t)[..., None] * b_t[:, None, :]
        return state, jnp.sum(state * c_t[:, None, :], axis=-1)

    @jax.checkpoint
    def block(state, blk):
        return jax.lax.scan(position, state, blk)

    def by_block(t):  # [N, S, F] -> [blocks, size, N, F]
        t = jnp.moveaxis(t, 1, 0)
        return t.reshape((s // size, size) + t.shape[1:])

    _, y = jax.lax.scan(block, jnp.zeros((n, e, a.shape[1]), jnp.float32),
                        tuple(by_block(t) for t in (xs, dt, b, c)))
    return jnp.moveaxis(y.reshape((s,) + y.shape[2:]), 0, 1)


def mamba_mixer(lp, h, rate, arch):
    """The Mamba-1 mixer on ``h`` [N, S, D]: (its output, the memory ``m``)."""
    a_ = dict(arch)
    rank, ns = a_["dt_rank"], a_["state"]
    w_in, a_log, dt_bias = _published(lp)
    proj = h @ w_in / rate
    inner = proj.shape[-1] // 2
    xs, z = proj[..., :inner], proj[..., inner:]
    taps = lp["ssm.conv.w"]
    n_taps = taps.shape[0]
    conv = lp["ssm.conv.b"]
    for j in range(n_taps):
        back = n_taps - 1 - j  # tap j reads the position ``back`` steps before
        shifted = xs if back == 0 else jnp.concatenate(
            [jnp.zeros_like(xs[:, :back]), xs[:, :-back]], axis=1)
        conv = conv + taps[j] * shifted
    xs = jax.nn.silu(conv)
    rbc = xs @ lp["ssm.x.w"]
    r, b, c = rbc[..., :rank], rbc[..., rank:rank + ns] / rate, rbc[..., rank + ns:] / rate
    dt = jax.nn.softplus(r @ lp["ssm.dt.w"] + dt_bias)
    y = recurrence(xs, dt, -jnp.exp(a_log), b, c) + lp["ssm.skip.g"] * xs
    return (y * jax.nn.silu(z)) @ lp["ssm.out.w"] / rate, y


def _attention(q, k, v, window):
    """Softmax attention ``[N, S, H, .]`` under the diagonal and, with
    ``window``, the band ``t - s < window``, query block by query block
    against every key (the keys a query does not see masked out); scores of
    one block only are alive at a time (and recomputed for the backward)."""
    n, s_len = q.shape[:2]
    size = ATTN_BLOCK if s_len % ATTN_BLOCK == 0 else s_len
    scale = 1.0 / np.sqrt(q.shape[-1])

    def block(xs):
        q_b, start = xs
        sc = jnp.einsum("nqhd,nkhd->nhqk", q_b, k) * scale
        q_pos, k_pos = (start + jnp.arange(size))[:, None], jnp.arange(s_len)[None, :]
        keep = q_pos >= k_pos
        if window is not None:
            keep = keep & (q_pos - k_pos < window)
        sc = jnp.where(keep, sc, -jnp.inf)
        return jnp.einsum("nhqk,nkhd->nqhd", jax.nn.softmax(sc, axis=-1), v)

    blocks = jnp.moveaxis(q.reshape((n, s_len // size, size) + q.shape[2:]), 1, 0)
    out = jax.lax.map(jax.checkpoint(block), (blocks, jnp.arange(0, s_len, size)))
    return jnp.moveaxis(out, 0, 1).reshape((n, s_len) + out.shape[3:])


def differential_attention(lp, h, rate, arch, layer, window=None, kv=None):
    """Differential attention on ``h`` [N, S, D] at PUBLISHED layer ``layer``:
    (its output, the keys and the value it read).  ``kv`` None: the layer's
    own (``sliding``, ``full``); else another layer's ``(k1, k2, v)``
    (``cross``)."""
    a_ = dict(arch)
    n, s, _ = h.shape
    pairs, kv_pairs, eps = a_["heads"] // 2, a_["kv_heads"] // 2, a_["eps"]
    q = ((h @ lp["attn.q.w"] + lp["attn.q.b"]) / rate).reshape(n, s, pairs, 2, -1)
    if kv is None:
        k = ((h @ lp["attn.k.w"] + lp["attn.k.b"]) / rate).reshape(n, s, kv_pairs, 2, -1)
        v = ((h @ lp["attn.v.w"] + lp["attn.v.b"]) / rate).reshape(n, s, kv_pairs, -1)
        kv = (k[:, :, :, 0], k[:, :, :, 1], v)
    k1, k2, v = (jnp.repeat(t, pairs // kv_pairs, axis=2) for t in kv)
    a1, a2 = (_attention(q[:, :, :, j], k_j, v, window) for j, k_j in ((0, k1), (1, k2)))
    lam0 = lam0_of(layer)
    lam = jnp.exp(jnp.sum(lp["attn.lq1.w"] * lp["attn.lk1.w"])) \
        - jnp.exp(jnp.sum(lp["attn.lq2.w"] * lp["attn.lk2.w"])) + lam0
    o = a1 - lam * a2
    o = o / jnp.sqrt(jnp.mean(o * o, axis=-1, keepdims=True) + eps) * lp["attn.sub.g"] * (1.0 - lam0)
    return (o.reshape(n, s, -1) @ lp["attn.o.w"] + lp["attn.o.b"]) / rate, kv


def mixer_part(lp, x, side, rate, arch, kind, number):
    """A layer's first half on ``x`` [N, S, D] at PUBLISHED layer ``number``:
    ``x + mixer(LN1(x))``; ``lp`` its leaves without their ``l{i}.`` prefix;
    ``side`` what earlier layers kept and this kind reads (``m``; ``kv``).
    Returns (x, what this layer keeps)."""
    a_ = dict(arch)
    h = _ln(x, lp["norm1.g"], lp["norm1.b"], a_["eps"])
    kept = {}
    if kind == "mamba":
        f, kept["m"] = mamba_mixer(lp, h, rate, arch)
    elif kind == "gmu":
        f = (side["m"] * jax.nn.silu(h @ lp["gmu.in.w"] / rate)) @ lp["gmu.out.w"] / rate
    elif kind == "cross":
        f, _ = differential_attention(lp, h, rate, arch, number, kv=side["kv"])
    else:
        f, kv = differential_attention(lp, h, rate, arch, number,
                                       a_["window"] if kind == "sliding" else None)
        if kind == "full":
            kept["kv"] = kv
    return x + f, kept


def ffn_part(lp, x, rate, arch):
    """A layer's second half: ``x + SwiGLU(LN2(x))``."""
    h = _ln(x, lp["norm2.g"], lp["norm2.b"], dict(arch)["eps"])
    return x + (jax.nn.silu(h @ lp["mlp.g.w"] / rate) * (h @ lp["mlp.u.w"] / rate)) \
        @ lp["mlp.d.w"] / rate


def layer(lp, x, side, rate, arch, kind, number):
    """One layer: (x, what it keeps)."""
    x, kept = mixer_part(lp, x, side, rate, arch, kind, number)
    return ffn_part(lp, x, rate, arch), kept


def forward(p, tokens, rate, arch):
    """Logits [rows, positions, vocabulary] of the sub-model in training."""
    a_ = dict(arch)
    one = jax.checkpoint(layer, static_argnums=(3, 4, 5, 6))
    x, side = p["tok.w"][tokens], {}
    for i, kind in enumerate(a_["kinds"]):
        pre = f"l{i}."
        lp = {k[len(pre):]: v for k, v in p.items() if k.startswith(pre)}
        x, kept = one(lp, x, side, rate, arch, kind, a_["offset"] + i)
        side = {**side, **kept}
    return _ln(x, p["norm.g"], p["norm.b"], a_["eps"]) @ p["tok.w"].T


def loss_fn(p, tokens, label_mask, rate, arch):
    logits = forward(p, tokens, rate, arch)
    logits = jnp.where(label_mask > 0, logits, 0.0)
    logp = jax.nn.log_softmax(logits[:, :-1], axis=-1)
    return -jnp.mean(jnp.take_along_axis(logp, tokens[:, 1:, None], axis=-1))


def arch_of(model):
    """The static description :func:`forward` takes (hashable)."""
    kinds = tuple(model["layer_types"])
    if set(kinds) - set(KINDS):
        raise ValueError(f"phi4flash reference: layer_types {kinds!r}")
    return (("kinds", kinds), ("offset", int(model["layer_offset"])),
            ("heads", model["num_attention_heads"]), ("kv_heads", model["num_key_value_heads"]),
            ("window", int(model["sliding_window"])), ("state", model["d_state"]),
            ("dt_rank", model["dt_rank"]), ("eps", float(model["layer_norm_eps"])))


# ---------------------------------------------------------------------------
# The training step, A PART AT A TIME (compile-cache size only; the Laguna
# reference's way).  As one program a level `jax.value_and_grad(loss_fn)` took
# 164 MB of device code for a described v5e, 37 MB in the compile cache, and
# five levels beside the cell's round program outgrow the 192 MiB the chip's
# machine caps that cache at: every run would compile all of them again.  So a
# layer is split into its mixer half and its feed-forward half, each half's
# forward and `jax.vjp` a jitted program of its own, and the loop over the
# layers runs on the host, forward keeping each part's input and backward
# handing the cotangents down -- a side value's from its readers to its
# producer.  The feed-forward's program is compiled once a level for all the
# layers.  tests hold this step to `jax.value_and_grad(loss_fn)`.
# ---------------------------------------------------------------------------

def _part(lp, x, side, *, rate, arch, what):
    """``what`` = ("mixer", kind, published layer) | ("ffn",): (x, kept)."""
    if what[0] == "ffn":
        return ffn_part(lp, x, rate, arch), {}
    return mixer_part(lp, x, side, rate, arch, *what[1:])


@functools.partial(jax.jit, static_argnames=("rate", "arch", "what"))
def _part_fwd(lp, x, side, *, rate, arch, what):
    return common.highest(_part)(lp, x, side, rate=rate, arch=arch, what=what)


@functools.partial(jax.jit, static_argnames=("rate", "arch", "what"))
def _part_bwd(lp, x, side, ct, *, rate, arch, what):
    """(gradient of the part's leaves, cotangent of its input, cotangents of
    the side values it read) from the cotangents ``ct`` of (x, kept)."""
    return jax.vjp(functools.partial(common.highest(_part), rate=rate, arch=arch, what=what),
                   lp, x, side)[1](ct)


def head_loss(p, x, tokens, label_mask, arch):
    logits = _ln(x, p["norm.g"], p["norm.b"], dict(arch)["eps"]) @ p["tok.w"].T
    logits = jnp.where(label_mask > 0, logits, 0.0)
    logp = jax.nn.log_softmax(logits[:, :-1], axis=-1)
    return -jnp.mean(jnp.take_along_axis(logp, tokens[:, 1:, None], axis=-1))


@functools.partial(jax.jit, static_argnames=("arch",))
def _head(p, x, tokens, label_mask, *, arch):
    """(loss, gradient of the final norm and the tied table AS A HEAD,
    cotangent of ``x``)."""
    loss, (g, ct) = jax.value_and_grad(common.highest(head_loss), argnums=(0, 1))(
        p, x, tokens, label_mask, arch)
    return loss, g, ct


@jax.jit
def _embed(table, tokens):
    return table[tokens]


@jax.jit
def _embed_bwd(head_grad, tokens, ct):
    """The tied table's gradient: its use as a head plus its use as a look-up."""
    return head_grad.at[tokens].add(ct)


@functools.partial(jax.jit, static_argnames=("hp",), donate_argnames=("p", "buf"))
def _update(p, g, buf, lr, *, hp):
    return common.sgd_step(p, g, buf, lr, *hp)


_POOL = concurrent.futures.ThreadPoolExecutor(8)
_COMPILED = {}


def _compiled(fn, args, static):
    """``fn`` compiled for the shapes of ``args`` (a future; started once)."""
    avals = jax.tree_util.tree_map(lambda v: jax.ShapeDtypeStruct(np.shape(v), v.dtype), args)
    leaves, tree = jax.tree_util.tree_flatten(avals)
    key = (fn.__name__, tuple(sorted(static.items())), tree,
           tuple((v.shape, str(v.dtype)) for v in leaves))
    if key not in _COMPILED:
        _COMPILED[key] = _POOL.submit(fn.lower(*avals, **static).compile)
    return _COMPILED[key]


def _run(fn, *args, **static):
    return _compiled(fn, args, static).result()(*args)


def _plan(fn, *args, **static):
    """Start ``fn``'s compilation for these shapes; returns its result's shapes."""
    _compiled(fn, args, static)
    return jax.eval_shape(functools.partial(fn, **static), *args)


def _parts_of(p, arch):
    """The model as the host loop walks it: per part (its leaves' names in
    ``p`` by their name inside the layer, what it is, the side values it
    reads)."""
    a_, out = dict(arch), []
    for i, kind in enumerate(a_["kinds"]):
        pre = f"l{i}."
        names = {k[len(pre):]: k for k in p if k.startswith(pre)}
        ffn = {n: k for n, k in names.items() if n.startswith(("mlp.", "norm2."))}
        reads = {"gmu": ("m",), "cross": ("kv",)}.get(kind, ())
        out.append(({n: k for n, k in names.items() if n not in ffn},
                    ("mixer", kind, a_["offset"] + i), reads))
        out.append((ffn, ("ffn",), ()))
    return out


def loss_and_grads(p, tokens, label_mask, rate, arch, run=_run):
    """``jax.value_and_grad(loss_fn)(p, ...)``, a part at a time.  With ``run``
    = :func:`_plan` and shapes for arrays it only starts the compilations."""
    static = dict(rate=rate, arch=arch)
    parts = _parts_of(p, arch)
    inputs, side, x = [], {}, run(_embed, p["tok.w"], tokens)
    for names, what, reads in parts:
        read = {k: side[k] for k in reads}
        x_in = x
        x, kept = run(_part_fwd, {n: p[k] for n, k in names.items()}, x, read, what=what, **static)
        inputs.append((x_in, read, kept))
        side.update(kept)
    top = {k: p[k] for k in ("norm.g", "norm.b", "tok.w")}
    loss, grads, ct = run(_head, top, x, tokens, label_mask, arch=arch)
    grads, ct_side = dict(grads), {}
    zeros = jnp.zeros_like if run is _run else (lambda v: v)
    add = (lambda a, b: jax.tree_util.tree_map(jnp.add, a, b)) if run is _run else (lambda a, b: a)
    for (names, what, reads), (x, read, kept) in zip(reversed(parts), reversed(inputs)):
        # what this part kept and a later part read has a cotangent; what nobody read, zeros
        ct_kept = {k: ct_side.pop(k) if k in ct_side else jax.tree_util.tree_map(zeros, v)
                   for k, v in kept.items()}
        g, ct, ct_read = run(_part_bwd, {n: p[k] for n, k in names.items()}, x, read,
                             (ct, ct_kept), what=what, **static)
        for k, v in ct_read.items():
            ct_side[k] = add(ct_side[k], v) if k in ct_side else v
        grads.update({names[n]: v for n, v in g.items()})
    grads["tok.w"] = run(_embed_bwd, grads["tok.w"], tokens, ct)
    return loss, grads


def local_train(sub, client, config, lr, key):
    """A client's local training: its token rows, window by window in order.
    Returns its trained sub-model and the mean of its window losses.  (No
    dropout and no token corruption: ``key`` is not used.)

    The first call starts the compilation of every level's programs side by
    side (a level's take the chip's compiler a while at "highest" precision,
    and a cohort holds up to five levels)."""
    m, opt = config["model"], config["optimizer"]
    label_mask = np.zeros(m["num_tokens"], np.float32)
    label_mask[np.asarray(client["labels"])] = 1.0
    rows = np.asarray(client["rows"], np.int32)
    bptt = int(m["bptt"])
    if rows.shape[1] % bptt:
        raise ValueError("the reference handles whole windows only")
    hp = (float(opt["momentum"]), float(opt["weight_decay"]))
    rate, arch, f32 = float(client["rate"]), arch_of(m), jnp.float32
    shapes = {k: np.shape(v) for k, v in sub.items()}
    for r in sorted({rate, *map(float, m.get("level_rates", {}).values())}):
        like = {k: jax.ShapeDtypeStruct(tuple(len(a) for a in axes), f32)
                for k, axes in index(shapes, m, r).items()}
        _, g = loss_and_grads(like, jax.ShapeDtypeStruct((rows.shape[0], bptt), jnp.int32),
                              jax.ShapeDtypeStruct(label_mask.shape, f32), r, arch, run=_plan)
        _plan(_update, like, g, like, jax.ShapeDtypeStruct((), f32), hp=hp)
    p = {k: jnp.asarray(v, f32) for k, v in sub.items()}
    buf = {k: jnp.zeros_like(v) for k, v in p.items()}
    losses = []
    for _ in range(int(client["epochs"])):
        for w in range(rows.shape[1] // bptt):
            loss, g = loss_and_grads(p, jnp.asarray(rows[:, w * bptt:(w + 1) * bptt]),
                                     jnp.asarray(label_mask), rate, arch)
            p, buf = _run(_update, p, g, buf, f32(lr), hp=hp)
            losses.append(float(loss))
    return p, sum(losses) / len(losses)
