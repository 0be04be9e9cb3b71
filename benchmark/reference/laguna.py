"""Plain reference: Laguna-XS.2 (``model_type: laguna``,
huggingface.co/poolside/Laguna-XS.2 ``config.json``) as the dense sub-model a
client at one level holds, with its next-token loss, gradients and local SGD.
float32, `jax.numpy` at "highest" matmul precision, no kernels, no client
vmap, a Python loop over the layers, both masks as explicit boolean matrices,
one expert at a time over ALL tokens.

Layer ``l`` of kind ``t`` in {full, sliding} (``layer_types[l]``), ``H_t`` =
``num_attention_heads_per_layer[l]`` query heads on 8 key/value heads of ``d``
= 128, ``x`` ``[T, D]`` (``T`` the positions of one row), ``rms(x, g) = x /
sqrt(mean(x^2) + eps) * g``, ``s(.)`` = HeteroFL's Scaler (divide by the
client's rate):

  h = rms(x, g1)
  q = s(h Wq) -> [T, H_t, d];  k, v = s(h Wk), s(h Wv) -> [T, 8, d];  q, k = rope_t(q), rope_t(k)
  allowed_full[i, j] = j <= i;   allowed_sliding[i, j] = j <= i and i - j < 512
  k, v repeated H_t / 8 times over the heads;  p = softmax over allowed j of q k^T / sqrt(d)
  gate = sigmoid(h Wgate) -> [T, H_t];   x = x + s(concat_h(gate_h * (p v)_h) Wo)
  h = rms(x, g2)
  dense (``mlp_layer_types[l]``):  x = x + s((silu(s(h Wg)) * s(h Wu)) Wd)
  sparse:  sc = sigmoid(h Wr) over all 256;  sel = top8(sc);  w = sc[sel] / sum(sc[sel]) * 2.5
           x = x + shared(h) + sum_{e in sel, e held} w_e expert_e(h)      (SwiGLUs as above)
  logits = rms(x, g_f) W_head; logits of tokens the client lacks set to zero;
  loss = mean cross entropy of position t against token t + 1 inside a row.

``rope_t`` turns the first ``r_t`` dims of a head (``partial_rotary_factor``:
128 sliding, 64 full) in half-split pairs (dim ``i`` with dim ``i + r_t/2``)
and leaves the rest alone.  Sliding: angle ``pos * 1e4^(-2i/128)``.  Full:
YaRN (:func:`frequencies`, from the five published numbers: theta 5e5, factor
64, original length 4,096, beta_fast 64, beta_slow 1): ``e_i = 5e5^(-2i/64)``,
``dim(b) = 64 ln(4096 / (2 pi b)) / (2 ln 5e5)``, ``low = floor(dim(64))`` = 5,
``high = ceil(dim(1))`` = 16, ``ramp_i = clip((i - low) / (high - low), 0,
1)``, ``freq_i = e_i (1 - ramp_i) + e_i / 64 ramp_i``; cos and sin times
``attention_factor`` 1.4158883.

Departures from the published model, none of which changes a value at rate 1
on one full share:

- What the catalog's ``config`` does not state (the configuration file's
  ``assumed``): ``gating: true`` is a per-head sigmoid gate on the attention's
  output from the layer's normed input; the router scores by sigmoid and
  renormalises the chosen scores (the ``deepseek_v3`` rule its scaling factor
  of 2.5 comes from), with no selection bias; query and key heads carry no
  norm.
- A head's ``q_proj`` / ``k_proj`` columns are held as two leaves, the rotary
  dims (``q.r`` / ``k.r``: STORED with each head's pairs adjacent, stored
  ``2i`` = published ``i``, stored ``2i + 1`` = published ``i + r/2``, so that
  a per-head prefix keeps whole pairs) and the pass-through dims (``q.n`` /
  ``k.n``; a sliding layer has none).  This file turns the rotary leaves back
  (:func:`_published_order`), applies the published half-split RoPE and puts
  the two parts side by side in the published order; a sliced head holds
  pairs ``0 .. r'/2 - 1`` with their full-width frequencies.
- Only the experts this share holds exist (``expert_share`` = index, of): the
  router still scores all 256, and what an absent expert would add is left
  out.  The published layer is the sum over all shares (tested on the CPU).
- The width slice, the Scaler (none after the router, the gate and the head)
  and zero-filled logits are HeteroFL's.
- Memory and compile time only: attention in blocks of query rows against
  every key (`lax.map`, each block recomputed for the backward: a ``[64,
  8192, 8192]`` float32 score is 17 GB); the head and the loss in blocks of
  positions; a layer's held experts as a `lax.scan` (one expert at a time
  over all tokens, as a Python loop would, but compiled once).  The model
  (:func:`loss_fn`) is a Python loop over the layers; local training takes
  the same chain rule a layer's PART at a time, each part's forward and
  `jax.vjp` a program of its own and the loop over the layers on the host
  (:func:`loss_and_grads`, which says why; a test holds it to
  ``jax.value_and_grad(loss_fn)``).

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


def rotary_dims(model, kind):
    """Dims of a published head of this kind that turn."""
    return int(model["head_dim"] * model["rope_parameters"][kind].get("partial_rotary_factor", 1.0))


def _widths(model, rate):
    p = common.prefix
    w = {"D": p(model["hidden_size"], rate), "d": _pairs(model["head_dim"], rate),
         "F": p(model["intermediate_size"], rate), "Fe": p(model["moe_intermediate_size"], rate),
         "Fs": p(model["shared_expert_intermediate_size"], rate)}
    for kind in set(model["layer_types"]):
        r = rotary_dims(model, kind)
        w[kind] = (_pairs(r, rate), p(model["head_dim"] - r, rate) if r < model["head_dim"] else 0)
    return w


def index(shapes, model, rate):
    w = _widths(model, rate)
    e = np.arange(w["D"])

    def heads(n, full, kept):
        return np.concatenate([h * full + np.arange(kept) for h in range(n)])

    hkv, hd = model["num_key_value_heads"], model["head_dim"]
    out = {}
    for name, shape in shapes.items():
        layered = name[0] == "l" and name[1].isdigit()
        leaf = name.split(".", 1)[1] if layered else name
        if layered:
            i = int(name[1:name.index(".")])
            kind, h = model["layer_types"][i], model["num_attention_heads_per_layer"][i]
            r_full = rotary_dims(model, kind)
            r, n = w[kind]
        if name == "embedding.tok.w":
            ax = (np.arange(shape[0]), e)
        elif name == "head.w":
            ax = (e, np.arange(shape[1]))
        elif leaf in ("attn.q.r.w", "attn.k.r.w"):
            ax = (e, heads(h if ".q." in leaf else hkv, r_full, r))
        elif leaf in ("attn.q.n.w", "attn.k.n.w"):
            ax = (e, heads(h if ".q." in leaf else hkv, hd - r_full, n))
        elif leaf == "attn.v.w":
            ax = (e, heads(hkv, hd, w["d"]))
        elif leaf == "attn.o.w":
            ax = (heads(h, hd, w["d"]), e)
        elif leaf in ("attn.gate.w", "moe.router.w"):
            ax = (e, np.arange(shape[1]))
        elif leaf.endswith((".g.w", ".u.w", ".d.w")):
            f = np.arange(w["F"] if leaf.startswith("mlp.") else
                          w["Fs"] if leaf.startswith("moe.shared.") else w["Fe"])
            ax = (f, e) if leaf.endswith(".d.w") else (e, f)
        elif leaf in ("norm.g", "norm1.g", "norm2.g"):
            ax = (e,)
        else:
            raise ValueError(f"laguna reference: unknown leaf {name!r}")
        out[name] = ax
    return out


def _rms(x, g, eps):
    return x / jnp.sqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) * g


def _published_order(t):
    """The last axis (a head's stored rotary dims, pairs adjacent) back in the
    published order: first halves of all pairs, then second halves."""
    d = t.shape[-1]
    return jnp.swapaxes(t.reshape(t.shape[:-1] + (d // 2, 2)), -1, -2).reshape(t.shape)


def yarn_range(rope, r):
    """(low, high) of YaRN's ramp over the ``r / 2`` pairs of a head's rotary
    dims, from the published numbers."""
    theta, original = float(rope["rope_theta"]), float(rope["original_max_position_embeddings"])

    def dim(beta):
        return r * math.log(original / (2 * math.pi * beta)) / (2 * math.log(theta))

    return max(math.floor(dim(rope["beta_fast"])), 0), min(math.ceil(dim(rope["beta_slow"])), r - 1)


def frequencies(rope, r):
    """(the ``r / 2`` pair frequencies of a published head's rotary dims, what
    cos and sin are multiplied by), float64."""
    i = np.arange(r // 2, dtype=np.float64)
    e = float(rope["rope_theta"]) ** (-2.0 * i / r)
    if rope.get("rope_type", "default") == "default":
        return e, 1.0
    low, high = yarn_range(rope, r)
    ramp = np.clip((i - low) / (high - low), 0.0, 1.0)
    factor = float(rope["factor"])
    scale = float(rope.get("attention_factor") or 0.1 * math.log(factor) + 1.0)
    return e * (1.0 - ramp) + e / factor * ramp, scale


def _rope_half(x, freq, scale):
    """Half-split RoPE of ``x`` ``[N, S, H, r']``: dim i turns with dim i +
    r'/2 by ``pos * freq[i]``, ``r'`` a client's share of the rotary dims
    (``freq`` the full head's table: the pairs held keep theirs)."""
    s, d = x.shape[1], x.shape[-1]
    ang = jnp.arange(s, dtype=jnp.float32)[:, None] * jnp.asarray(freq[:d // 2], jnp.float32)[None, :]
    ang = jnp.concatenate([ang, ang], axis=-1)[None, :, None, :]
    rotated = jnp.concatenate([-x[..., d // 2:], x[..., :d // 2]], axis=-1)
    return x * (jnp.cos(ang) * scale) + rotated * (jnp.sin(ang) * scale)


def allowed(q_pos, s_len, window):
    """The boolean mask ``[q, S]``: key j is allowed to query i if ``j <= i``
    and, under a window, ``i - j < window``."""
    i, j = q_pos[:, None], jnp.arange(s_len)[None, :]
    keep = j <= i
    return keep if window is None else keep & (i - j < window)


def _attention(q, k, v, window):
    """Softmax attention ``[N, S, H, d]`` under :func:`allowed`, query block by
    query block against every key; scores of one block only are alive at a
    time (and recomputed for the backward)."""
    n, s_len = q.shape[:2]
    size = QUERY_BLOCK if s_len % QUERY_BLOCK == 0 else s_len
    scale = 1.0 / np.sqrt(q.shape[-1])

    def block(xs):
        q_b, start = xs
        keep = allowed(start + jnp.arange(size), s_len, window)
        sc = jnp.einsum("nqhd,nkhd->nhqk", q_b, k) * scale
        sc = jnp.where(keep[None, None], sc, -jnp.inf)
        return jnp.einsum("nhqk,nkhd->nqhd", jax.nn.softmax(sc, axis=-1), v)

    blocks = jnp.moveaxis(q.reshape((n, s_len // size, size) + q.shape[2:]), 1, 0)
    out = jax.lax.map(jax.checkpoint(block), (blocks, jnp.arange(0, s_len, size)))
    return jnp.moveaxis(out, 0, 1).reshape((n, s_len) + out.shape[3:])


def attention_mixer(lp, h, rate, arch, kind, heads):
    n, s, _ = h.shape
    a = dict(arch)
    kv_heads = a["kv_heads"]
    freq, scale = dict(a["rope"])[kind]

    def project(m, count):
        t = _rope_half(_published_order((h @ lp[f"attn.{m}.r.w"] / rate).reshape(n, s, count, -1)),
                       np.asarray(freq), scale)
        if f"attn.{m}.n.w" in lp:
            t = jnp.concatenate([t, (h @ lp[f"attn.{m}.n.w"] / rate).reshape(n, s, count, -1)],
                                axis=-1)
        return t

    q, k = project("q", heads), project("k", kv_heads)
    v = (h @ lp["attn.v.w"] / rate).reshape(n, s, kv_heads, -1)
    k, v = (jnp.repeat(t, heads // kv_heads, axis=2) for t in (k, v))
    o = _attention(q, k, v, a["window"] if kind == "sliding_attention" else None)
    gate = jax.nn.sigmoid(h @ lp["attn.gate.w"])                      # [N, S, H]
    return (o * gate[..., None]).reshape(n, s, -1) @ lp["attn.o.w"] / rate


def routing(lp, h, top_k, scaling):
    """(chosen experts [T, k], their weights [T, k]): sigmoid over all
    experts, top-k, the chosen scores renormalised and scaled."""
    score = jax.nn.sigmoid(h @ lp["moe.router.w"])
    w, sel = jax.lax.top_k(score, top_k)
    return sel, w / jnp.sum(w, axis=-1, keepdims=True) * scaling


def _ffn(p, prefix, h, rate):
    gate = jax.nn.silu(h @ p[f"{prefix}.g.w"] / rate)
    return (gate * (h @ p[f"{prefix}.u.w"] / rate)) @ p[f"{prefix}.d.w"] / rate


def attention_part(lp, x, rate, arch, kind, heads):
    """``x + attention(rms(x, g1))`` of a layer of ``kind`` with ``heads`` query
    heads; ``lp`` the layer's leaves without their ``l{i}.`` prefix."""
    return x + attention_mixer(lp, _rms(x, lp["norm1.g"], dict(arch)["eps"]), rate, arch, kind,
                               heads)


def mlp_part(lp, x, rate, arch, sparse):
    """``x + mlp(rms(x, g2))``: the dense SwiGLU, or the shared expert and the
    held routed experts (``moe.e.{g,u,d}.w``: their leaves stacked on a leading
    axis), one expert at a time over ALL tokens."""
    a = dict(arch)
    n, s, _ = x.shape
    h = _rms(x, lp["norm2.g"], a["eps"])
    if not sparse:
        return x + _ffn(lp, "mlp", h, rate)
    flat = h.reshape(n * s, -1)
    sel, w = routing(lp, flat, a["top_k"], a["scaling"])

    def one_expert(y, xs):  # a held expert over ALL tokens, weighted
        e, leaves = xs
        w_e = jnp.sum(jnp.where(sel == e, w, 0.0), axis=-1)
        return y + w_e[:, None] * _ffn(leaves, "moe.e", flat, rate), None

    experts = {k: lp[k] for k in ("moe.e.g.w", "moe.e.u.w", "moe.e.d.w")}
    y, _ = jax.lax.scan(one_expert, _ffn(lp, "moe.shared", flat, rate),
                        (jnp.asarray(a["held"], jnp.int32), experts))
    return x + y.reshape(n, s, -1)


def layer(lp, x, rate, arch, kind, heads, sparse):
    """One decoder layer on ``x`` [N, S, D]."""
    return mlp_part(lp, attention_part(lp, x, rate, arch, kind, heads), rate, arch, sparse)


def _layer_leaves(p, i, held):
    """Layer ``i``'s leaves without the prefix, its held experts' leaves (an
    expert layer's) stacked in that order."""
    pre = f"l{i}."
    lp = {k[len(pre):]: v for k, v in p.items()
          if k.startswith(pre) and not k.startswith(pre + "moe.e")}
    if pre + "moe.router.w" in p:
        for m in "gud":
            lp[f"moe.e.{m}.w"] = jnp.stack([p[f"{pre}moe.e{e}.{m}.w"] for e in held])
    return lp


def hidden(p, tokens, rate, arch):
    """The states [rows, positions, D] after the last layer (before the final
    norm): a Python loop over the layers, each under ``jax.checkpoint``."""
    a = dict(arch)
    x = p["embedding.tok.w"][tokens]
    for i, (kind, heads, mlp) in enumerate(a["layers"]):
        one = jax.checkpoint(functools.partial(layer, rate=rate, arch=arch, kind=kind,
                                               heads=heads, sparse=mlp == "sparse"))
        x = one(_layer_leaves(p, i, a["held"]), x)
    return x


def forward(p, tokens, rate, arch):
    """Logits [rows, positions, vocabulary] of the sub-model in training."""
    return _rms(hidden(p, tokens, rate, arch), p["norm.g"], dict(arch)["eps"]) @ p["head.w"]


def head_loss(p, x, tokens, label_mask, arch):
    """Mean cross entropy of position t against token t + 1 inside a row, from
    the last layer's states ``x``: the final norm, then the head and the loss a
    block of positions at a time.  ``p``: ``norm.g`` and ``head.w``."""
    x = _rms(x, p["norm.g"], dict(arch)["eps"])[:, :-1]
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


def loss_fn(p, tokens, label_mask, rate, arch):
    """The sub-model's training loss on ``tokens`` [rows, positions]."""
    return head_loss(p, hidden(p, tokens, rate, arch), tokens, label_mask, arch)


def arch_of(model):
    """The static description :func:`hidden` takes (hashable)."""
    rope = tuple((kind, (tuple(f.tolist()), s)) for kind, (f, s) in
                 ((k, frequencies(model["rope_parameters"][k], rotary_dims(model, k)))
                  for k in sorted(set(model["layer_types"]))))
    return (("kv_heads", model["num_key_value_heads"]), ("window", model["sliding_window"]),
            ("layers", tuple(zip(model["layer_types"], model["num_attention_heads_per_layer"],
                                 model["mlp_layer_types"]))),
            ("rope", rope), ("eps", float(model["rms_norm_eps"])),
            ("top_k", model["num_experts_per_tok"]),
            ("scaling", float(model["moe_routed_scaling_factor"])),
            ("held", tuple(_held(model))))


# ---------------------------------------------------------------------------
# Local training.  `jax.value_and_grad(loss_fn)` as ONE program holds every
# layer's code, forward, recomputation and backward: 56-79 MB a level in the
# compile cache, and the five levels a cohort can hold outgrow the chip
# machine's 192 MiB, so every run would compile them again (6 minutes).  The
# same chain rule is taken a part at a time instead: a layer is its attention
# part and its feed-forward part, each part's forward and `jax.vjp` a jitted
# program of its own, and the loop over the layers runs on the host, forward
# keeping each part's input and backward handing the cotangent down.  A part's
# program is compiled once a level however many layers have its kind (four
# kinds: 20-28 MB a level), and only one part's intermediates are alive at a
# time.  tests hold this step to `jax.value_and_grad(loss_fn)` + `sgd_step`.
# ---------------------------------------------------------------------------

def _part(lp, x, *, rate, arch, what):
    """A layer's part on ``x``; ``what`` = ("attention", kind, heads) |
    ("mlp", sparse); ``lp`` the layer's leaves without prefix, every expert its
    own three leaves (stacked here)."""
    if what[0] == "attention":
        return attention_part(lp, x, rate, arch, *what[1:])
    if what[1]:
        lp = dict(lp, **{f"moe.e.{m}.w": jnp.stack([lp[f"moe.e{e}.{m}.w"]
                                                    for e in dict(arch)["held"]]) for m in "gud"})
    return mlp_part(lp, x, rate, arch, what[1])


@functools.partial(jax.jit, static_argnames=("rate", "arch", "what"))
def _part_fwd(lp, x, *, rate, arch, what):
    return common.highest(_part)(lp, x, rate=rate, arch=arch, what=what)


@functools.partial(jax.jit, static_argnames=("rate", "arch", "what"))
def _part_bwd(lp, x, ct, *, rate, arch, what):
    """(gradient of the part's leaves, cotangent of its input)."""
    return jax.vjp(functools.partial(common.highest(_part), rate=rate, arch=arch, what=what),
                   lp, x)[1](ct)


@functools.partial(jax.jit, static_argnames=("arch",))
def _head(p, x, tokens, label_mask, *, arch):
    """(loss, gradient of ``norm.g`` and ``head.w``, cotangent of ``x``)."""
    loss, (g, ct) = jax.value_and_grad(common.highest(head_loss), argnums=(0, 1))(
        p, x, tokens, label_mask, arch)
    return loss, g, ct


@jax.jit
def _embed(table, tokens):
    return table[tokens]


@jax.jit
def _embed_bwd(table, tokens, ct):
    return jnp.zeros_like(table).at[tokens].add(ct)


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
    ``p`` by their name inside the layer, what it is)."""
    out = []
    for i, (kind, heads, mlp) in enumerate(dict(arch)["layers"]):
        pre = f"l{i}."
        names = {k[len(pre):]: k for k in p if k.startswith(pre)}
        attn = {n: k for n, k in names.items() if n.startswith("attn.") or n == "norm1.g"}
        out.append((attn, ("attention", kind, heads)))
        out.append(({n: k for n, k in names.items() if n not in attn}, ("mlp", mlp == "sparse")))
    return out


def loss_and_grads(p, tokens, label_mask, rate, arch, run=_run):
    """``jax.value_and_grad(loss_fn)(p, ...)``, a part at a time.  With ``run``
    = :func:`_plan` and shapes for arrays it only starts the compilations."""
    static = dict(rate=rate, arch=arch)
    parts = _parts_of(p, arch)
    inputs, x = [], run(_embed, p["embedding.tok.w"], tokens)
    for names, what in parts:
        inputs.append(x)
        x = run(_part_fwd, {n: p[k] for n, k in names.items()}, x, what=what, **static)
    top = {k: p[k] for k in ("norm.g", "head.w")}
    loss, grads, ct = run(_head, top, x, tokens, label_mask, arch=arch)
    grads = dict(grads)
    for (names, what), x in zip(reversed(parts), reversed(inputs)):
        g, ct = run(_part_bwd, {n: p[k] for n, k in names.items()}, x, ct, what=what, **static)
        grads.update({names[n]: v for n, v in g.items()})
    grads["embedding.tok.w"] = run(_embed_bwd, p["embedding.tok.w"], tokens, ct)
    return loss, grads


def local_train(sub, client, config, lr, key):
    """A client's local training: its token rows, window by window in order.
    Returns its trained sub-model and the mean of its window losses.  (No
    dropout and no token corruption: ``key`` is not used.)

    The first call starts the compilation of every level's programs side by
    side (a level's take the chip's compiler minutes at "highest" precision,
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
