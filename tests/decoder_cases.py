"""The decoder families' tiny experiments, for the contract modules
(``test_decoder_families.py``, ``test_decoder_reference.py``) and for the tests
that are one family's alone (``test_<family>.py``): a family's case is its
row of ``config.DECODER_FAMILIES`` at the benchmark's tiny preset
(``benchmark/tests/tiny_<family>.py``) beside the benchmark's plain reference
(``benchmark/reference/<family>.py``).  A new family adds a row to
:data:`ROUNDS` and its cases to the two contract modules, not a file."""

import functools
import importlib

import jax
import jax.numpy as jnp
import numpy as np

from heterofl_tpu import config as C
from heterofl_tpu.models import make_model
from heterofl_tpu.models.spec import mask_params
from heterofl_tpu.parallel import RoundEngine, make_mesh

FAMILIES = tuple(C.DECODER_FAMILIES)
LEVELS = [1.0, 0.5, 0.25, 0.125, 0.0625]

_FOUR_LEVELS = "1_8_0.5_iid_fix_a1-b1-c1-e1_bn_1_1"
#: family -> (what its round case gives ``program_cfg``, tokens a row, local
#: steps a round): Keye's rows are longer than its ``topk``, so that the
#: selection binds; Laguna's two levels keep its five layers' round short, and
#: its window of 16 binds on rows of 32; Nemotron-H's two levels keep its seven
#: layers' round short, and rows of 32 are two chunks of its scan; Phi-4-flash's
#: alike: seven layers, a window of 16 that binds, two blocks of its scan
ROUNDS = {
    "kanana2": (dict(control=_FOUR_LEVELS, num_hidden_layers=2), 32, 2),
    "lfm2": (dict(control=_FOUR_LEVELS), 32, 2),
    "keye": (dict(control=_FOUR_LEVELS), 64, 1),
    "ouro": (dict(control=_FOUR_LEVELS), 32, 1),
    "laguna": (dict(control="1_8_0.5_iid_fix_a1-e1_bn_1_1", bptt=32), 32, 1),
    "nemotron_h": (dict(control="1_8_0.5_iid_fix_a1-e1_bn_1_1", bptt=32), 32, 1),
    "phi4flash": (dict(control="1_8_0.5_iid_fix_a1-e1_bn_1_1", bptt=32), 32, 1),
}

#: family -> leaves whose gradient is zero by the mathematics and rounding noise
#: in float32 (a key's bias: a softmax does not see a shift common to its keys),
#: which no relative tolerance holds
UNSEEN = {"phi4flash": lambda k: k.endswith("attn.k.b")}


def unseen(family, k):
    return UNSEEN.get(family, lambda k: False)(k)


def tiny(family):
    return importlib.import_module(f"benchmark.tests.tiny_{family}")


def reference(family):
    return importlib.import_module(f"benchmark.reference.{family}")


def case(family, seed=1, bptt=None, **arch):
    """(cfg, model, seeded params with the gains and the biases moved off
    their constants, tokens, a label mask with holes, the reference's model
    description)."""
    preset = tiny(family)
    cfg = preset.program_cfg(**({} if bptt is None else {"bptt": bptt}), **arch)
    model = make_model(cfg)
    params = model.init(jax.random.key(seed))
    keys = jax.random.split(jax.random.key(seed + 1), len(params))
    params = {k: v + 0.1 * jax.random.normal(kk, v.shape) if v.ndim == 1 else v
              for (k, v), kk in zip(sorted(params.items()), keys)}
    tokens = jax.random.randint(jax.random.key(seed + 2), (2, cfg["bptt"]), 0,
                                cfg["num_tokens"])
    label_mask = jnp.ones(cfg["num_tokens"]).at[jnp.arange(0, cfg["num_tokens"], 7)].set(0.0)
    return cfg, model, params, tokens, label_mask, preset.reference_model(cfg)


#: the preset as it stands, built once a module that asks
tiny_case = functools.lru_cache(maxsize=None)(case)


def masked_loss_and_grads(model, params, tokens, lm, rate):
    def system_loss(p):
        pm = mask_params(p, model.specs, model.groups, rate)
        out, _ = model.apply(pm, {"label": tokens}, train=True, width_rate=rate,
                             scaler_rate=rate, label_mask=lm)
        return out["loss"]

    return jax.value_and_grad(system_loss)(params)


@functools.lru_cache(maxsize=None)
def tiny_masked(family, rate):
    """Loss and gradients of the preset's masked full-width model at ``rate``."""
    _, model, params, tokens, lm, _ = tiny_case(family)
    loss, grads = masked_loss_and_grads(model, params, tokens, lm, rate)
    return float(loss), {k: np.asarray(v) for k, v in grads.items()}


def round_case(family):
    """(cfg, data) of 8 users with 2 rows of tokens each; every client lacks
    every fifth token and nobody holds token 3 or 4."""
    kwargs, tokens, _ = ROUNDS[family]
    cfg = tiny(family).program_cfg(**kwargs)
    vocab = cfg["num_tokens"]
    rows = np.random.default_rng(0).integers(5, vocab, size=(8, 2, tokens)).astype(np.int64)
    lm = np.ones((8, vocab), np.float32)
    lm[:, :5] = 0.0
    lm[:, ::5] = 0.0
    return cfg, (jnp.asarray(rows), jnp.asarray(lm))


def run_round(cfg, data, chunk, n_dev=1, users=np.arange(8), **extra):
    """One masked-engine round from the seeded init: (the parameters before,
    after, the metrics)."""
    cfg = dict(cfg, round_chunk=chunk, **extra)
    model = make_model(cfg)
    eng = RoundEngine(model, cfg, make_mesh(n_dev, 1))
    params0 = model.init(jax.random.key(0))
    before = {k: np.asarray(v) for k, v in params0.items()}  # the round donates its input
    out, ms = eng.train_round(params0, jax.random.key(5), 0.5, users, data)
    return (before, {k: np.asarray(v) for k, v in out.items()},
            {k: np.asarray(v) for k, v in ms.items()})
