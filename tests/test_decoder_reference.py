"""The decoder families against the benchmark's plain reference (ISSUE 45):
every row of ``config.DECODER_FAMILIES`` at its tiny preset, the masked
full-width model at each of the five levels against the reference on the sliced
sub-model, and the sliced sub-model the program builds against the masked one;
one case a family and level, each a test of its own, a family's preset and its
masked gradients computed once.  The rest of the contract (the family table,
slicing, counts, level tables, the rounds, the engines, the entry point, the
tiny cell) is ``test_decoder_families.py``; what is one family's alone is
``test_<family>.py``.  A new family adds its cases here, not a file."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from decoder_cases import FAMILIES, LEVELS, reference, tiny_case, tiny_masked, unseen
from heterofl_tpu.models import make_model

def _nothing(*a):
    pass


def _keye_leaf(k, g, grad):  # frozen by construction, in program and reference alike
    if ".idx." in k:
        assert not grad.any() and not g.any(), k


def _trained(k, g, grad):  # every leaf is trained, the gate too
    assert np.abs(g).max() > 0, k


def _trained_or_unreached(k, g, grad):  # ... an expert no token reached apart
    assert np.abs(g).max() > 0 or ".moe.e" in k, k


def _nemotron_h_leaf(k, g, grad):  # ... and the selection bias, which top-k alone reads
    assert np.abs(g).max() > 0 or ".moe.e" in k or k.endswith("router.b"), k


def _phi4flash_leaf(k, g, grad):  # ... a key's bias, which no softmax sees, apart: noise on both sides
    if unseen("phi4flash", k):
        assert np.abs(g).max() < 1e-6 and np.abs(grad).max() < 1e-6, k
    else:
        assert np.abs(g).max() > 0, k


def _lfm2_after(cfg, grads):
    assert not grads["l1.moe.router.b"].any()  # read by top-k only


def _keye_after(cfg, grads):
    assert sum(".idx." in k for k in grads) == 5 * cfg["keye"]["num_hidden_layers"]


#: family -> (the reference's gradients compiled, not op by op; the share of a
#: leaf's largest gradient that holds a level; a check a leaf; a check after).
#: float32 on both sides, so program and reference differ by summation order
#: alone -- amplified by the Scaler's 1/r after each linear and, at a near-tie
#: of two router or indexer scores, by a different choice; 1e-3 of a leaf's
#: largest gradient holds both (Laguna's level e 1e-2: a norm runs over 8 dims
#: there and 6e-3 is the most it reads), and a bfloat16 product, a missing
#: term, a pass too few or a mis-sliced head is off by 1e-2 or more
REFERENCE = {
    "kanana2": (False, lambda rate: 1e-3, _nothing, _nothing),
    "lfm2": (False, lambda rate: 1e-3, _nothing, _lfm2_after),
    "keye": (False, lambda rate: 1e-3, _keye_leaf, _keye_after),
    "ouro": (False, lambda rate: 1e-3, _trained, _nothing),
    "laguna": (True, lambda rate: 1e-2 if rate < 0.1 else 1e-3, _trained_or_unreached, _nothing),
    "nemotron_h": (True, lambda rate: 1e-3, _nemotron_h_leaf, _nothing),
    "phi4flash": (True, lambda rate: 1e-3, _phi4flash_leaf, _nothing),
}


@pytest.mark.parametrize("rate", LEVELS)
@pytest.mark.parametrize("family", FAMILIES)
def test_masked_model_is_the_references_dense_submodel(family, rate):
    """Loss and gradients of the masked full-width model at rate r against the
    plain reference on the sliced sub-model: rate 1 is the published model,
    every other level HeteroFL's slice of it; nothing outside the slice gets a
    gradient."""
    from benchmark.reference import common

    ref, (compiled, tol, leaf, after) = reference(family), REFERENCE[family]
    cfg, model, params, tokens, lm, rm = tiny_case(family)
    loss, grads = tiny_masked(family, rate)
    index = ref.index({k: v.shape for k, v in params.items()}, rm, rate)
    sub = {k: jnp.asarray(v) for k, v in common.take(params, index).items()}
    fn = jax.value_and_grad(lambda p: ref.loss_fn(p, tokens, lm, rate, ref.arch_of(rm)))
    ref_loss, ref_grads = (jax.jit(fn) if compiled else fn)(sub)
    np.testing.assert_allclose(loss, float(ref_loss), rtol=1e-5)
    inside = common.take(grads, index)
    for k, g in ref_grads.items():
        g = np.asarray(g)
        leaf(k, g, grads[k])
        if not unseen(family, k):
            np.testing.assert_allclose(inside[k], g, atol=tol(rate) * np.abs(g).max() + 1e-9,
                                       err_msg=k)
        outside = np.ones(grads[k].shape, bool)
        outside[np.ix_(*index[k])] = False
        assert not grads[k][outside].any(), k  # nothing outside the slice
    after(cfg, grads)


@pytest.mark.parametrize("rate", LEVELS)
@pytest.mark.parametrize("family, tol", [("lfm2", 1e-4), ("keye", 1e-4), ("ouro", 1e-3),
                                         ("nemotron_h", 1e-4), ("phi4flash", 1e-4)])
def test_sliced_submodel_is_the_masked_model(family, tol, rate):
    """HeteroFL's equivalence inside the program: the dense sub-model built at
    rate r (`make_model(cfg, r)`, what the grouped and sliced engines train)
    on the slice of the parameters gives the masked full-width model's loss
    and, inside the slice, its gradients; same float32 sums in another order,
    so 1e-5 relative on the loss and ``tol`` of a leaf's largest gradient."""
    from benchmark.reference import common

    ref = reference(family)
    cfg, model, params, tokens, lm, rm = tiny_case(family)
    loss, grads = tiny_masked(family, rate)
    index = ref.index({k: v.shape for k, v in params.items()}, rm, rate)
    sub = {k: jnp.asarray(v) for k, v in common.take(params, index).items()}
    small = make_model(cfg, rate)
    assert {k: tuple(v.shape) for k, v in sub.items()} == small.meta["shapes"]
    sub_loss, sub_grads = jax.value_and_grad(lambda p: small.apply(
        p, {"label": tokens}, train=True, scaler_rate=rate, label_mask=lm)[0]["loss"])(sub)
    np.testing.assert_allclose(float(sub_loss), loss, rtol=1e-5)
    inside = common.take(grads, index)
    for k, g in sub_grads.items():
        g = np.asarray(g)
        if not unseen(family, k):
            np.testing.assert_allclose(inside[k], g, atol=tol * np.abs(g).max() + 1e-9, err_msg=k)
