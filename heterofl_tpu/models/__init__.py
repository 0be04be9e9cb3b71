"""Model factory registry.

Parity with the reference factories (``src/models/conv.py:75-82``,
``src/models/resnet.py:161-208``, ``src/models/transformer.py:165-175``):
constructed widths are ``ceil(model_rate * base)``, the Scaler rate is
``model_rate / global_model_rate``.

``make_model(cfg)`` builds the **global** model; ``make_model(cfg, rate)``
builds a true sliced sub-model (used by the "sliced" strategy and the
equivalence tests).  In the default masked strategy only the global model is
ever constructed.
"""

from __future__ import annotations

from typing import Any, Dict, Optional

from ..config import MODEL_NAMES, ceil_width, scaled_hidden  # noqa: F401
from .base import ModelDef  # noqa: F401
from .conv import make_conv
from .kanana2 import make_kanana2
from .keye import make_keye
from .laguna import make_laguna
from .lfm2 import make_lfm2
from .ouro import make_ouro
from .resnet import make_resnet
from .spec import Group, ParamSpec, count_masks, mask_params, param_mask  # noqa: F401
from .transformer import make_transformer

RESNET_BLOCKS = {
    "resnet18": ([2, 2, 2, 2], False),
    "resnet34": ([3, 4, 6, 3], False),
    "resnet50": ([3, 4, 6, 3], True),
    "resnet101": ([3, 4, 23, 3], True),
    "resnet152": ([3, 8, 36, 3], True),
}

# the canonical registry lives in config (jax-free for analysis tooling); keep
# it in lockstep with the families actually buildable here.  A hard raise, not
# an assert: the guard must survive `python -O` (advisor r3).
_BUILDABLE = ("conv",) + tuple(RESNET_BLOCKS) + (
    "transformer", "kanana2", "lfm2", "keye", "ouro", "laguna")
if MODEL_NAMES != _BUILDABLE:
    raise ImportError(
        f"config.MODEL_NAMES {MODEL_NAMES!r} out of lockstep with buildable "
        f"families {_BUILDABLE!r}")


def parse_compute_dtype(cd):
    """cfg['compute_dtype'] -> jnp dtype or None, with validation."""
    import jax.numpy as jnp

    if cd in ("bfloat16", "bf16"):
        return jnp.bfloat16
    if cd in (None, "float32", "f32", "fp32"):
        return None
    raise ValueError(f"Not valid compute_dtype: {cd!r} (float32 | bfloat16)")


def make_model(cfg: Dict[str, Any], model_rate: Optional[float] = None) -> ModelDef:
    name = cfg["model_name"]
    if model_rate is None:
        model_rate = cfg["global_model_rate"]
    scaler_rate = model_rate / cfg["global_model_rate"]
    compute_dtype = parse_compute_dtype(cfg.get("compute_dtype"))
    pallas_norm = bool(cfg.get("pallas_norm", False))
    conv_impl = cfg.get("conv_impl")  # None (direct) | "im2col" (bmm path)
    if conv_impl not in (None, "direct", "im2col"):
        raise ValueError(f"Not valid conv_impl: {conv_impl!r}")
    if conv_impl == "direct":
        conv_impl = None
    if name == "conv":
        model = make_conv(cfg["data_shape"], scaled_hidden(cfg["conv"]["hidden_size"], model_rate),
                          cfg["classes_size"], norm=cfg["norm"], scale=cfg["scale"], mask=cfg["mask"],
                          compute_dtype=compute_dtype, pallas_norm=pallas_norm,
                          conv_impl=conv_impl)
    elif name in RESNET_BLOCKS:
        num_blocks, bottleneck = RESNET_BLOCKS[name]
        model = make_resnet(cfg["data_shape"], scaled_hidden(cfg["resnet"]["hidden_size"], model_rate),
                            num_blocks, cfg["classes_size"], bottleneck=bottleneck,
                            norm=cfg["norm"], scale=cfg["scale"], mask=cfg["mask"],
                            compute_dtype=compute_dtype, pallas_norm=pallas_norm,
                            conv_impl=conv_impl)
    elif name == "transformer":
        t = cfg["transformer"]
        model = make_transformer(
            cfg["num_tokens"], ceil_width(t["embedding_size"], model_rate), t["num_heads"],
            ceil_width(t["hidden_size"], model_rate), t["num_layers"], t["dropout"],
            cfg["bptt"], cfg["mask_rate"], mask=cfg["mask"], compute_dtype=compute_dtype)
    elif name == "kanana2":
        model = make_kanana2(cfg["num_tokens"], cfg["kanana2"], model_rate,
                             mask=cfg["mask"], compute_dtype=compute_dtype)
    elif name == "lfm2":
        model = make_lfm2(cfg["num_tokens"], cfg["lfm2"], model_rate,
                          mask=cfg["mask"], compute_dtype=compute_dtype)
    elif name == "keye":
        model = make_keye(cfg["num_tokens"], cfg["keye"], model_rate,
                          mask=cfg["mask"], compute_dtype=compute_dtype)
    elif name == "ouro":
        model = make_ouro(cfg["num_tokens"], cfg["ouro"], model_rate,
                          mask=cfg["mask"], compute_dtype=compute_dtype)
    elif name == "laguna":
        model = make_laguna(cfg["num_tokens"], cfg["laguna"], model_rate,
                            mask=cfg["mask"], compute_dtype=compute_dtype)
    else:
        raise ValueError("Not valid model name")
    model.meta["model_rate"] = model_rate
    model.meta["scaler_rate"] = scaler_rate
    return model
