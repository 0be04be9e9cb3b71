"""Model factory registry.

Parity with the reference factories (``src/models/conv.py:75-82``,
``src/models/resnet.py:161-208``, ``src/models/transformer.py:165-175``):
constructed widths are ``ceil(model_rate * base)``, the Scaler rate is
``model_rate / global_model_rate``.

``make_model(cfg)`` builds the **global** model; ``make_model(cfg, rate)``
builds a true sliced sub-model (used by the "sliced" strategy and the
equivalence tests).  In the default masked strategy only the global model is
ever constructed.

A decoder-only language-model family is a row of ``config.DECODER_FAMILIES``
and the module of its name here, whose one maker ``make_<name>(num_tokens,
arch, model_rate, *, mask, compute_dtype)`` ``make_model`` looks up; what the
families share (the leaf book, ``apply``'s prologue and tail, the run of alike
layers, grouped-query attention, the expert layers' helpers) is
``models/decoder.py``.
"""

from __future__ import annotations

from importlib import import_module
from typing import Any, Dict, Optional

from ..config import DECODER_FAMILIES, MODEL_NAMES, ceil_width, scaled_hidden  # noqa: F401
from .base import ModelDef  # noqa: F401
from .conv import make_conv
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
    elif name in DECODER_FAMILIES:
        maker = getattr(import_module(f"{__name__}.{name}"), f"make_{name}")
        model = maker(cfg["num_tokens"], cfg[name], model_rate,
                      mask=cfg["mask"], compute_dtype=compute_dtype)
    else:
        raise ValueError("Not valid model name")
    model.meta["model_rate"] = model_rate
    model.meta["scaler_rate"] = scaler_rate
    return model
