"""The Laguna cell cut down for the CPU tests (never a benchmark cell): the
cell's five layers (full + dense, three sliding + sparse, full + sparse) at
hidden 128, 6 | 8 query heads on 2 key/value heads of 32 (groups of 3 and 4;
a full layer's head half rotary, 16 + 16), a window of 16 on rows of 64
positions (the band holds 43 % of the causal pairs), dense width 256, 16
experts of width 32 (top-2) beside a shared one of 32, 4 of them held (share 0
of 4), vocabulary 96.  Every width halves four times (rates 1 .. 1/16; a
head keeps 32, 16, 8, 4, 2 dims, a full layer's rotary half 16, 8, 4, 2, 2
and its pass-through half 16, 8, 4, 2, 1).  Hidden 128 and not 64 for
`tiny_ouro`'s reason."""

import copy
import json
import os

HERE = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

ARCH = {"hidden_size": 128, "num_hidden_layers": 5,
        "layer_types": ["full_attention", "sliding_attention", "sliding_attention",
                        "sliding_attention", "full_attention"],
        "mlp_layer_types": ["dense", "sparse", "sparse", "sparse", "sparse"],
        "num_attention_heads_per_layer": [6, 8, 8, 8, 6],
        "num_key_value_heads": 2, "head_dim": 32, "sliding_window": 16,
        "intermediate_size": 256, "moe_intermediate_size": 32,
        "shared_expert_intermediate_size": 32, "num_experts": 16, "num_experts_per_tok": 2,
        "expert_share": [0, 4]}
VOCAB, BPTT = 96, 64
NAME = "laguna-xs.2.fix-a1-e1.train-8k"


def _load(kind, name):
    with open(os.path.join(HERE, kind, name + ".json"), encoding="utf-8") as f:
        return json.load(f)


def program_cfg(control="1_10_0.5_iid_fix_a1-b1-c1-d1-e1_bn_1_1", bptt=BPTT, **arch):
    """The program's cfg of the tiny model (``arch`` overrides :data:`ARCH`)."""
    from heterofl_tpu import config as C

    cfg = C.default_cfg()
    cfg["control"] = C.parse_control_name(control)
    cfg["data_name"], cfg["model_name"] = "WikiText2", "laguna"
    cfg["override"] = {"laguna": dict(ARCH, **arch), "bptt": bptt}
    cfg = C.process_control(cfg)
    cfg["num_tokens"] = cfg["classes_size"] = VOCAB
    return cfg


def reference_model(cfg):
    """What `benchmark/reference/laguna.py` takes as ``config["model"]``."""
    return dict(cfg["laguna"], num_tokens=cfg["num_tokens"], bptt=cfg["bptt"])


def cell():
    """(cell, configuration) of the real cell's files at the tiny sizes."""
    cell = _load("workloads", NAME)
    config = copy.deepcopy(_load("configs", "laguna-xs.2"))
    config["control"] = "1_10_0.5_iid_fix_a1-b1-c1-d1-e1_bn_1_1"
    config["model"].update(ARCH, num_tokens=VOCAB, bptt=BPTT, experts_held=4)
    config["cfg_overrides"] = {"laguna": dict(ARCH), "bptt": BPTT,
                               "batch_size": {"train": 20, "test": 10}}
    config["federation"].update(batch_rows=20, rows_per_user=2)
    config["data"]["sizes"] = {"types": VOCAB, "train": 33 * 39,  # 20 rows of 64 tokens: 1 window
                               "test": 33 * 10}
    config["limits"] = {"level_loss_gap": 1e-3, "update_norm_gap": 0.05}
    return cell, config
