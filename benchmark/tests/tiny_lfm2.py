"""The LFM2 cell cut down for the CPU tests (never a benchmark cell): one dense
conv layer, one attention-expert layer and two conv-expert layers (a scan),
hidden 64, 4 query heads on 2 key/value heads of 16, 3 taps, dense width 96,
16 experts of width 32 (top-4), 4 of them held (share 1 of 4), vocabulary 96.
Every width halves four times (rates 1 .. 1/16; a head keeps 16, 8, 4, 2 and
-- one dim rounded up to a whole rotary pair -- 2)."""

import copy
import json
import os

HERE = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

ARCH = {"hidden_size": 64, "conv_dim": 64, "num_hidden_layers": 4,
        "layer_types": ["conv", "full_attention", "conv", "conv"], "num_dense_layers": 1,
        "intermediate_size": 96, "moe_intermediate_size": 32, "num_experts": 16,
        "num_experts_per_tok": 4, "num_attention_heads": 4, "num_key_value_heads": 2,
        "head_dim": 16, "expert_share": [1, 4]}
VOCAB, BPTT = 96, 16
NAME = "lfm2-8b-a1b.fix-a1-e1.train"


def _load(kind, name):
    with open(os.path.join(HERE, kind, name + ".json"), encoding="utf-8") as f:
        return json.load(f)


def program_cfg(control="1_10_0.5_iid_fix_a1-b1-c1-d1-e1_bn_1_1", **arch):
    """The program's cfg of the tiny model (``arch`` overrides :data:`ARCH`)."""
    from heterofl_tpu import config as C

    cfg = C.default_cfg()
    cfg["control"] = C.parse_control_name(control)
    cfg["data_name"], cfg["model_name"] = "WikiText2", "lfm2"
    cfg["override"] = {"lfm2": dict(ARCH, **arch), "bptt": BPTT}
    cfg = C.process_control(cfg)
    cfg["num_tokens"] = cfg["classes_size"] = VOCAB
    return cfg


def reference_model(cfg):
    """What `benchmark/reference/lfm2.py` takes as ``config["model"]``."""
    return dict(cfg["lfm2"], num_tokens=cfg["num_tokens"], bptt=cfg["bptt"])


def cell():
    """(cell, configuration) of the real cell's files at the tiny sizes."""
    cell = _load("workloads", NAME)
    config = copy.deepcopy(_load("configs", "lfm2-8b-a1b"))
    config["control"] = "1_10_0.5_iid_fix_a1-b1-c1-d1-e1_bn_1_1"
    config["model"].update(ARCH, num_tokens=VOCAB, bptt=BPTT, experts_held=4)
    config["cfg_overrides"] = {"lfm2": dict(ARCH), "bptt": BPTT,
                               "batch_size": {"train": 10, "test": 10}}
    config["federation"].update(batch_rows=10)
    config["data"]["sizes"] = {"types": VOCAB, "train": 33 * 34,  # 10 rows of 112 tokens: 7 windows
                               "test": 33 * 10}
    config["limits"] = {"level_loss_gap": 1e-3, "update_norm_gap": 0.05}
    return cell, config
