"""The Keye cell cut down for the CPU tests (never a benchmark cell): two
layers, hidden 64, 4 query heads on 2 key/value heads of 16, an indexer of 4
heads of 8 on one key head choosing 16 keys a query, 8 experts of width 32
(top-2), 4 of them held (share 0 of 2), vocabulary 96, rows of 64 positions:
LONGER than ``topk``, so the selection binds for three quarters of the
queries, as in the cell (the program's query block is ``topk`` here: four
blocks a row, the first without a mask).  Every width halves four times
(rates 1 .. 1/16; a head keeps 16, 8, 4, 2, 2 dims and an indexer head 8, 4,
2, 2, 2)."""

import copy
import json
import os

HERE = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

ARCH = {"hidden_size": 64, "num_hidden_layers": 2, "moe_intermediate_size": 32,
        "num_experts": 8, "num_experts_per_tok": 2, "num_attention_heads": 4,
        "num_key_value_heads": 2, "head_dim": 16, "index_n_heads": 4, "index_head_dim": 8,
        "index_topk": 16, "expert_share": [0, 2]}
VOCAB, BPTT = 96, 64
NAME = "keye-vl-2-30b-a3b.fix-a1-e1.train-8k"


def _load(kind, name):
    with open(os.path.join(HERE, kind, name + ".json"), encoding="utf-8") as f:
        return json.load(f)


def program_cfg(control="1_10_0.5_iid_fix_a1-b1-c1-d1-e1_bn_1_1", bptt=BPTT, **arch):
    """The program's cfg of the tiny model (``arch`` overrides :data:`ARCH`)."""
    from heterofl_tpu import config as C

    cfg = C.default_cfg()
    cfg["control"] = C.parse_control_name(control)
    cfg["data_name"], cfg["model_name"] = "WikiText2", "keye"
    cfg["override"] = {"keye": dict(ARCH, **arch), "bptt": bptt}
    cfg = C.process_control(cfg)
    cfg["num_tokens"] = cfg["classes_size"] = VOCAB
    return cfg


def reference_model(cfg):
    """What `benchmark/reference/keye.py` takes as ``config["model"]``."""
    return dict(cfg["keye"], num_tokens=cfg["num_tokens"], bptt=cfg["bptt"])


def cell():
    """(cell, configuration) of the real cell's files at the tiny sizes."""
    cell = _load("workloads", NAME)
    config = copy.deepcopy(_load("configs", "keye-vl-2-30b-a3b"))
    config["control"] = "1_10_0.5_iid_fix_a1-b1-c1-d1-e1_bn_1_1"
    config["model"].update(ARCH, num_tokens=VOCAB, bptt=BPTT, experts_held=4)
    config["cfg_overrides"] = {"keye": dict(ARCH), "bptt": BPTT,
                               "batch_size": {"train": 20, "test": 10}}
    config["federation"].update(batch_rows=20, rows_per_user=2)
    config["data"]["sizes"] = {"types": VOCAB, "train": 33 * 39,  # 20 rows of 64 tokens: 1 window
                               "test": 33 * 10}
    config["limits"] = {"level_loss_gap": 1e-3, "update_norm_gap": 0.05}
    return cell, config
