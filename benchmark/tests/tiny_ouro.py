"""The Ouro cell cut down for the CPU tests (never a benchmark cell): two
layers run three times, hidden 128, 4 query heads on 4 key/value heads of 32,
SwiGLU of width 192, vocabulary 96, rows of 32 positions.  Every width halves
four times (rates 1 .. 1/16; a head keeps 32, 16, 8, 4, 2 dims).  Hidden 128
and not 64: six layer applications, each with two norms over the hidden
size behind a Scaler of 16, leave the float32 reference itself 2e-3 from its
float64 self at a hidden size of 4 (PERF.md, PR 40); at 8 it is 1e-4."""

import copy
import json
import os

HERE = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

ARCH = {"hidden_size": 128, "num_hidden_layers": 2, "intermediate_size": 192,
        "num_attention_heads": 4, "num_key_value_heads": 4, "head_dim": 32,
        "total_ut_steps": 3}
VOCAB, BPTT = 96, 32
NAME = "ouro-2.6b.fix-a1-e1.train-2k"


def _load(kind, name):
    with open(os.path.join(HERE, kind, name + ".json"), encoding="utf-8") as f:
        return json.load(f)


def program_cfg(control="1_10_0.5_iid_fix_a1-b1-c1-d1-e1_bn_1_1", bptt=BPTT, **arch):
    """The program's cfg of the tiny model (``arch`` overrides :data:`ARCH`)."""
    from heterofl_tpu import config as C

    cfg = C.default_cfg()
    cfg["control"] = C.parse_control_name(control)
    cfg["data_name"], cfg["model_name"] = "WikiText2", "ouro"
    cfg["override"] = {"ouro": dict(ARCH, **arch), "bptt": bptt}
    cfg = C.process_control(cfg)
    cfg["num_tokens"] = cfg["classes_size"] = VOCAB
    return cfg


def reference_model(cfg):
    """What `benchmark/reference/ouro.py` takes as ``config["model"]``."""
    return dict(cfg["ouro"], num_tokens=cfg["num_tokens"], bptt=cfg["bptt"])


def cell():
    """(cell, configuration) of the real cell's files at the tiny sizes."""
    cell = _load("workloads", NAME)
    config = copy.deepcopy(_load("configs", "ouro-2.6b"))
    config["control"] = "1_10_0.5_iid_fix_a1-b1-c1-d1-e1_bn_1_1"
    config["model"].update(ARCH, num_tokens=VOCAB, bptt=BPTT)
    config["cfg_overrides"] = {"ouro": dict(ARCH), "bptt": BPTT,
                               "batch_size": {"train": 20, "test": 10}}
    config["federation"].update(batch_rows=20, rows_per_user=2)
    config["data"]["sizes"] = {"types": VOCAB, "train": 33 * 39,  # 20 rows of 64 tokens: 2 windows
                               "test": 33 * 10}
    config["limits"] = {"level_loss_gap": 1e-3, "update_norm_gap": 0.05}
    return cell, config
