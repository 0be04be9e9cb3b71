"""The Phi-4-mini-flash cell cut down for the CPU tests (never a benchmark
cell): published layers 13-19 (`sliding`, `mamba`, `sliding`, `mamba`, `full`,
`gmu`, `cross`: all five kinds, both producers and both consumers, the memory
written by the SECOND Mamba layer) at hidden 128, 8 query heads on 4
key/value heads of 16 (4 query pairs on 2 key pairs, a group of 2 as
published; a pair's value 32 wide), 256 inner channels over a state of 8
through a rank of 8, 4 taps, a feed-forward of 256, a window of 16 on rows of
64 positions (one chunk of the scan, four blocks of 16 side by side: the
chunks' boundaries are `tests/test_phi4flash.py`'s), vocabulary 96.  Every sliced width halves four
times (rates 1 .. 1/16: a head keeps 16, 8, 4, 2, 1 dims, the inner channels
256 .. 16).  Hidden 128 and not 64 for `tiny_ouro`'s reason.  `CELL_LAYERS`:
the real cell's four kinds (layers 16-19) at these widths."""

import copy
import json
import os

HERE = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

ARCH = {"hidden_size": 128, "num_hidden_layers": 7, "layer_offset": 13,
        "layer_types": ["sliding", "mamba", "sliding", "mamba", "full", "gmu", "cross"],
        "num_attention_heads": 8, "num_key_value_heads": 4, "sliding_window": 16,
        "intermediate_size": 256, "d_state": 8, "d_conv": 4, "expand": 2, "dt_rank": 8}
CELL_LAYERS = {"num_hidden_layers": 4, "layer_offset": 16,
               "layer_types": ["mamba", "full", "gmu", "cross"]}
VOCAB, BPTT = 96, 64
NAME = "phi-4-mini-flash-reasoning.fix-a1-e1.train-8k"


def _load(kind, name):
    with open(os.path.join(HERE, kind, name + ".json"), encoding="utf-8") as f:
        return json.load(f)


def program_cfg(control="1_10_0.5_iid_fix_a1-b1-c1-d1-e1_bn_1_1", bptt=BPTT, **arch):
    """The program's cfg of the tiny model (``arch`` overrides :data:`ARCH`)."""
    from heterofl_tpu import config as C

    cfg = C.default_cfg()
    cfg["control"] = C.parse_control_name(control)
    cfg["data_name"], cfg["model_name"] = "WikiText2", "phi4flash"
    cfg["override"] = {"phi4flash": dict(ARCH, **arch), "bptt": bptt}
    cfg = C.process_control(cfg)
    cfg["num_tokens"] = cfg["classes_size"] = VOCAB
    return cfg


def reference_model(cfg):
    """What `benchmark/reference/phi4flash.py` takes as ``config["model"]``."""
    return dict(cfg["phi4flash"], num_tokens=cfg["num_tokens"], bptt=cfg["bptt"])


def cell():
    """(cell, configuration) of the real cell's files at the tiny sizes."""
    cell = _load("workloads", NAME)
    config = copy.deepcopy(_load("configs", "phi-4-mini-flash-reasoning"))
    config["control"] = "1_10_0.5_iid_fix_a1-b1-c1-d1-e1_bn_1_1"
    arch = dict(ARCH, **CELL_LAYERS)
    config["model"].update(arch, num_tokens=VOCAB, bptt=BPTT)
    config["cfg_overrides"] = {"phi4flash": arch, "bptt": BPTT,
                               "batch_size": {"train": 20, "test": 10}}
    config["federation"].update(batch_rows=20, rows_per_user=2)
    config["data"]["sizes"] = {"types": VOCAB, "train": 33 * 39,  # 20 rows of 64 tokens: 1 window
                               "test": 33 * 10}
    config["limits"] = {"level_loss_gap": 1e-3, "update_norm_gap": 0.05}
    return cell, config
