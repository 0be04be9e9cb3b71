"""Tiny cells for the CPU tests: the real cells' files with the widths, the
population and the data cut down (never a benchmark cell)."""

import copy
import json
import os

HERE = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _load(kind, name):
    with open(os.path.join(HERE, kind, name + ".json"), encoding="utf-8") as f:
        return json.load(f)


def resnet():
    cell = _load("workloads", "resnet18-cifar10.fix-a1-e1.train")
    config = _load("configs", "resnet18-cifar10")
    config = copy.deepcopy(config)
    config["control"] = "1_10_0.5_iid_fix_a1-b1-c1-d1-e1_bn_1_1"
    config["model"]["hidden_size"] = [8, 16, 16, 16]
    config["cfg_overrides"] = {"resnet": {"hidden_size": [8, 16, 16, 16]}}
    config["data"]["sizes"] = {"train": 500, "test": 100, "classes": 10}
    config["limits"] = {"level_loss_gap": 0.2, "update_norm_gap": 0.9}
    return cell, config


def transformer():
    cell = _load("workloads", "transformer-wikitext2.fix-a1-e1.train")
    config = copy.deepcopy(_load("configs", "transformer-wikitext2"))
    config["control"] = "1_10_0.5_iid_fix_a1-b1-c1-d1-e1_bn_1_1"
    config["model"].update(embedding_size=32, num_heads=2, hidden_size=64,
                           num_layers=1, num_tokens=302)
    config["cfg_overrides"] = {
        "transformer": {"embedding_size": 32, "num_heads": 2, "hidden_size": 64,
                        "num_layers": 1, "dropout": 0.2},
        "batch_size": {"train": 10, "test": 10}}
    config["federation"].update(batch_rows=10)
    config["data"]["sizes"] = {"types": 302, "train": 33 * 97,  # 320 tokens a row: 5 windows
                               "test": 33 * 20}
    config["limits"] = {"level_loss_gap": 0.2, "update_norm_gap": 0.9}
    return cell, config
