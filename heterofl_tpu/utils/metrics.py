"""Metric registry: Loss / Accuracy / Perplexity with Local-/Global- prefixed
variants (parity: ``src/metrics/metrics.py``).

Two consumption paths:

* :class:`Metric` -- name -> closure registry evaluated on a single batch's
  ``(input, output)`` dicts, like the reference.
* :func:`summarize_sums` -- converts the round engine's device-side weighted
  sums (``loss_sum`` / ``score_sum`` / ``n``) into the same named metrics
  without a host round-trip per batch.
"""

from __future__ import annotations

from typing import Dict, Iterable

import numpy as np

from ..config import LM_MODEL_NAMES


def accuracy(score, label, topk: int = 1) -> float:
    """Top-k accuracy in percent (ref metrics.py:7-13). Class axis is last."""
    score = np.asarray(score)
    label = np.asarray(label)
    flat = score.reshape(-1, score.shape[-1])
    lab = label.reshape(-1)
    if topk == 1:
        correct = (np.argmax(flat, -1) == lab).sum()
    else:
        top = np.argsort(-flat, axis=-1)[:, :topk]
        correct = (top == lab[:, None]).any(-1).sum()
    return float(correct * 100.0 / lab.shape[0])


def perplexity(score, label) -> float:
    """exp(cross entropy) (ref metrics.py:16-25). Class axis is last."""
    score = np.asarray(score, np.float64)
    label = np.asarray(label)
    flat = score.reshape(-1, score.shape[-1])
    lab = label.reshape(-1)
    mx = flat.max(-1, keepdims=True)
    logz = mx[:, 0] + np.log(np.exp(flat - mx).sum(-1))
    ce = (logz - flat[np.arange(lab.shape[0]), lab]).mean()
    return float(np.exp(ce))


class Metric:
    def __init__(self):
        loss = lambda inp, out: float(out["loss"])
        acc = lambda inp, out: accuracy(out["score"], inp["label"])
        ppl = lambda inp, out: perplexity(out["score"], inp["label"])
        self.metric = {}
        for prefix in ("", "Local-", "Global-"):
            self.metric[prefix + "Loss"] = loss
            self.metric[prefix + "Accuracy"] = acc
            self.metric[prefix + "Perplexity"] = ppl

    def evaluate(self, metric_names: Iterable[str], inp, out) -> Dict[str, float]:
        return {name: self.metric[name](inp, out) for name in metric_names}


def summarize_sums(sums: Dict[str, np.ndarray], kind: str, prefix: str = "Local-"
                   ) -> Dict[str, float]:
    """Round-engine sums -> named means.

    vision: ``score_sum`` is the weighted correct count -> Accuracy %%;
    LM: ``score_sum`` is the row-weighted sum of per-window exp(CE) ->
    Perplexity (the reference's size-weighted mean of batch perplexities).
    """
    n = float(np.sum(sums["n"]))
    if n <= 0:
        return {}
    loss = float(np.sum(sums["loss_sum"])) / n
    out = {prefix + "Loss": loss}
    if kind in LM_MODEL_NAMES:
        out[prefix + "Perplexity"] = float(np.sum(sums["score_sum"])) / n
    else:
        out[prefix + "Accuracy"] = float(np.sum(sums["score_sum"])) / n * 100.0
    return out
