"""Multiclass classification metrics.

Parity: evaluation/MulticlassClassifierEvaluator.scala:23,130 — a one-pass
confusion matrix plus the per-class / micro / macro statistics derived from
it. The confusion-matrix build is a single device-side scatter-add (the
reference's map + reduce over (pred, actual) pairs).
"""

from __future__ import annotations

from dataclasses import dataclass
from typing import Any, List

import jax
import jax.numpy as jnp
import numpy as np

from ..obs.tracer import span
from ..utils.params import to_device, to_host
from .base import Evaluator, resolve


@dataclass
class BinaryMetrics:
    """Per-class one-vs-rest counts (parity: BinaryClassificationMetrics)."""

    tp: float
    fp: float
    tn: float
    fn: float

    @property
    def accuracy(self) -> float:
        tot = self.tp + self.fp + self.tn + self.fn
        return (self.tp + self.tn) / tot if tot else 0.0

    @property
    def error(self) -> float:
        return 1.0 - self.accuracy

    @property
    def precision(self) -> float:
        denom = self.tp + self.fp
        return self.tp / denom if denom else 0.0

    @property
    def recall(self) -> float:
        denom = self.tp + self.fn
        return self.tp / denom if denom else 0.0

    def f_score(self, beta: float = 1.0) -> float:
        p, r = self.precision, self.recall
        b2 = beta * beta
        denom = b2 * p + r
        return (1 + b2) * p * r / denom if denom else 0.0

    def merge(self, other: "BinaryMetrics") -> "BinaryMetrics":
        return BinaryMetrics(
            self.tp + other.tp, self.fp + other.fp,
            self.tn + other.tn, self.fn + other.fn,
        )


class MulticlassMetrics:
    """Derived statistics over a (actual, predicted) confusion matrix
    (parity: MulticlassMetrics, MulticlassClassifierEvaluator.scala:23-121).
    ``confusion_matrix[actual, predicted]`` counts."""

    def __init__(self, confusion_matrix):
        self.confusion_matrix = np.asarray(confusion_matrix, dtype=np.float64)
        cm = self.confusion_matrix
        self.num_classes = cm.shape[0]
        total = cm.sum()
        actual_sums = cm.sum(axis=1)
        predicted_sums = cm.sum(axis=0)
        self.class_metrics: List[BinaryMetrics] = []
        for c in range(self.num_classes):
            tp = cm[c, c]
            fp = predicted_sums[c] - tp
            tn = total - actual_sums[c] - fp
            fn = total - tp - fp - tn
            self.class_metrics.append(BinaryMetrics(tp, fp, tn, fn))

    def _class_avg(self, f) -> float:
        return sum(f(m) for m in self.class_metrics) / self.num_classes

    def _micro(self, f) -> float:
        merged = self.class_metrics[0]
        for m in self.class_metrics[1:]:
            merged = merged.merge(m)
        return f(merged)

    @property
    def avg_accuracy(self) -> float:
        return self._class_avg(lambda m: m.accuracy)

    @property
    def macro_precision(self) -> float:
        return self._class_avg(lambda m: m.precision)

    @property
    def macro_recall(self) -> float:
        return self._class_avg(lambda m: m.recall)

    def macro_f_score(self, beta: float = 1.0) -> float:
        return self._class_avg(lambda m: m.f_score(beta))

    @property
    def total_accuracy(self) -> float:
        return self._micro(lambda m: m.precision)

    @property
    def total_error(self) -> float:
        return self._micro(
            lambda m: m.fn / (m.fn + m.tp) if (m.fn + m.tp) else 0.0
        )

    @property
    def micro_precision(self) -> float:
        return self._micro(lambda m: m.precision)

    @property
    def micro_recall(self) -> float:
        return self._micro(lambda m: m.recall)

    def micro_f_score(self, beta: float = 1.0) -> float:
        return self._micro(lambda m: m.f_score(beta))

    def summary(self, class_names=None) -> str:
        """Aggregate metrics; with ``class_names``, adds the per-class
        accuracy table (parity: MulticlassMetrics.summary(classLabels),
        MulticlassClassifierEvaluator.scala:130)."""
        lines = [
            f"total accuracy: {self.total_accuracy:.3f}",
            f"total error: {self.total_error:.3f}",
            f"macro precision: {self.macro_precision:.3f}",
            f"macro recall: {self.macro_recall:.3f}",
            f"macro f1: {self.macro_f_score():.3f}",
        ]
        if class_names is not None:
            for i, name in enumerate(class_names):
                if i >= len(self.class_metrics):
                    break
                m = self.class_metrics[i]
                lines.append(
                    f"  {name}: accuracy {m.accuracy:.3f} "
                    f"precision {m.precision:.3f} recall {m.recall:.3f}"
                )
        return "\n".join(lines)


@jax.jit
def _confusion(preds, actuals, cm0):
    idx = actuals * cm0.shape[0] + preds
    flat = jnp.zeros(cm0.shape[0] * cm0.shape[1], dtype=jnp.float32)
    flat = flat.at[idx].add(1.0)
    return flat.reshape(cm0.shape)


class MulticlassClassifierEvaluator(Evaluator):
    """Build MulticlassMetrics from predicted and actual int labels
    (parity: MulticlassClassifierEvaluator.scala:130-160)."""

    def __init__(self, num_classes: int):
        self.num_classes = num_classes

    def evaluate(self, predictions: Any, actuals: Any) -> MulticlassMetrics:
        with span("eval.metrics", classes=self.num_classes):
            preds = _device_labels(predictions)
            acts = _device_labels(actuals)
            if preds.shape[0] != acts.shape[0]:
                raise ValueError("predictions and actuals differ in length")
            cm0 = jnp.zeros((self.num_classes, self.num_classes))
            return MulticlassMetrics(to_host(_confusion(preds, acts, cm0)))


def _device_labels(x: Any) -> jax.Array:
    """``x`` as flat int32 labels on the device. ``resolve`` materializes
    on the host, so predictions that sit on the device make the round
    trip — the read-back is where a job waits for its apply to finish."""
    return jnp.asarray(to_device(resolve(x)), dtype=jnp.int32).ravel()
