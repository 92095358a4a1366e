"""Evaluator base (parity: evaluation/Evaluator.scala:19 — accepts any mix of
raw collections, Datasets and lazy PipelineDatasets for both arguments)."""

from __future__ import annotations

from typing import Any

import numpy as np

from ..utils.params import to_host


def resolve(x: Any) -> np.ndarray:
    """Materialize predictions/labels: PipelineDataset → Dataset → array."""
    from ..data.dataset import Dataset
    from ..workflow.pipeline import PipelineResult

    if isinstance(x, PipelineResult):
        x = x.get()
    if isinstance(x, Dataset):
        x = x.to_array()
    return to_host(x)  # a device array is read back under an xfer.d2h span


class Evaluator:
    def evaluate(self, predictions: Any, labels: Any):
        raise NotImplementedError
