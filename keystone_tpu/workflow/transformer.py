"""Typed Transformer / Estimator / LabelEstimator.

Parity targets: ``workflow/Transformer.scala``, ``Estimator.scala``,
``LabelEstimator.scala``. A Transformer is simultaneously (a) a chainable
pipeline stage and (b) the untyped operator that executes at its node — same
dual role as the reference.

TPU contract: numeric nodes implement ``trace_batch(x)``, a *pure jax*
function over the stacked array (leading batch dim). That single method gives
them: vectorized batch application, participation in whole-pipeline jit
fusion (see ``FittedPipeline.compile``), and mesh-sharded execution (the
stacked array may be sharded over devices; XLA inserts the collectives).
``apply(x)`` is the per-item fallback for host-side/ragged work.
"""

from __future__ import annotations

from typing import Any, Callable, Optional, Sequence

from ..data.dataset import Dataset
from .expressions import DatasetExpression, DatumExpression
from .graph import Graph
from .operators import (
    DelegatingOperator,
    EstimatorOperator,
    TransformerOperator,
)
from .pipeline import Chainable, Pipeline, attach_data

# re-exported for operator implementors
__all__ = [
    "Transformer",
    "Estimator",
    "LabelEstimator",
    "FunctionNode",
    "Identity",
]


class Transformer(Chainable, TransformerOperator):
    """A deterministic per-item function, batched on TPU.

    Implement at least one of:
      * ``trace_batch(X)`` — pure jax over the stacked array (preferred), or
      * ``apply(x)`` — per-item host function.
    """

    #: override in subclasses whose trace_batch is pure jax
    trace_batch: Optional[Callable] = None

    #: set True on transformers whose trace_batch couples rows (batch
    #: statistics, whole-batch normalization, ...). ``apply_chunked``
    #: refuses such chains — its pad-and-slice tail would silently change
    #: their output — and routes callers to ``apply`` instead.
    batch_coupled: bool = False

    #: set True on transformers over per-item (d, m) matrices whose
    #: trace_batch acts column by column: column c of an item's output
    #: reads column c of its input and no other, so sampling columns
    #: before it or after it gives the same sample
    #: (``nodes/images/chain.py:SampledSIFTRule``).
    column_wise: bool = False

    def apply(self, x: Any) -> Any:
        if self.trace_batch is not None:
            import jax.numpy as jnp

            return self.trace_batch(jnp.asarray(x)[None])[0]
        raise NotImplementedError(f"{type(self).__name__} implements neither apply nor trace_batch")

    def apply_batch(self, data: Dataset) -> Dataset:
        # Eager per-op dispatch here is deliberate: per-node jit costs one
        # XLA compile per node *instance* (measured slower end-to-end than
        # eager on TPU). Whole-chain fusion happens at the pipeline level
        # (FittedPipeline.compile), where one program covers every node.
        data = Dataset.of(data)
        if self.batch_coupled and getattr(data, "is_chunked", False):
            raise ValueError(
                f"{type(self).__name__} is batch-coupled: running it "
                "per-chunk would compute batch statistics per chunk, "
                "silently diverging from whole-batch output — "
                "materialize the dataset (e.g. .cache()) first"
            )
        if self.trace_batch is not None and data.is_batched:
            return data.map_batch(self.trace_batch)
        return data.map(self.apply)

    # -- operator-level glue -------------------------------------------

    def single_transform(self, inputs: Sequence[DatumExpression]) -> Any:
        (x,) = [d.get() for d in inputs]
        return self.apply(x)

    def batch_transform(self, inputs: Sequence[DatasetExpression]) -> Dataset:
        (ds,) = [d.get() for d in inputs]
        return self.apply_batch(ds)

    # -- chainable glue -------------------------------------------------

    def to_pipeline(self) -> Pipeline:
        graph = Graph()
        graph, source = graph.add_source()
        graph, node = graph.add_node(self, [source])
        graph, sink = graph.add_sink(node)
        return Pipeline(graph, source, sink)

    def __call__(self, data: Any):
        return self.to_pipeline().apply(data)


class FunctionNode(Transformer):
    """Wrap plain functions as a transformer: ``FunctionNode(item_fn=...)`` or
    ``FunctionNode(batch_fn=...)`` (batch_fn must be pure jax)."""

    def __init__(self, item_fn: Callable = None, batch_fn: Callable = None, label: str = None):
        if item_fn is None and batch_fn is None:
            raise ValueError("need item_fn or batch_fn")
        self._item_fn = item_fn
        self._label = label
        if batch_fn is not None:
            self.trace_batch = batch_fn

    @property
    def label(self) -> str:
        return self._label or getattr(
            self._item_fn or self.trace_batch, "__name__", type(self).__name__
        )

    def apply(self, x: Any) -> Any:
        if self._item_fn is not None:
            return self._item_fn(x)
        return super().apply(x)


class Identity(Transformer):
    """Pass-through (parity: ``workflow/Identity.scala``)."""

    def trace_batch(self, X):
        return X

    def apply(self, x: Any) -> Any:
        return x


class Estimator(Chainable, EstimatorOperator):
    """Fits on a dataset, producing a Transformer.

    Implement ``fit(data: Dataset) -> Transformer``.
    Use via ``est.with_data(data)`` or ``pipeline.and_then(est, data)``.
    """

    def fit(self, data: Dataset) -> Transformer:
        raise NotImplementedError

    def with_data(self, data: Any) -> Pipeline:
        """A pipeline that fits this estimator on ``data`` (lazily, once) and
        applies the fitted transformer to the pipeline input
        (parity: ``Estimator.scala:29-46``)."""
        graph = Graph()
        graph, source = graph.add_source()
        graph, data_id = attach_data(graph, data)
        graph, est_node = graph.add_node(self, [data_id])
        graph, delegating = graph.add_node(DelegatingOperator(), [est_node, source])
        graph, sink = graph.add_sink(delegating)
        return Pipeline(graph, source, sink)

    def to_pipeline(self) -> Pipeline:
        raise TypeError(
            "an Estimator is not directly chainable; use with_data(data) or "
            "and_then(est, data)"
        )

    def __call__(self, data: Any) -> Pipeline:
        return self.with_data(data)


class LabelEstimator(Chainable, EstimatorOperator):
    """Fits on (data, labels), producing a Transformer.

    Implement ``fit(data: Dataset, labels: Dataset) -> Transformer``.
    """

    def fit(self, data: Dataset, labels: Dataset) -> Transformer:
        raise NotImplementedError

    def with_data(self, data: Any, labels: Any = None) -> Pipeline:
        if labels is None:
            raise ValueError("LabelEstimator.with_data requires labels")
        graph = Graph()
        graph, source = graph.add_source()
        graph, data_id = attach_data(graph, data)
        graph, labels_id = attach_data(graph, labels)
        graph, est_node = graph.add_node(self, [data_id, labels_id])
        graph, delegating = graph.add_node(DelegatingOperator(), [est_node, source])
        graph, sink = graph.add_sink(delegating)
        return Pipeline(graph, source, sink)

    def to_pipeline(self) -> Pipeline:
        raise TypeError(
            "a LabelEstimator is not directly chainable; use with_data(data, labels)"
        )

    def __call__(self, data: Any, labels: Any = None) -> Pipeline:
        return self.with_data(data, labels)
