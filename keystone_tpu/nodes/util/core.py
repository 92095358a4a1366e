"""Glue nodes (parity: ``nodes/util/`` — ClassLabelIndicators.scala:15,38,
VectorSplitter.scala:10, VectorCombiner.scala, MaxClassifier.scala,
TopKClassifier.scala, Cacher.scala:15, Shuffler.scala:15, Densify/Sparsify,
FloatToDouble, MatrixVectorizer)."""

from __future__ import annotations

from typing import List, Optional, Sequence

import jax
import jax.numpy as jnp
import numpy as np

from ...data.dataset import Dataset
from ...workflow.transformer import Transformer


class ClassLabelIndicators(Transformer):
    """Int label → ±1 indicator vector: −1 everywhere, +1 at the class index
    (parity: ClassLabelIndicatorsFromIntLabels, ClassLabelIndicators.scala:15-30).
    The ±1 (not 0/1) coding is what makes plain least squares a classifier."""

    def __init__(self, num_classes: int):
        if num_classes <= 1:
            raise ValueError("num_classes must be > 1")
        self.num_classes = num_classes

    def trace_batch(self, y):
        y = y.astype(jnp.int32)
        return 2.0 * jax.nn.one_hot(y, self.num_classes, dtype=jnp.float32) - 1.0


class MultiClassLabelIndicators(Transformer):
    """Variable-length label sets → ±1 multi-hot vector (parity:
    ClassLabelIndicatorsFromIntArrayLabels, ClassLabelIndicators.scala:38-58).
    Per-item host path: label sets are ragged."""

    def __init__(self, num_classes: int):
        if num_classes <= 1:
            raise ValueError("num_classes must be > 1")
        self.num_classes = num_classes

    def apply(self, labels):
        out = np.full((self.num_classes,), -1.0, dtype=np.float32)
        out[np.asarray(labels, dtype=np.int64)] = 1.0
        return jnp.asarray(out)

    def out_spec(self, labels_spec=None):
        # ragged per-item host path: not abstractly evaluable, but the
        # output spec is fully determined by construction
        return ((self.num_classes,), "float32")


class MaxClassifier(Transformer):
    """argmax over the score vector (parity: MaxClassifier.scala)."""

    def trace_batch(self, X):
        with jax.named_scope("ks.apply.argmax"):
            return jnp.argmax(X, axis=-1)


class TopKClassifier(Transformer):
    """Indices of the k largest scores, descending
    (parity: TopKClassifier.scala)."""

    def __init__(self, k: int):
        self.k = k

    def trace_batch(self, X):
        _, idx = jax.lax.top_k(X, self.k)
        return idx


class VectorCombiner(Transformer):
    """Concatenate the gathered branch outputs feature-wise
    (parity: VectorCombiner.scala vertcat over Seq[DenseVector])."""

    def trace_batch(self, Xs):
        # Input is the gather node's tuple of branch outputs.
        if isinstance(Xs, (tuple, list)):
            return jnp.concatenate([jnp.asarray(x) for x in Xs], axis=-1)
        return jnp.asarray(Xs)

    def apply(self, xs: Sequence) -> jnp.ndarray:
        return jnp.concatenate([jnp.asarray(x) for x in xs], axis=-1)

    def apply_batch(self, data: Dataset) -> Dataset:
        from ...data.chunked import ChunkedDataset

        data = Dataset.of(data)
        if isinstance(data, ChunkedDataset):
            # zipped gather chunks are tuples — concat lazily per chunk
            return data.map_batch(self.trace_batch)
        if data.is_batched and isinstance(data.payload, (list, tuple)):
            # gather output: a tuple of (n, d_i) arrays — concat on device.
            return Dataset(
                jnp.concatenate(
                    [jnp.asarray(p) for p in data.payload], axis=-1
                ),
                batched=True,
            )
        return data.map(self.apply)


class VectorSplitter(Transformer):
    """Split (n, d) features into ceil(d/block_size) column blocks
    (parity: VectorSplitter.scala:10-37). Output is the list of blocks —
    consumed by the block solvers; mesh-native layout note in SURVEY §2.7."""

    def __init__(self, block_size: int, num_features: Optional[int] = None):
        self.block_size = block_size
        self.num_features = num_features

    def out_spec(self, in_spec=None):
        # block list: not abstractly evaluable (list output), but fully
        # determined by the input width. An unknown input spec stays
        # unknown — fabricating a dtype would let the checker "guess",
        # which its no-false-positives contract forbids.
        if in_spec is None:
            return None
        shape, dtype = in_spec
        if not shape:
            raise ValueError("VectorSplitter needs a feature axis")
        d = self.num_features or int(shape[-1])
        lead = tuple(shape[:-1])
        return tuple(
            (lead + (min(self.block_size, d - i),), dtype)
            for i in range(0, d, self.block_size)
        )

    def split_batch(self, X) -> List[jnp.ndarray]:
        X = jnp.asarray(X)
        d = self.num_features or X.shape[-1]
        return [
            X[..., i : min(i + self.block_size, d)]
            for i in range(0, d, self.block_size)
        ]

    def apply(self, x):
        return self.split_batch(x)

    def apply_batch(self, data: Dataset) -> Dataset:
        X = Dataset.of(data).to_array()
        return Dataset(tuple(self.split_batch(X)), batched=True)


class Cacher(Transformer):
    """Materialize and hold the upstream result (parity: Cacher.scala:15 —
    the node the AutoCacheRule inserts). On TPU this pins the array in HBM.

    Inside a fused traced program (FittedPipeline.trace_fn) caching is
    meaningless — XLA holds intermediates — so the traced form is identity;
    this keeps serve chains containing Cachers one-jaxpr compilable."""

    def __init__(self, name: Optional[str] = None):
        self.name = name

    def trace_batch(self, X):
        return X

    def apply(self, x):
        return x

    def apply_batch(self, data: Dataset) -> Dataset:
        return Dataset.of(data).cache()


class Shuffler(Transformer):
    """Deterministic-seed row shuffle (parity: Shuffler.scala:15)."""

    def __init__(self, seed: int = 42):
        self.seed = seed

    def out_spec(self, in_spec=None):
        return in_spec  # a permutation is spec-preserving

    def apply(self, x):
        return x

    def apply_batch(self, data: Dataset) -> Dataset:
        data = Dataset.of(data)
        n = len(data)
        perm = np.random.default_rng(self.seed).permutation(n)
        if data.is_batched:
            return Dataset(
                jax.tree_util.tree_map(
                    lambda a: a[jnp.asarray(perm)], data.payload
                ),
                batched=True,
            )
        items = data.collect()
        return Dataset.from_items([items[i] for i in perm])


class FloatToDouble(Transformer):
    """dtype widening (parity: FloatToDouble.scala). On TPU f64 is emulated
    and slow; this exists for numerical-parity experiments on CPU."""

    def trace_batch(self, X):
        return X.astype(jnp.float64)


class DoubleToFloat(Transformer):
    def trace_batch(self, X):
        return X.astype(jnp.float32)


class MatrixVectorizer(Transformer):
    """Flatten each matrix item column-major into a vector (parity:
    MatrixVectorizer.scala; breeze toDenseVector is column-major)."""

    def trace_batch(self, X):
        # X: (n, r, c) → (n, r*c) in column-major (Fortran) order.
        return jnp.transpose(X, (0, 2, 1)).reshape(X.shape[0], -1)


class Densify(Transformer):
    """Sparse→dense passthrough: arrays are already dense on TPU; accepts
    scipy.sparse items for API parity (Densify.scala)."""

    def apply(self, x):
        if hasattr(x, "todense"):
            return jnp.asarray(np.asarray(x.todense()).squeeze())
        return jnp.asarray(x)


class Sparsify(Transformer):
    """Dense→scipy CSR per item (Sparsify.scala). Host-side only — XLA has no
    dynamic sparsity; used at the text-featurization boundary."""

    def apply(self, x):
        import scipy.sparse as sp

        return sp.csr_matrix(np.asarray(x))
