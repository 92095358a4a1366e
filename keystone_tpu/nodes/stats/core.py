"""Statistics / random-feature nodes.

Parity targets: ``nodes/stats/`` in the reference — PaddedFFT.scala:13,
CosineRandomFeatures.scala:19,49, RandomSignNode.scala:11,
StandardScaler.scala:16,38, LinearRectifier.scala:12, NormalizeRows.scala:10,
SignedHellingerMapper.scala:12,18, Sampling.scala:12,28.

Every numeric node here is a pure ``trace_batch`` over the stacked (n, d)
array: elementwise ops fuse into neighbouring matmuls under jit, the
random-feature GEMM rides the MXU, and the fit-side reductions (mean/var)
lower to psum over the mesh when the input is sharded.
"""

from __future__ import annotations

import math
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

from ...data.dataset import Dataset
from ...ops.bounded_cos import LIMIT, cos_bounded
from ...workflow.transformer import Estimator, Transformer
from ...utils.params import as_param


class PaddedFFT(Transformer):
    """Zero-pad each vector to the next power of two and return the real part
    of the first half of its FFT (parity: PaddedFFT.scala:13-21). d →
    2^ceil(log2 d) / 2 output features; rfft keeps XLA from computing the
    redundant conjugate half."""

    def trace_batch(self, X):
        d = X.shape[-1]
        padded = 1 << max(0, (d - 1)).bit_length()
        X = jnp.pad(X, [(0, 0)] * (X.ndim - 1) + [(0, padded - d)])
        # rfft returns padded/2+1 bins; the reference keeps bins [0, padded/2).
        return jnp.fft.rfft(X, axis=-1).real[..., : padded // 2]


class RandomSignNode(Transformer):
    """Elementwise multiply by a fixed random ±1 vector
    (parity: RandomSignNode.scala:11,19-24)."""

    def __init__(self, signs):
        self.signs = as_param(signs)

    @staticmethod
    def create(size: int, seed: int = 0) -> "RandomSignNode":
        signs = 2.0 * jax.random.bernoulli(
            jax.random.PRNGKey(seed), 0.5, (size,)
        ).astype(jnp.float32) - 1.0
        return RandomSignNode(signs)

    def trace_batch(self, X):
        return X * self.signs


class LinearRectifier(Transformer):
    """max(maxVal, x − alpha) (parity: LinearRectifier.scala:12-17)."""

    def __init__(self, max_val: float = 0.0, alpha: float = 0.0):
        self.max_val = max_val
        self.alpha = alpha

    def trace_batch(self, X):
        return jnp.maximum(self.max_val, X - self.alpha)


class NormalizeRows(Transformer):
    """Scale each row to unit L2 norm (zero rows pass through unchanged)."""

    def trace_batch(self, X):
        norm = jnp.linalg.norm(X, axis=-1, keepdims=True)
        return X / jnp.where(norm == 0, 1.0, norm)


class SignedHellingerMapper(Transformer):
    """x → sign(x)·√|x| (parity: SignedHellingerMapper.scala:12-16; on a
    descriptor matrix, BatchSignedHellingerMapper)."""

    #: element by element: a column's root reads that column alone, so a
    #: sampler may be drawn ahead of it (``nodes/images/chain.py``)
    column_wise = True

    def trace_batch(self, X):
        with jax.named_scope("ks.featurize.hellinger"):
            return jnp.sign(X) * jnp.sqrt(jnp.abs(X))


class TermFrequency(Transformer):
    """Seq of terms → (unique term, weighting(count)) pairs
    (parity: TermFrequency.scala:18-21). ``fun`` maps the raw count, e.g.
    ``TermFrequency(lambda x: math.log(x) + 1)``; defaults to identity."""

    def __init__(self, fun=None):
        self.fun = fun

    def apply(self, terms):
        from collections import Counter

        fun = self.fun or (lambda x: x)
        counts = Counter(
            tuple(t) if isinstance(t, list) else t for t in terms
        )
        return [(term, float(fun(c))) for term, c in counts.items()]


class CosineRandomFeatures(Transformer):
    """Random Fourier features cos(x Wᵀ + b)
    (parity: CosineRandomFeatures.scala:19-44; batched GEMM is the reference's
    mapPartitions + BLAS3 path, here one MXU matmul).

    W: (num_output_features, num_input_features); b: (num_output_features,).

    The cosine behind the product is :func:`ops.bounded_cos.cos_bounded`
    wherever a call's arguments provably lie in its range (|z| ≤ ``LIMIT``
    = 4,096), ``jnp.cos`` anywhere else. The proof is made per call, on the
    device, from the call's own rows: by Cauchy–Schwarz ``|x·w + b| <=
    max‖x‖₂ · max‖w‖₂ + max|b|`` (:meth:`argument_bound`; one pass over
    the rows, W's and b's parts constants of the trace), and a ``lax.cond``
    on ``bound <= LIMIT`` around product-and-cosine. Gaussian W at TIMIT's
    γ over unit-variance rows is bounded by about 40 and takes the bounded
    body; Cauchy-drawn W, rows scaled by 10⁶, an ``inf`` or a ``nan``
    anywhere in the rows fail the comparison and get ``jnp.cos``'s own
    bits. No option chooses: the product, W, b and the order ``x·W + b``
    is formed in are the same in both bodies. The bounded body is within
    1.34·10⁻⁷ of the float64 cosine at EVERY float32 of its range (XLA's
    CPU; 1.28·10⁻⁷ without fused multiply-adds; ``jnp.cos`` itself reads
    1.3·10⁻⁷ on a TPU v5e and 3.3·10⁻⁸ on the CPU;
    ``tests/nodes/test_cosine_bounded.py`` holds 1.5·10⁻⁷).
    """

    #: the bound's slack over Cauchy–Schwarz: at one bf16 pass the product
    #: rounds x and W to 8 bits each, (1 + 2⁻⁸)² of the exact sum at most
    _BOUND_SLACK = 1.01

    def __init__(self, W, b):
        self.W = as_param(W)
        self.b = as_param(b)
        if self.b.shape[0] != self.W.shape[0]:
            raise ValueError("rows of W and size of b must match")

    @staticmethod
    def create(
        num_input_features: int,
        num_output_features: int,
        gamma: float,
        seed: int = 0,
    ) -> "CosineRandomFeatures":
        """Gaussian W scaled by gamma, uniform b in [0, 2π)
        (parity: CosineRandomFeatures.scala:49-61)."""
        kw, kb = jax.random.split(jax.random.PRNGKey(seed))
        W = gamma * jax.random.normal(
            kw, (num_output_features, num_input_features), dtype=jnp.float32
        )
        b = 2 * math.pi * jax.random.uniform(
            kb, (num_output_features,), dtype=jnp.float32
        )
        return CosineRandomFeatures(W, b)

    def _bound_terms(self) -> Tuple[np.float32, np.float32]:
        """``(slack · max‖w_j‖₂, max|b_j|)``: W's and b's parts of the
        bound, host numbers (float32 sums: 3·10⁻⁵ of the slack's 10⁻²)."""
        w_norm = math.sqrt(
            np.einsum("ij,ij->i", self.W, self.W).max(initial=0.0)
        )
        return (
            np.float32(self._BOUND_SLACK * w_norm),
            np.float32(np.abs(self.b).max(initial=0.0)),
        )

    def argument_bound(self, X):
        """A device scalar no ``|x·Wᵀ + b|`` of the rows ``X`` exceeds:
        ``nan`` or ``inf`` where a row holds one."""
        return _argument_bound(X, *self._bound_terms())

    def _guarded(self) -> bool:
        """Whether a call lowers the guarded body: float32 parameters, so
        that ``x·Wᵀ + b`` is float32 whatever the rows are."""
        canon = jax.dtypes.canonicalize_dtype
        return canon(self.W.dtype) == canon(self.b.dtype) == np.float32

    def exact_calls(self, X) -> int:
        """How many of the calls ``trace_batch(X)`` makes fall to
        ``jnp.cos`` (0 or 1). A device value read back: for tests and
        probes, never the hot loop."""
        if not self._guarded():
            return 1
        return int(not bool(self.argument_bound(jnp.asarray(X)) <= LIMIT))

    def segment_facts(self, shape: Tuple[int, ...], rows: int) -> dict:
        """What ``exec.segment`` says of ``rows`` rows through this node:
        their count where the guarded body is lowered."""
        return {"cosine_bounded_rows": rows} if self._guarded() else {}

    def trace_batch(self, X):
        with jax.named_scope("ks.featurize.cosine"):
            # float64 rows exist under x64 alone, which nothing here sets
            if not self._guarded() or X.dtype == jnp.float64:
                return jnp.cos(X @ self.W.T + self.b)
            # Wᵀ as the operand: the (440, 4096) bf16 constant XLA made of
            # ``X @ W.T`` with W a literal, and what the roofline reads
            return _guarded_cosine(
                X, jnp.asarray(self.W.T), jnp.asarray(self.b),
                *self._bound_terms(),
            )


def _argument_bound(X, w_norm, b_max):
    X = X.astype(jnp.float32)  # as the product promotes narrower rows
    x_norm = jnp.sqrt(jnp.max(jnp.sum(X * X, axis=-1), initial=0.0))
    return x_norm * w_norm + b_max


@jax.jit
def _guarded_cosine(X, Wt, b, w_norm, b_max):
    """``cos(X Wᵀ + b)``, the cosine bounded where the rows prove its
    range. ONE traced body for every node, branch and job of a process
    (the branches of a ``cond`` are traced at each call of it: 10 ms a
    node), and Wᵀ and b operands of it: one literal for both branches."""
    return lax.cond(
        _argument_bound(X, w_norm, b_max) <= LIMIT,
        lambda X, Wt, b: cos_bounded(X @ Wt + b),
        lambda X, Wt, b: jnp.cos(X @ Wt + b),
        X, Wt, b,
    )


@jax.jit
def _column_stats(X):
    # Sample variance (ddof=1), matching MultivariateOnlineSummarizer.
    return jnp.mean(X, axis=0), jnp.var(X, axis=0, ddof=1)


@jax.jit
def _chunk_center_stats(X):
    """One chunk's (column mean, CENTERED sum of squares) — the
    numerically-stable merge inputs for the streaming StandardScaler."""
    mean = jnp.mean(X, axis=0)
    diff = X - mean
    return mean, jnp.sum(diff * diff, axis=0)


def _chan_merge(a, b):
    """Chan/Welford merge of two (n, mean, M2) column-stat triples."""
    na, ma, sa = a
    nb, mb, sb = b
    tot = na + nb
    delta = mb - ma
    mean = ma + delta * (nb / tot)
    m2 = sa + sb + delta * delta * (na * nb / tot)
    return tot, mean, m2


@jax.jit
def _standardize(X, mean, std):
    """``(X − mean) / std`` as ONE program where a node runs eagerly: op by
    op the centred copy would sit in HBM beside its input and the result
    (three times 5.2 GB at 16,384 × 80,000). Inside a traced segment the
    call is inlined."""
    out = X - mean
    return out if std is None else out / std


class StandardScalerModel(Transformer):
    """(x − mean) / std; std of None means center-only
    (parity: StandardScaler.scala:16-32)."""

    def __init__(self, mean, std=None):
        self.mean = as_param(mean)
        self.std = as_param(std)

    def trace_batch(self, X):
        return _standardize(X, self.mean, self.std)


class StandardScaler(Estimator):
    """Fit column mean/std; degenerate stds (0/NaN/inf) become 1.0
    (parity: StandardScaler.scala:38-61). The treeAggregate summarizer
    collapses to jnp.mean/var — psum over the mesh when sharded."""

    def __init__(self, normalize_std_dev: bool = True, eps: float = 1e-12):
        self.normalize_std_dev = normalize_std_dev
        self.eps = eps

    def fitted_out_spec(self, fit_in, apply_in):
        # the fitted model is (x - mean)/std: spec-preserving
        return apply_in[0] if apply_in else None

    def fit(self, data: Dataset) -> StandardScalerModel:
        from ...data.chunked import ChunkedDataset

        if isinstance(data, ChunkedDataset):
            mean, var = self._streaming_stats(data)
        else:
            mean, var = _column_stats(data.to_array())
        if not self.normalize_std_dev:
            return StandardScalerModel(mean, None)
        std = jnp.sqrt(var)
        bad = jnp.isnan(std) | jnp.isinf(std) | (jnp.abs(std) < self.eps)
        std = jnp.where(bad, 1.0, std)
        return StandardScalerModel(mean, std)

    @staticmethod
    def _streaming_stats(data):
        """Column mean/var(ddof=1) of a chunked set in ONE pipelined scan
        — per-chunk centered statistics merged Chan/Welford-style (the
        raw sum-of-squares form cancels catastrophically in f32 when
        |mean| ≫ std) instead of materializing via ``to_array()``. Host
        chunk production overlaps the device reductions.

        Mesh-distributed like the streaming solvers: chunks round-robin
        across the data-axis lanes, each lane folds its own Chan triple
        (n, mean, M2) on its own device, and the lane triples merge across
        the mesh ONCE at finalize — O(1) collectives per scan. A 1-lane
        mesh runs the original sequential merge, bit-identical."""
        from ...parallel.lanes import gather_lane_partials, scan_lanes

        lanes = scan_lanes()
        it = data.chunks(lanes=lanes)
        lanes = getattr(it, "lanes", lanes)
        parts = [None] * lanes  # per-lane (n, mean, m2) Chan triples
        for i, chunk in enumerate(it):
            X = jnp.asarray(chunk)
            nc = int(X.shape[0])
            mc, m2c = _chunk_center_stats(X)
            lane = i % lanes
            if parts[lane] is None:
                parts[lane] = (nc, mc, m2c)
            else:
                parts[lane] = _chan_merge(parts[lane], (nc, mc, m2c))
        live = [p for p in parts if p is not None]
        if not live:
            raise ValueError("empty chunked dataset")
        # device partials hop to one chip (counts stay host), then the
        # same Chan merge combines the lanes in deterministic lane order
        gathered = gather_lane_partials(
            [(mc, m2c) for _, mc, m2c in live], scan=it
        )
        n, mean, m2 = (live[0][0],) + tuple(gathered[0])
        for (nc, _, _), (mc, m2c) in zip(live[1:], gathered[1:]):
            n, mean, m2 = _chan_merge((n, mean, m2), (nc, mc, m2c))
        # sample variance (ddof=1), matching _column_stats; n==1 yields a
        # zero m2 whose std the degenerate guard maps to 1.0
        var = m2 / max(n - 1, 1)
        return mean, var


class Sampler(Transformer):
    """Deterministic-seed sample of ``size`` rows without replacement
    (parity: Sampling.scala:28-33 takeSample). Operates dataset→dataset."""

    def __init__(self, size: int, seed: int = 42):
        self.size = size
        self.seed = seed

    def apply_batch(self, data: Dataset) -> Dataset:
        data = Dataset.of(data)
        n = len(data)
        k = min(self.size, n)
        idx = np.random.default_rng(self.seed).choice(n, size=k, replace=False)
        if data.is_batched:
            X = data.to_array()
            return Dataset(X[jnp.asarray(np.sort(idx))], batched=True)
        items = data.collect()
        return Dataset.from_items([items[i] for i in np.sort(idx)])

    def apply(self, x):
        return x


class RowKeyedTransformer(Transformer):
    """A transformer whose output for a row depends on the row's index in
    the data set (a draw keyed on it): ``trace_batch(X, rows=None)`` takes
    the indices, from 0 where ``X`` is a whole data set. ``row_keyed``:
    segment dispatch hands them over (``compile/segment.py``); here the
    same rows get the same indices item by item and chunk by chunk."""

    #: ``trace_batch`` takes ``rows``: the data-set index of each row
    row_keyed = True

    def apply(self, x, row: int = 0):
        return self.trace_batch(jnp.asarray(x)[None], jnp.asarray([row]))[0]

    def apply_batch(self, data):
        from ...data.chunked import ChunkedDataset

        data = Dataset.of(data)
        if not data.is_batched:
            return Dataset.from_items(
                [self.apply(x, i) for i, x in enumerate(data)]
            )
        if isinstance(data, ChunkedDataset):
            # per-chunk device gather, lazily — the sampled set is small and
            # materializes at the consumer; the descriptor stack never does.
            # raw_chunks: this factory COMPOSES into a downstream scan, which
            # pipelines the whole chain once at its consumer
            parent = data.raw_chunks

            def factory():
                at = 0
                for chunk in parent():
                    yield self.sample_chunk(chunk, at)
                    at += chunk.shape[0]

            return ChunkedDataset(factory, len(data), label="col_sample")
        return data.map_batch(self.trace_batch)

    def sample_chunk(self, X, row_start: int):
        """One chunk of a chunked scan whose first row is row ``row_start``
        of the data set: what the whole data set would give these rows. A
        lazy chunked chain re-runs on every scan and the lineage contract
        requires identical chunks each time. Shared by the chunked
        ``apply_batch`` path and callers that drive one combined scan
        themselves (the ImageNet FV branch builder draws PCA + GMM samples
        in a single featurize pass)."""
        return self.trace_batch(X, row_start + jnp.arange(X.shape[0]))


class ColumnSampler(RowKeyedTransformer):
    """Sample ``num_samples`` random columns (with replacement) of each
    (d, m) matrix item (parity: Sampling.scala:12-20). Used to subsample
    descriptor matrices before PCA/GMM estimation.

    The draw of a row is keyed on (seed, the row's index in the data set)
    and on nothing else — ``jax.random.fold_in(PRNGKey(seed), row)`` — so
    the same columns come out whole, in row slices of any size, chunk by
    chunk and item by item, and another program can draw them too. As a
    ``row_keyed`` member of the row-sliced segment that makes the
    descriptors, the descriptor stack of a whole data set never exists."""

    def __init__(self, num_samples_per_matrix: int, seed: int = 0):
        self.num_samples = num_samples_per_matrix
        self.seed = seed

    def columns(self, rows, m: int):
        """(len(rows), num_samples) int32 column draws for the rows whose
        data-set indices are ``rows``, each in [0, m)."""
        key = jax.random.PRNGKey(self.seed)
        return jax.vmap(
            lambda r: jax.random.randint(
                jax.random.fold_in(key, r), (self.num_samples,), 0, m
            )
        )(jnp.asarray(rows, jnp.uint32))

    def trace_batch(self, X, rows=None):
        n, _, m = X.shape
        if rows is None:
            rows = jnp.arange(n)
        return jnp.take_along_axis(
            X, self.columns(rows, m)[:, None, :], axis=2
        )
