"""Class-weighted block-coordinate least squares (the ImageNet FV solver).

Parity: nodes/learning/BlockWeightedLeastSquares.scala:36,86-321 and
PerClassWeightedLeastSquares.scala:31,63. Objective: per class c, ridge
regression under the mixture weighting that gives class-c examples total
weight ``w`` and the population weight ``1−w`` (Appendix of the KeystoneML
paper; jointXTX/jointXTR algebra preserved exactly).

Mesh-native mapping of the reference's choreography (SURVEY §2.7): the
"one class per partition" HashPartitioner trick becomes segment reductions
over the class-index vector — per-class means via one segment_sum, per-class
Grams via a chunked masked einsum — and the per-class executor-local solves
become ONE batched solve a class chunk (``linalg/weighted.py:_batched_solve``:
LU with partial pivoting, not Cholesky — a class covariance has rank at most
the class's row count, far under d at the published widths, and a float32
Cholesky of the near-semidefinite jointXTX gives NaNs; upstream's Breeze
``\\`` is a float64 LU). No resharding of the data ever happens.

Spans of the dense block step (``obs/tracer.py``): ``wls.block`` a feature
block and pass, with ``path`` (``primal``: one d × d system a class; ``dual``:
one (n + 3)² system a class in the span of the rows, taken where n + 3 < d),
``class_systems`` (d × d class systems solved: k, and 0 on the dual path),
``class_chunk`` (classes a dispatch) and ``gram_products`` (masked per-class
Gram products formed, as ``block_ls.solve`` counts its own: k, 0 on the dual
path); under it ``wls.stats`` (population and class moments, the cross
terms), ``wls.class_grams`` and ``wls.class_solve`` a class chunk
(``classes``, ``rows``, ``dims``) and ``wls.residual``.
"""

from __future__ import annotations

from functools import partial, wraps
from typing import List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from ...data.dataset import Dataset
from ...linalg.row_matrix import solve_spd
from ...obs.tracer import span
from ...parallel.mesh import shard_classes
from ...workflow.node_optimization import Optimizable
from ...workflow.transformer import LabelEstimator
from .cost import AutoSolverFrontDoor, CostModel, combine_cost
from .linear import BlockLinearMapper


def _f32_true(fn):
    """Run a weighted-family solve with f32-true matmuls.

    The mixture normal matrices are regularized with λ as small as the
    reference's ImageNet 6e-5 (ImageNetSiftLcsFV.scala:146) — BELOW the
    noise floor of the TPU's default-bf16 matmul lowering (~1e-3·‖XᵀX‖).
    At default precision the λ-decided near-null directions of jointXTX
    come out noise-dominated and held-out predictions from BOTH the
    dense and dual paths are near-random (measured: 9% argmax agreement
    between two correct algorithms; 97% under f32-true). The reference
    solves in f64 Breeze; f32-true is the TPU analogue, and these GEMMs
    are a negligible share of pipeline compute."""

    @wraps(fn)
    def wrapped(*args, **kwargs):
        with jax.default_matmul_precision("highest"):
            return fn(*args, **kwargs)

    return wrapped


@partial(jax.jit, static_argnames=("k",))
def _class_stats(A, y_idx, k):
    """Per-class counts (k,), means (k, d) via segment reductions."""
    onehot = jax.nn.one_hot(y_idx, k, dtype=A.dtype)  # (n, k)
    counts = jnp.sum(onehot, axis=0)
    sums = onehot.T @ A
    means = sums / jnp.maximum(counts, 1.0)[:, None]
    return counts, means


@partial(jax.jit, static_argnames=())
def _chunk_grams(A, mask_chunk):
    """Masked Grams for a chunk of classes: (C, d, d)."""
    with jax.named_scope("ks.solver.wls.gram"):
        return jnp.einsum("nd,nc,ne->cde", A, mask_chunk, A)


@jax.jit
def _joint_xtx(grams, counts, mu_c, pop_mean, pop_cov, w):
    """jointXTX of a class chunk (C, d, d) from its masked Grams:
    ``(1−w)·popCov + w·classCov_c + w(1−w)·(μ_c − μ)(μ_c − μ)ᵀ``
    (BlockWeightedLeastSquares.scala:236-262), one program, so that no
    (C, d, d) term of the sum is a buffer of its own."""
    with jax.named_scope("ks.solver.wls.gram"):
        cnt = jnp.maximum(counts, 1.0)[:, None, None]
        class_cov = grams / cnt - jnp.einsum("cd,ce->cde", mu_c, mu_c)
        mean_diff = mu_c - pop_mean
        return (
            (1 - w) * pop_cov
            + w * class_cov
            + w * (1 - w) * jnp.einsum("cd,ce->cde", mean_diff, mean_diff)
        )


@partial(jax.jit, static_argnames=("dual",))
def _block_gram(A, *, dual):
    """What a block step keeps of all the rows across passes: the
    population mean with the population covariance (primal) or the reduced
    QR of Aᵀ (dual) — never both."""
    pop_mean = jnp.mean(A, axis=0)
    if dual:
        return tuple(jnp.linalg.qr(A.T)), pop_mean  # (Q (d, n), R (n, n))
    with jax.named_scope("ks.solver.wls.gram"):
        n = A.shape[0]
        return jnp.matmul(A.T, A) / n - jnp.outer(pop_mean, pop_mean), pop_mean


@jax.jit
def _cross_terms(A, R, onehot, counts):
    """``(AᵀR / n, A_cᵀr_c / n_c)``, both (d, k), and the residual's
    population and class means (k,)."""
    n = A.shape[0]
    cnt = jnp.maximum(counts, 1.0)
    pop_xtr = jnp.matmul(A.T, R) / n
    class_xtr = jnp.matmul(A.T, onehot * R) / cnt
    return (
        pop_xtr, class_xtr, jnp.mean(R, axis=0),
        jnp.sum(onehot * R, axis=0) / cnt,
    )


@jax.jit
def _residual_update(R, A, delta):
    with jax.named_scope("ks.solver.wls.residual"):
        return R - jnp.matmul(A, delta)


# batched per-class ridge solve — shared with the streaming solver body,
# which now lives at the linalg layer (K-lane mesh distribution included)
from ...linalg.weighted import _batched_solve, solve_weighted_streaming


@jax.jit
def _dual_solve_chunk(Q, R, dvec, pm_proj, mu_proj, s3, rhs, lam):
    """Per-class solves in the SAMPLE-SPAN basis, vmapped over a class
    chunk — the few-shot/many-class regime (n ≪ d, e.g. the reference's
    1000-class ImageNet config) where the dense path factors a d×d
    system per class although every class covariance is rank ≤ n.

    With Aᵀ = QR (reduced QR, computed once per feature block) the
    per-class normal matrix lives entirely in span(Q):
        jointXTX_c + λI = λI + Q H_c Qᵀ,
        H_c = R diag(d_c) Rᵀ + Σⱼ s3ⱼ (Qᵀpⱼ)(Qᵀpⱼ)ᵀ,
    with d_c[i] = (1−w)/n + w·1[i∈c]/n_c (the diagonal of
    :func:`_class_sample_weights`) and pⱼ ∈ {pm, μ_c, μ_c−pm} — all in
    span(Aᵀ), so the projection is exact. The full inverse is
        x = Q (λI + H_c)⁻¹ Qᵀr + (r − QQᵀr)/λ,
    but the ⊥ term is IDENTICALLY ZERO here and must not be computed:
    rhs ∈ span(Q) by construction (jointXTR ∈ col(Aᵀ) and every Ws
    update is a previous output of this function, i.e. ∈ span(Q), by
    induction from Ws = 0) — so (r − QQᵀr) is pure rounding noise, and
    dividing that noise by the ImageNet-scale λ=6e-5 produced weights
    whose dominant component was noise orthogonal to the training rows:
    invisible on train predictions, near-random held-out (caught by the
    held-out assertion in the dual-vs-per-class test). The same 1/λ
    amplification killed the plain Woodbury form of this solve. Hence:
        x = Q (λI + H_c)⁻¹ Qᵀr,
    O(n³) per class instead of O(d³), with no 1/λ-amplified term at all.

    Q (d, n); R (n, n); dvec (C, n); pm_proj (n,) = Qᵀpm (projected ONCE
    per block — not per class); mu_proj (C, n) = μ_c Q; s3 (3,);
    rhs (C, d).
    """
    n = R.shape[0]
    eye = jnp.eye(n, dtype=R.dtype)

    # NOTE: no explicit precision= on any product here — an explicit
    # precision="high" would OVERRIDE the _f32_true("highest") context
    # the weighted family runs under (explicit args beat the context),
    # silently reintroducing bf16_3x rounding next to the λ floor.
    def one(dv, mu_p, r):
        Pp = jnp.stack([pm_proj, mu_p, mu_p - pm_proj])   # (3, n)
        H = jnp.matmul(R * dv[None, :], R.T)
        H = H + jnp.einsum("j,jm,jo->mo", s3, Pp, Pp)
        rp = jnp.matmul(Q.T, r)                           # (n,)
        z = jnp.linalg.solve(H + lam * eye, rp)
        return jnp.matmul(Q, z)

    return jax.vmap(one)(dvec, mu_proj, rhs)


class BlockWeightedLeastSquaresEstimator(LabelEstimator, CostModel):
    """(parity: BlockWeightedLeastSquaresEstimator,
    BlockWeightedLeastSquares.scala:36-84)."""

    supports_streaming = True

    def __init__(self, block_size: int, num_iter: int, lam: float,
                 mixture_weight: float,
                 num_features: Optional[int] = None,
                 class_chunk: int = 8,
                 snapshot: bool = False):
        if snapshot:
            from ...linalg.accumulators import NotAbsorbable

            raise NotAbsorbable(
                "the block-weighted BCD solver has no snapshot-able "
                "state: its iterates depend on block visitation order, "
                "so appended chunks cannot be folded in after the fact "
                "— fit with PerClassWeightedLeastSquaresEstimator("
                "snapshot=True) for an absorbable weighted model"
            )
        self.block_size = block_size
        self.num_iter = num_iter
        self.lam = lam
        self.mixture_weight = mixture_weight
        self.num_features = num_features
        self.class_chunk = class_chunk

    # passes over the data per iteration (parity: WeightedNode weight)
    @property
    def weight(self) -> int:
        return 3 * self.num_iter + 1

    def cost(self, n, d, k, sparsity, num_machines,
             cpu_weight, mem_weight, network_weight):
        from ...linalg.weighted import cost_signature

        return combine_cost(
            cost_signature(
                n, self.num_features or d, k, self.block_size,
                self.num_iter, num_machines, self.class_chunk,
            ),
            cpu_weight, mem_weight, network_weight,
        )

    def fit(self, data, labels: Dataset) -> BlockLinearMapper:
        from ...data.chunked import ChunkedDataset

        if isinstance(data, ChunkedDataset):
            Y = jnp.asarray(
                Dataset.of(labels).to_array(), dtype=jnp.float32
            )
            # RDD-cache semantics (one scan): a chunked featurized set that
            # fits the HBM budget materializes and solves in-memory; anything
            # bigger streams with per-chunk Gram accumulation. Either way the
            # upstream featurizer chain ran chunk-by-chunk — the full-size
            # featurization intermediates never coexist in HBM.
            cached = data.cache()
            if not isinstance(cached, ChunkedDataset):
                X = jnp.asarray(cached.to_array(), dtype=jnp.float32)
                d = self.num_features or X.shape[-1]
                blocks = [
                    X[..., i : min(i + self.block_size, d)]
                    for i in range(0, d, self.block_size)
                ]
                return self.train_with_l2(blocks, Y)
            return self.train_streaming(cached, Y)
        if isinstance(data, Dataset) and isinstance(data.payload, (list, tuple)):
            blocks = [jnp.asarray(p, dtype=jnp.float32) for p in data.payload]
        elif isinstance(data, (list, tuple)):
            blocks = [
                jnp.asarray(Dataset.of(d).to_array(), dtype=jnp.float32)
                for d in data
            ]
        else:
            X = jnp.asarray(
                Dataset.of(data).to_array(), dtype=jnp.float32
            )
            d = self.num_features or X.shape[-1]
            blocks = [
                X[..., i : min(i + self.block_size, d)]
                for i in range(0, d, self.block_size)
            ]
        Y = jnp.asarray(Dataset.of(labels).to_array(), dtype=jnp.float32)
        return self.train_with_l2(blocks, Y)

    @_f32_true
    def train_with_l2(self, blocks: Sequence, Y) -> BlockLinearMapper:
        """(parity: trainWithL2, BlockWeightedLeastSquares.scala:102-321)."""
        w = self.mixture_weight
        lam = self.lam
        n, k = Y.shape
        # a row with no positive indicator belongs to none of these k
        # classes (index k: an all-zero one-hot row) and enters the
        # population statistics alone — so a solve over a share of the
        # classes, Y cut to its columns, gives those classes' columns of
        # the uncut solve: the class systems are independent
        y_idx = jnp.where(
            jnp.max(Y, axis=1) > 0, jnp.argmax(Y, axis=1), k
        )

        counts = jnp.sum(
            jax.nn.one_hot(y_idx, k, dtype=jnp.float32), axis=0
        )
        # jointLabelMean_c = 2w + 2(1−w)·n_c/n − 1  (ref :148-155)
        joint_label_mean = 2 * w + 2 * (1 - w) * counts / n - 1.0
        R = Y - joint_label_mean

        onehot = jax.nn.one_hot(y_idx, k, dtype=jnp.float32)  # (n, k)
        Ws: List[jnp.ndarray] = [
            jnp.zeros((b.shape[1], k), dtype=jnp.float32) for b in blocks
        ]
        stats = [None] * len(blocks)  # (pop_cov, pop_mean, joint_means)

        for _ in range(self.num_iter):
            for j, A in enumerate(blocks):
                d = A.shape[1]
                # Strategy: dense primal (d×d per class) when classes are
                # well-populated; dual/Woodbury in sample space when
                # n + 3 < d — the few-shot/many-class regime where the
                # dense path would factor k rank-deficient d×d systems.
                # The cached per-block Gram is pop_cov (d×d) for the
                # dense path, AAᵀ (n×n) for the dual path — never both.
                use_dual = lam > 0 and (n + 3) < d
                if use_dual:
                    # dual systems are (n+3)² per class — far smaller than
                    # d² — so batch many more classes per dispatch (bound:
                    # ~256 MB of batched inner systems)
                    C = max(
                        1,
                        min(k, self.class_chunk * 8,
                            (1 << 26) // max((n + 3) ** 2, 1)),
                    )
                else:
                    C = max(1, self.class_chunk)
                # per-block span (parity: the reference's per-block solve
                # timing logs, BlockWeightedLeastSquares.scala:177-313);
                # syncs only under an installed tracer
                with span(
                    "wls.block", path="dual" if use_dual else "primal",
                    class_systems=0 if use_dual else k, class_chunk=C,
                    gram_products=0 if use_dual else k,
                    rows=n, dims=d,
                ) as block_span:
                    with span("wls.stats", rows=n, dims=d) as sp:
                        if stats[j] is None:
                            gram, pop_mean = _block_gram(A, dual=use_dual)
                            _, class_means = _class_stats(A, y_idx, k)
                            joint_means = (
                                w * class_means + (1 - w) * pop_mean
                            )
                            stats[j] = (
                                gram, pop_mean, joint_means, class_means
                            )
                        gram_j, pop_mean, joint_means, class_means = stats[j]
                        # per-class residual-column stats: r_c over the
                        # rows of class c
                        pop_xtr, class_xtr, residual_mean, class_r_mean = (
                            _cross_terms(A, R, onehot, counts)
                        )
                        sp.sync_on(class_xtr)
                    if use_dual:
                        s3 = jnp.asarray(
                            [-(1 - w), -w, w * (1 - w)], dtype=jnp.float32
                        )
                        # constant per block — projected once, not per chunk
                        Qb, Rb = gram_j
                        pm_proj = jnp.matmul(pop_mean, Qb)
                    delta_cols = []
                    for c0 in range(0, k, C):
                        cs = slice(c0, min(c0 + C, k))
                        mu_c = class_means[cs]  # (C, d)
                        mean_mixture = (
                            (1 - w) * residual_mean[cs]
                            + w * class_r_mean[cs]
                        )  # (C,)
                        jointXTR = (
                            (1 - w) * pop_xtr[:, cs].T
                            + w * class_xtr[:, cs].T
                            - joint_means[cs] * mean_mixture[:, None]
                        )  # (C, d)
                        rhs = jointXTR - lam * Ws[j][:, cs].T
                        sized = dict(
                            classes=int(mu_c.shape[0]), rows=n, dims=d
                        )
                        if use_dual:
                            dvec = (1 - w) / n + w * onehot[:, cs].T \
                                / jnp.maximum(counts[cs], 1.0)[:, None]
                            mu_proj = jnp.matmul(mu_c, Qb)  # (C, n)
                            with span("wls.class_solve", **sized) as sp:
                                delta_cols.append(
                                    _dual_solve_chunk(
                                        Qb, Rb, shard_classes(dvec),
                                        pm_proj, shard_classes(mu_proj), s3,
                                        shard_classes(rhs), lam,
                                    )
                                )
                                sp.sync_on(delta_cols[-1])
                            continue
                        # model-axis parallelism: the class dim of the
                        # masked Grams and the batched per-class solves
                        # shards over MODEL_AXIS (each model-device owns a
                        # slice of classes); a 1-wide model axis makes
                        # this a no-op
                        with span("wls.class_grams", **sized) as sp:
                            mask = shard_classes(onehot[:, cs], axis=1)
                            jointXTX = _joint_xtx(
                                _chunk_grams(A, mask), counts[cs], mu_c,
                                pop_mean, gram_j, w,
                            )
                            sp.sync_on(jointXTX)
                        with span("wls.class_solve", **sized) as sp:
                            delta_cols.append(
                                _batched_solve(
                                    shard_classes(jointXTX),
                                    shard_classes(rhs), lam,
                                )
                            )
                            sp.sync_on(delta_cols[-1])
                        del jointXTX  # 0.5 GB a chunk: not beside the next one's
                    delta = jnp.concatenate(delta_cols, axis=0).T  # (d, k)
                    Ws[j] = Ws[j] + delta
                    with span("wls.residual", rows=n, dims=d):
                        R = _residual_update(R, A, delta)
                    block_span.sync_on(R)

        # final intercept (ref :310-315)
        b = joint_label_mean - sum(
            jnp.einsum("cd,dc->c", stats[j][2], Ws[j])
            for j in range(len(blocks))
        )
        return BlockLinearMapper(Ws, self.block_size, b=b)

    def train_streaming(self, data, Y) -> BlockLinearMapper:
        """Out-of-core weighted solve: the featurized design matrix streams
        through in row chunks and NEVER materializes (parity: the
        reference's per-partition Gram iteration over the cached featurized
        RDD, BlockWeightedLeastSquares.scala:177-313 — Spark re-reads
        partitions from cluster RAM; here the chunked source recomputes
        them, lineage-style).

        The solver body lives at the linalg layer
        (:func:`~keystone_tpu.linalg.weighted.solve_weighted_streaming`),
        mesh-distributed across the data-axis scan lanes with per-lane
        partial accumulators reduced once per block. Resident state:
        labels/residual (n, k) — as per-lane slabs when laned — the
        per-block joint stats, one (C, bs, bs) masked-Gram accumulator,
        and one chunk. Scan count: num_iter × nblocks × (1 + ⌈k/C⌉) — the
        class-chunked Gram passes are the price of never holding the
        (k, bs, bs) per-class Grams; the reference pays the same shape as
        one shuffle of the full data to class-keyed partitions. The same
        delayed-residual-update trick as the streaming BCD fuses
        ``R −= A_prev·Δ_prev`` into the next block's accumulation scan."""
        n = Y.shape[0]
        if len(data) != n:
            raise ValueError(
                f"chunked features have {len(data)} rows, labels {n}"
            )
        # raw (unpipelined) scans compose here; the solver wraps them in
        # scan_pipeline so exactly ONE pipeline runs per scan
        if self.num_features is not None:
            dcap = self.num_features
            base_scan = data.raw_chunks

            def scan():
                for chunk in base_scan():
                    yield chunk[..., :dcap]

        else:
            scan = data.raw_chunks

        Ws, b = solve_weighted_streaming(
            scan, Y,
            block_size=self.block_size, num_iter=self.num_iter,
            lam=self.lam, mixture_weight=self.mixture_weight,
            class_chunk=self.class_chunk,
        )
        return BlockLinearMapper(Ws, self.block_size, b=b)


def _joint_weighted_stats(X, Y, w):
    """Shared mixture-weighting algebra of the per-class family (parity:
    computeJointFeatureMean / computeJointLabelMean / computeWeights,
    PerClassWeightedLeastSquares.scala:140-190). Returns
    (y_idx, counts, joint_label_mean (k,), joint_means (k, d))."""
    n, k = Y.shape
    y_idx = jnp.argmax(Y, axis=1)
    onehot = jax.nn.one_hot(y_idx, k, dtype=jnp.float32)
    counts = jnp.sum(onehot, axis=0)
    joint_label_mean = 2 * w + 2 * (1 - w) * counts / n - 1.0
    pop_mean = jnp.mean(X, axis=0)
    class_means = (onehot.T @ X) / jnp.maximum(counts, 1.0)[:, None]
    joint_means = w * class_means + (1 - w) * pop_mean  # (k, d)
    return y_idx, counts, joint_label_mean, joint_means


def _class_sample_weights(y_idx, counts, c, w, n):
    """diag(B) for class ``c``: (1−w)/n population term on every row plus
    w/n_c on class-c rows (class rows appear in both the population and
    the class statistics of the block solver)."""
    return (1 - w) / n + jnp.where(
        y_idx == c, w / jnp.maximum(counts[c], 1.0), 0.0
    )


class PerClassWeightedLeastSquaresEstimator(LabelEstimator, CostModel):
    """Same objective solved exactly, class-at-a-time, as a dense weighted
    ridge — the reference uses it as the agreement oracle for the block
    solver (parity: PerClassWeightedLeastSquares.scala:31-63;
    BlockWeightedLeastSquaresSuite.scala:115). Exact (non-iterative) when
    the full feature matrix fits; use for tests/small problems.

    ``snapshot=True`` fits through the per-class raw accumulators
    (:class:`~keystone_tpu.linalg.weighted.WeightedSolverState` — k
    per-class Grams plus label cross terms, all associative over row
    blocks) and attaches the state to the fitted mapper, so
    ``FittedPipeline.absorb`` can fold appended chunks into the weighted
    family exactly as it does the Gram family. The exact per-class
    solve is order-free, which is WHY this family absorbs while the
    BCD-iterated weighted solvers raise :class:`NotAbsorbable`."""

    def __init__(self, block_size: int, num_iter: int, lam: float,
                 mixture_weight: float,
                 num_features: Optional[int] = None,
                 snapshot: bool = False):
        self.block_size = block_size
        self.num_iter = num_iter
        self.lam = lam
        self.mixture_weight = mixture_weight
        self.num_features = num_features
        self.snapshot = snapshot

    def cost(self, n, d, k, sparsity, num_machines,
             cpu_weight, mem_weight, network_weight):
        # exact per-class dense ridge: every class pays the full weighted
        # Gram (2·n·d²) plus a d³ factorization, and re-reads X
        d = self.num_features or d
        return combine_cost(
            {
                "flops": k * (2.0 * n * d * d + d ** 3 / 3.0) / num_machines,
                "bytes": k * (n * d / num_machines + d * d),
                "network": d * (d + k),
                "passes": k,
            },
            cpu_weight, mem_weight, network_weight,
        )

    def _fit_snapshot(self, data, labels: Dataset) -> BlockLinearMapper:
        """The accumulator path: fold the data (chunked or not) into a
        :class:`~keystone_tpu.linalg.weighted.WeightedSolverState`, solve
        from the state, and attach the snapshot for later ``absorb``. The
        state solves in host float64, so this path is if anything MORE
        accurate than the f32 dense oracle it mirrors."""
        from ...data.chunked import ChunkedDataset
        from ...linalg.weighted import WeightedSolverState

        d_cap = self.num_features
        state = WeightedSolverState(
            lam=float(self.lam),
            mixture_weight=float(self.mixture_weight),
            block_size=int(self.block_size),
        )
        Y = jnp.asarray(Dataset.of(labels).to_array(), dtype=jnp.float32)
        if isinstance(data, ChunkedDataset):
            offset = 0
            for chunk in data.raw_chunks():
                chunk = jnp.asarray(chunk, dtype=jnp.float32)
                if d_cap is not None:
                    chunk = chunk[..., :d_cap]
                rows = int(chunk.shape[0])
                state.update(chunk, Y[offset : offset + rows])
                offset += rows
            if offset != int(Y.shape[0]):
                raise ValueError(
                    f"chunked features have {offset} rows, labels "
                    f"{Y.shape[0]}"
                )
        else:
            X = jnp.asarray(Dataset.of(data).to_array(), dtype=jnp.float32)
            if d_cap is not None:
                X = X[:, :d_cap]
            state.update(X, Y)
        W, b = state.solve()
        d = int(W.shape[0])
        blocks = [
            W[i : min(i + self.block_size, d)]
            for i in range(0, d, self.block_size)
        ]
        return BlockLinearMapper(
            blocks, self.block_size, b=b, solver_state=state.snapshot()
        )

    @_f32_true
    def fit(self, data, labels: Dataset) -> BlockLinearMapper:
        if self.snapshot:
            return self._fit_snapshot(data, labels)
        X = jnp.asarray(Dataset.of(data).to_array(), dtype=jnp.float32)
        Y = jnp.asarray(Dataset.of(labels).to_array(), dtype=jnp.float32)
        w = self.mixture_weight
        n, k = Y.shape
        d = X.shape[1]
        y_idx, counts, joint_label_mean, joint_means = _joint_weighted_stats(
            X, Y, w
        )

        cols = []
        for c in range(k):
            b_i = _class_sample_weights(y_idx, counts, c, w, n)
            mu = joint_means[c]
            Xc = X - mu
            yc = Y[:, c] - joint_label_mean[c]
            G = Xc.T @ (Xc * b_i[:, None])
            rhs = Xc.T @ (yc * b_i)
            Wc = jnp.linalg.solve(
                G + self.lam * jnp.eye(d, dtype=X.dtype), rhs
            )
            cols.append(Wc)
        W = jnp.stack(cols, axis=1)  # (d, k)
        b = joint_label_mean - jnp.einsum("cd,dc->c", joint_means, W)
        blocks = [
            W[i : min(i + self.block_size, d)]
            for i in range(0, d, self.block_size)
        ]
        return BlockLinearMapper(blocks, self.block_size, b=b)


@_f32_true
def solve_reweighted_l2(
    blocks: Sequence,
    y_zm,
    sample_weights,
    reg: float,
    num_iter: int = 1,
    means: Optional[Sequence] = None,
):
    """Iterative weighted BCD:  W = (Xᵀdiag(b)X + λI)⁻¹ Xᵀ(b∘y)  solved a
    feature block at a time (parity: the internal solver behind the
    per-class estimator, internal/ReWeightedLeastSquares.scala:18-150).

    blocks: list of (n, bs_j) feature blocks; ``y_zm`` (n, k) zero-meaned
    labels; ``sample_weights`` (n,) the diagonal of B; ``means`` optional
    per-block column means subtracted in-program (never materialized).

    Shape of the iteration, preserved from the reference: the weighted
    per-block Gram ``XⱼᵀBXⱼ`` is computed once on the first pass and cached
    (it never changes); the residual carries ``R = B∘(X·W)`` and each block
    update solves against ``Xⱼᵀ((B∘y) − (R − B∘(XⱼWⱼ)))``. The reference's
    map + treeReduce per term become one jitted program per block step.
    """
    y_zm = jnp.asarray(y_zm, dtype=jnp.float32)
    b = jnp.asarray(sample_weights, dtype=jnp.float32)
    if y_zm.ndim == 1:
        y_zm = y_zm[:, None]
    blocks = [jnp.asarray(a, dtype=jnp.float32) for a in blocks]
    if means is None:
        means = [jnp.zeros((a.shape[1],), dtype=jnp.float32) for a in blocks]
    k = y_zm.shape[1]
    Ws = [jnp.zeros((a.shape[1], k), dtype=jnp.float32) for a in blocks]
    R = jnp.zeros_like(y_zm)
    gram_cache: List[Optional[jax.Array]] = [None] * len(blocks)
    for it in range(num_iter):
        for j, Aj in enumerate(blocks):
            if gram_cache[j] is None:
                gram_cache[j] = _weighted_gram(Aj, means[j], b)
            Ws[j], R = _reweighted_block_update(
                Aj, means[j], gram_cache[j], Ws[j], R, y_zm, b,
                jnp.float32(reg),
            )
    return Ws


@jax.jit
def _weighted_gram(Aj, mj, b):
    # no explicit precision= — the _f32_true context governs (an explicit
    # "high" would override it and keep this at bf16_3x)
    Ajc = Aj - mj
    return jnp.matmul(Ajc.T, Ajc * b[:, None])


@jax.jit
def _reweighted_block_update(Aj, mj, G, Wj_old, R, y_zm, b, reg):
    Ajc = Aj - mj
    # remove this block's contribution from the weighted residual
    xw_old = jnp.matmul(Ajc, Wj_old)
    R_wo = R - xw_old * b[:, None]
    rhs = jnp.matmul(Ajc.T, y_zm * b[:, None] - R_wo)
    Wj = solve_spd(G, rhs, reg)
    R = R_wo + jnp.matmul(Ajc, Wj) * b[:, None]
    return Wj, R


class ReWeightedLeastSquaresEstimator(LabelEstimator, CostModel):
    """Per-class weighted least squares solved by the ITERATIVE reweighted
    BCD (parity: PerClassWeightedLeastSquares.scala:97-110 driving
    internal/ReWeightedLeastSquares.scala:18). Third agreement point for
    the weighted family next to the block solver and the exact per-class
    oracle — all three optimize the same objective, so they must agree."""

    def __init__(self, block_size: int, num_iter: int, lam: float,
                 mixture_weight: float,
                 num_features: Optional[int] = None):
        self.block_size = block_size
        self.num_iter = num_iter
        self.lam = lam
        self.mixture_weight = mixture_weight
        self.num_features = num_features

    def cost(self, n, d, k, sparsity, num_machines,
             cpu_weight, mem_weight, network_weight):
        # per class: weighted per-block Grams once (n·d·bs), then
        # num_iter residual/solve sweeps (2·n·d GEMV-shaped + d·bs² solves)
        d = self.num_features or d
        bs = min(self.block_size, d)
        return combine_cost(
            {
                "flops": k * (
                    n * d * bs + self.num_iter * (2.0 * n * d + d * bs * bs)
                ) / num_machines,
                "bytes": k * self.num_iter * (n * d / num_machines + d),
                "network": d * (bs + k),
                "passes": k * self.num_iter,
            },
            cpu_weight, mem_weight, network_weight,
        )

    @_f32_true
    def fit(self, data, labels: Dataset) -> BlockLinearMapper:
        X = jnp.asarray(Dataset.of(data).to_array(), dtype=jnp.float32)
        Y = jnp.asarray(Dataset.of(labels).to_array(), dtype=jnp.float32)
        w = self.mixture_weight
        n, k = Y.shape
        d = self.num_features or X.shape[1]
        X = X[:, :d]
        y_idx, counts, joint_label_mean, joint_means = _joint_weighted_stats(
            X, Y, w
        )

        splits = list(range(0, d, self.block_size))
        # feature blocks are class-independent; slice once outside the loop
        blocks = [X[:, i : min(i + self.block_size, d)] for i in splits]
        cols = []
        for c in range(k):
            b_i = _class_sample_weights(y_idx, counts, c, w, n)
            mu = joint_means[c]
            mean_blocks = [
                mu[i : min(i + self.block_size, d)] for i in splits
            ]
            yc = Y[:, c] - joint_label_mean[c]
            ws_c = solve_reweighted_l2(
                blocks, yc, b_i, reg=self.lam, num_iter=self.num_iter,
                means=mean_blocks,
            )
            cols.append(jnp.concatenate([wj[:, 0] for wj in ws_c]))
        W = jnp.stack(cols, axis=1)  # (d, k)
        b = joint_label_mean - jnp.einsum("cd,dc->c", joint_means, W)
        ws = [
            W[i : min(i + self.block_size, d)] for i in splits
        ]
        return BlockLinearMapper(ws, self.block_size, b=b)


class WeightedLeastSquaresEstimator(
    LabelEstimator, AutoSolverFrontDoor, CostModel, Optimizable
):
    """Cost-model auto-selecting front door for the weighted family — the
    class-weighted analogue of ``LeastSquaresEstimator``. All three
    physical solvers optimize the same mixture objective (the agreement
    contract pinned by the weighted parity tests), so selection is purely
    a cost question: the block solver streams and shares per-block Grams
    across classes, the per-class oracle is exact but pays k dense d×d
    factorizations, the reweighted BCD sits between. Selection runs
    through :class:`keystone_tpu.cost.SolverChooser`, so with a profile
    store configured (``KEYSTONE_PROFILE_DIR``) the family earns learned
    ``op/`` seconds-per-unit profiles from traced fits and future choices
    rank by predicted wall-clock."""

    def __init__(self, block_size: int, num_iter: int, lam: float,
                 mixture_weight: float,
                 num_features: Optional[int] = None,
                 num_machines: Optional[int] = None,
                 cpu_weight: Optional[float] = None,
                 mem_weight: Optional[float] = None,
                 network_weight: Optional[float] = None):
        self.block_size = block_size
        self.num_iter = num_iter
        self.lam = lam
        self.mixture_weight = mixture_weight
        self.num_features = num_features
        self.num_machines = num_machines
        self._init_chooser_weights(cpu_weight, mem_weight, network_weight)
        args = (block_size, num_iter, lam, mixture_weight)
        self.options: Sequence = [
            BlockWeightedLeastSquaresEstimator(
                *args, num_features=num_features
            ),
            PerClassWeightedLeastSquaresEstimator(
                *args, num_features=num_features
            ),
            ReWeightedLeastSquaresEstimator(
                *args, num_features=num_features
            ),
        ]
        self.default = self.options[0]

    def fit(self, data, labels: Dataset) -> BlockLinearMapper:
        from ...data.chunked import ChunkedDataset

        if isinstance(data, (list, tuple)):
            # pre-split block list: only the block solver understands it
            # (the per-class/reweighted options stack a dense (n, d)), and
            # the list container would corrupt the shape signature
            # (n = block count, not rows) — skip the chooser
            return self.default.fit(data, labels)
        chunked = isinstance(data, ChunkedDataset)
        sample = data.take(24) if chunked else Dataset.of(data)
        solver = self.sample_optimize(
            [sample, Dataset.of(labels)],
            len(Dataset.of(data)), chunked=chunked,
        )
        return solver.fit(data if chunked else Dataset.of(data), labels)
