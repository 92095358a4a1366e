"""Linear solvers: exact normal equations and block coordinate descent.

Parity: nodes/learning/LinearMapper.scala:18,69 (LinearMapper /
LinearMapEstimator) and nodes/learning/BlockLinearMapper.scala:22,199
(BlockLinearMapper / BlockLeastSquaresEstimator).

Semantics preserved from the reference:
  * features and labels are mean-centered before solving (StandardScaler with
    normalizeStdDev=false); the label mean becomes the intercept;
  * the block estimator centers each feature block independently;
  * ``num_iter=1`` is the one-pass BCD variant (solveOnePassL2).

TPU-native apply: the per-block GEMM+sum of the reference collapses into ONE
fused (n,d)×(d,k) MXU matmul over the concatenated model; block structure only
matters at fit time.
"""

from __future__ import annotations

import logging
from typing import List, Optional, Sequence

import jax
import jax.numpy as jnp

logger = logging.getLogger(__name__)

from ...data.dataset import Dataset
from ...linalg import solve_blockwise_l2, solve_least_squares
from ...obs.tracer import span
from ...parallel.mesh import shard_batch
from ...utils.params import as_param
from ...workflow.transformer import LabelEstimator, Transformer
from .cost import CostModel, combine_cost, label_dim_fitted_out_spec


class LinearMapper(Transformer):
    """out = (x − feature_mean) · W + b  (parity: LinearMapper.scala:18-63;
    scaling folded into the single GEMM)."""

    #: ``solver_state`` is refit bookkeeping (a snapshot-able
    #: GramSolverState), not part of the serve computation — W/b/mean fully
    #: determine trace_batch, so two mappers differing only in it must
    #: share AOT executables
    aot_fingerprint_exclude = ("solver_state",)

    def __init__(self, W, b=None, feature_mean=None, solver_state=None):
        self.W = as_param(W)
        self.b = as_param(b)
        self.feature_mean = as_param(feature_mean)
        #: optional :class:`~keystone_tpu.linalg.accumulators.GramSolverState`
        #: captured at fit time — what ``FittedPipeline.absorb`` folds
        #: appended chunks into (None when the fit didn't snapshot)
        self.solver_state = solver_state

    def trace_batch(self, X):
        if self.feature_mean is not None:
            X = X - self.feature_mean
        out = X @ self.W
        if self.b is not None:
            out = out + self.b
        return out


class LinearMapEstimator(LabelEstimator, CostModel):
    """Exact OLS via mesh normal equations
    (parity: LinearMapper.scala:69-100). Chunked inputs stream: a means
    pass, then centered (A, y) chunks through the laned Gram accumulator
    (``solve_least_squares_streaming``) — the exact solve never
    materializes the design matrix.

    ``snapshot=True`` fits through the raw-accumulator algebra
    (:class:`~keystone_tpu.linalg.accumulators.GramSolverState`: ΣAᵀA and
    ΣAᵀy with centering applied algebraically at the solve) and attaches
    the state to the fitted :class:`LinearMapper` — the handle
    ``FittedPipeline.absorb`` folds appended chunks into for an
    O(new chunks) incremental refit.

    ``checkpoint=dir`` makes a chunked fit RESUMABLE: the same
    accumulator state (plus a chunk/row cursor) persists atomically to
    ``dir`` every ``checkpoint_every`` chunks
    (:class:`~keystone_tpu.faults.FitCheckpoint`), so a killed fit
    re-run with the same arguments resumes from the last completed
    block — folding bit-identical solver state to an uninterrupted fit
    — instead of rescanning from chunk zero. The checkpoint is removed
    when the fit completes."""

    supports_streaming = True

    def __init__(
        self,
        lam: Optional[float] = None,
        snapshot: bool = False,
        checkpoint: Optional[str] = None,
        checkpoint_every: int = 1,
    ):
        self.lam = lam
        self.snapshot = snapshot
        self.checkpoint = checkpoint
        self.checkpoint_every = checkpoint_every

    def fitted_out_spec(self, fit_in, apply_in):
        return label_dim_fitted_out_spec(fit_in, apply_in)

    # -- sweep grid hooks (keystone_tpu/sweep/) -------------------------

    def grid_family(self):
        """Estimators of one sweep whose key matches fit as a group; λ is
        the swept axis, so it is excluded from the key. The checkpoint
        dir is part of the identity — a sweep's shared accumulation pass
        would otherwise silently drop a member's resume contract."""
        return ("gram_ne", bool(self.snapshot), self.checkpoint)

    @staticmethod
    def fit_lambda_grid(estimators: Sequence["LinearMapEstimator"],
                        data, labels: Dataset,
                        checkpoint: Optional[str] = None,
                        checkpoint_every: int = 1) -> List[LinearMapper]:
        """Fit a λ-only grid from ONE accumulation pass: the Gram and
        cross products don't depend on λ, so the grid costs
        O(prefix + n·d² + G·d³) instead of G full fits. Every returned
        mapper carries its own snapshot of the shared state (λ recorded),
        so any of them can later ``absorb`` appended chunks.

        With ``checkpoint``, the accumulation over a chunked ``data``
        persists ``(state, chunk cursor, row cursor)`` to that directory
        every ``checkpoint_every`` chunks and RESUMES from the last
        completed block on re-run — the fold is associative and the
        state is exact host float64, so the resumed accumulator is
        bit-identical to an uninterrupted pass."""
        from ...data.chunked import ChunkedDataset
        from ...linalg.accumulators import GramSolverState
        state = GramSolverState()
        with span("linear_map.grid_accumulate") as sp:
            if isinstance(data, ChunkedDataset):
                y = jnp.asarray(
                    Dataset.of(labels).to_array(), dtype=jnp.float32
                )
                ckpt = None
                start_chunk = 0
                offset = 0
                if checkpoint is not None:
                    from ...faults import FitCheckpoint

                    lams = [float(e.lam or 0.0) for e in estimators]
                    key = (
                        f"gram_ne|n={len(data)}"
                        f"|y={tuple(int(s) for s in y.shape)}|lams={lams}"
                    )
                    ckpt = FitCheckpoint(checkpoint, key)
                    loaded = ckpt.load()
                    if loaded is not None:
                        state, start_chunk, offset = loaded
                        logger.info(
                            "fit checkpoint: resuming Gram accumulation "
                            "at chunk %d (row %d) from %s",
                            start_chunk, offset, ckpt.path,
                        )
                every = max(1, int(checkpoint_every))
                i = start_chunk
                for chunk in data.raw_chunks(skip=start_chunk):
                    rows = int(chunk.shape[0])
                    state.update(chunk, y[offset : offset + rows])
                    offset += rows
                    i += 1
                    if ckpt is not None and i % every == 0:
                        ckpt.save(state, i, offset)
                if offset != y.shape[0]:
                    raise ValueError(
                        f"chunked features have {offset} rows, labels "
                        f"{y.shape[0]}"
                    )
                if ckpt is not None:
                    ckpt.complete()
            else:
                state.update(
                    Dataset.of(data).to_array(),
                    Dataset.of(labels).to_array(),
                )
            sp.sync_on(state.gram)
        models = []
        for est in estimators:
            W, b, mean = state.solve(est.lam or 0.0)
            snap = state.snapshot()
            snap.lam = float(est.lam or 0.0)
            models.append(
                LinearMapper(W, b=b, feature_mean=mean, solver_state=snap)
            )
        return models

    def fit(self, data: Dataset, labels: Dataset) -> LinearMapper:
        from ...data.chunked import ChunkedDataset

        if self.snapshot or self.checkpoint:
            # the checkpointed fit rides the same accumulator path the
            # snapshot fit uses — the state on disk IS the snapshot
            return LinearMapEstimator.fit_lambda_grid(
                [self], data, labels,
                checkpoint=self.checkpoint,
                checkpoint_every=self.checkpoint_every,
            )[0]
        if isinstance(data, ChunkedDataset):
            return self._fit_streaming(data, labels)
        A = shard_batch(data.to_array().astype(jnp.float32))
        b = shard_batch(labels.to_array().astype(jnp.float32))
        a_mean = jnp.mean(A, axis=0)
        b_mean = jnp.mean(b, axis=0)
        W = solve_least_squares(A - a_mean, b - b_mean, reg=self.lam or 0.0)
        return LinearMapper(W, b=b_mean, feature_mean=a_mean)

    def _fit_streaming(self, data, labels: Dataset) -> LinearMapper:
        """Out-of-core exact solve: one pass for column means, one laned
        Gram/cross pass over centered chunks (same two-pass shape as the
        streaming BCD path; collectives O(1) per scan)."""
        from ...linalg import solve_least_squares_streaming
        from ...linalg.bcd import stream_column_means
        y = jnp.asarray(Dataset.of(labels).to_array(), dtype=jnp.float32)
        with span("linear_map.stream_center") as sp:
            a_mean, n = stream_column_means(data.raw_chunks)
            if n != y.shape[0]:
                raise ValueError(
                    f"chunked features have {n} rows, labels {y.shape[0]}"
                )
            y_mean = jnp.mean(y, axis=0)
            sp.sync_on(y_mean)

        def centered():
            offset = 0
            for chunk in data.raw_chunks():
                chunk = jnp.asarray(chunk, dtype=jnp.float32)
                rows = int(chunk.shape[0])
                yield (
                    chunk - a_mean,
                    y[offset : offset + rows] - y_mean,
                )
                offset += rows

        with span("linear_map.stream_solve") as sp:
            W = solve_least_squares_streaming(centered(), reg=self.lam or 0.0)
            sp.sync_on(W)
        return LinearMapper(W, b=y_mean, feature_mean=a_mean)

    def cost(self, n, d, k, sparsity, num_machines,
             cpu_weight, mem_weight, network_weight):
        # parity: LinearMapper.scala:100-117
        from ...linalg.normal_equations import cost_signature

        return combine_cost(
            cost_signature(n, d, k, num_machines),
            cpu_weight, mem_weight, network_weight,
        )


def _per_block_work(widths: Sequence[int], block_size: int) -> dict:
    """The ``block_ls.solve`` span's attrs on the per-block-dispatch path
    (``scan_solver_work`` gives the scan path's): how many block programs an
    epoch dispatches, and the columns of a last block narrower than
    ``block_size`` (0: none)."""
    ragged = widths[-1] if widths and widths[-1] < block_size else 0
    return {"blocks": len(widths), "ragged_cols": int(ragged)}


class BlockLinearMapper(Transformer):
    """Fused apply of a block-solved model: block weights are vertically
    concatenated and per-block means concatenated, so application is one
    GEMM (parity: BlockLinearMapper.scala:22-98, whose per-block RDD zip+sum
    is pure network choreography the MXU doesn't need)."""

    #: refit bookkeeping (a snapshot-able WeightedSolverState from the
    #: per-class weighted family), never part of the serve computation
    aot_fingerprint_exclude = ("solver_state",)

    def __init__(self, xs: Sequence, block_size: int, b=None,
                 feature_means: Optional[Sequence] = None,
                 solver_state=None):
        import numpy as np

        #: optional :class:`~keystone_tpu.linalg.weighted.
        #: WeightedSolverState` captured at fit time — what
        #: ``FittedPipeline.absorb`` folds appended chunks into
        self.solver_state = solver_state
        # One batched device fetch; parameters live on host (utils/params.py).
        # A fit's solve is awaited here: the span covers that wait too
        with span("xfer.d2h", what="block_model"):
            xs, b, feature_means = jax.device_get(
                (list(xs), b, feature_means)
            )
        self.xs = [as_param(x) for x in xs]
        self.block_size = block_size
        self.b = as_param(b)
        self.feature_means = (
            None
            if feature_means is None
            else [as_param(m) for m in feature_means]
        )
        # the program's literals, made here and shared with nobody:
        # read-only in place, like every parameter (utils/params.py)
        self._W = np.concatenate(self.xs, axis=0)
        self._W.flags.writeable = False
        self._mean = None
        if self.feature_means is not None:
            self._mean = np.concatenate(self.feature_means, axis=0)
            self._mean.flags.writeable = False

    def trace_batch(self, X):
        with jax.named_scope("ks.apply.scores"):
            if self._mean is not None:
                X = X - self._mean
            out = X @ self._W
            if self.b is not None:
                out = out + self.b
            return out

    def apply_blocks(self, blocks: Sequence) -> jnp.ndarray:
        """Apply to pre-split feature blocks (parity:
        BlockLinearMapper.scala:50-73)."""
        out = None
        for j, (Aj, Wj) in enumerate(zip(blocks, self.xs)):
            Aj = jnp.asarray(Aj)
            if self.feature_means is not None:
                Aj = Aj - self.feature_means[j]
            term = Aj @ Wj
            out = term if out is None else out + term
        if self.b is not None:
            out = out + self.b
        return out


class BlockLeastSquaresEstimator(LabelEstimator, CostModel):
    """Block-coordinate-descent least squares — the workhorse solver
    (parity: BlockLinearMapper.scala:199-283)."""

    supports_streaming = True

    def __init__(self, block_size: int, num_iter: int, lam: float = 0.0,
                 num_features: Optional[int] = None,
                 snapshot: bool = False):
        if snapshot:
            from ...linalg.accumulators import NotAbsorbable

            raise NotAbsorbable(
                "block-coordinate descent has no snapshot-able state: "
                "its iterates depend on block visitation order, so "
                "appended chunks cannot be folded in after the fact — "
                "fit with LinearMapEstimator(snapshot=True) (exact Gram "
                "family) for an absorbable model"
            )
        self.block_size = block_size
        self.num_iter = num_iter
        self.lam = lam
        self.num_features = num_features
        #: per-block starting weights for the next fit (a λ-sweep warm
        #: start from the nearest-λ neighbor's model); consumed and
        #: cleared by ``fit`` — never part of the estimator's identity
        self.warm_start_ws: Optional[Sequence] = None

    def fitted_out_spec(self, fit_in, apply_in):
        return label_dim_fitted_out_spec(fit_in, apply_in)

    # passes over the input, for the auto-cache planner
    # (parity: BlockLinearMapper.scala:204)
    @property
    def weight(self) -> int:
        return 3 * self.num_iter + 1

    # -- sweep grid hooks (keystone_tpu/sweep/) -------------------------

    def grid_family(self):
        return ("bcd", self.block_size, self.num_iter, self.num_features)

    @staticmethod
    def fit_lambda_grid(
        estimators: Sequence["BlockLeastSquaresEstimator"], data, labels,
        warm_start: bool = True,
    ) -> List["BlockLinearMapper"]:
        """Fit a λ grid of BCD members, each warm-started from the
        nearest-λ neighbor already solved (ascending λ order). BCD is
        iterative, so warm-started iterates differ from cold ones while
        descending the same objective — a sweep only takes this path when
        asked (``GridSweep(warm_start=True)``). Chunked inputs fall back
        to independent cold fits (the streaming prediction buffer has no
        cheap consistent warm initialization)."""
        import copy

        from ...data.chunked import ChunkedDataset

        order = sorted(
            range(len(estimators)), key=lambda i: estimators[i].lam or 0.0
        )
        models: List[Optional[BlockLinearMapper]] = [None] * len(estimators)
        prev: Optional[BlockLinearMapper] = None
        chunked = isinstance(data, ChunkedDataset)
        for i in order:
            est = copy.copy(estimators[i])
            est.warm_start_ws = (
                [w for w in prev.xs] if (warm_start and prev is not None
                                         and not chunked) else None
            )
            models[i] = est.fit(data, labels)
            prev = models[i]
        return models

    def fit(self, data, labels: Dataset) -> BlockLinearMapper:
        """``data`` is either a Dataset of (n, d) features (split internally,
        parity :251-257) or an already-split sequence of blocks (:212).

        A contiguous (n, d) matrix with d divisible by ``block_size`` solves
        through :func:`solve_blockwise_l2_scan` — the whole BCD pass is ONE
        compiled program (zero host round trips per block). A contiguous
        matrix with a ragged last block, and pre-split blocks, take the
        per-block-dispatch path; the contiguous one is never cut into
        column slices (a second copy of the features in HBM).
        """
        from ...data.chunked import ChunkedDataset
        from ...linalg.bcd import _block_means

        warm = getattr(self, "warm_start_ws", None)  # pre-sweep pickles
        self.warm_start_ws = None
        if isinstance(data, ChunkedDataset):
            return self._fit_streaming(data, labels)

        X = None
        if isinstance(data, Dataset) and isinstance(data.payload, (list, tuple)):
            blocks = [jnp.asarray(p) for p in data.payload]
        elif isinstance(data, (list, tuple)):
            # stage pre-split blocks through the pipelined scan: block i+1
            # materializes (and its H2D transfer streams) while block i's
            # device placement completes, instead of a serial eager loop
            from ...data.pipeline_scan import scan_pipeline

            blocks = list(
                scan_pipeline(
                    (Dataset.of(d).to_array() for d in data),
                    label="block_ingest",
                )
            )
        else:
            X = Dataset.of(data).to_array()
            d = self.num_features or X.shape[-1]
            X = X[..., :d]
            blocks = None

        y = Dataset.of(labels).to_array().astype(jnp.float32)

        if X is not None:
            return self._fit_contiguous(X, y, warm)

        with span("block_ls.center") as sp:
            blocks = [
                shard_batch(b if b.dtype == jnp.float32 else b.astype(jnp.float32))
                for b in blocks
            ]
            # one program for every mean; centering itself is fused into the
            # per-block solve so centered copies never hit HBM
            means, y_mean = _block_means(blocks, y)
            sp.sync_on(y_mean)
        with span(
            "block_ls.solve", **_per_block_work(
                [int(b.shape[1]) for b in blocks], self.block_size
            )
        ):
            init = None
            if warm is not None and len(warm) == len(blocks) and all(
                tuple(w.shape) == (int(b.shape[1]), int(y.shape[1]))
                for w, b in zip(warm, blocks)
            ):
                init = [jnp.asarray(w) for w in warm]
            ws = solve_blockwise_l2(
                blocks, shard_batch(y - y_mean), reg=self.lam,
                num_iter=self.num_iter, means=means, init=init,
            )
        return BlockLinearMapper(
            ws, self.block_size, b=y_mean, feature_means=means
        )

    def _fit_contiguous(self, X, y, warm) -> BlockLinearMapper:
        """The fit on one (n, d) matrix in HBM: the scan solver where
        ``block_size`` divides d, else one dispatch a block with the last
        block narrower."""
        from ...linalg.bcd import (
            scan_solver_work,
            solve_blockwise_l2_columns,
            solve_blockwise_l2_scan,
        )

        d, bs = X.shape[-1], self.block_size
        starts = range(0, d, bs)
        widths = [min(bs, d - i) for i in starts]
        with span("block_ls.center") as sp:
            X = shard_batch(
                X if X.dtype == jnp.float32 else X.astype(jnp.float32)
            )
            mean_vec = jnp.mean(X, axis=0)
            y_mean = jnp.mean(y, axis=0)
            sp.sync_on((mean_vec, y_mean))
        y_zm = shard_batch(y - y_mean)
        if d % bs == 0:
            with span("block_ls.solve") as sp:
                sp.attrs.update(scan_solver_work(d, bs, self.num_iter))
                init = None
                if warm is not None:
                    cat = jnp.concatenate(
                        [jnp.asarray(w) for w in warm], axis=0
                    )
                    if cat.shape == (d, y.shape[1]):
                        init = cat
                W = solve_blockwise_l2_scan(
                    X, y_zm, reg=self.lam, block_size=bs,
                    num_iter=self.num_iter, means=mean_vec, init=init,
                )
                sp.sync_on(W)
            ws = [W[i : i + bs] for i in starts]
        else:
            with span("block_ls.solve", **_per_block_work(widths, bs)) as sp:
                init = None
                if warm is not None and [
                    tuple(w.shape) for w in warm
                ] == [(w, int(y.shape[1])) for w in widths]:
                    init = [jnp.asarray(w) for w in warm]
                ws = solve_blockwise_l2_columns(
                    X, y_zm, reg=self.lam, block_size=bs,
                    num_iter=self.num_iter, means=mean_vec, init=init,
                )
                sp.sync_on(ws[-1])
        means = [mean_vec[i : i + bs] for i in starts]
        return BlockLinearMapper(ws, bs, b=y_mean, feature_means=means)

    def _fit_streaming(self, data, labels: Dataset) -> BlockLinearMapper:
        """Fit from a :class:`~keystone_tpu.data.chunked.ChunkedDataset`
        without ever materializing the featurized design matrix — the
        out-of-core path (parity: the reference's BCD scanning its cached
        featurized RDD per block step, BlockLinearMapper.scala:199-257 over
        ImageNet/TIMIT-scale training sets that exceed one machine).

        Scans the source num_iter × nblocks + 1 times (one centering pass;
        each block step fuses the previous block's prediction update)."""
        from ...linalg.bcd import (
            solve_blockwise_l2_streaming,
            stream_column_means,
        )
        y = jnp.asarray(Dataset.of(labels).to_array(), dtype=jnp.float32)

        # raw (unpipelined) scans compose here: the streaming solvers wrap
        # chunk_scan() in scan_pipeline themselves, so exactly ONE
        # producer thread runs the whole chain per scan
        if self.num_features is not None:
            d = self.num_features
            base_scan = data.raw_chunks

            def chunk_scan():
                for chunk in base_scan():
                    yield chunk[..., :d]

        else:
            chunk_scan = data.raw_chunks

        with span("block_ls.stream_center") as sp:
            mean_vec, n = stream_column_means(chunk_scan)
            if n != y.shape[0]:
                raise ValueError(
                    f"chunked features have {n} rows, labels {y.shape[0]}"
                )
            y_mean = jnp.mean(y, axis=0)
            sp.sync_on(y_mean)
        with span("block_ls.stream_solve") as sp:
            ws = solve_blockwise_l2_streaming(
                chunk_scan, y - y_mean, reg=self.lam,
                block_size=self.block_size, num_iter=self.num_iter,
                means=mean_vec,
            )
            sp.sync_on(ws[-1])
        d = int(mean_vec.shape[0])
        means = [
            mean_vec[i : min(i + self.block_size, d)]
            for i in range(0, d, self.block_size)
        ]
        return BlockLinearMapper(
            ws, self.block_size, b=y_mean, feature_means=means
        )

    def cost(self, n, d, k, sparsity, num_machines,
             cpu_weight, mem_weight, network_weight):
        # parity: BlockLinearMapper.scala:268-282
        from ...linalg.bcd import cost_signature

        return combine_cost(
            cost_signature(
                n, d, k, self.block_size, self.num_iter, num_machines
            ),
            cpu_weight, mem_weight, network_weight,
        )


class TSQRLeastSquaresEstimator(LabelEstimator, CostModel):
    """Exact least squares via tall-skinny QR of the AUGMENTED design
    matrix — the numerically robust sibling of the normal equations.

    Parity root: mlmatrix's TSQR (DistributedPCA.scala:48 uses qrR); the
    reference never wires it into LeastSquaresEstimator's option set, but
    the factorization is the classic cure for the Gram route squaring the
    condition number. One QR of ``[A−μ | y−ν ; √λ·I | 0]`` yields an
    upper-triangular ``R`` whose blocks satisfy ``R₁₁ᵀR₁₁ = AᵀA + λI``
    and ``R₁₁ᵀR₁₂ = Aᵀy`` (centered), so the solution is ONE triangular
    solve ``W = R₁₁⁻¹R₁₂`` — no Gram matrix ever forms. Costs ~2× the
    Gram contraction in flops (see ``linalg.tsqr.cost_signature``): the
    cost model prefers it only when learned profiles or conditioning
    evidence say so.

    Chunked inputs stream through :func:`linalg.tsqr.tsqr_r_streaming`
    (per-lane R folds, one cross-mesh gather at finalize), so the exact
    QR solve is available out-of-core too.

    ``checkpoint=dir`` makes the chunked fit resumable: it runs the
    sequential :class:`~keystone_tpu.linalg.accumulators.TsqrRState`
    recurrence (restartable by construction) instead of the laned fold,
    persists the R state + column means + chunk cursor to ``dir`` every
    ``checkpoint_every`` chunks, and a killed fit re-run resumes from
    the last completed block — the means pass is checkpointed too, so
    resume re-reads NO already-folded chunk.
    """

    supports_streaming = True

    def __init__(
        self,
        lam: float = 0.0,
        checkpoint: Optional[str] = None,
        checkpoint_every: int = 1,
    ):
        self.lam = lam
        self.checkpoint = checkpoint
        self.checkpoint_every = checkpoint_every

    def fitted_out_spec(self, fit_in, apply_in):
        return label_dim_fitted_out_spec(fit_in, apply_in)

    # -- sweep grid hooks (keystone_tpu/sweep/) -------------------------

    def grid_family(self):
        return ("tsqr", self.checkpoint)

    @staticmethod
    def fit_lambda_grid(
        estimators: Sequence["TSQRLeastSquaresEstimator"], data, labels
    ) -> List[LinearMapper]:
        """Fit a λ-only grid from ONE factorization: the R factor of the
        UNregularized centered augmented matrix is λ-independent, and
        ``qr([A; B]).R == qr([qr(A).R; B]).R`` (up to row signs, which
        the triangular solve cancels) — so each member folds only its
        √λ·I rows into the shared R, an O((d+k)³) fold against one
        O(n·(d+k)²) factorization."""
        from ...data.chunked import ChunkedDataset
        from ...linalg.bcd import stream_column_means
        from ...linalg.tsqr import _qr_fold, tsqr_r, tsqr_r_streaming
        y = jnp.asarray(Dataset.of(labels).to_array(), dtype=jnp.float32)
        chunked = isinstance(data, ChunkedDataset)
        with span("tsqr_ls.grid_factorize") as sp:
            if chunked:
                a_mean, n = stream_column_means(data.raw_chunks)
                if n != y.shape[0]:
                    raise ValueError(
                        f"chunked features have {n} rows, labels {y.shape[0]}"
                    )
                y_mean = jnp.mean(y, axis=0)
                d = int(a_mean.shape[0])

                def augmented():
                    offset = 0
                    for chunk in data.raw_chunks():
                        chunk = jnp.asarray(chunk, dtype=jnp.float32)
                        rows = int(chunk.shape[0])
                        yield jnp.concatenate(
                            [chunk - a_mean,
                             y[offset : offset + rows] - y_mean],
                            axis=1,
                        )
                        offset += rows

                R_base = tsqr_r_streaming(augmented)
            else:
                A = jnp.asarray(
                    Dataset.of(data).to_array(), dtype=jnp.float32
                )
                a_mean = jnp.mean(A, axis=0)
                y_mean = jnp.mean(y, axis=0)
                d = int(A.shape[1])
                R_base = tsqr_r(
                    jnp.concatenate([A - a_mean, y - y_mean], axis=1)
                )
            sp.sync_on(R_base)
        k = int(y.shape[1])
        models = []
        for est in estimators:
            reg = est._reg_rows(d, k)
            R = R_base if reg is None else _qr_fold(R_base, reg)
            W = TSQRLeastSquaresEstimator._solve_from_r(R, d)
            models.append(LinearMapper(W, b=y_mean, feature_mean=a_mean))
        return models

    @staticmethod
    def _solve_from_r(R, d: int):
        from jax.scipy.linalg import solve_triangular

        return solve_triangular(R[:d, :d], R[:d, d:], lower=False)

    def _reg_rows(self, d: int, k: int):
        if not self.lam:
            return None
        return jnp.concatenate(
            [
                jnp.sqrt(jnp.float32(self.lam)) * jnp.eye(d, dtype=jnp.float32),
                jnp.zeros((d, k), dtype=jnp.float32),
            ],
            axis=1,
        )

    def fit(self, data, labels: Dataset) -> LinearMapper:
        from ...data.chunked import ChunkedDataset
        from ...linalg.tsqr import tsqr_r

        if isinstance(data, ChunkedDataset):
            return self._fit_streaming(data, labels)
        A = jnp.asarray(Dataset.of(data).to_array(), dtype=jnp.float32)
        y = jnp.asarray(Dataset.of(labels).to_array(), dtype=jnp.float32)
        a_mean = jnp.mean(A, axis=0)
        y_mean = jnp.mean(y, axis=0)
        d, k = A.shape[1], y.shape[1]
        aug = jnp.concatenate([A - a_mean, y - y_mean], axis=1)
        reg = self._reg_rows(d, k)
        if reg is not None:
            aug = jnp.concatenate([aug, reg], axis=0)
        W = self._solve_from_r(tsqr_r(aug), d)
        return LinearMapper(W, b=y_mean, feature_mean=a_mean)

    def _fit_streaming_checkpointed(self, data, labels: Dataset) -> LinearMapper:
        """The resumable out-of-core TSQR fit: sequential
        :class:`TsqrRState` fold (exactly the streaming recurrence, so
        restart-from-R is restart-from-the-math) with the column means
        and the chunk/row cursor persisted alongside the R factor. The
        √λ rows fold only at the end — they must never be inside a
        checkpointed prefix."""
        from ...faults import FitCheckpoint
        from ...linalg.accumulators import TsqrRState
        from ...linalg.bcd import stream_column_means
        from ...linalg.tsqr import _qr_fold
        y = jnp.asarray(Dataset.of(labels).to_array(), dtype=jnp.float32)
        key = (
            f"tsqr|n={len(data)}|y={tuple(int(s) for s in y.shape)}"
            f"|lam={float(self.lam or 0.0)}"
        )
        ckpt = FitCheckpoint(self.checkpoint, key)
        loaded = ckpt.load()
        if loaded is not None:
            doc, start_chunk, offset = loaded
            a_mean = jnp.asarray(doc["a_mean"])
            y_mean = jnp.asarray(doc["y_mean"])
            state = doc["state"]
            logger.info(
                "fit checkpoint: resuming TSQR fold at chunk %d (row %d) "
                "from %s", start_chunk, offset, ckpt.path,
            )
        else:
            with span("tsqr_ls.stream_center") as sp:
                a_mean, n = stream_column_means(data.raw_chunks)
                if n != y.shape[0]:
                    raise ValueError(
                        f"chunked features have {n} rows, labels "
                        f"{y.shape[0]}"
                    )
                y_mean = jnp.mean(y, axis=0)
                sp.sync_on(y_mean)
            state = TsqrRState()
            start_chunk, offset = 0, 0
            # block 0's checkpoint carries the means: a fit killed during
            # the fold must not re-pay the centering pass on resume
            ckpt.save(self._ckpt_doc(a_mean, y_mean, state), 0, 0)
        d = int(a_mean.shape[0])
        k = int(y.shape[1])
        every = max(1, int(self.checkpoint_every))
        with span("tsqr_ls.stream_solve") as sp:
            i = start_chunk
            for chunk in data.raw_chunks(skip=start_chunk):
                chunk = jnp.asarray(chunk, dtype=jnp.float32)
                rows = int(chunk.shape[0])
                state.update(
                    jnp.concatenate(
                        [chunk - a_mean, y[offset : offset + rows] - y_mean],
                        axis=1,
                    )
                )
                offset += rows
                i += 1
                if i % every == 0:
                    ckpt.save(self._ckpt_doc(a_mean, y_mean, state), i, offset)
            if offset != y.shape[0]:
                raise ValueError(
                    f"chunked features have {offset} rows, labels "
                    f"{y.shape[0]}"
                )
            R = state.finalize()
            reg = self._reg_rows(d, k)
            if reg is not None:
                R = _qr_fold(R, reg)
            W = self._solve_from_r(R, d)
            sp.sync_on(W)
        ckpt.complete()
        return LinearMapper(W, b=y_mean, feature_mean=a_mean)

    @staticmethod
    def _ckpt_doc(a_mean, y_mean, state):
        import numpy as np

        return {
            "a_mean": np.asarray(a_mean),
            "y_mean": np.asarray(y_mean),
            "state": state.snapshot(),
        }

    def _fit_streaming(self, data, labels: Dataset) -> LinearMapper:
        """Means pass, then centered augmented chunks through the laned
        streaming TSQR; the √λ regularization rows ride as a final chunk
        (``qr([A; √λI])`` has the regularized Gram as RᵀR)."""
        from ...linalg.bcd import stream_column_means
        from ...linalg.tsqr import tsqr_r_streaming
        if self.checkpoint:
            return self._fit_streaming_checkpointed(data, labels)

        y = jnp.asarray(Dataset.of(labels).to_array(), dtype=jnp.float32)
        with span("tsqr_ls.stream_center") as sp:
            a_mean, n = stream_column_means(data.raw_chunks)
            if n != y.shape[0]:
                raise ValueError(
                    f"chunked features have {n} rows, labels {y.shape[0]}"
                )
            y_mean = jnp.mean(y, axis=0)
            sp.sync_on(y_mean)
        d = int(a_mean.shape[0])
        k = int(y.shape[1])
        reg = self._reg_rows(d, k)

        def augmented():
            offset = 0
            for chunk in data.raw_chunks():
                chunk = jnp.asarray(chunk, dtype=jnp.float32)
                rows = int(chunk.shape[0])
                yield jnp.concatenate(
                    [chunk - a_mean, y[offset : offset + rows] - y_mean],
                    axis=1,
                )
                offset += rows
            if reg is not None:
                yield reg

        with span("tsqr_ls.stream_solve") as sp:
            W = self._solve_from_r(tsqr_r_streaming(augmented), d)
            sp.sync_on(W)
        return LinearMapper(W, b=y_mean, feature_mean=a_mean)

    def cost(self, n, d, k, sparsity, num_machines,
             cpu_weight, mem_weight, network_weight):
        from ...linalg.tsqr import cost_signature

        return combine_cost(
            cost_signature(n, d, k, num_machines),
            cpu_weight, mem_weight, network_weight,
        )


class SparseLinearMapper(Transformer):
    """Apply a dense trained model to sparse input rows: xᵀ·W (+ b)
    (parity: SparseLinearMapper.scala:13-50).

    TPU path: ``SparseRows`` batches apply as an embedding-style gather
    (W[indices]·values, data/sparse.py) — no densification at any width.
    """

    def __init__(self, W, b=None):
        self.W = as_param(W)
        self.b = as_param(b)

    def apply_batch(self, data):
        from ...data.sparse import SparseRows

        data = Dataset.of(data)
        if isinstance(data.payload, SparseRows):
            out = data.payload.matmul(self.W)
            if self.b is not None:
                out = out + self.b
            return Dataset(out, batched=True)
        return data.map_batch(self.trace_batch)

    def trace_batch(self, X):
        out = jnp.asarray(X) @ self.W
        if self.b is not None:
            out = out + self.b
        return out

    def apply(self, x):
        from ...data.sparse import SparseRows

        sr = SparseRows.datum_from_pairs(x, self.W.shape[0])
        if sr is not None:
            x = sr
        if isinstance(x, SparseRows):
            out = x.matmul(self.W)
            out = out if self.b is None else out + self.b
            return out[0] if len(x) == 1 else out
        if hasattr(x, "nnz"):  # scipy sparse vector/matrix
            import numpy as np

            dense = jnp.asarray(np.asarray(x.todense()))
            if dense.ndim == 2 and dense.shape[0] > 1:
                return self.trace_batch(dense)  # r×d matrix → r×k batch
            x = dense.reshape(-1)
        return self.trace_batch(jnp.asarray(x)[None])[0]
