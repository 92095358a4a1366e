"""Kernel methods: Gaussian kernel blocks + Gauss-Seidel kernel ridge
regression (arXiv:1602.05310 recipe).

Parity: nodes/learning/KernelGenerator.scala:36,84,138-206 (lazy column-block
kernel computation), KernelMatrix.scala:17,50 (block caching),
KernelRidgeRegression.scala:37,67,86-235 (blockwise Gauss-Seidel solve),
KernelBlockLinearMapper.scala:28 (test-time application).

Mesh-native shape: the n×n kernel matrix is never materialized — one n×b
column block at a time is computed as a single GEMM + elementwise exp
(row-sharded train data × replicated block), cached in HBM, and freed after
its solve; exactly the reference's streaming pattern with the
broadcast/treeReduce choreography replaced by XLA collectives.

**Precision.** Every product states its precision; on a TPU an unstated
float32 product is ONE bf16 pass. The published λ is 0: the 5,000² block of
RandomPatchCifarKernel's kernel has eigenvalues from 0.014 to 3,640, and
its coefficients reach 100 against scores of ±1, so a one-pass product
anywhere the coefficients are read cancels every digit of the scores
(``benchmark/configs/cifar_krr.json``'s ``precision`` has the readings):

* :data:`KERNEL_PRECISION` — the cross product ``x·y`` of a kernel block,
  in the fit's generation and in the mapper's apply;
* :data:`SOLVE_PRECISION` — the residual ``K_Bᵀ W`` of a block step. The
  block's Cholesky factor and its triangular solves are XLA's own, whose
  blocked updates run at HIGHEST on the TPU (every dot of a 5,000² factor
  and solve, compiled for a described v5e, carries
  ``operand_precision={highest,highest}``);
* :data:`APPLY_PRECISION` — the mapper's ``K_b · W_b``.
"""

from __future__ import annotations

from functools import partial
from typing import Dict, List, Optional, Sequence

import jax
import jax.numpy as jnp
import numpy as np

from ...data.dataset import Dataset
from ...linalg.row_matrix import solve_spd
from ...obs.tracer import span
from ...workflow.transformer import LabelEstimator, Transformer
from ...workflow.node_optimization import Optimizable
from .cost import AutoSolverFrontDoor, CostModel, combine_cost

#: the kernel's cross product: three bf16 passes. One pass moves the
#: held-out scores of a 5,000-row fit by 1.5e-2 of their norm, three by
#: 4.9e-4 — under what float32 itself leaves (CPU emulation; the chip's
#: readings are the configuration's)
KERNEL_PRECISION = "high"
#: the residual of a block step: three passes move the scores by 2.1e-3
SOLVE_PRECISION = "highest"
#: ``K_b · W_b`` of the apply: one pass moves the scores by 2.1 (they are
#: sums of ±100 coefficients), three by 3.6e-3
APPLY_PRECISION = "highest"


@jax.jit
def _gaussian_block(X, Xb, gamma):
    """exp(−γ‖x−y‖²) for all (row of X, row of Xb): (n, b), the cross
    product at :data:`KERNEL_PRECISION`
    (parity: computeKernel, KernelGenerator.scala:138-206)."""
    xn = jnp.sum(X * X, axis=1, keepdims=True)
    bn = jnp.sum(Xb * Xb, axis=1)
    sq = xn - 2.0 * jnp.matmul(X, Xb.T, precision=KERNEL_PRECISION) + bn
    return jnp.exp(-gamma * jnp.maximum(sq, 0.0))


def _kernel_span(rows: int, cols: int):
    """The span of one kernel generation: its ``rows`` × ``cols`` entries."""
    return span("krr.kernel", rows=rows, cols=cols, entries=rows * cols)


class BlockKernelMatrix:
    """Lazily computed, cached n×b kernel column blocks
    (parity: BlockKernelMatrix, KernelMatrix.scala:50-90)."""

    def __init__(self, X, gamma: float, cache_blocks: bool = True):
        self.X = jnp.asarray(X, dtype=jnp.float32)
        self.gamma = gamma
        self.cache_blocks = cache_blocks
        self._cache: Dict[tuple, jnp.ndarray] = {}

    def block(self, idxs) -> jnp.ndarray:
        key = (int(idxs[0]), int(idxs[-1]))
        if key in self._cache:
            return self._cache[key]
        Kb = _gaussian_block(
            self.X, self.X[jnp.asarray(np.asarray(idxs))], self.gamma
        )
        if self.cache_blocks:
            self._cache[key] = Kb
        return Kb

    def diag_block(self, idxs) -> jnp.ndarray:
        Kb = self.block(idxs)
        return Kb[jnp.asarray(np.asarray(idxs))]

    def unpersist(self, idxs) -> None:
        self._cache.pop((int(idxs[0]), int(idxs[-1])), None)


class KernelBlockLinearMapper(Transformer):
    """Apply a kernel model: out = Σ_B K(test, train_B) · W_B
    (parity: KernelBlockLinearMapper.scala:28-90)."""

    # Never a segment member (``check/segments.py`` makes it a barrier):
    # train_X/W are dataset-sized, so baking them into a segment's XLA
    # module as literals (or fetching them host-side) is exactly the wrong
    # trade. They stay device-resident; every block is ONE program that
    # takes them as jit *arguments* (``_kernel_apply_block``).
    no_fuse = True

    def __init__(self, train_X, model_W, gamma: float, block_size: int):
        self.train_X = jnp.asarray(train_X, dtype=jnp.float32)
        self.W = jnp.asarray(model_W, dtype=jnp.float32)  # (n_train, k)
        self.gamma = gamma
        self.block_size = block_size

    def _blocks(self):
        n_train = self.train_X.shape[0]
        for start in range(0, n_train, self.block_size):
            yield start, min(self.block_size, n_train - start)

    def trace_batch(self, X):
        X = jnp.asarray(X, dtype=jnp.float32)
        out = jnp.zeros((X.shape[0], self.W.shape[1]), dtype=jnp.float32)
        for start, size in self._blocks():
            out = _kernel_apply_block(
                X, self.train_X, self.W, out, start, jnp.float32(self.gamma),
                bs=size,
            )
        return out

    def apply_batch(self, data: Dataset) -> Dataset:
        """A data set block by block, each kernel generation on a
        ``krr.kernel`` span, the whole under ``krr.apply`` with the kernel
        entries it made."""
        data = Dataset.of(data)
        if not data.is_batched:
            return super().apply_batch(data)
        X = jnp.asarray(data.to_array(), dtype=jnp.float32)
        rows, n_train = X.shape[0], self.train_X.shape[0]
        out = jnp.zeros((rows, self.W.shape[1]), dtype=jnp.float32)
        with span(
            "krr.apply", rows=rows, cols=n_train, entries=rows * n_train,
        ) as sp:
            for start, size in self._blocks():
                with _kernel_span(rows, size) as kp:
                    out = _kernel_apply_block(
                        X, self.train_X, self.W, out, start,
                        jnp.float32(self.gamma), bs=size,
                    )
                    kp.sync_on(out)
            sp.sync_on(out)
        return Dataset(out, batched=True)


@partial(jax.jit, static_argnames=("bs",))
def _kernel_apply_block(X, train_X, W, out, start, gamma, *, bs):
    """``out + K(X, train_X[start:start+bs]) · W[start:start+bs]``: one
    block of the apply as one program, the training rows and the
    coefficients sliced inside it."""
    Tb = jax.lax.dynamic_slice_in_dim(train_X, start, bs, axis=0)
    Wb = jax.lax.dynamic_slice_in_dim(W, start, bs, axis=0)
    Kb = _gaussian_block(X, Tb, gamma)
    return out + jnp.matmul(Kb, Wb, precision=APPLY_PRECISION)


def _krr_block_step_impl(X, Y, W, start, gamma, lam, *, bs):
    """One Gauss-Seidel block step as ONE fused program (kernel-block
    generation from a dynamic row slice, residual, SPD solve, in-place
    model update). The eager form paid four separate TPU sins per block:
    a row GATHER for X[idxs] (~20M elem/s on this part vs dense streaming),
    an LU factorization where Cholesky applies (K_BB + λI is SPD), a
    scatter for W.at[idxs].set (XLA pads scatter operands ~66×), and
    4+ dispatch round trips — measured 7.6 s → 1.3 s for the 50k-row
    CIFAR-shape fit."""
    Xb = jax.lax.dynamic_slice_in_dim(X, start, bs, axis=0)
    Kb = _gaussian_block(X, Xb, gamma)                       # (n, bs)
    Kbb = jax.lax.dynamic_slice_in_dim(Kb, start, bs, axis=0)
    W_old = jax.lax.dynamic_slice_in_dim(W, start, bs, axis=0)
    Yb = jax.lax.dynamic_slice_in_dim(Y, start, bs, axis=0)
    W_new = solve_spd(Kbb, Yb - _residual(Kb, Kbb, W, W_old), lam)
    return jax.lax.dynamic_update_slice_in_dim(W, W_new, start, axis=0)


def _residual(Kb, Kbb, W, W_old):
    """``K_Bᵀ W − K_BBᵀ W_B``: what the other blocks predict for block B."""
    return (
        jnp.matmul(Kb.T, W, precision=SOLVE_PRECISION)
        - jnp.matmul(Kbb.T, W_old, precision=SOLVE_PRECISION)
    )


def _krr_block_step_cached_impl(Kb, Y, W, start, lam, *, bs):
    """Cached-kernel variant: same step minus the kernel generation."""
    Kbb = jax.lax.dynamic_slice_in_dim(Kb, start, bs, axis=0)
    W_old = jax.lax.dynamic_slice_in_dim(W, start, bs, axis=0)
    Yb = jax.lax.dynamic_slice_in_dim(Y, start, bs, axis=0)
    W_new = solve_spd(Kbb, Yb - _residual(Kb, Kbb, W, W_old), lam)
    return jax.lax.dynamic_update_slice_in_dim(W, W_new, start, axis=0)


_krr_block_step_donating = jax.jit(
    _krr_block_step_impl, static_argnames=("bs",), donate_argnums=(2,)
)
_krr_block_step_plain = jax.jit(
    _krr_block_step_impl, static_argnames=("bs",)
)
_krr_block_step_cached_donating = jax.jit(
    _krr_block_step_cached_impl, static_argnames=("bs",), donate_argnums=(2,)
)
_krr_block_step_cached_plain = jax.jit(
    _krr_block_step_cached_impl, static_argnames=("bs",)
)


def _krr_block_step(*args, **kwargs):
    # CPU donation intermittently aborts (same workaround as linalg/bcd.py)
    if jax.default_backend() == "cpu":
        return _krr_block_step_plain(*args, **kwargs)
    return _krr_block_step_donating(*args, **kwargs)


def _krr_block_step_cached(*args, **kwargs):
    if jax.default_backend() == "cpu":
        return _krr_block_step_cached_plain(*args, **kwargs)
    return _krr_block_step_cached_donating(*args, **kwargs)


@partial(jax.jit, static_argnames=("bs",))
def _kernel_block_slice(X, start, gamma, bs):
    """K(X, X[start:start+bs]) with the block rows dynamic-sliced (never
    gathered) — the generation path for cached-kernel mode."""
    Xb = jax.lax.dynamic_slice_in_dim(X, start, bs, axis=0)
    return _gaussian_block(X, Xb, gamma)


class KernelRidgeRegression(LabelEstimator, CostModel):
    """Gauss-Seidel block-coordinate kernel ridge regression
    (parity: KernelRidgeRegression.scala:37-235). Per block B:
        (K_BB + λI) W_B ← y_B − (K_Bᵀ W − K_BBᵀ W_B_old)
    """

    def __init__(self, gamma: float, lam: float, block_size: int,
                 num_epochs: int, block_permuter: Optional[int] = None,
                 cache_kernel: bool = True,
                 checkpoint_dir: Optional[str] = None,
                 checkpoint_interval: int = 25):
        self.gamma = gamma
        self.lam = lam
        self.block_size = block_size
        self.num_epochs = num_epochs
        self.block_permuter = block_permuter
        self.cache_kernel = cache_kernel
        # Solver-state checkpoint every N blocks — the TPU analogue of the
        # reference's truncateLineage/RDD.checkpoint call
        # (KernelRidgeRegression.scala:204-208, utils/MatrixUtils.scala:163-189):
        # there it bounds RDD lineage depth; here the model has no lineage,
        # so the surviving purpose is restart — a killed long fit resumes
        # from the last saved (epoch, step, W).
        self.checkpoint_dir = checkpoint_dir
        self.checkpoint_interval = checkpoint_interval

    def _ckpt_path(self) -> Optional[str]:
        if not self.checkpoint_dir:
            return None
        import os

        os.makedirs(self.checkpoint_dir, exist_ok=True)
        return os.path.join(self.checkpoint_dir, "krr_state.npz")

    def cost(self, n, d, k, sparsity, num_machines,
             cpu_weight, mem_weight, network_weight):
        # kernel generation n²·d once (cached) or per epoch; per epoch
        # every block pays the n×bs residual GEMM (n²·k total) and a bs³
        # Cholesky (n·bs² total); cached-kernel epochs re-stream n² floats
        bs = min(self.block_size, n)
        gen_epochs = 1 if self.cache_kernel else self.num_epochs
        return combine_cost(
            {
                "flops": (
                    gen_epochs * float(n) * n * d
                    + self.num_epochs * (float(n) * n * k + float(n) * bs * bs)
                ) / num_machines,
                "bytes": (
                    self.num_epochs * float(n) * n / num_machines
                    + float(n) * d
                ),
                "network": float(n) * k * self.num_epochs,
                "passes": self.num_epochs,
            },
            cpu_weight, mem_weight, network_weight,
        )

    def fit(self, data: Dataset, labels: Dataset) -> KernelBlockLinearMapper:
        import os

        X = jnp.asarray(Dataset.of(data).to_array(), dtype=jnp.float32)
        Y = jnp.asarray(Dataset.of(labels).to_array(), dtype=jnp.float32)
        n, k = Y.shape
        bs = self.block_size
        kernel_cache: Dict[int, jnp.ndarray] = {}
        W = jnp.zeros((n, k), dtype=jnp.float32)

        num_blocks = -(-n // bs)
        rng = (
            np.random.default_rng(self.block_permuter)
            if self.block_permuter is not None
            else None
        )
        start_epoch, start_step = 0, 0
        ckpt = self._ckpt_path()
        if ckpt and os.path.exists(ckpt):
            saved = np.load(ckpt)
            if saved["W"].shape == (n, k):
                W = jnp.asarray(saved["W"])
                start_epoch = int(saved["epoch"])
                start_step = int(saved["step"])
        steps_done = 0
        with span(
            "krr.fit", blocks=num_blocks, epochs=self.num_epochs,
            block_size=bs,
        ) as fit_sp:
            for epoch in range(self.num_epochs):
                # the permutation stream must be identical across a resume,
                # so draw it per epoch regardless of where we restart
                order = list(range(num_blocks))
                if rng is not None:
                    rng.shuffle(order)
                if epoch < start_epoch:
                    continue
                for step, blk in enumerate(order):
                    if epoch == start_epoch and step < start_step:
                        continue
                    start = blk * bs
                    size = min(bs, n - start)
                    # ONE program per block step (residual + Cholesky solve
                    # + in-place model update; with the generation too where
                    # the kernel is not cached); the span keeps the
                    # per-block wall (parity: the reference's per-block
                    # timing logs, KernelRidgeRegression.scala:216-224)
                    with span(
                        "krr.block_step", block=blk, rows=n, cols=size,
                    ) as sp:
                        if self.cache_kernel:
                            Kb = kernel_cache.get(start)
                            if Kb is None:
                                with _kernel_span(n, size) as kp:
                                    Kb = _kernel_block_slice(
                                        X, start, jnp.float32(self.gamma),
                                        size,
                                    )
                                    kp.sync_on(Kb)
                                kernel_cache[start] = Kb
                            W = _krr_block_step_cached(
                                Kb, Y, W, start, jnp.float32(self.lam),
                                bs=size,
                            )
                        else:
                            with _kernel_span(n, size):
                                W = _krr_block_step(
                                    X, Y, W, start, jnp.float32(self.gamma),
                                    jnp.float32(self.lam), bs=size,
                                )
                        sp.sync_on(W)
                    steps_done += 1
                    if ckpt and steps_done % self.checkpoint_interval == 0:
                        np.savez(
                            ckpt,
                            W=np.asarray(jax.block_until_ready(W)),
                            epoch=epoch,
                            step=step + 1,
                        )
            # what the cache held when the solve ended
            fit_sp.attrs["cached_bytes"] = sum(
                int(K.nbytes) for K in kernel_cache.values()
            )
            fit_sp.sync_on(W)
        if ckpt and os.path.exists(ckpt):
            os.remove(ckpt)  # complete fit: drop the restart state
        return KernelBlockLinearMapper(X, W, self.gamma, bs)


class ExactKernelRidge(LabelEstimator, CostModel):
    """Direct kernel ridge: materialize K block-by-block and solve
    (K + λI) W = Y with one Cholesky — exact, one shot, O(n²) memory and
    an n³/3 factorization. The cheap end of the KRR family when n is
    small enough that the full kernel fits and the cubic solve beats
    ``num_epochs`` Gauss-Seidel sweeps; prices out fast as n grows. Same
    fitted-model contract as the Gauss-Seidel solver
    (:class:`KernelBlockLinearMapper`), so the two are interchangeable
    physical implementations behind :class:`KernelRidgeEstimator`."""

    def __init__(self, gamma: float, lam: float, block_size: int):
        self.gamma = gamma
        self.lam = lam
        self.block_size = block_size

    def cost(self, n, d, k, sparsity, num_machines,
             cpu_weight, mem_weight, network_weight):
        return combine_cost(
            {
                # generation + one Cholesky + the triangular solves
                "flops": (
                    float(n) * n * d + float(n) ** 3 / 3.0
                    + float(n) * n * k
                ) / num_machines,
                "bytes": float(n) * n / num_machines + float(n) * d,
                "network": float(n) * k,
                "passes": 1,
            },
            cpu_weight, mem_weight, network_weight,
        )

    def fit(self, data: Dataset, labels: Dataset) -> KernelBlockLinearMapper:
        X = jnp.asarray(Dataset.of(data).to_array(), dtype=jnp.float32)
        Y = jnp.asarray(Dataset.of(labels).to_array(), dtype=jnp.float32)
        n = X.shape[0]
        bs = self.block_size
        with span("krr.exact_solve") as sp:
            cols = [
                _kernel_block_slice(
                    X, start, jnp.float32(self.gamma), min(bs, n - start)
                )
                for start in range(0, n, bs)
            ]
            K = jnp.concatenate(cols, axis=1)  # (n, n)
            W = solve_spd(K, Y, jnp.float32(self.lam))
            sp.sync_on(W)
        return KernelBlockLinearMapper(X, W, self.gamma, bs)


class KernelRidgeEstimator(
    LabelEstimator, AutoSolverFrontDoor, CostModel, Optimizable
):
    """Cost-model auto-selecting front door for kernel ridge regression:
    the exact full-kernel solve vs the Gauss-Seidel block solver — both
    produce a :class:`KernelBlockLinearMapper` for the same (γ, λ), so
    selection is purely a cost question (the cubic factorization wins at
    small n, the epoch-bounded block sweeps win once n³ dominates).
    Runs through :class:`keystone_tpu.cost.SolverChooser`: with a profile
    store configured the family earns learned ``op/`` seconds-per-unit
    profiles from traced fits, and borderline shapes are decided by
    predicted wall-clock instead of analytic units."""

    def __init__(self, gamma: float, lam: float, block_size: int,
                 num_epochs: int, block_permuter: Optional[int] = None,
                 cache_kernel: bool = True,
                 num_machines: Optional[int] = None,
                 cpu_weight: Optional[float] = None,
                 mem_weight: Optional[float] = None,
                 network_weight: Optional[float] = None):
        self.gamma = gamma
        self.lam = lam
        self.block_size = block_size
        self.num_epochs = num_epochs
        self.num_machines = num_machines
        self._init_chooser_weights(cpu_weight, mem_weight, network_weight)
        self.options: Sequence = [
            KernelRidgeRegression(
                gamma, lam, block_size, num_epochs,
                block_permuter=block_permuter, cache_kernel=cache_kernel,
            ),
            ExactKernelRidge(gamma, lam, block_size),
        ]
        self.default = self.options[0]

    def fit(self, data: Dataset, labels: Dataset) -> KernelBlockLinearMapper:
        data = Dataset.of(data)
        labels = Dataset.of(labels)
        solver = self.sample_optimize(
            [data.take(24), labels.take(24)], len(data)
        )
        return solver.fit(data, labels)


class GaussianKernelGenerator(LabelEstimator):
    """Convenience estimator shape used by RandomPatchCifarKernel: fit KRR on
    Gaussian-kernel features (parity: GaussianKernelGenerator +
    KernelRidgeRegression composition, KernelGenerator.scala:36-84)."""

    def __init__(self, gamma: float):
        self.gamma = gamma

    def kernel_matrix(self, data: Dataset, cache: bool = True
                      ) -> BlockKernelMatrix:
        return BlockKernelMatrix(
            Dataset.of(data).to_array(), self.gamma, cache
        )
