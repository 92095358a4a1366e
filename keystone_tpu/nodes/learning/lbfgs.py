"""Distributed L-BFGS least squares.

Parity: nodes/learning/LBFGS.scala:14-281 (runLBFGS/CostFun/DenseLBFGSwithL2/
SparseLBFGSwithL2) + Gradient.scala:10-119. The reference computes
per-partition batched gradients, treeReduces them to the driver and drives
Breeze's LBFGS; here the ENTIRE optimization — gradients (per-shard GEMM +
psum over ICI for row-sharded data), two-loop recursion, line search, and
convergence test — is one compiled ``lax.while_loop`` program (see
:func:`minimize_lbfgs`).

Loss (CostFun, LBFGS.scala:69-123):
  f(W) = Σ ½‖AW − B‖² / n + ½·λ‖W‖²,  ∇f = Aᵀ(AW−B)/n + λW.
"""

from __future__ import annotations

from typing import Callable, List, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from ...data.dataset import Dataset
from ...parallel.mesh import shard_batch
from ...workflow.transformer import LabelEstimator
from .cost import CostModel
from .linear import LinearMapper


@jax.jit
def _ls_value_and_grad(W, A, B, lam):
    n = A.shape[0]
    axb = A @ W - B
    loss = 0.5 * jnp.sum(axb * axb) / n + 0.5 * lam * jnp.sum(W * W)
    grad = A.T @ axb / n + lam * W
    return loss, grad


def minimize_lbfgs(
    value_and_grad: Callable,
    w0,
    max_iterations: int = 100,
    num_corrections: int = 10,
    convergence_tol: float = 1e-4,
    vag_args: tuple = (),
):
    """L-BFGS with two-loop recursion + Armijo backtracking, as ONE
    compiled ``lax.while_loop`` program. ``value_and_grad(W) -> (f, g)``
    must be jax-traceable; it is inlined into the program, so every
    iteration — recursion, line search, convergence test — runs on
    device with ZERO host round trips. The first cut drove the loop from
    the host (the reference's shape: Breeze LBFGS on the driver,
    LBFGS.scala:69-123): its 3-4 blocking scalar fetches per iteration
    each drain the device queue, a per-iteration floor under every solve
    regardless of problem size.

    The history lives in fixed (m, *W.shape) buffers rolled so the
    newest correction sits at index m−1; ``count`` masks unfilled (or
    memory-reset) entries. Semantics match the host loop: Armijo with
    c1=1e-4 and 20 halvings, non-descent directions reset the memory,
    line-search failure terminates, and convergence compares consecutive
    f values against ``convergence_tol``.
    """
    W0 = jnp.asarray(w0, dtype=jnp.float32)
    m = int(num_corrections)
    tol = jnp.float32(convergence_tol)

    # The data operands (vag_args) enter as JIT ARGUMENTS, never as
    # closures: a closed-over device array becomes an HLO constant, and
    # baking a GB-scale Gram/design matrix into the program means
    # fetching it to the host and serializing it into the module on
    # every trace — a long stall before the first iteration.
    def _run_body(st, vag):
        it, done, W, f, g, S, Y, count, prev_f = st

        # two-loop recursion over the masked circular history
        def bwd(i, qa):
            q, alphas = qa
            idx = m - 1 - i  # newest first
            valid = i < count
            s, y = S[idx], Y[idx]
            denom = jnp.vdot(y, s)
            rho = jnp.where(valid & (denom != 0), 1.0 / denom, 0.0)
            a = rho * jnp.vdot(s, q)
            q = q - a * y
            return q, alphas.at[idx].set(a)

        q, alphas = jax.lax.fori_loop(
            0, m, bwd, (g, jnp.zeros((m,), dtype=jnp.float32))
        )
        sy = jnp.vdot(S[m - 1], Y[m - 1])
        yy = jnp.vdot(Y[m - 1], Y[m - 1])
        gamma = jnp.where((count > 0) & (yy != 0), sy / yy, 1.0)
        q = gamma * q

        def fwd(i, q):
            valid = i >= (m - count)  # oldest first
            s, y = S[i], Y[i]
            denom = jnp.vdot(y, s)
            rho = jnp.where(valid & (denom != 0), 1.0 / denom, 0.0)
            b = rho * jnp.vdot(y, q)
            return q + (alphas[i] - b) * s

        direction = -jax.lax.fori_loop(0, m, fwd, q)
        gd = jnp.vdot(g, direction)
        # non-descent → steepest descent + memory reset
        reset = gd >= 0
        direction = jnp.where(reset, -g, direction)
        gd = jnp.where(reset, -jnp.vdot(g, g), gd)
        count = jnp.where(reset, 0, count)

        # Armijo backtracking, up to 20 halvings
        def ls_cond(ls):
            tries, _, ok, *_ = ls
            return (tries < 20) & ~ok

        def ls_body(ls):
            tries, step, ok, Wn, fn, gn = ls
            cand = W + step * direction
            cf, cg = vag(cand)
            good = cf <= f + 1e-4 * step * gd
            Wn = jnp.where(good, cand, Wn)
            fn = jnp.where(good, cf, fn)
            gn = jnp.where(good, cg, gn)
            return tries + 1, step * 0.5, ok | good, Wn, fn, gn

        _, _, ok, Wn, fn, gn = jax.lax.while_loop(
            ls_cond, ls_body,
            (jnp.int32(0), jnp.float32(1.0), jnp.bool_(False), W, f, g),
        )

        S2 = jnp.roll(S, -1, axis=0).at[m - 1].set(Wn - W)
        Y2 = jnp.roll(Y, -1, axis=0).at[m - 1].set(gn - g)
        count2 = jnp.minimum(count + 1, m)
        converged = jnp.abs(prev_f - fn) < tol * jnp.maximum(
            jnp.abs(fn), 1.0
        )
        done2 = ~ok | converged
        # line-search failure keeps the pre-step state
        W3 = jnp.where(ok, Wn, W)
        f3 = jnp.where(ok, fn, f)
        g3 = jnp.where(ok, gn, g)
        S3 = jnp.where(ok, S2, S)
        Y3 = jnp.where(ok, Y2, Y)
        c3 = jnp.where(ok, count2, count)
        return (it + 1, done2, W3, f3, g3, S3, Y3, c3, f3)

    @jax.jit
    def run(W, vag_args):
        def vag(w):
            f, g = value_and_grad(w, *vag_args)
            return jnp.asarray(f, dtype=jnp.float32), g

        def cond(st):
            it, done = st[0], st[1]
            return (it < max_iterations) & ~done

        f0, g0 = vag(W)
        S = jnp.zeros((m,) + W.shape, dtype=jnp.float32)
        Y = jnp.zeros_like(S)
        init = (
            jnp.int32(0), jnp.bool_(False), W, f0, g0, S, Y,
            jnp.int32(0), jnp.float32(jnp.inf),
        )
        return jax.lax.while_loop(
            cond, lambda st: _run_body(st, vag), init
        )[2]

    return run(W0, tuple(vag_args))


class DenseLBFGSwithL2(LabelEstimator, CostModel):
    """(parity: DenseLBFGSwithL2, LBFGS.scala:135-186)."""

    def __init__(self, convergence_tol: float = 1e-4,
                 num_iterations: int = 100, reg_param: float = 0.0,
                 num_corrections: int = 10):
        self.convergence_tol = convergence_tol
        self.num_iterations = num_iterations
        self.reg_param = reg_param
        self.num_corrections = num_corrections

    @property
    def weight(self) -> int:
        return self.num_iterations + 1

    def fit(self, data: Dataset, labels: Dataset) -> LinearMapper:
        A = shard_batch(
            jnp.asarray(Dataset.of(data).to_array(), dtype=jnp.float32)
        )
        B = shard_batch(
            jnp.asarray(Dataset.of(labels).to_array(), dtype=jnp.float32)
        )
        lam = jnp.float32(self.reg_param)
        W0 = jnp.zeros((A.shape[1], B.shape[1]), dtype=jnp.float32)
        W = minimize_lbfgs(
            _ls_value_and_grad,
            W0,
            max_iterations=self.num_iterations,
            num_corrections=self.num_corrections,
            convergence_tol=self.convergence_tol,
            vag_args=(A, B, lam),
        )
        return LinearMapper(W)

    def cost(self, n, d, k, sparsity, num_machines,
             cpu_weight, mem_weight, network_weight):
        import math

        flops = n * d * k / num_machines
        bytes_scanned = n * d / num_machines
        network = 2.0 * d * k * math.log2(max(num_machines, 2))
        return self.num_iterations * (
            max(cpu_weight * flops, mem_weight * bytes_scanned)
            + network_weight * network
        )


def _streamed_gram(X, B):
    """G = AᵀA (d, d) and c = AᵀB, accumulated over dense row blocks:
    each block is one SMALL scatter (bounded padding) + one MXU GEMM,
    and is dropped before the next, so peak memory is G + one block."""
    d = X.shape[1]
    n, m = X.indices.shape
    # chunk rows by BOTH the scatter-update count (padding bound) and the
    # densified block's bytes (rows·d·4 — at d=16384 an update-count-only
    # bound allowed ~1.6 GB blocks, breaking the "G + one block" claim)
    block_budget_bytes = 256 << 20
    row_chunk = max(
        1,
        min((1 << 21) // max(m, 1), block_budget_bytes // max(4 * d, 1)),
    )
    G = jnp.zeros((d, d), dtype=jnp.float32)
    c = jnp.zeros((d,) + B.shape[1:], dtype=jnp.float32)
    for i in range(0, n, row_chunk):
        Ab = X.row_slice(i, min(i + row_chunk, n)).to_dense()
        G = G + jnp.matmul(Ab.T, Ab, precision="high")
        c = c + jnp.matmul(Ab.T, B[i : i + row_chunk], precision="high")
    return G, c


class SparseLBFGSwithL2(DenseLBFGSwithL2):
    """Sparse-input variant (parity: SparseLBFGSwithL2, LBFGS.scala:208).

    XLA has no dynamic sparsity, so sparse rows arrive as a padded-COO
    ``SparseRows`` batch. Two execution strategies, chosen by memory:

    * **precomputed-Gram quadratic** (default whenever the d×d Gram fits
      ``gram_budget_bytes``): the least-squares objective is a fixed
      quadratic, f(W) = (½WᵀGW − cᵀW + ½‖B‖²)/n + ½λ‖W‖² with G = AᵀA,
      so G and c = AᵀB are accumulated ONCE by streaming dense row
      blocks through the MXU (each block scattered small — XLA's TPU
      scatter pads its operands ~66×, so one huge scatter OOMs — then
      immediately contracted and discarded), after which every L-BFGS
      iteration is one d×d GEMV touching no data. TPU-first twice over:
      the MXU streams dense blocks 50-100× faster than the fine-grained
      gather path at text densities (~20M random elements/s measured on
      a v5e), and the iteration cost becomes data-size independent.
    * **gather/scatter** (the fallback, SURVEY §7's original decision):
      gather-matmul (A·W) + scatter-add (Aᵀ·residual), used when d² is
      too large for the Gram (``gram_budget_bytes=0`` forces it).

    Both run the same :func:`minimize_lbfgs` on the same objective —
    the strategies produce the same iterates up to f32 rounding
    (asserted by the strategy-agreement test). scipy.sparse inputs are
    converted to SparseRows first. Returns a SparseLinearMapper so the
    fitted model applies sparsely either way.
    """

    sparse_overhead = 10.0

    def __init__(self, *args, gram_budget_bytes: float = 2e9, **kwargs):
        super().__init__(*args, **kwargs)
        self.gram_budget_bytes = gram_budget_bytes

    def fit(self, data: Dataset, labels: Dataset):
        from ...data.sparse import SparseRows
        from .linear import SparseLinearMapper

        data = Dataset.of(data)
        X = None
        if isinstance(data.payload, SparseRows):
            X = data.payload
        elif not data.is_batched:
            import scipy.sparse as sp

            items = data.collect()
            if items and sp.issparse(items[0]):
                X = SparseRows.from_scipy(sp.vstack(items))
            else:
                return super().fit(Dataset.of(np.asarray(items)), labels)
        if X is None:
            return super().fit(data, labels)

        B = jnp.asarray(Dataset.of(labels).to_array(), dtype=jnp.float32)
        lam = jnp.float32(self.reg_param)
        n = B.shape[0]
        d = X.shape[1]

        # k=1 (binary) runs on 1-D vectors: XLA pads a minor dim of 1 to
        # 128, so every (n, 1) residual/carry in the compiled loop would
        # cost 128x its size — measured 10.8 GB of pure padding (an HBM
        # OOM) at the Amazon shape. Squeeze in, unsqueeze out.
        squeeze = B.ndim == 2 and B.shape[1] == 1
        if squeeze:
            B = B[:, 0]

        if 4.0 * d * d <= self.gram_budget_bytes:
            G, c = _streamed_gram(X, B)
            e = 0.5 * jnp.sum(B * B)

            def vag(W, G, c, e, lam):
                GW = jnp.matmul(G, W, precision="high")
                loss = (0.5 * jnp.vdot(W, GW) - jnp.vdot(c, W) + e) / n \
                    + 0.5 * lam * jnp.sum(W * W)
                grad = (GW - c) / n + lam * W
                return loss, grad

            vag_args = (G, c, e, lam)
        else:
            from ...data.sparse import SparseRows as _SR

            def vag(W, idx, vals, B, lam):
                Xa = _SR(idx, vals, d)
                W2 = W[:, None] if squeeze else W
                axb = Xa.matmul(W2) - (B[:, None] if squeeze else B)
                loss = 0.5 * jnp.sum(axb * axb) / n \
                    + 0.5 * lam * jnp.sum(W * W)
                grad = Xa.rmatmul(axb) / n + lam * W2
                return loss, (grad[:, 0] if squeeze else grad)

            vag_args = (X.indices, X.values, B, lam)

        W0 = jnp.zeros((d,) if squeeze else (d, B.shape[1]),
                       dtype=jnp.float32)
        W = minimize_lbfgs(
            vag,
            W0,
            max_iterations=self.num_iterations,
            num_corrections=self.num_corrections,
            convergence_tol=self.convergence_tol,
            vag_args=vag_args,
        )
        if squeeze:
            W = W[:, None]
        return SparseLinearMapper(W)

    def cost(self, n, d, k, sparsity, num_machines,
             cpu_weight, mem_weight, network_weight):
        import math

        flops = n * sparsity * d * k / num_machines
        bytes_scanned = n * d * sparsity / num_machines
        network = 2.0 * d * k * math.log2(max(num_machines, 2))
        return self.num_iterations * (
            self.sparse_overhead
            * max(cpu_weight * flops, mem_weight * bytes_scanned)
            + network_weight * network
        )


class LocalLeastSquaresEstimator(LabelEstimator):
    """Dual-form OLS for d ≫ n: solve in the n×n Gram space
    (parity: LocalLeastSquaresEstimator.scala:16-61)."""

    def __init__(self, lam: float):
        self.lam = lam

    def fit(self, data: Dataset, labels: Dataset) -> LinearMapper:
        A = jnp.asarray(Dataset.of(data).to_array(), dtype=jnp.float32)
        B = jnp.asarray(Dataset.of(labels).to_array(), dtype=jnp.float32)
        a_mean = jnp.mean(A, axis=0)
        b_mean = jnp.mean(B, axis=0)
        Az = A - a_mean
        Bz = B - b_mean
        AAt = Az @ Az.T
        n = AAt.shape[0]
        inner = jnp.linalg.solve(
            AAt + self.lam * jnp.eye(n, dtype=A.dtype), Bz
        )
        W = Az.T @ inner
        return LinearMapper(W, b=b_mean, feature_mean=a_mean)
