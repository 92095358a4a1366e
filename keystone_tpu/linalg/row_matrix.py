"""Row-sharded tall-skinny matrices — the mesh-native ``RowPartitionedMatrix``.

The reference's distributed linear algebra lives in the external mlmatrix
package (build.sbt:45): ``RowPartitionedMatrix`` (an RDD of row blocks),
``NormalEquations``, ``TSQR``. Here a "distributed matrix" is simply a
``jax.Array`` whose leading dim is sharded over the mesh's data axis; all the
block-wise map + treeReduce choreography collapses into jit-compiled programs
where XLA inserts the ICI collectives.

Everything takes/returns plain arrays — there is deliberately no wrapper class
to thread through jit. ``RowShardedMatrix`` below is a thin convenience holder
for host-side code that wants the reference's vocabulary.
"""

from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp

from ..parallel.mesh import default_mesh, shard_batch

#: Matmul precision for every solver GEMM. TPU MXUs multiply in bf16;
#: single-pass bf16 ("default") loses ~2e-3 relative accuracy vs float64 at
#: reference solver shapes — enough to fail the 1e-3 float64-agreement bar
#: (tests/linalg/test_solver_accuracy.py). "high" (bf16_3x decomposition)
#: measures 1.3e-5 relative at d=8192 while sustaining ~35 Tf/s of the
#: 98.5 Tf/s f32 peak on v5e. The reference solves in float64 Breeze;
#: f32+high is the TPU-native accuracy/throughput point.
SOLVER_PRECISION = "high"


def _mm(a: jax.Array, b: jax.Array) -> jax.Array:
    return jnp.matmul(a, b, precision=SOLVER_PRECISION)


@partial(jax.jit, static_argnames=("dtype",))
def gram(A: jax.Array, dtype=None) -> jax.Array:
    """AᵀA. With A row-sharded, XLA lowers this to per-shard GEMM + psum over
    ICI — the reference's map+treeReduce Gram pattern
    (BlockWeightedLeastSquares.scala:212-225) with the tree left to XLA.
    Runs at SOLVER_PRECISION: single-pass bf16 Gram fails the
    float64-agreement bar."""
    if dtype is not None:
        A = A.astype(dtype)
    return _mm(A.T, A)


@jax.jit
def cross(A: jax.Array, B: jax.Array) -> jax.Array:
    """AᵀB with both row-sharded: per-shard GEMM + psum (solver precision)."""
    return _mm(A.T, B)


def factor_spd(G: jax.Array, reg: float = 0.0) -> jax.Array:
    """The lower Cholesky factor of (G + reg·I), for :func:`solve_factored`:
    a caller that solves against one G many times factors it once."""
    G = G + reg * jnp.eye(G.shape[0], dtype=G.dtype)
    return jax.scipy.linalg.cho_factor(G, lower=True)[0]


def solve_factored(L: jax.Array, rhs: jax.Array) -> jax.Array:
    """Solve (L Lᵀ) X = rhs for a lower factor from :func:`factor_spd`."""
    return jax.scipy.linalg.cho_solve((L, True), rhs)


def solve_spd(G: jax.Array, rhs: jax.Array, reg: float = 0.0) -> jax.Array:
    """Solve (G + reg·I) X = rhs for symmetric positive-definite G via
    Cholesky (the reference's driver-side ``(G+λI) \\ rhs``)."""
    return solve_factored(factor_spd(G, reg), rhs)


class RowShardedMatrix:
    """Host-side convenience wrapper: a tall-skinny matrix sharded by rows.

    Parity: mlmatrix ``RowPartitionedMatrix.fromArray`` (used at
    LinearMapper.scala:121). ``data`` is an (n, d) jax.Array living sharded
    in HBM.
    """

    def __init__(self, data, mesh=None):
        self.mesh = mesh or default_mesh()
        self.data = shard_batch(jnp.asarray(data), self.mesh)

    @property
    def shape(self):
        return self.data.shape

    def gram(self, dtype=None) -> jax.Array:
        return gram(self.data, dtype=dtype)

    def t_times(self, other: "RowShardedMatrix | jax.Array") -> jax.Array:
        o = other.data if isinstance(other, RowShardedMatrix) else other
        return cross(self.data, o)

    def qr_r(self) -> jax.Array:
        from .tsqr import tsqr_r

        return tsqr_r(self.data, mesh=self.mesh)
