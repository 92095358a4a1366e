"""Mesh-native distributed linear algebra (replaces the external mlmatrix
package: RowPartitionedMatrix, NormalEquations, BlockCoordinateDescent, TSQR —
build.sbt:45)."""

from .row_matrix import RowShardedMatrix, cross, gram, solve_spd
from .normal_equations import (
    gram_accumulate,
    solve_least_squares,
    solve_least_squares_streaming,
    solve_least_squares_with_intercept,
)
from .bcd import (
    solve_blockwise_l2,
    solve_blockwise_l2_columns,
    solve_blockwise_l2_scan,
    solve_blockwise_l2_streaming,
    stream_column_means,
)
from .tsqr import tsqr_r, tsqr_r_streaming
from .accumulators import (
    GramSolverState,
    MomentsState,
    NotAbsorbable,
    TsqrRState,
)
from .weighted import WeightedSolverState, solve_weighted_streaming

__all__ = [
    "GramSolverState",
    "MomentsState",
    "NotAbsorbable",
    "TsqrRState",
    "WeightedSolverState",
    "RowShardedMatrix",
    "gram",
    "cross",
    "solve_spd",
    "solve_least_squares",
    "solve_least_squares_streaming",
    "gram_accumulate",
    "solve_least_squares_with_intercept",
    "solve_blockwise_l2",
    "solve_blockwise_l2_columns",
    "solve_blockwise_l2_scan",
    "solve_blockwise_l2_streaming",
    "solve_weighted_streaming",
    "stream_column_means",
    "tsqr_r",
    "tsqr_r_streaming",
]
