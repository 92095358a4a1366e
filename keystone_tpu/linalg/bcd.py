"""Block-coordinate-descent least squares — the workhorse solver substrate.

Parity: mlmatrix ``BlockCoordinateDescent.solveLeastSquaresWithL2`` /
``solveOnePassL2`` as driven by ``BlockLeastSquaresEstimator``
(nodes/learning/BlockLinearMapper.scala:212-243). The reference's shape: a
driver loop over feature blocks; per block a cluster-wide Gram + cross-product
(map + treeReduce over the network) and a driver-local ``(G+λI) \\ rhs`` solve,
then a broadcast + residual update.

Mesh-native shape: the same host loop over blocks (keeps HBM bounded and
shapes static), but each block step is ONE jit-compiled program — per-shard
GEMMs with XLA-inserted psum over ICI for the Gram/cross terms, Cholesky solve
on-device, and a donated, row-sharded prediction buffer updated in place. No
broadcast step exists: the block model comes out replicated.

Objective: min_W  Σ‖Σ_j A_j W_j − y‖² + λ Σ_j ‖W_j‖²  (one W_j per block).
"""

from __future__ import annotations

from functools import partial
from typing import List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp

# SOLVER_PRECISION and _mm live in row_matrix (the bottom of the linalg
# stack); re-exported here because bcd is where the precision decision is
# most visible to solver readers.
from ..data.pipeline_scan import scan_pipeline
from ..obs.tracer import span
from .row_matrix import (  # noqa: F401
    SOLVER_PRECISION,
    _mm,
    factor_spd,
    solve_factored,
    solve_spd,
)


def _block_update_impl(
    Aj: jax.Array,
    mj: jax.Array,
    Wj_old: jax.Array,
    pred: jax.Array,
    y: jax.Array,
    reg: float,
) -> Tuple[jax.Array, jax.Array]:
    """One BCD block step on a raw (uncentered) block. Returns
    (Wj_new, new_pred).

    Centering (A_j − m_j) happens inside the program so XLA fuses the
    subtract into the GEMM operand reads — the centered matrix is never
    materialized in HBM.

    residual for block j:  r_j = y − pred + Ã_j W_j_old
    W_j ← (Ã_jᵀÃ_j + λI)⁻¹ Ã_jᵀ r_j ; pred ← pred + Ã_j (W_j − W_j_old)
    """
    Ajc = Aj - mj
    # the scopes of _bcd_scan_impl: metadata, not lowering
    with jax.named_scope("ks.solver.residual"):
        r = y - pred + _mm(Ajc, Wj_old)
    with jax.named_scope("ks.solver.gram"):
        G = _mm(Ajc.T, Ajc)    # psum over data axis
    with jax.named_scope("ks.solver.cross"):
        c = _mm(Ajc.T, r)      # psum over data axis
    with jax.named_scope("ks.solver.factor_solve"):
        Wj = solve_spd(G, c, reg)
    with jax.named_scope("ks.solver.residual"):
        pred = pred + _mm(Ajc, Wj - Wj_old)
    return Wj, pred


def _block_update_at_impl(A, means, start, Wj_old, pred, y, reg, *, width):
    """:func:`_block_update_impl` on the ``width`` columns of ``A`` from
    ``start``. The block is cut inside the program (as ``_bcd_scan_impl``
    cuts its blocks), so no column slice of a design matrix sits in HBM
    beside it, and one program serves every block of one width; a block
    that is an array of its own is its columns from 0."""
    Aj = jax.lax.dynamic_slice_in_dim(A, start, width, axis=1)
    mj = jax.lax.dynamic_slice_in_dim(means, start, width)
    return _block_update_impl(Aj, mj, Wj_old, pred, y, reg)


# Donate the prediction buffer on accelerators (in-place HBM update per
# block). On the CPU backend donation intermittently aborts the process
# (observed under the 8-device virtual mesh), so plain jit there.
_block_update_donating = jax.jit(
    _block_update_at_impl, static_argnames=("width",), donate_argnums=(4,)
)
_block_update_plain = jax.jit(
    _block_update_at_impl, static_argnames=("width",)
)


def _block_update(A, means, start, Wj_old, pred, y, reg, width):
    step = (
        _block_update_plain if jax.default_backend() == "cpu"
        else _block_update_donating
    )
    return step(A, means, start, Wj_old, pred, y, reg, width=width)


@jax.jit
def _block_means(blocks, y):
    """Column means of every block + labels in ONE program (one dispatch)."""
    return [jnp.mean(b, axis=0) for b in blocks], jnp.mean(y, axis=0)


def cost_signature(
    n: int, d: int, k: int, block_size: int, num_iter: int, machines: int = 1
) -> dict:
    """Work terms for pricing a BCD solve: ``num_iter`` sweeps, each
    scanning the data once per block and touching only a (block, k) slab
    of model state (parity: BlockLinearMapper.scala:268-282; consumed by
    ``keystone_tpu.cost``)."""
    import math

    return {
        # every term carries num_iter so combine_cost's max() distributes
        # exactly like the reference's num_iter * (max(...) + net) form
        "flops": num_iter * n * d * (block_size + k) / machines,
        "bytes": num_iter * (n * d / machines + d * k),
        "network": (
            2.0 * num_iter * d * (block_size + k)
            * math.log2(max(machines, 2))
        ),
        "passes": 3 * num_iter + 1,
    }


#: a block of the descent: (matrix, its column means, first column, width)
_Block = Tuple[jax.Array, jax.Array, int, int]


def _descend(
    blocks: Sequence[_Block], y: jax.Array, reg, num_iter: int, dtype,
    init: Optional[Sequence[jax.Array]],
) -> List[jax.Array]:
    """The host loop of the per-block-dispatch solvers: ``num_iter`` sweeps
    over ``blocks``, one :func:`_block_update` program a block."""
    k = y.shape[1]
    pred = jnp.zeros_like(y)
    if init is None:
        Ws = [jnp.zeros((width, k), dtype=dtype) for *_, width in blocks]
    else:
        if len(init) != len(blocks):
            raise ValueError(
                f"init has {len(init)} blocks, expected {len(blocks)}"
            )
        Ws = [jnp.asarray(w, dtype=dtype) for w in init]
        for (A, means, start, width), Wj in zip(blocks, Ws):
            Aj = jax.lax.dynamic_slice_in_dim(A, start, width, axis=1)
            mj = jax.lax.dynamic_slice_in_dim(means, start, width)
            pred = pred + _mm(Aj - mj, Wj)
    # Per-block spans (parity: KernelRidgeRegression.scala:216-224's
    # per-block phase table). Gram/solve/update run as ONE compiled program
    # per block shape, so one span covers the device step.
    for _ in range(num_iter):
        for j, (A, means, start, width) in enumerate(blocks):
            with span("bcd.block_update") as sp:
                Ws[j], pred = _block_update(
                    A, means, start, Ws[j], pred, y, reg, width
                )
                sp.sync_on(pred)
    return Ws


def solve_blockwise_l2(
    blocks: Sequence[jax.Array],
    y: jax.Array,
    reg: float,
    num_iter: int = 1,
    dtype=jnp.float32,
    means: Optional[Sequence[jax.Array]] = None,
    init: Optional[Sequence[jax.Array]] = None,
) -> List[jax.Array]:
    """L2-regularised least squares over feature blocks by BCD.

    blocks: list of (n, b_j) row-sharded arrays (the VectorSplitter output);
    y: (n, k) row-sharded. ``num_iter=1`` is the reference's one-pass variant
    (``solveOnePassL2``), used by MNIST/CIFAR/VOC. ``means`` (per-block
    column means) are subtracted inside the block program; pass them to get
    centered solving without materializing centered copies. ``init``
    (per-block starting weights) warm-starts the descent — a λ-sweep
    member starting from its nearest-λ neighbor's model converges in
    fewer sweeps than from zero; the prediction buffer is initialized
    consistently (pred = Σ Ãⱼ Wⱼ⁰). Returns per-block (b_j, k) weights.
    """
    y = jnp.asarray(y, dtype=dtype)
    blocks = [jnp.asarray(b, dtype=dtype) for b in blocks]
    if means is None:
        means = [jnp.zeros((b.shape[1],), dtype=dtype) for b in blocks]
    return _descend(
        [(b, m, 0, int(b.shape[1])) for b, m in zip(blocks, means)],
        y, reg, num_iter, dtype, init,
    )


def solve_blockwise_l2_columns(
    A: jax.Array,
    y: jax.Array,
    reg: float,
    block_size: int,
    num_iter: int = 1,
    dtype=jnp.float32,
    means: Optional[jax.Array] = None,
    init: Optional[Sequence[jax.Array]] = None,
) -> List[jax.Array]:
    """:func:`solve_blockwise_l2` over the column blocks of ONE (n, d)
    matrix whose last block may be narrower (d = 80,000 in blocks of 4,096
    leaves 2,176): the same host loop and the same block step, one dispatch
    a block, each block read out of ``A`` by the program that uses it.
    ``means`` is the (d,) column-mean vector. A d that ``block_size``
    divides belongs to :func:`solve_blockwise_l2_scan`."""
    A = jnp.asarray(A, dtype=dtype)
    d = A.shape[1]
    means = (
        jnp.zeros((d,), dtype=dtype) if means is None
        else jnp.asarray(means, dtype=dtype).reshape(d)
    )
    return _descend(
        [
            (A, means, start, min(block_size, d - start))
            for start in range(0, d, block_size)
        ],
        jnp.asarray(y, dtype=dtype), reg, num_iter, dtype, init,
    )


def solve_blockwise_l2_scan(
    A: jax.Array,
    y: jax.Array,
    reg: float,
    block_size: int,
    num_iter: int = 1,
    dtype=jnp.float32,
    means: Optional[jax.Array] = None,
    init: Optional[jax.Array] = None,
) -> jax.Array:
    """Fully-compiled BCD when the whole design matrix fits in HBM.

    A: (n, d) with d divisible into uniform ``block_size`` column blocks. The
    block loop becomes a ``lax.scan`` inside one jit program — zero host round
    trips per block, the compiled analogue of the reference's driver loop.
    Blocks are read by ``dynamic_slice`` straight out of A so no second copy
    of the design matrix ever lands in HBM (at reference scale A is the HBM
    budget: 131072×16384 f32 is 8 GB of a v5e's 16). ``means`` is the full
    (d,) column-mean vector; centering is fused into the block GEMMs.
    Returns the full (d, k) weight matrix.

    With ``num_iter > 1`` each block's Gram is formed and Cholesky-factored
    once a fit, ahead of the epochs, and the lower factors stay in HBM as
    one (d / block_size, block_size, block_size) array — d·block_size·4
    bytes, a block_size/n share of A (268 MB at d=16384, bs=4096); every
    epoch then does the residual, the cross product, the two triangular
    solves and the prediction update. ``num_iter == 1`` keeps nothing.
    What it costs and gains on the chip: ``PERF.md`` §5 and §6 (PR 26).
    """
    A = jnp.asarray(A, dtype=dtype)
    y = jnp.asarray(y, dtype=dtype)
    d = A.shape[1]
    if d % block_size != 0:
        raise ValueError(f"d={d} not divisible by block_size={block_size}")
    if means is not None:
        means = jnp.asarray(means, dtype=dtype).reshape(d)
    if init is not None:
        # warm-started sweep members are solve-sized; the model-sharded
        # compile stays specialized to the cold path
        init = jnp.asarray(init, dtype=dtype).reshape(d, y.shape[1])
        return _bcd_scan(
            A, y, jnp.asarray(reg, dtype), means, init,
            block_size=block_size, num_iter=num_iter,
        )
    fn = _bcd_scan_model_sharded(
        A.shape[0], d, block_size, num_iter, means is not None
    )
    if fn is not None:
        return fn(A, y, jnp.asarray(reg, dtype), means)
    return _bcd_scan(
        A, y, jnp.asarray(reg, dtype), means,
        block_size=block_size, num_iter=num_iter,
    )


def _bcd_scan_model_sharded(n, d, block_size, num_iter, has_means):
    """A model-axis-distributed compile of :func:`_bcd_scan`, or None.

    The reference distributes the d dimension across the cluster
    (VectorSplitter + BlockLinearMapper.scala:199-257: each feature block's
    rows live cluster-wide and the driver walks blocks). Mesh-native form:
    A's columns, the column means, and the output W shard over MODEL_AXIS
    (P(data, model) / P(model) / P(model, None) respectively), so a d too
    large for one device's HBM (d=65k: W + per-block Grams) memory-scales
    across the model axis while the Gram/cross psums still ride the data
    axis. The block loop stays sequential — same as the reference, where
    BCD is inherently block-serial; the model axis buys MEMORY, not
    parallel block solves. Requires each model shard to hold whole blocks
    (d/n_model divisible by block_size); returns None (unsharded compile)
    otherwise or on a 1-wide model axis."""
    from ..parallel.mesh import DATA_AXIS, MODEL_AXIS, default_mesh

    mesh = default_mesh()
    n_model = mesh.shape.get(MODEL_AXIS, 1)
    if n_model <= 1 or d % n_model != 0 or (d // n_model) % block_size != 0:
        return None
    if n % mesh.shape.get(DATA_AXIS, 1) != 0:
        return None
    from jax.sharding import NamedSharding, PartitionSpec as P

    key = (mesh, d, block_size, num_iter, has_means)
    entry = _bcd_sharded_cache.get(key)
    if entry is None:
        a_s = NamedSharding(mesh, P(DATA_AXIS, MODEL_AXIS))
        y_s = NamedSharding(mesh, P(DATA_AXIS))
        m_s = NamedSharding(mesh, P(MODEL_AXIS)) if has_means else None
        w_s = NamedSharding(mesh, P(MODEL_AXIS))
        rep = NamedSharding(mesh, P())

        def fn(A, y, reg, means):
            return _bcd_scan_impl(
                A, y, reg, means, block_size=block_size, num_iter=num_iter,
                factor_sharding=w_s,
            )

        jitted = jax.jit(
            fn, in_shardings=(a_s, y_s, rep, m_s), out_shardings=w_s
        )

        def call(A, y, reg, means):
            # inputs may arrive committed to other layouts (the estimator's
            # row-only shard_batch) — re-place to the 2-D sharding first
            A = jax.device_put(A, a_s)
            y = jax.device_put(y, y_s)
            if has_means:
                means = jax.device_put(means, m_s)
            return jitted(A, y, jax.device_put(reg, rep), means)

        call.lower = jitted.lower  # for HLO inspection in tests
        entry = _bcd_sharded_cache[key] = call
    return entry


def _stream_chunk_update_impl(
    A_chunk, pred, G, c, W_cur, delta_prev, means, y_zm, row0,
    jprev, jcur, *, cur_size, prev_size, do_prev, do_gram,
):
    """One chunk of one streaming BCD block step — a single fused program.

    Applies the PREVIOUS block's delayed prediction update (so each block
    step costs one scan, not two), then accumulates this block's Gram and
    cross terms against the freshly-updated prediction. Centering is fused
    into the GEMM operand reads; the centered chunk never lands in HBM.
    """
    rows = A_chunk.shape[0]
    pred_c = jax.lax.dynamic_slice_in_dim(pred, row0, rows, axis=0)
    if do_prev:
        Ap = jax.lax.dynamic_slice_in_dim(A_chunk, jprev, prev_size, axis=1)
        Ap = Ap - jax.lax.dynamic_slice_in_dim(means, jprev, prev_size)
        pred_c = pred_c + _mm(Ap, delta_prev)
        pred = jax.lax.dynamic_update_slice_in_dim(pred, pred_c, row0, axis=0)
    Ac = jax.lax.dynamic_slice_in_dim(A_chunk, jcur, cur_size, axis=1)
    Ac = Ac - jax.lax.dynamic_slice_in_dim(means, jcur, cur_size)
    y_c = jax.lax.dynamic_slice_in_dim(y_zm, row0, rows, axis=0)
    r = y_c - pred_c + _mm(Ac, W_cur)
    if do_gram:
        G = G + _mm(Ac.T, Ac)
    c = c + _mm(Ac.T, r)
    return pred, G, c


_stream_chunk_update_donating = jax.jit(
    _stream_chunk_update_impl,
    static_argnames=("cur_size", "prev_size", "do_prev", "do_gram"),
    donate_argnums=(1, 2, 3),
)
_stream_chunk_update_plain = jax.jit(
    _stream_chunk_update_impl,
    static_argnames=("cur_size", "prev_size", "do_prev", "do_gram"),
)


def _stream_chunk_update(*args, **kwargs):
    if jax.default_backend() == "cpu":
        return _stream_chunk_update_plain(*args, **kwargs)
    return _stream_chunk_update_donating(*args, **kwargs)


def solve_blockwise_l2_streaming(
    chunk_scan,
    y_zm: jax.Array,
    reg: float,
    block_size: int,
    num_iter: int = 1,
    dtype=jnp.float32,
    means: Optional[jax.Array] = None,
    lanes: Optional[int] = None,
) -> List[jax.Array]:
    """BCD least squares over a design matrix that NEVER materializes.

    ``chunk_scan`` is a re-iterable source: each call returns a fresh
    iterator of (rows, d) feature chunks (same chunks every scan — the
    lineage-recompute contract of ``data/chunked.py``). Only the labels,
    the (n, k) prediction buffer, one chunk, and the per-block Grams are
    ever resident: a 2.2M×16384 f32 design matrix (146 GB) streams through
    a 16 GB chip. Parity: the reference's BCD scans its cached RDD once per
    block step the same way (BlockLinearMapper.scala:199-257 driving
    mlmatrix BlockCoordinateDescent) — Spark re-reads partitions from
    executor memory; here the source regenerates/refeaturizes them.

    Scan count: num_iter × nblocks + 0 — each block step fuses the previous
    block's prediction update into its accumulation scan (delayed update),
    and the final block's delta needs no flush (weights are already final).
    Per-block Grams are computed on the first epoch and cached (nblocks ×
    block_size² — e.g. 1 GB at d=16384, bs=4096 — the only superlinear
    state).

    ``y_zm``: (n, k) pre-centered labels, resident. ``means``: (d,) column
    means (compute with :func:`stream_column_means`), or None for no
    centering. Returns the per-block weight list.

    Mesh-distributed (``lanes`` from the data-axis size of the active
    mesh; ``KEYSTONE_SCAN_LANES`` overrides): chunks round-robin across
    per-device staging lanes, each chunk's prediction slab and label slice
    live resident on its lane's chip, and every lane folds its own
    Gram/cross partials per block step — the mesh reduces ONCE per block
    (plus a per-block model broadcast to the lanes), so cross-mesh traffic
    is O(blocks · lanes), independent of the chunk count (the PAPERS.md #3
    gate). ``lanes=1`` runs the original single-accumulator loop,
    bit-identical.
    """
    from ..parallel.lanes import scan_lanes

    if lanes is None:
        lanes = scan_lanes()
    y_zm = jnp.asarray(y_zm, dtype=dtype)
    n, k = y_zm.shape
    starts: List[int] = []
    sizes: List[int] = []
    j = 0
    if means is not None:
        # d is already known — don't burn a chunk of the upstream chain
        d = int(jnp.asarray(means).reshape(-1).shape[0])
    else:
        d = None
        # block layout needs d: peek it from the first chunk of one scan
        it = chunk_scan()
        try:
            for chunk in it:
                d = int(chunk.shape[1])
                break
        finally:
            close = getattr(it, "close", None)
            if close is not None:
                close()  # abandoning a pipelined scan joins its producer
        if d is None:
            raise ValueError("empty chunk source")
    while j < d:
        starts.append(j)
        sizes.append(min(block_size, d - j))
        j += block_size
    nblocks = len(starts)
    if means is None:
        means = jnp.zeros((d,), dtype=dtype)
    means = jnp.asarray(means, dtype=dtype).reshape(d)

    if lanes > 1:
        return _solve_blockwise_l2_streaming_lanes(
            chunk_scan, y_zm, reg, starts, sizes, num_iter, dtype, means,
            lanes,
        )

    Ws = [jnp.zeros((sz, k), dtype=dtype) for sz in sizes]
    grams: List[Optional[jax.Array]] = [None] * nblocks
    pred = jnp.zeros_like(y_zm)
    delta_prev = None
    jprev = 0
    prev_size = sizes[0]

    reg = jnp.asarray(reg, dtype)
    for epoch in range(num_iter):
        for b in range(nblocks):
            do_prev = delta_prev is not None
            do_gram = grams[b] is None
            G = (
                jnp.zeros((sizes[b], sizes[b]), dtype=dtype)
                if do_gram
                else grams[b]
            )
            c = jnp.zeros((sizes[b], k), dtype=dtype)
            row0 = 0
            with span("bcd.stream_block") as sp:
                for chunk in scan_pipeline(chunk_scan(), label="bcd.stream"):
                    chunk = jnp.asarray(chunk, dtype=dtype)
                    pred, G, c = _stream_chunk_update(
                        chunk, pred, G, c, Ws[b],
                        delta_prev
                        if do_prev
                        else jnp.zeros((prev_size, k), dtype=dtype),
                        means, y_zm, row0, jprev, starts[b],
                        cur_size=sizes[b], prev_size=prev_size,
                        do_prev=do_prev, do_gram=do_gram,
                    )
                    row0 += int(chunk.shape[0])
                if row0 != n:
                    raise ValueError(
                        f"chunk source produced {row0} rows, labels have {n}"
                    )
                grams[b] = G
                W_new = solve_spd(G, c, reg)
                delta_prev = W_new - Ws[b]
                Ws[b] = W_new
                jprev = starts[b]
                prev_size = sizes[b]
                sp.sync_on(W_new)
    return Ws


def _lane_chunk_update_impl(
    A_chunk, pred_c, G, c, W_cur, delta_prev, means, y_c,
    jprev, jcur, *, cur_size, prev_size, do_prev, do_gram,
):
    """One chunk of one MESH-SHARDED streaming BCD block step — entirely
    lane-local: applies the previous block's delayed prediction update to
    this chunk's resident prediction slab, then folds the lane's Gram and
    cross partials against it. No cross-device traffic here — the mesh
    reduces once per block, after the scan. ``G`` is a (1, 1) dummy when
    ``do_gram`` is False (the cached reduced Gram lives on the solve
    device and must not be shipped per chunk)."""
    if do_prev:
        Ap = jax.lax.dynamic_slice_in_dim(A_chunk, jprev, prev_size, axis=1)
        Ap = Ap - jax.lax.dynamic_slice_in_dim(means, jprev, prev_size)
        pred_c = pred_c + _mm(Ap, delta_prev)
    Ac = jax.lax.dynamic_slice_in_dim(A_chunk, jcur, cur_size, axis=1)
    Ac = Ac - jax.lax.dynamic_slice_in_dim(means, jcur, cur_size)
    r = y_c - pred_c + _mm(Ac, W_cur)
    if do_gram:
        G = G + _mm(Ac.T, Ac)
    c = c + _mm(Ac.T, r)
    return pred_c, G, c


_lane_chunk_update_donating = jax.jit(
    _lane_chunk_update_impl,
    static_argnames=("cur_size", "prev_size", "do_prev", "do_gram"),
    donate_argnums=(1, 2, 3),
)
_lane_chunk_update_plain = jax.jit(
    _lane_chunk_update_impl,
    static_argnames=("cur_size", "prev_size", "do_prev", "do_gram"),
)


def _lane_chunk_update(*args, **kwargs):
    if jax.default_backend() == "cpu":
        return _lane_chunk_update_plain(*args, **kwargs)
    return _lane_chunk_update_donating(*args, **kwargs)


def _single_device_is(x, device) -> bool:
    from ..parallel.lanes import _single_device

    return _single_device(x) == device


def _solve_blockwise_l2_streaming_lanes(
    chunk_scan, y_zm, reg, starts, sizes, num_iter, dtype, means, lanes
) -> List[jax.Array]:
    """The mesh-distributed body of :func:`solve_blockwise_l2_streaming`.

    Residency: chunk *i*'s prediction slab and label slice are committed to
    lane ``i % lanes``'s device on the FIRST scan and stay there for the
    whole fit, so every per-chunk program is single-device local. Per block
    step: the block model (and previous block's delta) broadcasts to each
    lane once, each lane folds its own Gram/cross partials over its chunks,
    and the partials reduce across the mesh once — the solve then runs on
    the reduced (G, c). Collective count per scan: <= 2·lanes broadcasts +
    <= 2·(lanes−1) reduction hops, independent of how many chunks stream.
    """
    from ..data.pipeline_scan import scan_pipeline
    from ..parallel.lanes import (
        lane_devices,
        record_scan_collectives,
        reduce_lane_partials,
    )
    n, k = y_zm.shape
    nblocks = len(starts)
    devs = lane_devices(lanes)
    means_lane = [jax.device_put(means, d) for d in devs]
    # per-chunk resident state, built on the first scan
    pred_chunks: List[jax.Array] = []
    y_chunks: List[jax.Array] = []
    chunk_rows: List[int] = []
    Ws = [jnp.zeros((sz, k), dtype=dtype) for sz in sizes]
    grams: List[Optional[jax.Array]] = [None] * nblocks
    delta_prev = None
    jprev = 0
    prev_size = sizes[0]
    reg = jnp.asarray(reg, dtype)
    first_scan = True
    for _epoch in range(num_iter):
        for b in range(nblocks):
            do_prev = delta_prev is not None
            do_gram = grams[b] is None
            G_l: List[Optional[jax.Array]] = [None] * lanes
            c_l: List[Optional[jax.Array]] = [None] * lanes
            # per-block model broadcast: the lanes read W (and the delayed
            # delta) replicated — counted as collectives on this scan
            W_lane = [jax.device_put(Ws[b], d) for d in devs]
            delta_src = (
                delta_prev
                if do_prev
                else jnp.zeros((prev_size, k), dtype=dtype)
            )
            delta_lane = [jax.device_put(delta_src, d) for d in devs]
            pipe = scan_pipeline(
                chunk_scan(), label="bcd.stream", lanes=lanes, devices=devs
            )
            record_scan_collectives(pipe, (2 if do_prev else 1) * lanes)
            row0 = 0
            with span("bcd.stream_block") as sp:
                for i, chunk in enumerate(pipe):
                    chunk = jnp.asarray(chunk, dtype=dtype)
                    rows = int(chunk.shape[0])
                    lane = i % lanes
                    if not _single_device_is(chunk, devs[lane]):
                        # a passthrough source (caller handed an already-
                        # pipelined/staged iterator) bypassed lane staging;
                        # co-locate with the resident slabs or the lane
                        # program would mix committed devices and fail
                        chunk = jax.device_put(chunk, devs[lane])
                    if first_scan:
                        chunk_rows.append(rows)
                        y_chunks.append(
                            jax.device_put(
                                y_zm[row0 : row0 + rows], devs[lane]
                            )
                        )
                        pred_chunks.append(
                            jax.device_put(
                                jnp.zeros((rows, k), dtype=dtype), devs[lane]
                            )
                        )
                    elif i >= len(chunk_rows) or chunk_rows[i] != rows:
                        raise ValueError(
                            "chunk source changed boundaries between scans "
                            f"(chunk {i}: {rows} rows)"
                        )
                    if do_gram and G_l[lane] is None:
                        G_l[lane] = jnp.zeros(
                            (sizes[b], sizes[b]), dtype=dtype
                        )
                    if c_l[lane] is None:
                        c_l[lane] = jnp.zeros((sizes[b], k), dtype=dtype)
                    # fresh dummy per call: the Gram slot is donated, so a
                    # shared placeholder would be consumed on first use
                    g_arg = (
                        G_l[lane]
                        if do_gram
                        else jnp.zeros((1, 1), dtype=dtype)
                    )
                    pred_chunks[i], g_new, c_l[lane] = _lane_chunk_update(
                        chunk, pred_chunks[i], g_arg,
                        c_l[lane], W_lane[lane], delta_lane[lane],
                        means_lane[lane], y_chunks[i], jprev, starts[b],
                        cur_size=sizes[b], prev_size=prev_size,
                        do_prev=do_prev, do_gram=do_gram,
                    )
                    if do_gram:
                        G_l[lane] = g_new
                    row0 += rows
                if row0 != n:
                    raise ValueError(
                        f"chunk source produced {row0} rows, labels have {n}"
                    )
                first_scan = False
                if do_gram:
                    grams[b] = reduce_lane_partials(G_l, scan=pipe)
                c = reduce_lane_partials(c_l, scan=pipe)
                if c is None:
                    raise ValueError("empty chunk source")
                W_new = solve_spd(grams[b], c, reg)
                delta_prev = W_new - Ws[b]
                Ws[b] = W_new
                jprev = starts[b]
                prev_size = sizes[b]
                sp.sync_on(W_new)
    return Ws


def stream_column_means(chunk_scan, dtype=jnp.float32, lanes: Optional[int] = None):
    """One scan computing (column_sums / n, n) of a chunked design matrix —
    the centering pass the streaming solvers run before accumulating.
    Mesh-distributed like the solvers: per-lane partial sums, reduced
    across the mesh once at finalize (O(1) collectives per scan)."""
    from ..parallel.lanes import reduce_lane_partials, scan_lanes

    if lanes is None:
        lanes = scan_lanes()
    pipe = scan_pipeline(chunk_scan(), label="column_means", lanes=lanes)
    lanes = getattr(pipe, "lanes", lanes)
    sums: List[Optional[jax.Array]] = [None] * lanes
    n = 0
    for i, chunk in enumerate(pipe):
        chunk = jnp.asarray(chunk, dtype=dtype)
        s = jnp.sum(chunk, axis=0)
        lane = i % lanes
        sums[lane] = s if sums[lane] is None else sums[lane] + s
        n += int(chunk.shape[0])
    total = reduce_lane_partials(sums, scan=pipe)
    if total is None:
        raise ValueError("empty chunk source")
    return total / n, n


def _keeps_factors(num_iter: int) -> bool:
    """Whether :func:`_bcd_scan_impl` factors each block once, ahead of its
    epochs, and keeps the factors: whenever a second epoch would otherwise
    form and factor the same matrices again."""
    return num_iter > 1


def scan_solver_work(d: int, block_size: int, num_iter: int) -> dict:
    """What the program :func:`_bcd_scan_impl` lowers to computes and keeps
    (the ``block_ls.solve`` span's attrs): its (block_size, block_size) Gram
    products, each followed by one Cholesky factorisation, and the bytes of
    the float32 factor stack it holds for the length of the fit."""
    nblocks = d // block_size
    if not _keeps_factors(num_iter):
        return {"gram_products": nblocks * num_iter, "factor_bytes": 0}
    return {
        "gram_products": nblocks,
        "factor_bytes": nblocks * block_size * block_size * 4,
    }


def _bcd_scan_impl(
    A, y, reg, means, init=None, *, block_size, num_iter, factor_sharding=None
):
    """The whole BCD solve as one program: a scan over epochs of a scan over
    blocks.

    Invariant: ``G_j = Ã_jᵀÃ_j`` and the factor of ``G_j + reg·I`` depend on
    ``A``, ``means`` and ``reg`` only — never on the epoch. So with more
    than one epoch a scan over blocks AHEAD of the epochs forms and factors
    each once, and every epoch solves against the kept (nblocks, block_size,
    block_size) stack; a one-pass fit factors inside its only epoch and
    keeps nothing. ``factor_sharding`` (the model-sharded compile's) lays
    the stack out by blocks, as ``W`` is.
    """
    n, d = A.shape
    nblocks = d // block_size
    k = y.shape[1]
    if init is None:
        W0 = jnp.zeros((nblocks, block_size, k), dtype=A.dtype)
        pred0 = jnp.zeros_like(y)
    else:
        # warm start: the prediction buffer must be consistent with W0
        # (pred = Σ Ãⱼ Wⱼ⁰) or the first residuals are garbage
        W0 = init.reshape(nblocks, block_size, k)
        Ac = A if means is None else A - means
        pred0 = _mm(Ac, init)

    def block(j):
        Aj = jax.lax.dynamic_slice_in_dim(A, j * block_size, block_size, axis=1)
        if means is not None:
            mj = jax.lax.dynamic_slice_in_dim(means, j * block_size, block_size)
            Aj = Aj - mj
        return Aj

    # named scopes are metadata (a profile groups device time by them, the
    # benchmark's readers match on them): they change neither the lowering
    # nor the persistent cache's key
    def factor(_, j):
        Aj = block(j)
        with jax.named_scope("ks.solver.gram"):
            G = _mm(Aj.T, Aj)
        with jax.named_scope("ks.solver.factor_solve"):
            return None, factor_spd(G, reg)

    L = None
    if _keeps_factors(num_iter):
        _, L = jax.lax.scan(factor, None, jnp.arange(nblocks))
        if factor_sharding is not None:
            L = jax.lax.with_sharding_constraint(L, factor_sharding)

    def kept_factor(j):
        if factor_sharding is None:
            return L[j]
        # sharded by blocks: a masked sum is a reduce on the shard that holds
        # block j plus one (block_size, block_size) all-reduce, where L[j]
        # would all-gather the whole stack in every block step
        mine = (jnp.arange(nblocks) == j)[:, None, None]
        return jnp.sum(jnp.where(mine, L, 0), axis=0)

    def epoch(carry, _):
        W, pred = carry

        def block_step(carry, j):
            W, pred = carry
            Aj = block(j)
            Wj = W[j]
            with jax.named_scope("ks.solver.residual"):
                r = y - pred + _mm(Aj, Wj)
            if L is None:
                with jax.named_scope("ks.solver.gram"):
                    G = _mm(Aj.T, Aj)
            with jax.named_scope("ks.solver.cross"):
                c = _mm(Aj.T, r)
            with jax.named_scope("ks.solver.factor_solve"):
                if L is None:
                    Wj_new = solve_spd(G, c, reg)
                else:
                    Wj_new = solve_factored(kept_factor(j), c)
            with jax.named_scope("ks.solver.residual"):
                pred = pred + _mm(Aj, Wj_new - Wj)
            W = W.at[j].set(Wj_new)
            return (W, pred), None

        (W, pred), _ = jax.lax.scan(block_step, (W, pred), jnp.arange(nblocks))
        return (W, pred), None

    (W, pred), _ = jax.lax.scan(epoch, (W0, pred0), None, length=num_iter)
    return W.reshape(d, k)


_bcd_scan = jax.jit(_bcd_scan_impl, static_argnames=("block_size", "num_iter"))

#: jitted model-sharded _bcd_scan compiles, keyed by (mesh, shape, config) —
#: a fresh jax.jit wrapper per call would retrace every fit
_bcd_sharded_cache: dict = {}
