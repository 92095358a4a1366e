"""Streaming class-weighted least squares — the out-of-core solver body of
``nodes/learning/weighted.py``, factored to the linalg layer and extended
with K-lane mesh distribution (ROADMAP PR-7 follow-on).

The design matrix streams through in row chunks and never materializes;
resident state is the (n, k) residual, the per-block joint statistics, one
masked-Gram accumulator, and one chunk. Lane discipline matches the other
streaming solvers (``bcd.py``): chunk *i* of a K-lane scan is staged to
(and consumed on) lane ``i % K``'s device, its residual slab and class
indices live there for the whole fit, every lane folds its own cross-term/
Gram/class-sum partials, and the mesh reduces ONCE per block step (plus a
per-block broadcast of the previous block's delta) — collectives are
O(blocks · lanes), independent of the chunk count (the PAPERS.md #3 gate).
``lanes=1`` runs the original single-accumulator loop, bit-identical.

The whole solve runs under f32-true matmuls: the mixture normal matrices
are regularized with λ below the noise floor of the default-bf16 matmul
lowering (see the measurement in ``nodes/learning/weighted.py``).
"""

from __future__ import annotations

from dataclasses import dataclass, field
from typing import List, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from ..data.pipeline_scan import scan_pipeline
from ..obs.tracer import span
from ..parallel.mesh import shard_classes
from .accumulators import MomentsState, _np


@jax.jit
def _batched_solve(jointXTX, rhs, lam):
    """(C, d, d), (C, d) → (C, d) batched ridge solves.

    LU with partial pivoting, not Cholesky: per-class covariances are
    rank-deficient whenever d exceeds the class count (ImageNet FV:
    d=4096, tens of images per class), and f32 Cholesky NaNs on the
    resulting near-semidefinite jointXTX. The reference survives because
    Breeze's ``\\`` is f64 LU (BlockWeightedLeastSquares.scala:294)."""
    with jax.named_scope("ks.solver.wls.solve"):
        d = jointXTX.shape[-1]
        G = jointXTX + lam * jnp.eye(d, dtype=jointXTX.dtype)
        return jnp.linalg.solve(G, rhs[..., None])[..., 0]


def _wls_stream_scan1_impl(
    A_chunk, R, delta_prev, y_idx, xtR, xtRc, G, class_sums, pop_sum,
    row0, jprev, jcur, *, bs, prev_bs, k, do_prev, do_stats,
):
    """Per-chunk program for a streaming weighted block step: applies the
    previous block's delayed residual update, then accumulates this block's
    raw-A cross terms (and, on the first epoch, its Gram + class sums)."""
    rows = A_chunk.shape[0]
    Ac = jax.lax.dynamic_slice_in_dim(A_chunk, jcur, bs, axis=1)
    Rc = jax.lax.dynamic_slice_in_dim(R, row0, rows, axis=0)
    if do_prev:
        Ap = jax.lax.dynamic_slice_in_dim(A_chunk, jprev, prev_bs, axis=1)
        Rc = Rc - jnp.matmul(Ap, delta_prev)
        R = jax.lax.dynamic_update_slice_in_dim(R, Rc, row0, axis=0)
    yc = jax.lax.dynamic_slice_in_dim(y_idx, row0, rows, axis=0)
    oh = jax.nn.one_hot(yc, k, dtype=A_chunk.dtype)  # (rows, k)
    xtR = xtR + jnp.matmul(Ac.T, Rc)
    xtRc = xtRc + jnp.matmul(Ac.T, oh * Rc)
    if do_stats:
        G = G + jnp.matmul(Ac.T, Ac)
        class_sums = class_sums + jnp.matmul(oh.T, Ac)
        pop_sum = pop_sum + jnp.sum(Ac, axis=0)
    return R, xtR, xtRc, G, class_sums, pop_sum


def _wls_stream_scan2_impl(A_chunk, y_idx, grams, row0, jcur, c0, *, bs, C):
    """Per-chunk masked-Gram accumulation for classes [c0, c0+C)."""
    rows = A_chunk.shape[0]
    Ac = jax.lax.dynamic_slice_in_dim(A_chunk, jcur, bs, axis=1)
    yc = jax.lax.dynamic_slice_in_dim(y_idx, row0, rows, axis=0)
    local = yc - c0
    in_range = (local >= 0) & (local < C)
    mask = jax.nn.one_hot(
        jnp.where(in_range, local, 0), C, dtype=A_chunk.dtype
    ) * in_range[:, None].astype(A_chunk.dtype)
    return grams + jnp.einsum("nd,nc,ne->cde", Ac, mask, Ac)


_wls_scan1_donating = jax.jit(
    _wls_stream_scan1_impl,
    static_argnames=("bs", "prev_bs", "k", "do_prev", "do_stats"),
    donate_argnums=(1, 4, 5, 6, 7, 8),
)
_wls_scan1_plain = jax.jit(
    _wls_stream_scan1_impl,
    static_argnames=("bs", "prev_bs", "k", "do_prev", "do_stats"),
)
_wls_scan2_donating = jax.jit(
    _wls_stream_scan2_impl, static_argnames=("bs", "C"), donate_argnums=(2,)
)
_wls_scan2_plain = jax.jit(
    _wls_stream_scan2_impl, static_argnames=("bs", "C")
)


def _wls_scan1(*args, **kwargs):
    if jax.default_backend() == "cpu":
        return _wls_scan1_plain(*args, **kwargs)
    return _wls_scan1_donating(*args, **kwargs)


def _wls_scan2(*args, **kwargs):
    if jax.default_backend() == "cpu":
        return _wls_scan2_plain(*args, **kwargs)
    return _wls_scan2_donating(*args, **kwargs)


# -- K-lane per-chunk programs ------------------------------------------------


def _wls_lane_scan1_impl(
    A_chunk, R_c, delta_prev, yid_c, xtR, xtRc, r_sum, cr_sum,
    G, class_sums, pop_sum, jprev, jcur,
    *, bs, prev_bs, k, do_prev, do_stats,
):
    """One chunk of one MESH-SHARDED weighted block step — entirely
    lane-local: the delayed residual update lands on this chunk's resident
    residual slab, then the lane's cross-term partials (and, first epoch,
    Gram/class-sum/population-sum partials) fold against it. The residual
    row sums (``r_sum``/``cr_sum``) accumulate here too — the laned scan
    has no resident (n, k) residual to reduce after the fact. No
    cross-device traffic; the mesh reduces once per block, after the
    scan. The stats slots are (1, 1)/(1,) dummies when ``do_stats`` is
    False."""
    if do_prev:
        Ap = jax.lax.dynamic_slice_in_dim(A_chunk, jprev, prev_bs, axis=1)
        R_c = R_c - jnp.matmul(Ap, delta_prev)
    Ac = jax.lax.dynamic_slice_in_dim(A_chunk, jcur, bs, axis=1)
    oh = jax.nn.one_hot(yid_c, k, dtype=A_chunk.dtype)  # (rows, k)
    xtR = xtR + jnp.matmul(Ac.T, R_c)
    xtRc = xtRc + jnp.matmul(Ac.T, oh * R_c)
    r_sum = r_sum + jnp.sum(R_c, axis=0)
    cr_sum = cr_sum + jnp.sum(oh * R_c, axis=0)
    if do_stats:
        G = G + jnp.matmul(Ac.T, Ac)
        class_sums = class_sums + jnp.matmul(oh.T, Ac)
        pop_sum = pop_sum + jnp.sum(Ac, axis=0)
    return R_c, xtR, xtRc, r_sum, cr_sum, G, class_sums, pop_sum


def _wls_lane_scan2_impl(A_chunk, yid_c, grams, jcur, c0, *, bs, C):
    """Lane-local masked-Gram accumulation for classes [c0, c0+C)."""
    Ac = jax.lax.dynamic_slice_in_dim(A_chunk, jcur, bs, axis=1)
    local = yid_c - c0
    in_range = (local >= 0) & (local < C)
    mask = jax.nn.one_hot(
        jnp.where(in_range, local, 0), C, dtype=A_chunk.dtype
    ) * in_range[:, None].astype(A_chunk.dtype)
    return grams + jnp.einsum("nd,nc,ne->cde", Ac, mask, Ac)


_wls_lane_scan1_donating = jax.jit(
    _wls_lane_scan1_impl,
    static_argnames=("bs", "prev_bs", "k", "do_prev", "do_stats"),
    donate_argnums=(1, 4, 5, 6, 7, 8, 9, 10),
)
_wls_lane_scan1_plain = jax.jit(
    _wls_lane_scan1_impl,
    static_argnames=("bs", "prev_bs", "k", "do_prev", "do_stats"),
)
_wls_lane_scan2_donating = jax.jit(
    _wls_lane_scan2_impl, static_argnames=("bs", "C"), donate_argnums=(2,)
)
_wls_lane_scan2_plain = jax.jit(
    _wls_lane_scan2_impl, static_argnames=("bs", "C")
)


def _wls_lane_scan1(*args, **kwargs):
    if jax.default_backend() == "cpu":
        return _wls_lane_scan1_plain(*args, **kwargs)
    return _wls_lane_scan1_donating(*args, **kwargs)


def _wls_lane_scan2(*args, **kwargs):
    if jax.default_backend() == "cpu":
        return _wls_lane_scan2_plain(*args, **kwargs)
    return _wls_lane_scan2_donating(*args, **kwargs)


def _single_device_is(x, device) -> bool:
    from ..parallel.lanes import _single_device

    return _single_device(x) == device


# -- the solver ---------------------------------------------------------------


def cost_signature(
    n: int,
    d: int,
    k: int,
    block_size: int,
    num_iter: int,
    machines: int = 1,
    class_chunk: int = 8,
) -> dict:
    """Work terms for pricing the block-weighted mixture solve — consumed
    by ``keystone_tpu.cost`` through the weighted family's ``cost()``
    methods. Per sweep, each block pays one cross-term scan (2·n·bs·k)
    plus ⌈k/C⌉ masked-Gram scans (the einsum executes n·C·bs² per chunk
    of C classes → n·k·bs² per block) and k per-class (bs³) solves."""
    import math

    bs = min(block_size, d)
    # the masked-Gram accumulator grows until C·bs² ≈ 256 MB f32 (same
    # policy as the solver body), so the scan count matches execution
    C = max(1, min(k, max(class_chunk, (1 << 26) // max(bs * bs, 1))))
    scans_per_block = 1 + math.ceil(k / C)
    return {
        "flops": num_iter * (
            2.0 * n * d * k + n * k * d * bs + k * d * bs * bs
        ) / machines,
        "bytes": num_iter * (
            (d / bs) * scans_per_block * n * d / machines + d * k
        ),
        "network": (
            2.0 * num_iter * d * (bs + k) * math.log2(max(machines, 2))
        ),
        "passes": num_iter * (d / max(bs, 1)) * scans_per_block,
    }


def solve_weighted_streaming(
    chunk_scan,
    Y: jax.Array,
    *,
    block_size: int,
    num_iter: int,
    lam: float,
    mixture_weight: float,
    class_chunk: int = 8,
    lanes: Optional[int] = None,
) -> Tuple[List[jax.Array], jax.Array]:
    """Out-of-core class-weighted block solve over a chunk source.

    ``chunk_scan`` is a re-iterable source: each call returns a fresh
    iterator of (rows, d) feature chunks (same chunks every scan — the
    lineage-recompute contract of ``data/chunked.py``). ``Y`` is the full
    (n, k) ±1 label matrix, resident. Objective and iteration shape are
    the block-weighted solver's (see
    ``nodes/learning/weighted.py::BlockWeightedLeastSquaresEstimator``,
    parity BlockWeightedLeastSquares.scala:177-313). Returns
    ``(per-block weights, intercept)``.

    ``lanes`` (default: the data-axis size of the active mesh;
    ``KEYSTONE_SCAN_LANES`` overrides) shards the scans across per-device
    staging lanes with per-lane partial accumulators reduced once per
    block — see the module docstring. ``lanes=1`` is the original
    single-accumulator loop.
    """
    from ..parallel.lanes import scan_lanes

    if lanes is None:
        lanes = scan_lanes()
    with jax.default_matmul_precision("highest"):
        # f32-true: λ as small as the reference's ImageNet 6e-5 sits below
        # the default-bf16 matmul noise floor of the normal matrices
        if lanes > 1:
            return _solve_weighted_streaming_lanes(
                chunk_scan, Y, lam, mixture_weight, block_size, num_iter,
                class_chunk, lanes,
            )
        return _solve_weighted_streaming_serial(
            chunk_scan, Y, lam, mixture_weight, block_size, num_iter,
            class_chunk,
        )


def _block_layout(chunk_scan, block_size: int):
    """Peek d from one chunk; return (starts, sizes)."""
    d = None
    it = chunk_scan()
    try:
        for chunk in it:
            d = int(chunk.shape[-1])
            break
    finally:
        close = getattr(it, "close", None)
        if close is not None:
            close()
    if d is None:
        raise ValueError("empty chunk source")
    starts = list(range(0, d, block_size))
    sizes = [min(block_size, d - j0) for j0 in starts]
    return starts, sizes


def _solve_weighted_streaming_serial(
    chunk_scan, Y, lam, w, block_size, num_iter, class_chunk
) -> Tuple[List[jax.Array], jax.Array]:
    """Single-lane body: resident (n, k) residual updated in row slices,
    one accumulator set, model-axis (``shard_classes``) parallelism over
    the per-class Grams and solves."""
    Y = jnp.asarray(Y, dtype=jnp.float32)
    n, k = Y.shape
    y_idx = jnp.argmax(Y, axis=1)
    counts = jnp.zeros((k,), jnp.float32).at[y_idx].add(1.0)
    safe_counts = jnp.maximum(counts, 1.0)
    joint_label_mean = 2 * w + 2 * (1 - w) * counts / n - 1.0
    R = Y - joint_label_mean

    starts, sizes = _block_layout(chunk_scan, block_size)
    nblocks = len(starts)
    Ws: List[jax.Array] = [
        jnp.zeros((bs, k), dtype=jnp.float32) for bs in sizes
    ]
    stats = [None] * nblocks  # (pop_cov, pop_mean, joint_means, class_means)
    delta_prev = None
    jprev, prev_bs = 0, sizes[0]

    for _ in range(num_iter):
        for bidx, (j0, bs) in enumerate(zip(starts, sizes)):
            do_stats = stats[bidx] is None
            xtR = jnp.zeros((bs, k), jnp.float32)
            xtRc = jnp.zeros((bs, k), jnp.float32)
            G = jnp.zeros((bs, bs), jnp.float32)
            class_sums = jnp.zeros((k, bs), jnp.float32)
            pop_sum = jnp.zeros((bs,), jnp.float32)
            row0 = 0
            with span("wls.stream_cross") as sp:
                for chunk in scan_pipeline(chunk_scan(), label="wls.stream"):
                    chunk = jnp.asarray(chunk, dtype=jnp.float32)
                    R, xtR, xtRc, G, class_sums, pop_sum = _wls_scan1(
                        chunk, R,
                        delta_prev
                        if delta_prev is not None
                        else jnp.zeros((prev_bs, k), jnp.float32),
                        y_idx, xtR, xtRc, G, class_sums, pop_sum,
                        row0, jprev, j0,
                        bs=bs, prev_bs=prev_bs, k=k,
                        do_prev=delta_prev is not None,
                        do_stats=do_stats,
                    )
                    row0 += int(chunk.shape[0])
                if row0 != n:
                    raise ValueError(
                        f"chunk source produced {row0} rows, labels {n}"
                    )
                sp.sync_on(xtR)
            if do_stats:
                pop_mean = pop_sum / n
                class_means = class_sums / safe_counts[:, None]
                joint_means = w * class_means + (1 - w) * pop_mean
                pop_cov = G / n - jnp.outer(pop_mean, pop_mean)
                stats[bidx] = (pop_cov, pop_mean, joint_means, class_means)
            pop_cov, pop_mean, joint_means, class_means = stats[bidx]
            pop_xtr = xtR / n
            class_xtr = xtRc / safe_counts[None, :]
            residual_mean = jnp.mean(R, axis=0)
            vals = jnp.take_along_axis(R, y_idx[:, None], axis=1)[:, 0]
            class_r_mean = (
                jnp.zeros((k,), jnp.float32).at[y_idx].add(vals)
                / safe_counts
            )

            # masked-Gram accumulator sized to >= class_chunk classes,
            # grown until C·bs² reaches ~256 MB f32 (fewer data scans)
            C = max(
                1,
                min(k, max(class_chunk, (1 << 26) // max(bs * bs, 1))),
            )
            delta_cols = []
            for c0 in range(0, k, C):
                Ccur = min(C, k - c0)
                # class-sharded accumulator: each model-axis device owns
                # a class slice of the einsum + solve (the streaming twin
                # of the in-memory path's shard_classes(onehot) layout)
                grams = shard_classes(
                    jnp.zeros((Ccur, bs, bs), jnp.float32)
                )
                row0 = 0
                with span("wls.stream_grams") as sp:
                    for chunk in scan_pipeline(
                        chunk_scan(), label="wls.stream"
                    ):
                        chunk = jnp.asarray(chunk, dtype=jnp.float32)
                        grams = _wls_scan2(
                            chunk, y_idx, grams, row0, j0, c0,
                            bs=bs, C=Ccur,
                        )
                        row0 += int(chunk.shape[0])
                    sp.sync_on(grams)
                delta_cols.append(
                    _wls_class_delta(
                        grams, counts, class_means, pop_mean, joint_means,
                        pop_xtr, class_xtr, residual_mean, class_r_mean,
                        pop_cov, Ws[bidx], w, lam, c0, Ccur, sharded=True,
                    )
                )
            delta = jnp.concatenate(delta_cols, axis=0).T  # (bs, k)
            Ws[bidx] = Ws[bidx] + delta
            delta_prev, jprev, prev_bs = delta, j0, bs

    b = joint_label_mean - sum(
        jnp.einsum("cd,dc->c", stats[j][2], Ws[j]) for j in range(nblocks)
    )
    return Ws, b


def _wls_class_delta(
    grams, counts, class_means, pop_mean, joint_means, pop_xtr, class_xtr,
    residual_mean, class_r_mean, pop_cov, W_cur, w, lam, c0, Ccur,
    *, sharded: bool,
):
    """The per-class-chunk mixture algebra + batched ridge solve shared by
    the serial and laned scan bodies (parity: the jointXTX/jointXTR terms
    of BlockWeightedLeastSquares.scala:102-321)."""
    cs = slice(c0, c0 + Ccur)
    mu_c = class_means[cs]
    mean_diff = mu_c - pop_mean
    mean_mixture = (1 - w) * residual_mean[cs] + w * class_r_mean[cs]
    jointXTR = (
        (1 - w) * pop_xtr[:, cs].T
        + w * class_xtr[:, cs].T
        - joint_means[cs] * mean_mixture[:, None]
    )
    rhs = jointXTR - lam * W_cur[:, cs].T
    cnt = counts[cs][:, None, None]
    class_cov = grams / jnp.maximum(cnt, 1.0) - jnp.einsum(
        "cd,ce->cde", mu_c, mu_c
    )
    jointXTX = (
        (1 - w) * pop_cov
        + w * class_cov
        + w * (1 - w) * jnp.einsum("cd,ce->cde", mean_diff, mean_diff)
    )
    if sharded:
        jointXTX = shard_classes(jointXTX)
        rhs = shard_classes(rhs)
    return _batched_solve(jointXTX, rhs, lam)


def _solve_weighted_streaming_lanes(
    chunk_scan, Y, lam, w, block_size, num_iter, class_chunk, lanes
) -> Tuple[List[jax.Array], jax.Array]:
    """The mesh-distributed body of :func:`solve_weighted_streaming`.

    Residency: chunk *i*'s residual slab and class-index slice are
    committed to lane ``i % lanes``'s device on the FIRST scan and stay
    there for the whole fit, so every per-chunk program is single-device
    local. Per block step: the previous block's delta broadcasts to each
    lane once, each lane folds its own cross/Gram/class-sum partials (and
    residual row sums — there is no resident (n, k) residual to reduce
    afterwards), and the partials reduce across the mesh once; the
    masked-Gram scans reduce once per class chunk. Collectives per block:
    <= lanes broadcasts + O(lanes) reduction hops per scan, independent
    of how many chunks stream. The per-class solves run on the reduced
    accumulators (no model-axis resharding of lane-resident state)."""
    from ..parallel.lanes import (
        lane_devices,
        record_scan_collectives,
        reduce_lane_partials,
    )
    Y = jnp.asarray(Y, dtype=jnp.float32)
    n, k = Y.shape
    y_idx = jnp.argmax(Y, axis=1)
    counts = jnp.zeros((k,), jnp.float32).at[y_idx].add(1.0)
    safe_counts = jnp.maximum(counts, 1.0)
    joint_label_mean = 2 * w + 2 * (1 - w) * counts / n - 1.0
    R0 = Y - joint_label_mean

    starts, sizes = _block_layout(chunk_scan, block_size)
    nblocks = len(starts)
    devs = lane_devices(lanes)
    Ws: List[jax.Array] = [
        jnp.zeros((bs, k), dtype=jnp.float32) for bs in sizes
    ]
    stats = [None] * nblocks
    delta_prev = None
    jprev, prev_bs = 0, sizes[0]
    # per-chunk resident state, built on the first scan
    R_chunks: List[jax.Array] = []
    yid_chunks: List[jax.Array] = []
    chunk_rows: List[int] = []
    first_scan = True

    for _ in range(num_iter):
        for bidx, (j0, bs) in enumerate(zip(starts, sizes)):
            do_prev = delta_prev is not None
            do_stats = stats[bidx] is None
            acc: List[Optional[tuple]] = [None] * lanes
            delta_src = (
                delta_prev
                if do_prev
                else jnp.zeros((prev_bs, k), jnp.float32)
            )
            delta_lane = [jax.device_put(delta_src, d) for d in devs]
            pipe = scan_pipeline(
                chunk_scan(), label="wls.stream", lanes=lanes, devices=devs
            )
            record_scan_collectives(pipe, lanes if do_prev else 0)
            row0 = 0
            with span("wls.stream_cross") as sp:
                for i, chunk in enumerate(pipe):
                    chunk = jnp.asarray(chunk, dtype=jnp.float32)
                    rows = int(chunk.shape[0])
                    lane = i % lanes
                    if not _single_device_is(chunk, devs[lane]):
                        # a passthrough source bypassed lane staging —
                        # co-locate with the resident slabs (same guard as
                        # the laned BCD)
                        chunk = jax.device_put(chunk, devs[lane])
                    if first_scan:
                        chunk_rows.append(rows)
                        R_chunks.append(
                            jax.device_put(
                                R0[row0 : row0 + rows], devs[lane]
                            )
                        )
                        yid_chunks.append(
                            jax.device_put(
                                y_idx[row0 : row0 + rows], devs[lane]
                            )
                        )
                    elif i >= len(chunk_rows) or chunk_rows[i] != rows:
                        raise ValueError(
                            "chunk source changed boundaries between scans "
                            f"(chunk {i}: {rows} rows)"
                        )
                    if acc[lane] is None:
                        acc[lane] = (
                            jnp.zeros((bs, k), jnp.float32),
                            jnp.zeros((bs, k), jnp.float32),
                            jnp.zeros((k,), jnp.float32),
                            jnp.zeros((k,), jnp.float32),
                            jnp.zeros(
                                (bs, bs) if do_stats else (1, 1),
                                jnp.float32,
                            ),
                            jnp.zeros(
                                (k, bs) if do_stats else (1, 1),
                                jnp.float32,
                            ),
                            jnp.zeros(
                                (bs,) if do_stats else (1,), jnp.float32
                            ),
                        )
                    R_chunks[i], *acc[lane] = _wls_lane_scan1(
                        chunk, R_chunks[i], delta_lane[lane],
                        yid_chunks[i], *acc[lane], jprev, j0,
                        bs=bs, prev_bs=prev_bs, k=k,
                        do_prev=do_prev, do_stats=do_stats,
                    )
                    acc[lane] = tuple(acc[lane])
                    row0 += rows
                if row0 != n:
                    raise ValueError(
                        f"chunk source produced {row0} rows, labels {n}"
                    )
                first_scan = False
                red = reduce_lane_partials(acc, scan=pipe)
                if red is None:
                    raise ValueError("empty chunk source")
                xtR, xtRc, r_sum, cr_sum, G, class_sums, pop_sum = red
                sp.sync_on(xtR)
            if do_stats:
                pop_mean = pop_sum / n
                class_means = class_sums / safe_counts[:, None]
                joint_means = w * class_means + (1 - w) * pop_mean
                pop_cov = G / n - jnp.outer(pop_mean, pop_mean)
                stats[bidx] = (pop_cov, pop_mean, joint_means, class_means)
            pop_cov, pop_mean, joint_means, class_means = stats[bidx]
            pop_xtr = xtR / n
            class_xtr = xtRc / safe_counts[None, :]
            residual_mean = r_sum / n
            class_r_mean = cr_sum / safe_counts

            C = max(
                1,
                min(k, max(class_chunk, (1 << 26) // max(bs * bs, 1))),
            )
            delta_cols = []
            for c0 in range(0, k, C):
                Ccur = min(C, k - c0)
                grams_l: List[Optional[jax.Array]] = [None] * lanes
                pipe2 = scan_pipeline(
                    chunk_scan(), label="wls.stream", lanes=lanes,
                    devices=devs,
                )
                row0 = 0
                with span("wls.stream_grams") as sp:
                    for i, chunk in enumerate(pipe2):
                        chunk = jnp.asarray(chunk, dtype=jnp.float32)
                        rows = int(chunk.shape[0])
                        lane = i % lanes
                        if not _single_device_is(chunk, devs[lane]):
                            chunk = jax.device_put(chunk, devs[lane])
                        if i >= len(chunk_rows) or chunk_rows[i] != rows:
                            raise ValueError(
                                "chunk source changed boundaries between "
                                f"scans (chunk {i}: {rows} rows)"
                            )
                        if grams_l[lane] is None:
                            grams_l[lane] = jax.device_put(
                                jnp.zeros((Ccur, bs, bs), jnp.float32),
                                devs[lane],
                            )
                        grams_l[lane] = _wls_lane_scan2(
                            chunk, yid_chunks[i], grams_l[lane], j0, c0,
                            bs=bs, C=Ccur,
                        )
                        row0 += rows
                    if row0 != n:
                        raise ValueError(
                            f"chunk source produced {row0} rows, labels {n}"
                        )
                    grams = reduce_lane_partials(grams_l, scan=pipe2)
                    sp.sync_on(grams)
                delta_cols.append(
                    _wls_class_delta(
                        grams, counts, class_means, pop_mean, joint_means,
                        pop_xtr, class_xtr, residual_mean, class_r_mean,
                        pop_cov, Ws[bidx], w, lam, c0, Ccur, sharded=False,
                    )
                )
            delta = jnp.concatenate(delta_cols, axis=0).T  # (bs, k)
            Ws[bidx] = Ws[bidx] + delta
            delta_prev, jprev, prev_bs = delta, j0, bs

    b = joint_label_mean - sum(
        jnp.einsum("cd,dc->c", stats[j][2], Ws[j]) for j in range(nblocks)
    )
    return Ws, b


# -- snapshot-able per-class accumulators (incremental refit) -----------------


@jax.jit
def _weighted_chunk_stats(Xs, Y):
    """One chunk's per-class raw statistics (shift already subtracted):
    gram Σ(x−s)(x−s)ᵀ, per-class grams, label cross terms, per-class
    sums — the associative pieces :class:`WeightedSolverState` folds.
    One jitted program per chunk shape; everything here is f32-true
    GEMM work against the provisional shift (same policy as
    ``GramSolverState.update``)."""
    with jax.default_matmul_precision("highest"):
        k = Y.shape[1]
        y_idx = jnp.argmax(Y, axis=1)
        oh = jax.nn.one_hot(y_idx, k, dtype=Xs.dtype)          # (rows, k)
        ohy = oh * Y                                           # (rows, k)
        return (
            jnp.matmul(Xs.T, Xs),            # gram_s   (d, d)
            jnp.einsum("nd,nc,ne->cde", Xs, oh, Xs),  # class_gram_s (k, d, d)
            jnp.matmul(Xs.T, Y),             # cross_s  (d, k)
            jnp.matmul(ohy.T, Xs),           # class_cross_s (k, d)
            jnp.sum(Xs, axis=0),             # sum_dx   (d,)
            jnp.matmul(oh.T, Xs),            # class_sum_dx (k, d)
            jnp.sum(Y, axis=0),              # sum_y    (k,)
            jnp.sum(ohy, axis=0),            # class_sum_y (k,)
            jnp.sum(oh, axis=0),             # counts   (k,)
        )


@dataclass
class WeightedSolverState:
    """Per-class sufficient statistics of the EXACT class-weighted
    mixture ridge — the weighted family's snapshot-able accumulator
    (``FittedPipeline.absorb`` beyond the Gram family).

    For every class c the per-class oracle solves
    ``(Σᵢ bᵢ(xᵢ−μ_c)(xᵢ−μ_c)ᵀ + λI) W_c = Σᵢ bᵢ(xᵢ−μ_c)(y_ic − m_c)``
    with sample weights ``bᵢ = (1−w)/n + w·1[i∈c]/n_c``, joint mean
    ``μ_c = w·mean_c + (1−w)·mean`` and joint label mean ``m_c``
    (``nodes/learning/weighted.py::PerClassWeightedLeastSquares
    Estimator``). Every term is a linear/bilinear functional of the row
    stream, so the whole solve is derivable from raw per-class sums that
    are ASSOCIATIVE over row blocks: the population Gram, one (k, d, d)
    per-class Gram stack, the label cross terms, and the per-class
    count/sum vectors. Appended chunks fold in O(new chunks); the k
    solves are O(k·d³) with no data pass.

    The BCD-iterated families (block-weighted, reweighted) have NO such
    statistic — their iterates depend on block visitation order — and
    raise the typed :class:`~keystone_tpu.linalg.accumulators.
    NotAbsorbable` instead of silently refitting wrong.

    Accumulation discipline mirrors :class:`~keystone_tpu.linalg.
    accumulators.GramSolverState`: host float64 totals, per-chunk f32
    products on device against a provisional first-chunk shift s (the
    centered quantities are re-derived algebraically at solve time, so
    the class means may keep moving as chunks arrive). Memory is
    O(k·d²) — the price of k per-class Grams; the Gram-family state
    stays the right choice when k·d² won't sit in host RAM.
    """

    #: the mixture/ridge identity the owning model was solved with —
    #: what ``FittedPipeline.absorb`` re-solves at
    lam: float = 0.0
    mixture_weight: float = 0.5
    #: block split of the rebuilt ``BlockLinearMapper`` (0 = one block)
    block_size: int = 0
    n: int = 0
    counts: Optional[np.ndarray] = None          # (k,)
    shift: Optional[np.ndarray] = None           # (d,) f32 provisional
    sum_dx: Optional[np.ndarray] = None          # (d,)   Σ (x−s)
    class_sum_dx: Optional[np.ndarray] = None    # (k, d) Σ_{i∈c} (x−s)
    sum_y: Optional[np.ndarray] = None           # (k,)   Σ y
    class_sum_y: Optional[np.ndarray] = None     # (k,)   Σ_{i∈c} y_ic
    gram_s: Optional[np.ndarray] = None          # (d, d)
    class_gram_s: Optional[np.ndarray] = None    # (k, d, d)
    cross_s: Optional[np.ndarray] = None         # (d, k) Σ (x−s) yᵀ
    class_cross_s: Optional[np.ndarray] = None   # (k, d) Σ_{i∈c} (x−s) y_ic
    #: rows folded since construction OR the last snapshot() — the
    #: O(new chunks) work gate reads this, not ``n``
    rows_folded: int = field(default=0, compare=False)

    @property
    def d(self) -> int:
        return 0 if self.gram_s is None else int(self.gram_s.shape[0])

    @property
    def k(self) -> int:
        return 0 if self.cross_s is None else int(self.cross_s.shape[1])

    def update(self, A_chunk, y_chunk) -> "WeightedSolverState":
        """Fold one (rows, d) feature chunk and its (rows, k) class-
        indicator slice (class = argmax of the row, the convention of
        the whole weighted family)."""
        A = jnp.asarray(A_chunk, dtype=jnp.float32)
        Y = jnp.asarray(y_chunk, dtype=jnp.float32)
        if A.ndim != 2 or Y.ndim != 2:
            raise ValueError(
                f"chunks must be 2-D (A: {A.shape}, y: {Y.shape})"
            )
        if A.shape[0] != Y.shape[0]:
            raise ValueError(
                f"feature chunk has {A.shape[0]} rows, labels {Y.shape[0]}"
            )
        rows, d = int(A.shape[0]), int(A.shape[1])
        k = int(Y.shape[1])
        if self.gram_s is None:
            self.counts = np.zeros((k,), np.float64)
            self.sum_dx = np.zeros((d,), np.float64)
            self.class_sum_dx = np.zeros((k, d), np.float64)
            self.sum_y = np.zeros((k,), np.float64)
            self.class_sum_y = np.zeros((k,), np.float64)
            self.gram_s = np.zeros((d, d), np.float64)
            self.class_gram_s = np.zeros((k, d, d), np.float64)
            self.cross_s = np.zeros((d, k), np.float64)
            self.class_cross_s = np.zeros((k, d), np.float64)
            self.shift = _np(jnp.mean(A, axis=0)).astype(np.float32)
        elif d != self.d or k != self.k:
            raise ValueError(
                f"chunk shape ({d}, {k}) does not match accumulated "
                f"({self.d}, {self.k})"
            )
        parts = _weighted_chunk_stats(A - jnp.asarray(self.shift), Y)
        (g, cg, cr, ccr, sdx, csdx, sy, csy, cnt) = (
            _np(p).astype(np.float64) for p in parts
        )
        self.gram_s += g
        self.class_gram_s += cg
        self.cross_s += cr
        self.class_cross_s += ccr
        self.sum_dx += sdx
        self.class_sum_dx += csdx
        self.sum_y += sy
        self.class_sum_y += csy
        self.counts += cnt
        self.n += rows
        self.rows_folded += rows
        return self

    def solve(self, lam: Optional[float] = None):
        """``(W (d, k), b (k,))`` of the exact per-class mixture ridge
        from the CURRENT accumulated state — O(k·d³), no data pass. The
        centering algebra happens here in float64: with δ_c = μ_c − s,
        ``G_c = (1−w)/n·Σ(x−s)(x−s)ᵀ + w/n_c·Σ_{i∈c}(x−s)(x−s)ᵀ − δ_cδ_cᵀ``
        and ``rhs_c = (1−w)/n·Σ(x−s)y_c + w/n_c·Σ_{i∈c}(x−s)y_ic − m_c·δ_c``
        (both follow from Σᵢbᵢ = 1 and Σᵢbᵢ(x−s) = δ_c)."""
        if self.gram_s is None or self.n == 0:
            raise ValueError("solve of an empty WeightedSolverState")
        lam = self.lam if lam is None else float(lam)
        w = float(self.mixture_weight)
        n = float(self.n)
        d, k = self.d, self.k
        s = self.shift.astype(np.float64)
        safe = np.maximum(self.counts, 1.0)
        pop_mean = s + self.sum_dx / n
        class_means = s[None, :] + self.class_sum_dx / safe[:, None]
        joint_means = w * class_means + (1 - w) * pop_mean[None, :]
        jlm = (1 - w) * self.sum_y / n + w * self.class_sum_y / safe
        eye = np.eye(d)
        cols = []
        for c in range(k):
            delta = joint_means[c] - s
            Gmix = (
                (1 - w) / n * self.gram_s
                + w / safe[c] * self.class_gram_s[c]
            )
            G = Gmix - np.outer(delta, delta)
            rhs = (
                (1 - w) / n * self.cross_s[:, c]
                + w / safe[c] * self.class_cross_s[c]
                - jlm[c] * delta
            )
            cols.append(np.linalg.solve(G + lam * eye, rhs))
        W = np.stack(cols, axis=1)  # (d, k)
        b = jlm - np.einsum("cd,dc->c", joint_means, W)
        return (
            jnp.asarray(W, dtype=jnp.float32),
            jnp.asarray(b, dtype=jnp.float32),
        )

    def rebuild_mapper(self, mapper):
        """Re-solve and rebuild the fitted ``BlockLinearMapper`` at the
        recorded block split — the absorb state-protocol hook."""
        W, b = self.solve()
        d = int(W.shape[0])
        bs = self.block_size or d
        blocks = [W[i : min(i + bs, d)] for i in range(0, d, bs)]
        return type(mapper)(
            blocks, bs, b=b, solver_state=self.snapshot()
        )

    def moments(self) -> MomentsState:
        """Column moments of every row folded so far (same derivation as
        ``GramSolverState.moments``) — the drift-monitor baseline."""
        if self.gram_s is None or self.n == 0:
            raise ValueError("moments of an empty WeightedSolverState")
        mu = self.shift.astype(np.float64) + self.sum_dx / float(self.n)
        dmu = mu - self.shift.astype(np.float64)
        m2 = np.maximum(np.diag(self.gram_s) - self.n * dmu * dmu, 0.0)
        return MomentsState(n=self.n, mean=mu, m2=m2)

    def snapshot(self) -> "WeightedSolverState":
        """Independent copy with the ``rows_folded`` work counter zeroed
        (the absorb contract, same as ``GramSolverState.snapshot``)."""

        def cp(a):
            return None if a is None else a.copy()

        return WeightedSolverState(
            lam=self.lam,
            mixture_weight=self.mixture_weight,
            block_size=self.block_size,
            n=self.n,
            counts=cp(self.counts),
            shift=cp(self.shift),
            sum_dx=cp(self.sum_dx),
            class_sum_dx=cp(self.class_sum_dx),
            sum_y=cp(self.sum_y),
            class_sum_y=cp(self.class_sum_y),
            gram_s=cp(self.gram_s),
            class_gram_s=cp(self.class_gram_s),
            cross_s=cp(self.cross_s),
            class_cross_s=cp(self.class_cross_s),
            rows_folded=0,
        )
