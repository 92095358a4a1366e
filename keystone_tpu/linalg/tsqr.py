"""Tall-skinny QR over the mesh.

Parity: mlmatrix ``TSQR().qrR`` used by DistributedPCA
(nodes/learning/DistributedPCA.scala:48). The reference runs per-partition
local QRs and tree-reduces the R factors through Spark's network stack; here
each mesh shard takes a local ``qr`` of its rows, the d×d R factors ride an
``all_gather`` over ICI, and one stacked QR finishes the job — the classic
TSQR reduction with the tree flattened (d is small, so gathering n_dev·d rows
is cheap and one level suffices).
"""

from __future__ import annotations

from functools import lru_cache, partial
from typing import Optional

import jax
import jax.numpy as jnp
from jax import shard_map
from jax.sharding import Mesh, PartitionSpec as P

from ..parallel.mesh import DATA_AXIS, default_mesh, pad_to_multiple, shard_batch


def _fix_sign(R: jax.Array) -> jax.Array:
    """Normalise so diag(R) ≥ 0 — makes the factor unique/deterministic for
    cross-implementation tests."""
    s = jnp.sign(jnp.diagonal(R))
    s = jnp.where(s == 0, 1.0, s)
    return R * s[:, None]


@lru_cache(maxsize=None)
def _tsqr_fn(mesh: Mesh):
    """Per-mesh compiled TSQR program (cached so repeated calls — e.g. a
    DistributedPCA loop — hit the jit cache instead of re-compiling)."""

    @jax.jit
    @partial(
        shard_map,
        mesh=mesh,
        in_specs=P(DATA_AXIS, None),
        out_specs=P(None, None),
        check_vma=False,
    )
    def _tsqr(A_local):
        R_local = jnp.linalg.qr(A_local, mode="r")
        R_all = jax.lax.all_gather(R_local, DATA_AXIS)  # (ndev, d, d)
        R_stacked = R_all.reshape(-1, R_all.shape[-1])
        R = jnp.linalg.qr(R_stacked, mode="r")
        return _fix_sign(R)

    return _tsqr


def tsqr_r(A, mesh: Optional[Mesh] = None) -> jax.Array:
    """The R factor of A's QR decomposition; A (n, d) row-sharded, R (d, d)
    replicated. Row counts that don't divide the data-axis size are zero-row
    padded first — [A; 0] has the same R factor."""
    mesh = mesh or default_mesh()
    A, _ = pad_to_multiple(jnp.asarray(A), mesh.shape[DATA_AXIS], axis=0)
    A = shard_batch(A, mesh)
    return _tsqr_fn(mesh)(A)


def cost_signature(n: int, d: int, k: int = 0, machines: int = 1) -> dict:
    """Work terms for pricing a TSQR factorization of an (n, d+k)
    augmented design matrix (consumed by ``keystone_tpu.cost``). A
    Householder QR pays ~2·n·w² flops for width w = d+k — twice the Gram
    route's contraction — in exchange for never squaring the condition
    number; the reduction gathers one w×w factor per shard."""
    w = d + k
    return {
        "flops": 2.0 * n * w * w / machines + machines * float(w) ** 3,
        "bytes": n * w / machines + w * w,
        "network": machines * w * w,
        "passes": 1,
    }


@jax.jit
def _qr_r(chunk):
    return jnp.linalg.qr(chunk, mode="r")


@jax.jit
def _qr_fold(R, chunk):
    """Fold one chunk into a running R factor: qr([R; chunk]) — the
    sequential TSQR recurrence each lane runs locally."""
    return jnp.linalg.qr(jnp.concatenate([R, chunk], axis=0), mode="r")


def tsqr_r_streaming(
    chunk_scan, dtype=jnp.float32, lanes: Optional[int] = None
) -> jax.Array:
    """Out-of-core TSQR: the R factor of a chunked (n, d) design matrix
    whose rows never materialize together.

    ``chunk_scan`` is a re-iterable source of (rows, d) chunks (the same
    contract as the streaming solvers). Chunks ride the pipelined scan
    runtime round-robined over the mesh's data-axis lanes; each lane folds
    its chunks into a lane-local (d, d) R factor (``qr([R_l; chunk])``),
    and the per-lane factors gather across the mesh ONCE at finalize for a
    single stacked QR — the same one-level reduction tree as
    :func:`tsqr_r`, with the leaves streamed. Collectives: O(1) per scan,
    never per chunk. The result is sign-fixed like :func:`tsqr_r`, so the
    two agree to fp tolerance."""
    from ..data.pipeline_scan import scan_pipeline
    from ..parallel.lanes import gather_lane_partials, scan_lanes

    if lanes is None:
        lanes = scan_lanes()
    pipe = scan_pipeline(chunk_scan(), label="tsqr", lanes=lanes)
    lanes = getattr(pipe, "lanes", lanes)
    Rs: list = [None] * lanes
    for i, chunk in enumerate(pipe):
        chunk = jnp.asarray(chunk, dtype=dtype)
        lane = i % lanes
        Rs[lane] = (
            _qr_r(chunk) if Rs[lane] is None else _qr_fold(Rs[lane], chunk)
        )
    parts = gather_lane_partials(Rs, scan=pipe)
    if not parts:
        raise ValueError("empty chunk source")
    stacked = parts[0] if len(parts) == 1 else jnp.concatenate(parts, axis=0)
    return _fix_sign(jnp.linalg.qr(stacked, mode="r"))
