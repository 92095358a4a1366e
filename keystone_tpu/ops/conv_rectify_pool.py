"""Pallas TPU kernel: filter-bank convolution → symmetric rectifier →
sum-pool with the convolution's output kept on the chip.

Written out as three XLA operations (``nodes/images/core.py``: ``Convolver``
→ ``SymmetricRectifier`` → ``Pooler``) the chain writes the convolution's
(n, rx, ry, K) float32 output to HBM and reads it back — 29.2 MB an image
at 27×27 windows and K = 10,000 filters, of which 320 KB leave the pool
(PERF.md §5, PR 29: 168 µs an image, both halves bound by that array).
Here a (window rows, filter tile) block of it lives in VMEM only:

* XLA, in the caller's program, cuts the windows into patch rows — one
  convolution with a 0/1 kernel on the image rounded to bf16, exactly the
  rounding ``conv_general_dilated`` applies at the default precision —
  ordered by POOL CLASS (below), 200 KB an image;
* the kernel, over (image tile, filter tile): patch rows × filter tile on
  the MXU (bf16 operands, float32 accumulation), then on the VPU in
  float32 the patch normalisation (a multiply by 1/sd, the window moments
  being XLA's), the bias, both rectified halves and the pool's sums;
* only the pooled sums, (n, [cell, half], K) float32, are written.

**Pool classes.** The pool's windows along an axis, ``[i·stride,
i·stride + w)`` clipped to the axis, may overlap (14/13 on 27: [0, 14) and
[13, 27) share index 13). The axis is cut wherever membership changes —
[0, 13) ∈ {0}, [13, 14) ∈ {0, 1}, [14, 27) ∈ {1} — and the product of the
two axes' classes gives GROUPS of window positions whose every member
feeds the same pooled cells. Patch rows are laid out group by group, each
group's start aligned to the bf16 sublane tile, so a group's sum is a run
of whole-vreg float32 additions and a pooled cell is the sum of its
groups: every convolution output is added once. Positions no window covers
get no row.

Reference parity: Convolver.scala:128-203 (im2col + GEMM, which this
restores at the tile level), SymmetricRectifier.scala:7-32,
Pooler.scala:21-84.

Used through ``nodes/images/core.py:ConvRectifyPool`` on the TPU backend
where the shapes fit (:func:`supported`); everywhere else the three bodies
compute the same values (``tests/nodes/test_conv_rectify_pool.py``: 1e-5
relative with both sides' operands rounded to bf16 alike).
"""

from __future__ import annotations

import dataclasses
import functools
from typing import List, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

#: rows of a group start on a multiple of this (the bf16 sublane tile)
_ROW_ALIGN = 16
_LANES = 128
#: widest filter tile, in lanes
_MAX_TILE_K = 1024
#: most images a grid step (the patch block stays in VMEM across filter tiles)
_TILE_N = 8
#: groups are padded to the row alignment: where the pool's windows cut the
#: axes so finely that padding outgrows this share of the real rows, the
#: three bodies run
_MAX_ROW_WASTE = 1.25
_VMEM_LIMIT_BYTES = 48 * 2**20
#: what the blocks may take of it: the compiler's own scratch needs the rest
_VMEM_BUDGET_BYTES = 36 * 2**20


def _axis_classes(dim: int, stride: int, pool_size: int):
    """``(windows, classes)`` of one axis of length ``dim``: the Pooler's
    window count, and the maximal runs ``(start, stop, members)`` of
    indices that belong to the same non-empty set of windows."""
    start = pool_size // 2
    w = 2 * (pool_size // 2)
    windows = max(1, -(-(dim - start) // stride))
    member = [
        tuple(i for i in range(windows) if i * stride <= x < i * stride + w)
        for x in range(dim)
    ]
    classes = []
    x = 0
    while x < dim:
        stop = x
        while stop < dim and member[stop] == member[x]:
            stop += 1
        if member[x]:
            classes.append((x, stop, member[x]))
        x = stop
    return windows, classes


@dataclasses.dataclass(frozen=True)
class PoolPlan:
    """Patch-row layout of one image: ``groups`` are ``(x0, x1, y0, y1,
    row_start)`` blocks of window positions, laid out row-major from
    ``row_start``; ``cells[c]`` lists the groups summed into pooled cell
    ``c = px·npy + py``."""

    npx: int
    npy: int
    groups: Tuple[Tuple[int, int, int, int, int], ...]
    cells: Tuple[Tuple[int, ...], ...]
    rows: int
    real_rows: int


@functools.lru_cache(maxsize=None)
def pool_plan(res_x: int, res_y: int, stride: int, pool_size: int) -> PoolPlan:
    npx, xs = _axis_classes(res_x, stride, pool_size)
    npy, ys = _axis_classes(res_y, stride, pool_size)
    groups = []
    cells: List[List[int]] = [[] for _ in range(npx * npy)]
    row = real = 0
    for x0, x1, mx in xs:
        for y0, y1, my in ys:
            for px in mx:
                for py in my:
                    cells[px * npy + py].append(len(groups))
            groups.append((x0, x1, y0, y1, row))
            count = (x1 - x0) * (y1 - y0)
            real += count
            row += -(-count // _ROW_ALIGN) * _ROW_ALIGN
    return PoolPlan(
        npx, npy, tuple(groups), tuple(tuple(c) for c in cells), row, real
    )


def _tile_k(k: int) -> Tuple[int, int]:
    """``(tile, padded K)``: the fewest tiles of at most ``_MAX_TILE_K``
    lanes, each a multiple of the lane width."""
    tiles = -(-k // _MAX_TILE_K)
    tile = -(-(-(-k // tiles)) // _LANES) * _LANES
    return tile, tile * tiles


def _tile_n(plan: PoolPlan, tk: int) -> int:
    """Images a grid step: the most, up to ``_TILE_N``, whose double-buffered
    blocks (patch rows bf16, 1/sd a lane of a float32 tile, pooled sums)
    fit the VMEM budget beside the filter tile and the product's
    (rows, tile) float32; 0 where not one image does."""
    fixed = 4 * plan.rows * tk + 2 * 2 * _LANES * tk + 4 * 2 * 8 * tk
    image = 2 * (
        (2 + 4) * _LANES * plan.rows + 4 * 8 * -(-len(plan.cells) // 4) * tk
    )
    return max(0, min(_TILE_N, (_VMEM_BUDGET_BYTES - fixed) // image))


def supported(
    res_x: int, res_y: int, contraction: int, k: int, stride: int,
    pool_size: int,
) -> bool:
    """Whether the kernel's tiling admits these shapes: one contraction
    pass (a patch fits the lane width), every pooled cell fed, the row
    padding of the pool classes within ``_MAX_ROW_WASTE``, and an image's
    blocks within the VMEM budget."""
    if res_x < 1 or res_y < 1 or contraction > _LANES:
        return False
    plan = pool_plan(res_x, res_y, stride, pool_size)
    if not plan.real_rows or any(not c for c in plan.cells):
        return False
    if plan.rows > _MAX_ROW_WASTE * plan.real_rows:
        return False
    return _tile_n(plan, _tile_k(k)[0]) >= 1


def scratch_bytes(res_x: int, res_y: int, stride: int, pool_size: int) -> int:
    """HBM bytes ONE image takes between the XLA operations around the
    kernel: the windows and the patch rows (bf16, 128 lanes) and 1/sd a row
    (float32, one lane of a padded tile); the kernel's output takes the
    windows' place. The TPU compiler gives 807 KB an image for the program
    at 1,024 images and 10,000 filters; this counts 803."""
    return 8 * _LANES * pool_plan(res_x, res_y, stride, pool_size).rows


def _by_group(a, plan: PoolPlan):
    """(n, rx, ry, c) → (n, plan.rows, c): the plan's groups one after
    another, each padded with zero rows to its aligned length."""
    n, c = a.shape[0], a.shape[-1]
    parts = []
    for x0, x1, y0, y1, _ in plan.groups:
        count = (x1 - x0) * (y1 - y0)
        block = a[:, x0:x1, y0:y1, :].reshape(n, count, c)
        pad = -count % _ROW_ALIGN
        if pad:
            block = jnp.pad(block, ((0, 0), (0, pad), (0, 0)))
        parts.append(block)
    return jnp.concatenate(parts, axis=1) if len(parts) > 1 else parts[0]


def _kernel(
    p_ref, inv_ref, f_ref, lo_ref, hi_ref, o_ref, acc_ref, *,
    plan: PoolPlan, max_val: float, images: int,
):
    from jax.experimental import pallas as pl

    tk = f_ref.shape[1]
    # x − α = t − (bias + α) and −x − α = (bias − α) − t for x = t − bias
    lo = jnp.broadcast_to(lo_ref[...], (8, tk))
    hi = jnp.broadcast_to(hi_ref[...], (8, tk))
    zeros = jnp.zeros((8, tk), jnp.float32)

    def halves(i, r):
        """Both rectified halves of the 8 window rows from ``r``."""
        t = acc_ref[pl.ds(r, 8), :] * inv_ref[i, pl.ds(r, 8), :]
        return jnp.maximum(max_val, t - lo), jnp.maximum(max_val, hi - t)

    def one_image(i, carry):
        acc_ref[...] = jnp.dot(
            p_ref[i], f_ref[...], preferred_element_type=jnp.float32
        )                                                 # (rows, TK), MXU
        sums = []
        for x0, x1, y0, y1, start in plan.groups:
            count = (x1 - x0) * (y1 - y0)

            def whole_rows(j, acc):
                p, q = halves(i, pl.multiple_of(start + 8 * j, 8))
                return acc[0] + p, acc[1] + q

            pos, neg = jax.lax.fori_loop(
                0, count // 8, whole_rows, (zeros, zeros), unroll=True
            )
            live = count % 8
            if live:  # the group's last rows: its padding adds nothing
                p, q = halves(i, start + count - live)
                keep = jax.lax.broadcasted_iota(jnp.int32, (8, tk), 0) < live
                pos = pos + jnp.where(keep, p, 0.0)
                neg = neg + jnp.where(keep, q, 0.0)
            sums.append((pos, neg))
        for c, members in enumerate(plan.cells):
            # rows [py, px, half]: y-major, as ``vectorize_images`` flattens
            row = 2 * ((c % plan.npy) * plan.npx + c // plan.npy)
            pos = sums[members[0]][0]
            neg = sums[members[0]][1]
            for g in members[1:]:
                pos = pos + sums[g][0]
                neg = neg + sums[g][1]
            o_ref[i, row:row + 1, :] = jnp.sum(pos, axis=0, keepdims=True)
            o_ref[i, row + 1:row + 2, :] = jnp.sum(neg, axis=0, keepdims=True)
        return carry

    jax.lax.fori_loop(0, images, one_image, 0)


def conv_rectify_pool(
    X, filters, inv_sd, bias, *, patch: int, alpha: float, max_val: float,
    stride: int, pool_size: int, interpret: bool = False,
):
    """``Pooler(stride, pool_size, None, "sum")`` of ``SymmetricRectifier(
    max_val, alpha)`` of ``conv(X, filters) · inv_sd − bias`` as
    (n, npx, npy, 2·K) float32, the (n, rx, ry, K) array never in HBM.

    ``X`` (n, xd, yd, C) and ``filters`` (K, patch²·C, rows in the
    Convolver's layout c + px·C + py·C·patch) are contracted at bf16 with
    float32 accumulation; ``inv_sd`` (n, rx, ry) float32 or None scales each
    window's products, ``bias`` (K,) float32 or None is taken from them.
    """
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    n, xd, yd, C = X.shape
    K = filters.shape[0]
    S = patch
    rx, ry = xd - S + 1, yd - S + 1
    m = S * S * C
    plan = pool_plan(rx, ry, stride, pool_size)
    n_cells = len(plan.cells)

    # the windows as rows of 128 lanes, lane c + px·C + py·C·S (the filters'
    # own layout) and zeros beyond: one convolution with a 0/1 kernel on the
    # image rounded to bf16, so each lane is that rounding of one pixel
    lane = np.arange(m).reshape(S, S, C).transpose(1, 0, 2)  # [px, py, c]
    pick = np.zeros((S, S, C, _LANES), np.float32)
    np.put_along_axis(pick, lane[..., None], 1.0, axis=-1)
    windows = jax.lax.conv_general_dilated(
        X.astype(jnp.bfloat16), jnp.asarray(pick, jnp.bfloat16), (1, 1),
        "VALID", dimension_numbers=("NHWC", "HWIO", "NHWC"),
    ).astype(jnp.bfloat16)  # (n, rx, ry, 128); exact: one pixel a lane
    rows = _by_group(windows, plan)
    if inv_sd is None:
        inv_sd = jnp.ones((n, rx, ry), jnp.float32)
    inv = _by_group(inv_sd.astype(jnp.float32)[..., None], plan)

    # filters (m, K), zero rows and columns to tile
    tk, kp = _tile_k(K)
    f = jnp.pad(
        filters.astype(jnp.bfloat16).T, ((0, _LANES - m), (0, kp - K))
    )
    b = jnp.zeros((K,), jnp.float32) if bias is None else bias
    b = jnp.pad(b.astype(jnp.float32), (0, kp - K))[None, :]

    tile_n = min(_tile_n(plan, tk), n)
    n_pad = -n % tile_n
    if n_pad:
        rows = jnp.pad(rows, ((0, n_pad), (0, 0), (0, 0)))
        inv = jnp.pad(inv, ((0, n_pad), (0, 0), (0, 0)))

    pooled = pl.pallas_call(
        functools.partial(
            _kernel, plan=plan, max_val=float(max_val), images=tile_n,
        ),
        grid=((n + n_pad) // tile_n, kp // tk),
        in_specs=[
            pl.BlockSpec((tile_n, plan.rows, _LANES), lambda i, j: (i, 0, 0),
                         memory_space=pltpu.VMEM),
            pl.BlockSpec((tile_n, plan.rows, 1), lambda i, j: (i, 0, 0),
                         memory_space=pltpu.VMEM),
            pl.BlockSpec((_LANES, tk), lambda i, j: (0, j),
                         memory_space=pltpu.VMEM),
            pl.BlockSpec((1, tk), lambda i, j: (0, j),
                         memory_space=pltpu.VMEM),
            pl.BlockSpec((1, tk), lambda i, j: (0, j),
                         memory_space=pltpu.VMEM),
        ],
        out_specs=pl.BlockSpec(
            (tile_n, 2 * n_cells, tk), lambda i, j: (i, 0, j),
            memory_space=pltpu.VMEM,
        ),
        out_shape=jax.ShapeDtypeStruct(
            (n + n_pad, 2 * n_cells, K), jnp.float32
        ),
        scratch_shapes=[pltpu.VMEM((plan.rows, tk), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary"),
            vmem_limit_bytes=_VMEM_LIMIT_BYTES,
        ),
        name="conv_rectify_pool",
        interpret=interpret,
    )(rows, inv, f, b + alpha, b - alpha)

    # (n, [py, px, half], K) to (n, px, py, [half, K]), the rectifier's
    # channel order, positive half first. The rows come y-major because the
    # features' one reader, ``vectorize_images``, swaps x and y back: the
    # two transposes cancel in the program and the pooled sums are laid out
    # once
    pooled = pooled[:n].reshape(n, plan.npy, plan.npx, 2 * K)
    return jnp.transpose(pooled, (0, 2, 1, 3))


def kernel_mode() -> Optional[str]:
    """How the kernel would run in this process: ``"compiled"`` on one TPU
    chip, None anywhere else — the callers then run the XLA bodies, which
    XLA can partition over a mesh as a custom call cannot. (The CPU tests
    run it as ``"interpret"``.)"""
    on_one_tpu = jax.default_backend() == "tpu" and jax.device_count() == 1
    return "compiled" if on_one_tpu else None
