"""Pallas TPU kernel: cosine random features → a linear map's scores, with
the features kept on the chip.

Written out as XLA operations (``nodes/stats`` ``CosineRandomFeatures`` a
branch → gather → ``VectorCombiner`` → ``BlockLinearMapper``) the chain
writes every branch's (n, 4,096) float32 features to HBM and the map reads
all of them back: 4.3 GB a chunk of 65,536 rows at ``timit_cos4``'s 16,384
features, and that read alone was 5.84 of a chunk's 14.41 ms of device
time, at the memory's rate (PERF.md §5). Here a (row tile, feature
tile) block of them lives in VMEM only:

* over (row tile, feature tile), the feature axis last and reduced: the
  row tile of X rounded to bf16 once, then a feature tile at a time the
  product with the tile of Wᵀ on the MXU (float32 accumulation), then on
  the VPU the bias, :func:`.bounded_cos.cos_bounded`, the map's feature
  mean taken out in float32, the rounding to bf16, then the product with
  the model's tile on the MXU into a (rows, classes) float32 accumulator;
* only the scores, with the intercept, are written.

That is the rounding XLA gives the chain written out at the TPU's default
precision, one bf16 pass a product (``benchmark/configs/timit_cos4.json``:
``"featurizer": "bf16"``, ``"apply": "bf16"``); the sums differ from it in
their order alone. The cosine is the bounded one: its caller proves
``|x·w + b| <= LIMIT`` for every argument first
(``nodes/learning/cosine_linear.py`` does, per call, on the device).

X reaches the kernel transposed, (dim, rows): the TPU lays a float32
(rows, 440) array out column-major, so the transpose is free there, and the
kernel turns the row tile back once, as it rounds it.
"""

from __future__ import annotations

import importlib
import sys
import threading
from typing import Optional

import jax
import jax.numpy as jnp

from . import kernel_mode  # noqa: F401  (how this kernel runs here)
from .bounded_cos import cos_bounded

_LANES = 128
#: most rows a grid step (X's tile, rounded once, stays in VMEM across the
#: feature tiles). The tiles were chosen on a TPU v5e at ``timit_cos4``'s
#: widths, a chunk of 65,536 rows (PERF.md §6): 2,048 × 2,048 / 256
#: 9.06 ms, 1,024 × 2,048 / 256 9.15, 1,024 × 1,024 / 512 9.18, 1,024 ×
#: 1,024 / 256 9.44, 1,024 × 512 / 256 9.88, 1,024 × 1,024 / 128 9.96 —
#: the products' floor at the published peak is 8.4
_TILE_ROWS = 2048
#: widest feature tile, in lanes
_TILE_FEATURES = 2048
#: rows of the step's tile taken through product, cosine and scores at a
#: time: the tile's float32 arguments never exist whole
_SUB_ROWS = 256
_VMEM_LIMIT_BYTES = 48 * 2**20
#: what the blocks may take of it: the compiler's own scratch needs the rest
_VMEM_BUDGET_BYTES = 36 * 2**20


def _round_up(x: int, m: int) -> int:
    return -(-x // m) * m


def _tile_features(features: int) -> int:
    """The widest feature tile, a multiple of the lane width up to
    ``_TILE_FEATURES``, that divides ``features``; 0 where none does."""
    for tile in range(_TILE_FEATURES, 0, -_LANES):
        if features % tile == 0:
            return tile
    return 0


def _tile_rows(rows: int) -> int:
    return min(_TILE_ROWS, _round_up(max(rows, 1), _SUB_ROWS))


def _vmem_bytes(dim: int, tm: int, tf: int, classes: int) -> int:
    """VMEM the blocks take: X's tile (float32, double-buffered) and its
    bf16 copy, Wᵀ's tile, bias and mean, the model's tile, the scores'
    block and accumulator, and one sub-tile's arguments and features."""
    k = _round_up(classes, _LANES)
    x = 2 * 4 * _round_up(dim, 8) * tm + 2 * tm * _round_up(dim, _LANES)
    w = 2 * 2 * _round_up(dim, 16) * tf + 2 * 2 * 4 * 8 * tf
    model = 2 * 2 * tf * k + 2 * 4 * 8 * k
    scores = 3 * 4 * tm * k
    sub = 2 * (4 + 2) * _SUB_ROWS * tf
    return x + w + model + scores + sub


def supported(dim: int, features: int, classes: int) -> bool:
    """Whether the tiling admits these widths: ``dim`` inputs a whole
    number of float32 sublanes, ``features`` a whole number of lanes, and
    the blocks within the VMEM budget."""
    if dim < 1 or dim % 8 or classes < 1 or features < 1 or features % _LANES:
        return False
    tf = _tile_features(features)
    return _vmem_bytes(dim, _TILE_ROWS, tf, classes) <= _VMEM_BUDGET_BYTES


def scratch_bytes(dim: int) -> int:
    """HBM bytes ONE row may hold between the XLA operations around the
    kernel: its copy where the rows are padded to a whole tile (a ragged
    count). Nothing else — the transposed rows are X's own bytes on the
    TPU, and the features never leave VMEM."""
    return 4 * dim


def _kernel(
    x_ref, w_ref, b_ref, mu_ref, m_ref, c_ref, o_ref, xb_ref, acc_ref
):
    from jax.experimental import pallas as pl

    j = pl.program_id(1)

    @pl.when(j == 0)
    def _():
        xb_ref[...] = x_ref[...].T.astype(jnp.bfloat16)       # (TM, dim)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    def rows(r, carry):
        at = pl.ds(pl.multiple_of(r * _SUB_ROWS, _SUB_ROWS), _SUB_ROWS)
        z = jnp.dot(
            xb_ref[at, :], w_ref[...], preferred_element_type=jnp.float32
        ) + b_ref[...]                                        # MXU, (S, TF)
        f = (cos_bounded(z) - mu_ref[...]).astype(jnp.bfloat16)
        acc_ref[at, :] += jnp.dot(
            f, m_ref[...], preferred_element_type=jnp.float32
        )                                                     # MXU, (S, k)
        return carry

    jax.lax.fori_loop(0, acc_ref.shape[0] // _SUB_ROWS, rows, 0)

    @pl.when(j == pl.num_programs(1) - 1)
    def _():
        o_ref[...] = acc_ref[...] + c_ref[...]


def cosine_scores(
    X, wt, b, mean: Optional[jnp.ndarray], model,
    intercept: Optional[jnp.ndarray], *, interpret: bool = False,
):
    """``(cos_bounded(X Wᵀ + b) − mean) · model + intercept`` as (n,
    classes) float32, the (n, features) array never in HBM.

    ``X`` (n, dim) float32; ``wt`` the branches' Wᵀ side by side, (dim,
    features), and ``b`` their biases, (features,) float32; ``mean``
    (features,) float32 or None; ``model`` (features, classes);
    ``intercept`` (classes,) float32 or None. Both products take bf16
    operands with float32 accumulation; the caller has proved every
    argument of the cosine within ``bounded_cos.LIMIT``.
    """
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    n, dim = X.shape
    features, classes = model.shape
    tm, tf = _tile_rows(n), _tile_features(features)
    pad = -n % tm
    if pad:
        X = jnp.pad(X, ((0, pad), (0, 0)))
    mu = (
        jnp.zeros((1, features), jnp.float32) if mean is None
        else mean.astype(jnp.float32)[None, :]
    )
    c = (
        jnp.zeros((1, classes), jnp.float32) if intercept is None
        else intercept.astype(jnp.float32)[None, :]
    )
    scores = pl.pallas_call(
        _kernel,
        grid=((n + pad) // tm, features // tf),
        in_specs=[
            pl.BlockSpec((dim, tm), lambda i, j: (0, i),
                         memory_space=pltpu.VMEM),
            pl.BlockSpec((dim, tf), lambda i, j: (0, j),
                         memory_space=pltpu.VMEM),
            pl.BlockSpec((1, tf), lambda i, j: (0, j),
                         memory_space=pltpu.VMEM),
            pl.BlockSpec((1, tf), lambda i, j: (0, j),
                         memory_space=pltpu.VMEM),
            pl.BlockSpec((tf, classes), lambda i, j: (j, 0),
                         memory_space=pltpu.VMEM),
            pl.BlockSpec((1, classes), lambda i, j: (0, 0),
                         memory_space=pltpu.VMEM),
        ],
        out_specs=pl.BlockSpec(
            (tm, classes), lambda i, j: (i, 0), memory_space=pltpu.VMEM
        ),
        out_shape=jax.ShapeDtypeStruct((n + pad, classes), jnp.float32),
        scratch_shapes=[
            pltpu.VMEM((tm, dim), jnp.bfloat16),
            pltpu.VMEM((tm, classes), jnp.float32),
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary"),
            vmem_limit_bytes=_VMEM_LIMIT_BYTES,
        ),
        name="cosine_scores",
        interpret=interpret,
    )(
        X.T, wt.astype(jnp.bfloat16), b.astype(jnp.float32)[None, :], mu,
        model.astype(jnp.bfloat16), c,
    )
    return scores[:n] if pad else scores


#: ``jax._src.pallas.pallas_call`` imports Pallas's Mosaic GPU interpreter
#: on every backend — 1.0 of the 1.2 s Pallas takes to import on a TPU
#: v5e's host — and keeps a stand-in where that import fails
_GPU_INTERPRETER = "jax._src.pallas.mosaic_gpu.interpret.interpret_pallas_call"

_preload_lock = threading.Lock()
_preloading = False


def preload() -> None:
    """Import Pallas for the TPU on a thread of its own, once a process,
    started where a node that will run the kernel is made, so that the
    import overlaps what the process does before the kernel's first trace
    (which takes the import's module lock and waits for what is left of
    it). On a TPU v5e's host the import takes 1.2 s and the set-up of
    ``timit_cos4.apply`` leaves it 1.9 s, in which the interpreter lock is
    shared: without the GPU interpreter it ends before the first trace,
    with it that trace waits 0.5 s (PERF.md §6)."""
    global _preloading
    with _preload_lock:
        if _preloading:
            return
        _preloading = True
    threading.Thread(
        target=_import_for_tpu, name="pallas-import", daemon=True
    ).start()


def _import_for_tpu() -> None:
    """``jax.experimental.pallas.tpu``, the GPU interpreter left out where
    nothing has imported it yet; whole where Pallas will not do without."""
    if _GPU_INTERPRETER not in sys.modules:
        sys.modules[_GPU_INTERPRETER] = None  # its import raises ImportError
        try:
            importlib.import_module("jax.experimental.pallas.tpu")
        except ImportError:
            pass  # imported whole below
        finally:
            if sys.modules.get(_GPU_INTERPRETER, False) is None:
                del sys.modules[_GPU_INTERPRETER]
    importlib.import_module("jax.experimental.pallas.tpu")
