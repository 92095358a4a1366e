"""Pallas TPU kernel: fused Gaussian kernel block  exp(−γ‖x−y‖²).

The KRR hot loop (nodes/learning/kernel.py) computes n×b kernel column
blocks as GEMM → broadcast-add of row/col norms → exp. Under XLA the
(n, b) squared-distance intermediate flows through HBM between the MXU
matmul and the VPU epilogue unless fusion kicks in; this kernel keeps each
(TILE_N, b) tile resident in VMEM — cross-product on the MXU, norms and
exp on the VPU — and writes the finished kernel tile once.

Reference parity: computeKernel (KernelGenerator.scala:138-206), which
does the same −2xy + ‖x‖² + ‖y‖² → exp algebra per Spark partition.

Used on the TPU backend when shapes fit the VMEM budget; everywhere else
(CPU tests, odd shapes) the jnp fallback in nodes/learning/kernel.py
computes the identical values (max abs diff ~1e-9 measured).

Last checked on a chip by chip_smoke.py's ``kernel`` leg (TPU v5e, jax
0.9.0, PR 21): Mosaic compiles it at d=512, b=2048 — the largest b the
budget below admits at that d — the front door picks it, and it matches
the XLA lowering to 6.6e-7 (max abs). Both run the cross product as one
bf16 pass: each is 2.1e-4 from the same algebra at true-f32 GEMM precision.

Timed in round 5, before PR 1, and not re-measured since (n=131072, d=512,
b=2048, amortized over 10 dispatches): this kernel 9.7 ms/call (28.4 Tf/s) with <1% trial-to-trial
variance; the XLA lowering of the same algebra 9.2-34.5 ms/call across
trials (8-30 Tf/s). Peak throughput is parity; the win is the stable
tail — the KRR hot loop dispatches hundreds of these blocks back-to-back.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

_TILE_N = 512
# VMEM is ~16 MB/core; keep Xb + one X tile + one out tile well under it.
_VMEM_BUDGET_BYTES = 10 * 2**20


def _kernel(gamma_ref, x_ref, xb_ref, out_ref):
    x = x_ref[:]                      # (TILE_N, d)
    xb = xb_ref[:]                    # (b, d)
    xx = jnp.sum(x * x, axis=1, keepdims=True)          # (TILE_N, 1)
    bb = jnp.sum(xb * xb, axis=1)[None, :]              # (1, b)
    cross = jax.lax.dot_general(
        x, xb, (((1,), (1,)), ((), ())),
        preferred_element_type=jnp.float32,
    )                                                   # (TILE_N, b) on MXU
    sq = xx - 2.0 * cross + bb
    out_ref[:] = jnp.exp(-gamma_ref[0] * jnp.maximum(sq, 0.0))


@functools.partial(jax.jit, static_argnames=("interpret",))
def gaussian_kernel_block_pallas(X, Xb, gamma, interpret: bool = False):
    """(n, d), (b, d) → (n, b) Gaussian kernel block, tiled over n."""
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    X = jnp.asarray(X, jnp.float32)
    Xb = jnp.asarray(Xb, jnp.float32)
    n, d = X.shape
    b = Xb.shape[0]
    n_pad = -n % _TILE_N
    Xp = jnp.pad(X, ((0, n_pad), (0, 0))) if n_pad else X
    gamma_arr = jnp.asarray([gamma], jnp.float32)

    out = pl.pallas_call(
        _kernel,
        grid=((n + n_pad) // _TILE_N,),
        in_specs=[
            pl.BlockSpec(memory_space=pltpu.SMEM),
            pl.BlockSpec((_TILE_N, d), lambda i: (i, 0),
                         memory_space=pltpu.VMEM),
            pl.BlockSpec((b, d), lambda i: (0, 0),
                         memory_space=pltpu.VMEM),
        ],
        out_specs=pl.BlockSpec((_TILE_N, b), lambda i: (i, 0),
                               memory_space=pltpu.VMEM),
        out_shape=jax.ShapeDtypeStruct((n + n_pad, b), jnp.float32),
        interpret=interpret,
    )(gamma_arr, Xp, Xb)
    return out[:n]


def pallas_block_supported(n: int, d: int, b: int) -> bool:
    """Whether the fused kernel's working set fits the VMEM budget on the
    TPU backend (lane alignment: d and b multiples of 128)."""
    if jax.default_backend() != "tpu":
        return False
    if d % 128 or b % 128:
        return False
    working = 4 * (b * d + _TILE_N * d + _TILE_N * b)
    return working <= _VMEM_BUDGET_BYTES
