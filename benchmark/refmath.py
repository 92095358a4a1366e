"""Arithmetic the plain references share: a matrix product at a stated
precision, the centred Gram of a featurized row set accumulated block of
rows by block of rows, and block-coordinate-descent ridge on it.

Nothing here imports the program. ``precision`` is one of

* ``highest`` — float32 (six bf16 passes on a TPU): the reference;
* ``high``    — three bf16 passes: what the program's solvers state;
* ``bf16``    — operands rounded to bfloat16, float32 accumulation: one
  pass, what a TPU does to a float32 product by default;
* ``fp8``     — operands rounded to float8_e4m3fn, float32 accumulation.

``bf16`` and ``fp8`` round explicitly, so a control reads the same on the
CPU (where ``jax.lax.Precision`` changes nothing) as on the chip.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

PRECISIONS = ("highest", "high", "bf16", "fp8")

#: the nearest precision below each, the step a later PR would be tempted by
BELOW = {"highest": "high", "high": "bf16", "bf16": "fp8"}


def mm(a, b, precision: str):
    """``a @ b`` in float32 at ``precision``."""
    if precision == "highest":
        return jnp.matmul(a, b, precision=jax.lax.Precision.HIGHEST)
    if precision == "high":
        return jnp.matmul(a, b, precision=jax.lax.Precision.HIGH)
    if precision == "bf16":
        narrow = jnp.bfloat16
    elif precision == "fp8":
        narrow = jnp.float8_e4m3fn
    else:
        raise ValueError(
            f"precision {precision!r} is none of {', '.join(PRECISIONS)}"
        )
    a = a.astype(narrow).astype(jnp.float32)
    b = b.astype(narrow).astype(jnp.float32)
    return jnp.matmul(a, b, precision=jax.lax.Precision.HIGHEST)


@functools.partial(jax.jit, static_argnums=(2,))
def _row_block(X, start, rows: int):
    return jax.lax.dynamic_slice_in_dim(X, start, rows, axis=0)


def row_blocks(X, rows: int):
    """``X`` in blocks of ``rows`` rows (the last may be shorter), each cut
    by ONE compiled program whatever its start: a Python slice would
    compile one program a block."""
    n = X.shape[0]
    rows = min(rows, n)
    whole = n - n % rows
    for start in range(0, whole, rows):
        yield _row_block(X, start, rows)
    if whole < n:
        yield _row_block(X, whole, n - whole)


def one_hot_pm(labels, num_classes: int):
    """The ±1 class indicators a KeystoneML classifier regresses on
    (ClassLabelIndicators: +1 for the class, −1 elsewhere)."""
    return 2.0 * jax.nn.one_hot(labels, num_classes, dtype=jnp.float32) - 1.0


def normal_equations(feat, X, Y, *, rows_per_block: int, precision: str):
    """Column means and the CENTRED Gram / cross-product of the featurized
    ``X`` against ``Y``, one block of rows at a time so that the featurized
    rows never sit on the device whole. ``feat`` is ``(apply, params)`` with
    ``apply(params, rows)``; the parameters are handed to every compiled
    function as an argument, never closed over (a 16,384×440 constant in a
    program text costs the compiler a minute). Returns ``(G, C, mean,
    y_mean)`` with ``G = Σ (f−mean)ᵀ(f−mean)`` and
    ``C = Σ (f−mean)ᵀ(y−y_mean)``."""
    apply, params = feat
    n = X.shape[0]

    @jax.jit
    def sums(params, Xb):
        return jnp.sum(apply(params, Xb), axis=0)

    @jax.jit
    def accumulate(params, G, C, Xb, Yb, mean, y_mean):
        F = apply(params, Xb) - mean
        return G + mm(F.T, F, precision), C + mm(F.T, Yb - y_mean, precision)

    total = None
    for Xb in row_blocks(X, rows_per_block):
        s = sums(params, Xb)
        total = s if total is None else total + s
    mean = total / n
    y_mean = jnp.mean(Y, axis=0)
    d, k = mean.shape[0], Y.shape[1]
    G = jnp.zeros((d, d), jnp.float32)
    C = jnp.zeros((d, k), jnp.float32)
    blocks = zip(row_blocks(X, rows_per_block), row_blocks(Y, rows_per_block))
    for Xb, Yb in blocks:
        G, C = accumulate(params, G, C, Xb, Yb, mean, y_mean)
    return G, C, mean, y_mean


def bcd_ridge(G, C, *, block_size: int, epochs: int, lam: float,
              precision: str):
    """Block coordinate descent on ``min ‖F W − Y‖² + λ Σ‖W_j‖²`` from the
    normal equations: for each block j in turn,
    ``W_j ← (G_jj + λI)⁻¹ (C_j − Σ_{i≠j} G_ji W_i)``, ``epochs`` sweeps from
    zero. With one block this is the exact ridge solution.

    The products run on the device at ``precision``; each block's Cholesky
    factor is taken once, on the host in float64 (a 4,096-wide Cholesky
    compiles for minutes on the chip and its executable crowds the compile
    cache, and the reference has no use for either)."""
    import numpy as np
    import scipy.linalg

    d, k = C.shape
    if d % block_size:
        raise ValueError(f"d={d} does not split into blocks of {block_size}")
    starts = range(0, d, block_size)
    rows = [_row_block(G, j, block_size) for j in starts]
    factors = [
        scipy.linalg.cho_factor(
            np.asarray(Gj[:, j : j + block_size], np.float64)
            + lam * np.eye(block_size),
            lower=True,
        )
        for j, Gj in zip(starts, rows)
    ]

    @jax.jit
    def rhs(Gj, Cj, W, Wj):
        # C_j − Σ_{i≠j} G_ji W_i, the own block's term added back
        own = jax.lax.dynamic_slice_in_dim(Gj, Wj[1], block_size, axis=1)
        return Cj - mm(Gj, W, precision) + mm(own, Wj[0], precision)

    W = jnp.zeros((d, k), jnp.float32)
    for _ in range(epochs):
        for j, Gj, factor in zip(starts, rows, factors):
            Wj = _row_block(W, j, block_size)
            r = rhs(Gj, _row_block(C, j, block_size), W, (Wj, j))
            solved = scipy.linalg.cho_solve(factor, np.asarray(r, np.float64))
            W = jax.lax.dynamic_update_slice_in_dim(
                W, jnp.asarray(solved, jnp.float32), j, axis=0
            )
    return W


def fit_linear(feat, config: dict, X, y, *, precision: dict,
               rows_per_block: int) -> dict:
    """The model ``{"W", "b", "mean"}`` a KeystoneML classifier pipeline
    defines: ±1 indicators regressed on the centred features by block
    coordinate descent (``block_size``, ``epochs``, ``lam`` of ``config``).
    ``config["reference_rows"]`` overrides the rows a block (tests)."""
    Y = one_hot_pm(y, config["num_classes"])
    G, C, mean, y_mean = normal_equations(
        feat, X, Y, precision=precision["solver"],
        rows_per_block=config.get("reference_rows", rows_per_block),
    )
    W = bcd_ridge(
        G, C, block_size=config["block_size"], epochs=config["epochs"],
        lam=config["lam"], precision=precision["solver"],
    )
    return {"W": W, "b": y_mean, "mean": mean}


def scores(feat, X, model, *, rows_per_block: int, precision: str):
    """``(featurized X − mean) W + b`` block of rows by block of rows."""
    apply, params = feat
    model = {k: jnp.asarray(v, jnp.float32) for k, v in model.items()}

    @jax.jit
    def block(params, model, Xb):
        centred = apply(params, Xb) - model["mean"]
        return mm(centred, model["W"], precision) + model["b"]

    return jnp.concatenate(
        [block(params, model, Xb) for Xb in row_blocks(X, rows_per_block)],
        axis=0,
    )
