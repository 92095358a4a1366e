"""k-means++ (parity: nodes/learning/KMeansPlusPlus.scala:16,83).

One round = the k-means++ initialization; more rounds = Lloyd's algorithm.
Everything — including the sequential D²-weighted seeding — runs as
compiled device programs: the seeding is one ``lax.scan`` over k−1 steps
with on-device categorical draws, and Lloyd's iterations are one
``lax.while_loop`` with the reference's stop-on-non-improving-cost
semantics. The first cut kept the seeding host-side ("inherently
sequential and tiny: k draws") — but each draw fetched an n-element
probability vector to the host, k−1 blocking fetches that each drain the
device queue; as one program the whole fit is a handful of dispatches.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from ...data.dataset import Dataset
from ...obs.tracer import span
from ...workflow.transformer import Estimator, Transformer
from ...utils.params import as_param

# Distances and the centres' moments at float32 whatever the backend's
# default (one bf16 pass on a TPU). The seeding draws each centre by an
# arg-max over the log of these distances and the assignments by an
# arg-min: an error of 4e-3 picks other points than a float32 run does,
# and from another seeding comes another codebook. A centre is a mean of
# descriptors of 0..255: rounded to bfloat16 it is off by a whole unit.
# The products are n × d × k with k a few hundred, once a fit: nothing
# beside the featurizer they are learnt for.
_PREC = jax.lax.Precision.HIGHEST


@jax.jit
def _sq_dists(X, means):
    """½‖x‖² − x·μ + ½‖μ‖² per (sample, center) — the reference's vectorized
    distance trick (KMeansPlusPlus.scala:34-39)."""
    xsq = 0.5 * jnp.sum(X * X, axis=1, keepdims=True)
    msq = 0.5 * jnp.sum(means * means, axis=1)
    return xsq - jnp.matmul(X, means.T, precision=_PREC) + msq


@jax.jit
def _one_hot_assign(X, means):
    d = _sq_dists(X, means)
    idx = jnp.argmin(d, axis=1)
    return jax.nn.one_hot(idx, means.shape[0], dtype=X.dtype)


@functools.partial(jax.jit, static_argnames=("k",))
def _seed_plus_plus(X, key, k: int):
    """k-means++ seeding as ONE program: scan over k−1 D²-weighted draws
    (parity: the seeding loop of KMeansPlusPlusEstimator; the degenerate
    all-points-covered case falls back to a uniform draw, as the host
    version did)."""
    n = X.shape[0]
    xsq_half = 0.5 * jnp.sum(X * X, axis=1)
    k0, key = jax.random.split(key)
    c0 = X[jax.random.randint(k0, (), 0, n)]
    if k == 1:
        return c0[None]

    def step(carry, _):
        cur_sq, last_c, key = carry
        sq_new = (
            xsq_half - jnp.matmul(X, last_c, precision=_PREC)
            + 0.5 * jnp.dot(last_c, last_c)
        )
        cur_sq = jnp.minimum(cur_sq, sq_new)
        probs = jnp.maximum(cur_sq, 0.0)
        key, kw, ku = jax.random.split(key, 3)
        # log(0) = −inf excludes already-covered points from the draw
        idx_weighted = jax.random.categorical(kw, jnp.log(probs))
        idx_uniform = jax.random.randint(ku, (), 0, n)
        idx = jnp.where(jnp.sum(probs) > 0, idx_weighted, idx_uniform)
        new_c = X[idx]
        return (cur_sq, new_c, key), new_c

    init = (jnp.full((n,), jnp.inf, X.dtype), c0, key)
    _, rest = jax.lax.scan(step, init, None, length=k - 1)
    return jnp.concatenate([c0[None], rest], axis=0)


@functools.partial(
    jax.jit, static_argnames=("max_iterations", "stop_tolerance")
)
def _lloyd_loop(X, means, *, max_iterations: int, stop_tolerance: float):
    """Lloyd's iterations as ONE ``lax.while_loop`` program. Break
    semantics match the host loop exactly: when the cost stops improving,
    KEEP the current means (no final update); empty clusters stay where
    they were."""
    k = means.shape[0]

    def cond(carry):
        i, done, *_ = carry
        return (i < max_iterations) & ~done

    def body(carry):
        i, done, prev_cost, has_prev, means = carry
        dists = _sq_dists(X, means)
        cost = jnp.mean(jnp.min(dists, axis=1))
        stop = has_prev & ~(
            prev_cost - cost >= stop_tolerance * jnp.abs(prev_cost)
        )
        assign = jax.nn.one_hot(jnp.argmin(dists, axis=1), k, dtype=X.dtype)
        counts = assign.sum(axis=0)
        new_means = (
            jnp.matmul(assign.T, X, precision=_PREC)
            / jnp.maximum(counts, 1.0)[:, None]
        )
        new_means = jnp.where((counts > 0)[:, None], new_means, means)
        m2 = jnp.where(stop, means, new_means)
        return (i + 1, stop, cost, True, m2)

    init = (
        jnp.int32(0), jnp.bool_(False), jnp.float32(0.0), jnp.bool_(False),
        means,
    )
    *_, means = jax.lax.while_loop(cond, body, init)
    return means


class KMeansModel(Transformer):
    """Maps each vector to its one-hot nearest-center assignment
    (parity: KMeansModel, KMeansPlusPlus.scala:16-78)."""

    def __init__(self, means):
        self.means = as_param(means)

    def trace_batch(self, X):
        return _one_hot_assign(X, self.means)


class KMeansPlusPlusEstimator(Estimator):
    """(parity: KMeansPlusPlusEstimator, KMeansPlusPlus.scala:83-181)."""

    def __init__(self, num_means: int, max_iterations: int,
                 stop_tolerance: float = 1e-3, seed: int = 0):
        self.num_means = num_means
        self.max_iterations = max_iterations
        self.stop_tolerance = stop_tolerance
        self.seed = seed

    def fit(self, data: Dataset) -> KMeansModel:
        return self.fit_matrix(Dataset.of(data).to_array())

    def fit_matrix(self, X) -> KMeansModel:
        X = jnp.asarray(X, dtype=jnp.float32)
        with span("kmeans.seed", centres=self.num_means) as sp:
            means = _seed_plus_plus(
                X, jax.random.PRNGKey(self.seed), self.num_means
            )
            sp.sync_on(means)
        means = _lloyd_loop(
            X, means,
            max_iterations=self.max_iterations,
            stop_tolerance=self.stop_tolerance,
        )
        return KMeansModel(means)
