"""The plain reference of ``timit_cos4``: TimitPipeline written out in
``jax.numpy`` — ``cos(x Wᵀ + b)`` random features (Gaussian ``W`` scaled
by γ, ``b`` uniform on [0, 2π)), block coordinate descent over the four
4,096-wide blocks for five epochs, argmax — and the seeded synthetic
frames it is fed.

Imports nothing of the program. Branch ``i``'s ``W`` and ``b`` follow the
published recipe from ``seed + i`` (``jax.random.normal`` / ``uniform``
on the two halves of the split key), so the configuration's
``feature_seed`` gives the program and the reference the same featurizer
without either handing the other a table.
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp

from benchmark import refmath


@functools.partial(jax.jit, static_argnums=(2, 3))
def _rows(task_key, row_key, n: int, sizes: tuple):
    dim, num_classes, class_scale, sigma = sizes
    protos = class_scale * jax.random.normal(
        task_key, (num_classes, dim), jnp.float32
    )
    ky, kn = jax.random.split(row_key)
    y = jax.random.randint(ky, (n,), 0, num_classes)
    X = protos[y] + sigma * jax.random.normal(kn, (n, dim), jnp.float32)
    return X, y


def make_rows(config: dict, seed: int, n: int):
    """``(X, y)``: ``n`` frames of the configuration's task on the device,
    drawn from ``seed``: fixed Gaussian class prototypes in 440 dims (the
    configuration's, ``task_seed``) plus noise (``synthetic_timit``'s
    recipe; that one is host numpy), scaled to about unit variance a
    dimension with classes that overlap as TIMIT's phones do."""
    a = config["assumed"]
    sizes = (
        config["input_dim"], config["num_classes"], a["class_scale"],
        a["noise_sigma"],
    )
    return _rows(
        jax.random.PRNGKey(config["task_seed"]), jax.random.PRNGKey(seed),
        n, sizes,
    )


def featurizer(config: dict, precision: str):
    """``(apply, params)``: rows (n, 440) → (n, 16384), the four cosine
    branches side by side; ``apply(params, rows)``."""
    Ws, bs = [], []
    for i in range(config["num_cosines"]):
        key = jax.random.PRNGKey(config["feature_seed"] + i)
        kw, kb = jax.random.split(key)
        Ws.append(config["gamma"] * jax.random.normal(
            kw, (config["cosine_features"], config["input_dim"]), jnp.float32
        ))
        bs.append(2 * math.pi * jax.random.uniform(
            kb, (config["cosine_features"],), jnp.float32
        ))
    params = {"Wt": jnp.concatenate(Ws, axis=0).T, "b": jnp.concatenate(bs)}

    def apply(params, X):
        return jnp.cos(refmath.mm(X, params["Wt"], precision) + params["b"])

    return apply, params


def fit(config: dict, X, y, *, precision: dict):
    """The model ``{"W", "b", "mean"}`` the configuration defines."""
    return refmath.fit_linear(
        featurizer(config, precision["featurizer"]), config, X, y,
        precision=precision, rows_per_block=8192,
    )
