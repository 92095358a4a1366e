"""The plain reference of ``mnist_fft``: MnistRandomFFT written out in
``jax.numpy`` — random signs, zero-padded FFT, rectifier, ridge on the
centred features, argmax — and the seeded synthetic task it is fed.

Imports nothing of the program. The random signs follow the published
recipe (a fair ±1 per pixel and branch, branch ``i`` seeded ``seed + i``
through ``jax.random.bernoulli``), so the configuration's
``feature_seed`` gives the program and the reference the same featurizer
without either handing the other a table.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from benchmark import refmath


@functools.partial(jax.jit, static_argnums=(2, 3))
def _rows(task_key, row_key, n: int, sizes: tuple):
    image_size, num_classes, latent_dim, radius, sigma_l, sigma_a = sizes
    kmu, ku = jax.random.split(task_key)
    mu = jax.random.normal(kmu, (num_classes, latent_dim), jnp.float32)
    mu = radius * mu / jnp.linalg.norm(mu, axis=1, keepdims=True)
    U, _ = jnp.linalg.qr(
        jax.random.normal(ku, (image_size, latent_dim), jnp.float32)
    )
    ky, ks, kl, ka = jax.random.split(row_key, 4)
    y = jax.random.randint(ky, (n,), 0, num_classes)
    s = jax.random.rademacher(ks, (n,), jnp.float32)
    u = s[:, None] * mu[y] + sigma_l * jax.random.normal(
        kl, (n, latent_dim), jnp.float32
    )
    X = jnp.matmul(u, U.T, precision=jax.lax.Precision.HIGHEST)
    X = X + sigma_a * jax.random.normal(ka, (n, image_size), jnp.float32)
    return X, y


def make_rows(config: dict, seed: int, n: int):
    """``(X, y)``: ``n`` rows of the configuration's task on the device,
    drawn from ``seed``. The task — class means on a sphere in an 8-dim
    latent, a random sign per row (so no linear function of the pixels
    carries the class), an orthonormal embedding into 784 pixels, a little
    ambient noise — is the configuration's (``task_seed``); the rows are
    the seed's."""
    a = config["assumed"]
    sizes = (
        config["image_size"], config["num_classes"], a["latent_dim"],
        a["proto_radius"], a["latent_sigma"], a["ambient_sigma"],
    )
    return _rows(
        jax.random.PRNGKey(config["task_seed"]), jax.random.PRNGKey(seed),
        n, sizes,
    )


def featurizer(config: dict, precision: str):
    """``(apply, params)``: rows (n, 784) → (n, numFFTs·512): for each
    branch, random signs, pad to 1,024, the real part of the first 512 FFT
    bins, max(0, ·). No matrix product, so ``precision`` changes nothing
    here."""
    del precision
    size, padded = config["image_size"], config["fft_size"]
    params = {"signs": jnp.stack([
        2.0 * jax.random.bernoulli(
            jax.random.PRNGKey(config["feature_seed"] + i), 0.5, (size,)
        ).astype(jnp.float32) - 1.0
        for i in range(config["num_ffts"])
    ])}

    def apply(params, X):
        out = []
        for s in params["signs"]:
            Z = jnp.pad(X * s, [(0, 0), (0, padded - size)])
            bins = jnp.fft.rfft(Z, axis=-1).real[:, : padded // 2]
            out.append(jnp.maximum(0.0, bins))
        return jnp.concatenate(out, axis=1)

    return apply, params


def fit(config: dict, X, y, *, precision: dict):
    """The model ``{"W", "b", "mean"}`` the configuration defines."""
    return refmath.fit_linear(
        featurizer(config, precision["featurizer"]), config, X, y,
        precision=precision, rows_per_block=10000,
    )
