"""The kernel solver states the precision of every product it makes, and
nothing it calls computes below it: every kernel block is made at the
stated precision, a Convolver that states a precision keeps the one-pass
fused convolution kernel off, and the mapper's apply over a data set is on
spans that count the kernel entries it made."""

import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from keystone_tpu.data.dataset import Dataset
from keystone_tpu.nodes.learning import kernel
from keystone_tpu.nodes.learning.kernel import KernelBlockLinearMapper

PRECISION = re.compile(r"precision = \[(\w+), (\w+)\]")


def _precisions(fn, *args, **kw):
    text = fn.lower(*args, **kw).as_text()
    return sorted(set(PRECISION.findall(text)))


@pytest.mark.parametrize("n, d, b", [
    (16, 8, 4),
    (700, 128, 256),
    # the configuration's block: 50,000 rows of 800 against 5,000
    (50000, 800, 5000),
])
def test_the_kernel_block_runs_at_the_stated_precision(n, d, b):
    """One body makes every kernel block, whatever its shape, and its cross
    product is at the solver's stated precision: nothing lower."""
    assert kernel.KERNEL_PRECISION == "high"
    X = jax.ShapeDtypeStruct((n, d), jnp.float32)
    Xb = jax.ShapeDtypeStruct((b, d), jnp.float32)
    assert _precisions(
        kernel._gaussian_block, X, Xb, jnp.float32(0.5)
    ) == [("HIGH", "HIGH")]


def test_every_program_of_the_solver_states_its_precision():
    n, d, bs, k = 96, 8, 32, 3
    X = jnp.ones((n, d), jnp.float32)
    W = jnp.zeros((n, k), jnp.float32)
    start, gamma, lam = jnp.int32(0), jnp.float32(0.1), jnp.float32(1.0)
    high, highest = ("HIGH", "HIGH"), ("HIGHEST", "HIGHEST")
    # the generation: the kernel's cross product
    assert _precisions(
        kernel._kernel_block_slice, X, start, gamma, bs=bs
    ) == [high]
    # a cached block step: the residual at the solver's precision (the
    # Cholesky factor and its solves are XLA's own operations)
    Kb = jnp.ones((n, bs), jnp.float32)
    assert _precisions(
        kernel._krr_block_step_cached_plain, Kb, W, W, start, lam, bs=bs
    ) == [highest]
    # a streamed block step makes its block too
    assert _precisions(
        kernel._krr_block_step_plain, X, W, W, start, gamma, lam, bs=bs
    ) == [high, highest]
    # one block of the apply: the kernel, then K_b · W_b
    assert _precisions(
        kernel._kernel_apply_block, X, X, W, W, start, gamma, bs=bs
    ) == [high, highest]


def _problem(n=200, d=6, k=3, seed=0):
    rng = np.random.default_rng(seed)
    X = rng.standard_normal((n, d)).astype(np.float32)
    W = rng.standard_normal((n, k)).astype(np.float32)
    return X, W


def test_the_mapper_applies_block_by_block_as_the_closed_form():
    X, W = _problem()
    Xq = X[:37] + 0.1
    mapper = KernelBlockLinearMapper(X, W, gamma=0.2, block_size=64)
    got = np.asarray(mapper.apply_batch(Dataset.of(Xq)).to_array())
    K = np.exp(-0.2 * ((Xq[:, None, :] - X[None, :, :]) ** 2).sum(-1))
    np.testing.assert_allclose(got, K @ W, rtol=1e-4, atol=1e-4)
    # the traceable body gives the same, inside a jit too
    np.testing.assert_allclose(
        np.asarray(jax.jit(mapper.trace_batch)(Xq)), got, rtol=1e-6, atol=1e-6
    )


def test_the_mapper_apply_is_on_spans_that_count_its_entries():
    from keystone_tpu.obs import tracer as tracer_mod

    X, W = _problem()
    mapper = KernelBlockLinearMapper(X, W, gamma=0.2, block_size=64)
    tracer = tracer_mod.start()
    try:
        mapper.apply_batch(Dataset.of(X[:50]))
    finally:
        tracer_mod.stop()
    spans = tracer.spans()
    (apply,) = [sp for sp in spans if sp.name == "krr.apply"]
    assert apply.attrs == {"rows": 50, "cols": 200, "entries": 50 * 200}
    blocks = [sp for sp in spans if sp.name == "krr.kernel"]
    assert all(sp.parent_id == apply.span_id for sp in blocks)
    # three blocks of 64 and a ragged one of 8
    assert [sp.attrs["cols"] for sp in blocks] == [64, 64, 64, 8]
    assert sum(sp.attrs["entries"] for sp in blocks) == 50 * 200


def test_a_stated_convolution_precision_keeps_the_fused_kernel_off(
    monkeypatch,
):
    from keystone_tpu.nodes.images import chain
    from keystone_tpu.nodes.images.core import (
        Convolver,
        Pooler,
        SymmetricRectifier,
    )

    monkeypatch.setattr(chain._kernel, "kernel_mode", lambda: "compiled")
    monkeypatch.setattr(chain._kernel, "supported", lambda *a: True)
    filters = np.random.default_rng(0).standard_normal((8, 108))
    rect, pool = SymmetricRectifier(alpha=0.25), Pooler(13, 14, None, "sum")
    shape = (4, 32, 32, 3)
    default = chain.ConvRectifyPool(Convolver(filters, 32, 32, 3), rect, pool)
    assert default.kernel_mode(shape) == "compiled"
    for precision in ("high", "highest"):
        stated = chain.ConvRectifyPool(
            Convolver(filters, 32, 32, 3, precision=precision), rect, pool
        )
        assert stated.kernel_mode(shape) is None
        assert stated.segment_facts(shape, 4) == {}


def test_the_convolver_states_its_precision():
    from keystone_tpu.nodes.images.core import Convolver

    filters = np.random.default_rng(0).standard_normal((4, 27))
    X = jnp.ones((2, 8, 8, 3), jnp.float32)
    text = jax.jit(
        Convolver(filters, 8, 8, 3, precision="high").trace_batch
    ).lower(X).as_text()
    assert "precision_config = [#stablehlo<precision HIGH>" in text
    plain = jax.jit(Convolver(filters, 8, 8, 3).trace_batch).lower(X).as_text()
    assert "precision HIGH" not in plain


def test_where_the_bodies_run_an_image_is_priced_at_their_outputs():
    """Segment dispatch cuts a batch into row slices by what a row holds:
    where the three bodies run (a stated precision, or off the chip), an
    image holds the convolution's (27, 27, K) output and the rectifier's
    (27, 27, 2K), float32 in 128-lane tiles — not the kernel's patch rows,
    and never nothing (a whole-batch program of 50,000 images asked the
    chip for 42.4 GB)."""
    from keystone_tpu.nodes.images import chain
    from keystone_tpu.nodes.images.core import (
        Convolver,
        Pooler,
        SymmetricRectifier,
    )

    filters = np.random.default_rng(0).standard_normal((100, 108))
    node = chain.ConvRectifyPool(
        Convolver(filters, 32, 32, 3, precision="high"),
        SymmetricRectifier(alpha=0.25), Pooler(13, 14, None, "sum"),
    )
    assert node.kernel_mode((50000, 32, 32, 3)) is None
    assert node.row_scratch_bytes((50000, 32, 32, 3)) == 4 * 729 * (128 + 256)
    assert node.row_scratch_bytes((50000, 800)) == 0
