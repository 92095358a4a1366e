"""The fused conv → rectify → pool kernel (``ops/conv_rectify_pool.py``,
interpret mode on the CPU) against the three XLA bodies it replaces, both
sides' product operands rounded to bf16 alike — the rounding the TPU's
default precision gives ``conv_general_dilated``."""

import jax.numpy as jnp
import numpy as np
import pytest

from keystone_tpu.nodes.images.chain import ConvRectifyPool
from keystone_tpu.nodes.images.core import (
    Convolver,
    Pooler,
    SymmetricRectifier,
)
from keystone_tpu.nodes.learning.zca import ZCAWhitener
from keystone_tpu.ops import conv_rectify_pool as crp

S, C = 6, 3


@pytest.fixture
def interpreted(monkeypatch):
    monkeypatch.setattr(crp, "kernel_mode", lambda: "interpret")


def _chain(k, side, *, whitened, normalize, alpha, stride=13, pool=14,
           seed=0):
    rng = np.random.default_rng(seed)
    m = S * S * C
    filters = rng.standard_normal((k, m)).astype(np.float32)
    whitener = None
    if whitened:
        whitener = ZCAWhitener(
            np.eye(m, dtype=np.float32),
            rng.standard_normal(m).astype(np.float32),
        )
    conv = Convolver(
        filters, side, side, C, whitener=whitener,
        normalize_patches=normalize,
    )
    return conv, SymmetricRectifier(alpha=alpha), Pooler(
        stride, pool, None, "sum"
    )


def _bodies(conv, rect, pool, X):
    return pool.trace_batch(rect.trace_batch(conv.trace_batch(X)))


#: (filters, image side, batch, whitener, normalize_patches, alpha)
CASES = {
    "k100_default": (100, 32, 5, True, True, 0.25),
    "k100_no_whitener": (100, 32, 3, False, True, 0.25),
    "k100_raw_patches": (100, 32, 3, True, False, 0.25),
    "k100_raw_no_whitener_alpha0": (100, 32, 2, False, False, 0.0),
    "k256_alpha0": (256, 32, 4, True, True, 0.0),
    "k272_ragged_tile": (272, 32, 3, True, True, 0.25),
    "k100_batch_past_one_tile": (100, 32, 11, True, True, 0.25),
    "k100_side24_one_window": (100, 24, 3, True, True, 0.25),
    "k256_side20_no_whitener": (256, 20, 9, False, True, 0.0),
}


@pytest.mark.parametrize("case", sorted(CASES))
def test_fused_matches_three_bodies(case, bf16_products, interpreted):
    k, side, n, whitened, normalize, alpha = CASES[case]
    conv, rect, pool = _chain(
        k, side, whitened=whitened, normalize=normalize, alpha=alpha
    )
    X = jnp.asarray(
        np.random.default_rng(1).uniform(0, 255, (n, side, side, C)),
        jnp.float32,
    )
    want = np.asarray(_bodies(conv, rect, pool, X))
    node = ConvRectifyPool(conv, rect, pool)
    assert node.kernel_mode(X.shape) == "interpret"
    got = np.asarray(node.trace_batch(X))
    assert got.shape == want.shape
    np.testing.assert_allclose(
        got, want, rtol=1e-5, atol=1e-5 * np.abs(want).max()
    )


def test_pool_overlap_lands_in_both_cells(interpreted):
    """14/13 on 27 windows: [0, 14) and [13, 27) share index 13. An image
    whose only non-zero convolution output is at window (13, 5) feeds the
    pooled cells (0, 0) AND (1, 0), and no other."""
    side, k = 32, 128
    # a one-pixel filter on a one-pixel image: conv[x, y] = X[x, y, 0]·1
    filters = np.zeros((k, S * S * C), np.float32)
    filters[:, 0] = 1.0
    conv = Convolver(filters, side, side, C, normalize_patches=False)
    rect, pool = SymmetricRectifier(alpha=0.0), Pooler(13, 14, None, "sum")
    X = np.zeros((1, side, side, C), np.float32)
    X[0, 13, 5, 0] = 3.0
    want = np.asarray(_bodies(conv, rect, pool, jnp.asarray(X)))
    got = np.asarray(ConvRectifyPool(conv, rect, pool).trace_batch(
        jnp.asarray(X)
    ))
    np.testing.assert_array_equal(got, want)
    assert got[0, 0, 0, 0] == 3.0 and got[0, 1, 0, 0] == 3.0
    assert got[0, 0, 1, 0] == 0.0 and got[0, 1, 1, 0] == 0.0
    assert not got[..., k:].any()  # the negative half of a positive pixel


def test_pool_plan_covers_every_window_once():
    plan = crp.pool_plan(27, 27, 13, 14)
    assert (plan.npx, plan.npy, plan.real_rows, plan.rows) == (2, 2, 729, 784)
    seen = np.zeros((27, 27), int)
    for x0, x1, y0, y1, start in plan.groups:
        seen[x0:x1, y0:y1] += 1
        assert start % 16 == 0
    assert (seen == 1).all()
    # cell (px, py) is fed by exactly the positions of its window
    for px in range(2):
        for py in range(2):
            fed = np.zeros((27, 27), int)
            for g in plan.cells[px * 2 + py]:
                x0, x1, y0, y1, _ = plan.groups[g]
                fed[x0:x1, y0:y1] += 1
            want = np.zeros((27, 27), int)
            want[13 * px:13 * px + 14, 13 * py:13 * py + 14] = 1
            np.testing.assert_array_equal(fed, want)


@pytest.mark.parametrize("shape,why", [
    ((4, 32, 32, 3), None),
    ((4, 32, 32, 1), "channels"),
    ((4, 3072), "not images"),
])
def test_kernel_mode_follows_the_shapes(shape, why, interpreted):
    conv, rect, pool = _chain(100, 32, whitened=False, normalize=True,
                              alpha=0.25)
    mode = ConvRectifyPool(conv, rect, pool).kernel_mode(shape)
    assert mode == ("interpret" if why is None else None)


def test_fine_pool_windows_run_the_bodies(interpreted):
    """Stride-1 pooling cuts every axis into one-index classes: 16 padded
    rows a window. The tiling does not admit it, the bodies run, the
    values are the bodies'."""
    conv, rect, _ = _chain(100, 12, whitened=False, normalize=True,
                           alpha=0.25)
    pool = Pooler(1, 2, None, "sum")
    node = ConvRectifyPool(conv, rect, pool)
    X = jnp.asarray(
        np.random.default_rng(2).uniform(0, 255, (2, 12, 12, C)), jnp.float32
    )
    assert node.kernel_mode(X.shape) is None
    np.testing.assert_array_equal(
        np.asarray(node.trace_batch(X)),
        np.asarray(_bodies(conv, rect, pool, X)),
    )


def test_off_the_tpu_the_bodies_run():
    assert crp.kernel_mode() is None  # the CPU backend of the tests
    conv, rect, pool = _chain(100, 32, whitened=True, normalize=True,
                              alpha=0.25)
    node = ConvRectifyPool(conv, rect, pool)
    X = jnp.asarray(
        np.random.default_rng(3).uniform(0, 255, (3, 32, 32, C)), jnp.float32
    )
    assert node.kernel_mode(X.shape) is None
    assert node.label == "Convolver»SymmetricRectifier»Pooler"
    np.testing.assert_array_equal(
        np.asarray(node.trace_batch(X)),
        np.asarray(_bodies(conv, rect, pool, X)),
    )
