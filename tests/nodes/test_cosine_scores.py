"""The fused cosine-scores kernel (``ops/cosine_scores.py``, interpret mode
on the CPU) and the node that runs it (``CosineLinearMapper``) against the
bodies it replaces: cosine branches, their join, the fitted map.

The kernel rounds both products' operands to bf16 with float32
accumulation — what the TPU's default precision does to the bodies written
out — so it is held to the bodies with their operands rounded alike; to
the bodies as the CPU computes them (float32 products) its labels may
differ only where two classes' scores nearly tie."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from keystone_tpu.nodes.learning.cosine_linear import CosineLinearMapper
from keystone_tpu.nodes.learning.linear import BlockLinearMapper
from keystone_tpu.nodes.stats import CosineRandomFeatures
from keystone_tpu.ops import cosine_scores as cs
from keystone_tpu.ops.bounded_cos import LIMIT, cos_bounded

F = np.float32
DIM, GAMMA, CLASSES = 24, 0.2, 7


@pytest.fixture
def interpreted(monkeypatch):
    monkeypatch.setattr(cs, "kernel_mode", lambda: "interpret")


def _node(branches, features, *, mean, intercept, cauchy=0.0, seed=0):
    rng = np.random.default_rng(seed)
    cos = []
    for i in range(branches):
        if cauchy:  # pipelines/timit.py:_cosine_branch, rf_type="cauchy"
            kw, kb = jax.random.split(jax.random.PRNGKey(seed + i))
            W = cauchy * jax.random.cauchy(kw, (features, DIM))
            b = jax.random.uniform(kb, (features,), maxval=2 * np.pi)
            cos.append(CosineRandomFeatures(W, b))
        else:
            cos.append(
                CosineRandomFeatures.create(DIM, features, GAMMA, seed=seed + i)
            )
    mapper = BlockLinearMapper(
        [rng.standard_normal((features, CLASSES)).astype(F) / features
         for _ in range(branches)],
        features,
        b=rng.standard_normal(CLASSES).astype(F) if intercept else None,
        feature_means=(
            [0.1 * rng.standard_normal(features).astype(F)
             for _ in range(branches)] if mean else None
        ),
    )
    return CosineLinearMapper(cos, mapper)


def _rows(n, seed=1):
    return np.random.default_rng(seed).standard_normal((n, DIM)).astype(F)


def _with(X, value):
    X = X.copy()
    X[3, 5] = value
    return X


def _written_out(node, X):
    """The chain's bodies: each branch's features, joined, centred, mapped."""
    F_ = jnp.concatenate(
        [jnp.cos(X @ br.W.T + br.b) for br in node.branches], axis=-1
    )
    return BlockLinearMapper.trace_batch(node, F_)


def _one_pass(node, X):
    """The bodies with both products' operands rounded to bf16, as the
    TPU's default precision rounds them."""

    def r16(a):
        return jnp.asarray(a).astype(jnp.bfloat16).astype(jnp.float32)

    F_ = jnp.concatenate(
        [cos_bounded(r16(X) @ r16(br.W.T) + br.b) for br in node.branches],
        axis=-1,
    )
    if node._mean is not None:
        F_ = F_ - node._mean
    out = r16(F_) @ r16(node._W)
    return out if node.b is None else out + node.b


#: case -> (rows, branches, features a branch, feature means, intercept)
CASES = {
    "one_branch": (256, 1, 256, True, True),
    "four_branches": (256, 4, 128, True, True),
    "ragged_rows": (300, 2, 128, True, True),
    "rows_past_one_tile": (1030, 2, 128, True, True),
    "few_rows": (24, 3, 128, True, True),
    "no_mean_no_intercept": (96, 2, 128, False, False),
    "mean_alone": (96, 2, 256, True, False),
    "intercept_alone": (96, 1, 384, False, True),
}


@pytest.mark.parametrize("case", sorted(CASES))
def test_kernel_matches_the_bodies(case, interpreted):
    n, branches, features, mean, intercept = CASES[case]
    node = _node(branches, features, mean=mean, intercept=intercept)
    X = jnp.asarray(_rows(n))
    assert node.kernel_mode(X.shape) == "interpret"
    got = np.asarray(jax.jit(node.trace_batch)(X))
    assert got.shape == (n, CLASSES)
    want = np.asarray(jax.jit(lambda X: _one_pass(node, X))(X))
    scale = np.abs(want).max()
    np.testing.assert_allclose(got, want, rtol=0, atol=1e-5 * scale)
    # against float32 products: a label differs only at a near-tie
    exact = np.asarray(jax.jit(lambda X: _written_out(node, X))(X))
    rows = np.nonzero(got.argmax(1) != exact.argmax(1))[0]
    gap = exact[rows, exact.argmax(1)[rows]] - exact[rows, got.argmax(1)[rows]]
    assert len(rows) <= n // 20 and np.all(gap <= 1e-2 * scale), (rows, gap)


#: case -> (node, rows): the guard finds an argument it cannot bound
FALLBACK = {
    "rows_scaled_1e6": (
        lambda: _node(2, 128, mean=True, intercept=True),
        lambda: _rows(64) * F(1e6),
    ),
    "cauchy_w": (
        # at γ = 2 the heavy tail's widest row passes what LIMIT allows
        lambda: _node(2, 128, mean=True, intercept=True, cauchy=2.0),
        lambda: _rows(64),
    ),
    "inf_in_a_row": (
        lambda: _node(1, 128, mean=False, intercept=True),
        lambda: _with(_rows(64), np.inf),
    ),
}


@pytest.mark.parametrize("case", sorted(FALLBACK))
def test_the_guard_falls_to_the_cosine_written_out(case, interpreted):
    make, rows = FALLBACK[case]
    node, X = make(), jnp.asarray(rows())
    bound = max(float(br.argument_bound(X)) for br in node.branches)
    assert not bound <= LIMIT
    got = np.asarray(jax.jit(node.trace_batch)(X))
    want = np.asarray(jax.jit(lambda X: _written_out(node, X))(X))
    np.testing.assert_allclose(
        got, want, rtol=1e-5, atol=1e-5 * np.nanmax(np.abs(want)),
        equal_nan=True,
    )


def test_off_the_tpu_the_bodies_run():
    """No kernel on the CPU backend: the node is the chain's bodies, bit
    for bit, each branch through its own guarded cosine."""
    assert cs.kernel_mode() is None
    node = _node(2, 64, mean=True, intercept=True)
    X = jnp.asarray(_rows(48))
    assert node.kernel_mode(X.shape) is None
    want = np.asarray(jax.jit(
        lambda X: BlockLinearMapper.trace_batch(node, jnp.concatenate(
            [br.trace_batch(X) for br in node.branches], axis=-1
        ))
    )(X))
    np.testing.assert_array_equal(np.asarray(jax.jit(node.trace_batch)(X)), want)
    assert node.segment_facts((48, DIM), 48) == {"cosine_bounded_rows": 48}


@pytest.mark.parametrize("what,why", [
    (lambda n: n, None),
    (lambda n: CosineLinearMapper(
        [CosineRandomFeatures(br.W.astype(np.float16), br.b)
         for br in n.branches], n), "float16 parameters"),
    (lambda n: _node(1, 64, mean=True, intercept=True), "64 features"),
])
def test_kernel_mode_follows_widths_and_dtypes(what, why, interpreted):
    node = what(_node(2, 128, mean=True, intercept=True))
    mode = node.kernel_mode((256, DIM))
    assert mode == ("interpret" if why is None else None), why
    facts = node.segment_facts((256, DIM), 256)
    assert ("cosine_scores_rows" in facts) == (why is None)
    assert node.kernel_mode((256, DIM, 1)) is None


def test_the_tiling_admits_timit_cos4s_widths():
    assert cs.supported(440, 4 * 4096, 147)
    assert cs._tile_features(4 * 4096) == cs._TILE_FEATURES
    assert not cs.supported(440, 4096 + 4000, 147)  # not whole lanes
    assert not cs.supported(441, 4096, 147)  # not whole sublanes
    assert not cs.supported(440, 4096, 100_000)  # past the VMEM budget
