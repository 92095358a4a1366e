"""The bounded-argument cosine (``ops/bounded_cos.py``) and the guard of
``CosineRandomFeatures`` that decides, per call and from the call's own
rows, whether it may answer: the function's error over its whole range,
the node's two bodies, the span attribute and W's one literal."""

import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from keystone_tpu.compile.segment import reset_dispatchers
from keystone_tpu.nodes.stats import CosineRandomFeatures
from keystone_tpu.obs import tracer as tracer_mod
from keystone_tpu.ops.bounded_cos import LIMIT, MAX_ABS_ERROR, cos_bounded

F = np.float32
#: TimitPipeline's γ and input width (``benchmark/configs/timit_cos4.json``)
GAMMA, DIM, FEATURES = 0.05555, 440, 4096


def _around(points, steps=4):
    """``points`` in float32 with their ``steps`` neighbours either side."""
    at = np.asarray(points, F)
    out, up, down = [at], at, at
    for _ in range(steps):
        up = np.nextafter(up, F(np.inf))
        down = np.nextafter(down, F(-np.inf))
        out += [up, down]
    z = np.concatenate(out)
    return z[np.abs(z) <= LIMIT]


def _sweep(name):
    rng = np.random.default_rng(38)
    if name == "dense":
        return rng.uniform(-LIMIT, LIMIT, 4_000_000).astype(F)
    if name == "small":  # where timit_cos4's arguments lie
        return rng.uniform(-16.0, 16.0, 4_000_000).astype(F)
    if name == "grid":  # every float32 of a stretch, across a quadrant edge
        return np.arange(2_000_000, dtype=F) * F(2.0**-18) + F(97.0)
    multiples = np.arange(-int(LIMIT / (np.pi / 2)), int(LIMIT / (np.pi / 2)) + 1)
    if name == "quadrant_edges":  # the neighbours of every multiple of π/2:
        # where k steps (multiples of π) and where the cosine crosses 0
        return _around(multiples * (np.pi / 2))
    if name == "octant_edges":
        return _around((multiples + 0.5) * (np.pi / 2))
    if name == "zeros_denormals_ends":
        tiny = np.array([0.0, -0.0, 1e-45, -1e-45, 1e-39, -1e-39, 1.17549435e-38], F)
        return np.concatenate([tiny, _around([LIMIT, -LIMIT], 16)])
    if name == "worst_found":
        # the largest errors over EVERY float32 in [2⁻⁴, LIMIT], either sign:
        # numpy's float32 arithmetic 1.282e-7, XLA's CPU (fused multiply-adds)
        # 1.339e-7 (exhaustive sweeps of PR 38, too long for a test)
        return _around([0.11538117378950119, -2987.65478515625], 64)
    raise KeyError(name)


@pytest.mark.parametrize(
    "name",
    [
        "dense", "small", "grid", "quadrant_edges", "octant_edges",
        "zeros_denormals_ends", "worst_found",
    ],
)
def test_cos_bounded_holds_its_error_over_its_range(name):
    z = _sweep(name)
    assert z.dtype == F and np.all(np.abs(z) <= LIMIT)
    got = np.asarray(jax.jit(cos_bounded)(z))
    assert got.dtype == F
    err = np.max(np.abs(got.astype(np.float64) - np.cos(z.astype(np.float64))))
    assert err <= MAX_ABS_ERROR, (name, err)


def test_cos_bounded_is_wrong_beyond_its_range():
    """Why the guard exists: past LIMIT the reduction's products round."""
    z = np.random.default_rng(0).uniform(1e7, 1e8, 100_000).astype(F)
    got = np.asarray(jax.jit(cos_bounded)(z)).astype(np.float64)
    assert np.max(np.abs(got - np.cos(z.astype(np.float64)))) > 1e-2


def _rows(n=512, seed=0):
    return np.random.default_rng(seed).standard_normal((n, DIM)).astype(F)


def _gaussian():
    return CosineRandomFeatures.create(DIM, FEATURES, GAMMA, seed=123)


def _cauchy():
    # pipelines/timit.py:_cosine_branch, rf_type="cauchy"
    kw, kb = jax.random.split(jax.random.PRNGKey(123))
    W = GAMMA * jax.random.cauchy(kw, (FEATURES, DIM))
    b = jax.random.uniform(kb, (FEATURES,), maxval=2 * np.pi)
    return CosineRandomFeatures(W, b)


def _with(X, value):
    X = X.copy()
    X[3, 5] = value
    return X


#: case -> (node, rows, calls that fall to jnp.cos)
_CASES = {
    "gaussian": (_gaussian, lambda: _rows(), 0),
    "gaussian_zero_rows": (_gaussian, lambda: np.zeros((8, DIM), F), 0),
    "cauchy": (_cauchy, lambda: _rows(), 1),
    "rows_scaled_1e6": (_gaussian, lambda: _rows() * F(1e6), 1),
    "inf_in_rows": (_gaussian, lambda: _with(_rows(), np.inf), 1),
    "nan_in_rows": (_gaussian, lambda: _with(_rows(), np.nan), 1),
    "float64_rows": (_gaussian, lambda: _rows().astype(np.float64), 0),
    "bfloat16_rows": (_gaussian, lambda: jnp.asarray(_rows(), jnp.bfloat16), 0),
    "float16_parameters": (
        lambda: CosineRandomFeatures(
            _gaussian().W.astype(np.float16), _gaussian().b
        ),
        lambda: _rows(), 1,
    ),
}


@pytest.mark.parametrize("case", list(_CASES))
def test_the_guard_picks_the_body_from_the_rows(case):
    make, rows, exact = _CASES[case]
    node, X = make(), rows()
    Wt, b = jnp.asarray(node.W.T), jnp.asarray(node.b)
    # the references as programs of their own: the product of an eager
    # ``X @ W.T`` is blocked otherwise and differs in the sixth digit
    with_cos = np.asarray(jax.jit(lambda X: jnp.cos(X @ Wt + b))(X))
    with_bounded = np.asarray(jax.jit(lambda X: cos_bounded(X @ Wt + b))(X))
    got = np.asarray(jax.jit(node.trace_batch)(X))
    assert node.exact_calls(X) == exact
    if exact:
        # jnp.cos's own bits, nan for nan
        assert np.array_equal(got, with_cos, equal_nan=True)
        assert not np.array_equal(got, with_bounded, equal_nan=True)
    else:
        assert np.array_equal(got, with_bounded)
        assert np.max(np.abs(got - with_cos)) <= 2e-7
        z = np.asarray(X, np.float64) @ node.W.T.astype(np.float64) + node.b
        assert np.max(np.abs(z)) <= float(node.argument_bound(jnp.asarray(X))) <= LIMIT


def test_an_eager_call_and_a_single_row_take_the_guard_too():
    node, X = _gaussian(), _rows(16)
    want = np.asarray(jax.jit(node.trace_batch)(X))
    np.testing.assert_allclose(np.asarray(node.trace_batch(X)), want, atol=1e-5)
    np.testing.assert_allclose(np.asarray(node.apply(X[0])), want[0], atol=1e-5)


def _timit_job(n_train=96, n_test=24):
    from keystone_tpu.nodes.learning.linear import BlockLeastSquaresEstimator
    from keystone_tpu.nodes.util import ClassLabelIndicators, MaxClassifier
    from keystone_tpu.pipelines.timit import (
        TimitConfig,
        build_featurizer,
        synthetic_timit,
    )

    conf = TimitConfig(
        num_cosines=2, cosine_features=64, input_dim=24, num_epochs=1,
        lam=1e-2, num_classes=4,
    )
    train = synthetic_timit(n_train, 4, dim=24, seed=0)
    test = synthetic_timit(n_test, 4, dim=24, seed=1)
    labels = ClassLabelIndicators(4).apply_batch(train.labels)
    predictor = (
        build_featurizer(conf)
        .and_then(
            BlockLeastSquaresEstimator(
                conf.cosine_features, conf.num_epochs, conf.lam
            ),
            train.data,
            labels,
        )
        .and_then(MaxClassifier())
    )
    return predictor, test


def test_exec_segment_counts_the_rows_through_the_guarded_body(monkeypatch):
    reset_dispatchers()
    predictor, test = _timit_job()
    traces = []
    body = CosineRandomFeatures.trace_batch
    monkeypatch.setattr(
        CosineRandomFeatures, "trace_batch",
        lambda self, X: traces.append(X.shape) or body(self, X),
    )
    tracer = tracer_mod.install(tracer_mod.Tracer())
    try:
        fitted = predictor.fit()
        fitted.apply(test.data).to_array()
        spans = [s for s in tracer.spans() if s.name == "exec.segment"]
        # a second dispatch of a shape traces no member: the facts of a
        # speaking member need no other member's value here
        traced = len(traces)
        fitted.apply(test.data).to_array()
        assert len(traces) == traced
    finally:
        tracer_mod.reset()
        reset_dispatchers()
    assert [s.attrs["rows"] for s in spans] == [96, 24]
    # the fit's featurizer, then the fitted chain as ONE node with its map
    # (``CosineScoresRule``), whose branches say the same
    assert spans[0].attrs["label"].startswith("CosineRandomFeatures+")
    assert spans[1].attrs["label"].startswith("CosineLinearMapper+")
    for sp in spans:
        assert sp.attrs["path"] == "compiled"
        assert sp.attrs["cosine_bounded_rows"] == sp.attrs["rows"]
    narrow = CosineRandomFeatures(np.ones((4, 24), np.float16), np.zeros(4, F))
    assert narrow.segment_facts((24, 24), 24) == {}  # no guarded body lowered


def test_the_lowered_chain_holds_each_w_once():
    predictor, test = _timit_job()
    fitted = predictor.fit()
    text = jax.jit(fitted.trace_fn()).lower(
        jnp.asarray(test.data.to_array())
    ).as_text()
    as_stored = re.findall(r"stablehlo\.constant[^\n]*tensor<64x24xf32>", text)
    transposed = re.findall(r"stablehlo\.constant[^\n]*tensor<24x64xf32>", text)
    assert len(transposed) == 2 and not as_stored  # two branches, one Wᵀ each
    # ONE guarded body (its ``cond`` lowered once), called by each branch
    assert text.count("call @_guarded_cosine") == 2
    assert text.count("stablehlo.case") + text.count("stablehlo.if") == 1
