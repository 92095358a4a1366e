"""Structural CSE (VERDICT r3 #6).

Parity: ``EquivalentNodeMergeRule.scala:13`` — the reference's operators are
Scala case classes, so *separately constructed* equal nodes compare equal and
merge. Here :func:`keystone_tpu.workflow.operators.structural_key` recovers
that: class + canonicalized parameters (numpy arrays by content digest),
with object-identity fallback for closures and arbitrary state.
"""

import numpy as np

from keystone_tpu.data.dataset import Dataset
from keystone_tpu.workflow.operators import structural_key
from keystone_tpu.workflow.pipeline import Pipeline
from keystone_tpu.workflow.rules import EquivalentNodeMergeRule
from keystone_tpu.workflow.transformer import LabelEstimator, Transformer


class _Scale(Transformer):
    def __init__(self, s):
        self.s = s

    def trace_batch(self, X):
        return X * self.s


class _Shift(Transformer):
    def __init__(self, offset):
        self.offset = np.asarray(offset, dtype=np.float32)

    def trace_batch(self, X):
        return X + self.offset


class _Closure(Transformer):
    def __init__(self, fn):
        self.fn = fn

    def apply_batch(self, data):
        return Dataset.of(data).map_batch(self.fn)


class _CountingEstimator(LabelEstimator):
    def __init__(self, s):
        self.s = s
        self.num_fits = 0

    def fit(self, data, labels):
        self.num_fits += 1
        return _Scale(self.s)


def _n_nodes(graph):
    return len(list(graph.nodes))


def test_structural_key_equal_for_equal_params():
    assert structural_key(_Scale(2.0)) == structural_key(_Scale(2.0))
    assert structural_key(_Scale(2.0)) != structural_key(_Scale(3.0))
    # array params compare by content
    a = structural_key(_Shift([1.0, 2.0]))
    b = structural_key(_Shift([1.0, 2.0]))
    c = structural_key(_Shift([1.0, 2.5]))
    assert a == b and a != c


def test_structural_key_closure_falls_back_to_identity():
    f = lambda X: X  # noqa: E731
    t1, t2 = _Closure(f), _Closure(f)
    # even sharing the same callable, separately built nodes keep identity
    assert structural_key(t1) is t1
    assert structural_key(t2) is t2


def test_independently_built_equal_prefixes_merge():
    """The reference suite's scenario: two branches that independently
    construct the same PixelScaler→GrayScaler-style prefix collapse to
    one (EquivalentNodeMergeRule.scala merge-equal-nodes)."""
    b1 = _Scale(2.0).and_then(_Shift([1.0]))
    b2 = _Scale(2.0).and_then(_Shift([1.0]))  # separate, equal objects
    pipe = Pipeline.gather([b1.and_then(_Scale(3.0)), b2.and_then(_Scale(5.0))])
    before = _n_nodes(pipe.graph)
    graph, _ = EquivalentNodeMergeRule().apply(pipe.graph, {})
    # the two-node equal prefix merged; the distinct tails did not
    assert _n_nodes(graph) == before - 2
    X = np.ones((2, 3), dtype=np.float32)
    out = Pipeline(graph, pipe.source, pipe.sink)(X).get()
    got = [np.asarray(a) for a in out.payload]
    np.testing.assert_allclose(got[0], (X * 2.0 + 1.0) * 3.0)
    np.testing.assert_allclose(got[1], (X * 2.0 + 1.0) * 5.0)


def test_unequal_params_do_not_merge():
    b1 = _Scale(2.0)
    b2 = _Scale(2.0000001)
    pipe = Pipeline.gather([b1, b2])
    before = _n_nodes(pipe.graph)
    graph, _ = EquivalentNodeMergeRule().apply(pipe.graph, {})
    assert _n_nodes(graph) == before


def test_closure_nodes_do_not_merge():
    f = lambda X: np.asarray(X) * 2.0  # noqa: E731
    pipe = Pipeline.gather([_Closure(f), _Closure(f)])
    before = _n_nodes(pipe.graph)
    graph, _ = EquivalentNodeMergeRule().apply(pipe.graph, {})
    assert _n_nodes(graph) == before


def test_equal_estimators_fit_once_after_merge():
    """Fit-once survives: two structurally-equal estimators over the same
    data merge into one estimator node, so exactly one fit runs."""
    X = np.arange(12, dtype=np.float32).reshape(4, 3)
    y = np.ones((4, 1), dtype=np.float32)
    data = Dataset.of(X)
    labels = Dataset.of(y)
    e1 = _CountingEstimator(2.0)
    e2 = _CountingEstimator(2.0)
    p1 = _Scale(1.0).and_then(e1, data, labels)
    p2 = _Scale(1.0).and_then(e2, data, labels)
    pipe = Pipeline.gather([p1, p2])
    out = pipe(X).get()
    got = [np.asarray(a) for a in out.payload]
    np.testing.assert_allclose(got[0], X * 2.0)
    np.testing.assert_allclose(got[1], X * 2.0)
    # exactly one of the two estimator objects fit, exactly once
    assert e1.num_fits + e2.num_fits == 1


def _cosine_pair(flip=None):
    """Two separately built ``CosineRandomFeatures`` over one input; with
    ``flip``, the second's ``W`` differs in that one element."""
    from keystone_tpu.nodes.stats import CosineRandomFeatures

    rng = np.random.default_rng(3)
    W = rng.standard_normal((32, 8)).astype(np.float32)
    b = rng.uniform(0, 6.28, 32).astype(np.float32)
    W2 = W.copy()
    if flip is not None:
        W2[flip] += 1e-3
    return Pipeline.gather(
        [CosineRandomFeatures(W, b), CosineRandomFeatures(W2, b.copy())]
    )


def test_equal_cosine_features_merge_and_the_second_rule_pass_hashes_nothing():
    from keystone_tpu.obs.tracer import Tracer

    pipe = _cosine_pair()
    before = _n_nodes(pipe.graph)
    tracer = Tracer(sync=False)
    with tracer.span("first") as first:
        graph, _ = EquivalentNodeMergeRule().apply(pipe.graph, {})
    with tracer.span("second") as second:
        EquivalentNodeMergeRule().apply(pipe.graph, {})
    assert _n_nodes(graph) == before - 1
    param_bytes = 2 * (32 * 8 + 32) * 4
    assert (first.digest_bytes, first.digest_hits) == (param_bytes, 0)
    # a fresh key_cache, the same (read-only) arrays: answered from memory
    assert (second.digest_bytes, second.digest_hits) == (0, 4)


def test_cosine_features_differing_in_one_element_do_not_merge():
    pipe = _cosine_pair(flip=(31, 7))
    before = _n_nodes(pipe.graph)
    graph, _ = EquivalentNodeMergeRule().apply(pipe.graph, {})
    assert _n_nodes(graph) == before


def test_structural_key_takes_object_arrays_by_their_elements():
    class _Meta(Transformer):
        def __init__(self, meta):
            self.meta = np.array(meta, dtype=object)

    assert structural_key(_Meta(["a", 1.5])) == structural_key(_Meta(["a", 1.5]))
    assert structural_key(_Meta(["a", 1.5])) != structural_key(_Meta(["b", 1.5]))
