"""A fit hands each estimator's executor the upstream results of the last.
Segment planning treats what an executor already holds as data: a segment
through a memoized node would compute it again from ITS inputs — the
featurizer once an estimator."""

import numpy as np

from keystone_tpu.check import lattice
from keystone_tpu.check.segments import BARRIER_SAVED, plan_segments
from keystone_tpu.data.dataset import Dataset
from keystone_tpu.nodes.learning.linear import LinearMapEstimator
from keystone_tpu.nodes.stats import StandardScaler
from keystone_tpu.obs import tracer as tracer_mod
from keystone_tpu.workflow.transformer import Transformer

CALLS = []


class _Featurize(Transformer):
    def __init__(self, k):
        self.k = k

    def trace_batch(self, X):
        import jax.numpy as jnp

        CALLS.append(X.shape)  # traced once a program, counted by spans below
        return jnp.maximum(X * self.k, 0.1 * X)


def _pipeline(X, Y):
    feat = _Featurize(2.0).and_then(_Featurize(0.5))
    return feat.and_then(StandardScaler(), Dataset.of(X)).and_then(
        LinearMapEstimator(lam=1.0), Dataset.of(X), Dataset.of(Y)
    )


def test_two_chained_estimators_featurize_the_training_rows_once():
    rng = np.random.default_rng(0)
    X = rng.standard_normal((96, 6)).astype(np.float32)
    Y = rng.standard_normal((96, 2)).astype(np.float32)
    tracer = tracer_mod.start()
    try:
        fitted = _pipeline(X, Y).fit()
    finally:
        tracer_mod.stop()
    segments = [sp for sp in tracer.spans() if sp.name == "exec.segment"]
    featurized = [sp for sp in segments if "_Featurize" in sp.attrs["label"]]
    # the chain ran once, for the scaler's fit; the solver read the kept
    # result through the scaler's model
    assert [sp.attrs["rows"] for sp in featurized] == [96]
    # and the answers are those of the plain composition
    got = np.asarray(fitted.apply(Dataset.of(X)).to_array())
    F = np.maximum(np.maximum(X * 2.0, 0.1 * X) * 0.5, 0.1 * np.maximum(X * 2.0, 0.1 * X))
    Fs = (F - F.mean(0)) / F.std(0, ddof=1)
    A = Fs - Fs.mean(0)
    W = np.linalg.solve(A.T @ A + np.eye(6), A.T @ (Y - Y.mean(0)))
    np.testing.assert_allclose(got, A @ W + Y.mean(0), atol=2e-4)


def test_a_materialized_node_is_a_barrier():
    pipe = _Featurize(2.0).and_then(_Featurize(3.0)).and_then(_Featurize(4.0))
    graph = pipe.graph
    verdicts = {n: lattice.classify(graph.get_operator(n)) for n in graph.nodes}
    whole, _ = plan_segments(graph, verdicts, {})
    assert [len(s) for s in whole] == [3]
    middle = whole[0].nodes[1]
    cut, barriers = plan_segments(graph, verdicts, {}, materialized={middle})
    assert barriers[middle] == BARRIER_SAVED
    assert sorted(len(s) for s in cut) == [1, 1]
    assert cut[1].inputs == [middle]
