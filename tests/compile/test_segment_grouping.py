"""Which nodes make one program: the segment planner's grouping rules and
the binding that dispatches each group.

``check/segments.py:plan_segments`` is the one grouping decision and
``compile/segment.py`` the one lowering and dispatch, on every fit/apply
path, so the rules get direct coverage: host and annotated and ``no_fuse``
barriers, a value read outside a segment as a segment OUTPUT, a gather
join as a member where every reader is one, the item-list fallback, the
unfingerprintable chain. The reference is node dispatch, operator by
operator: ``GraphExecutor(graph, segment_plan={})``.
"""

import pickle

import numpy as np
import pytest

from keystone_tpu.check import lattice
from keystone_tpu.check.segments import (
    BARRIER_GATHER,
    BARRIER_HOST,
    BARRIER_NO_FUSE,
    BARRIER_SAVED,
    plan_segments,
)
from keystone_tpu.compile.segment import bind_segment, reset_dispatchers
from keystone_tpu.data.chunked import ChunkedDataset
from keystone_tpu.data.dataset import Dataset
from keystone_tpu.obs import tracer as tracer_mod
from keystone_tpu.workflow.executor import GraphExecutor
from keystone_tpu.workflow.graph import Graph
from keystone_tpu.workflow.pipeline import FittedPipeline, Pipeline, attach_data
from keystone_tpu.workflow.rules import EquivalentNodeMergeRule
from keystone_tpu.workflow.transformer import FunctionNode, Transformer


@pytest.fixture(autouse=True)
def _fresh_dispatchers():
    reset_dispatchers()
    yield
    reset_dispatchers()


class _Mul(Transformer):
    def __init__(self, k):
        self.k = k

    def trace_batch(self, X):
        return X * self.k


class _HostOnly(Transformer):
    """No trace_batch — a segment barrier, like Cacher/Shuffler."""

    def apply(self, x):
        return x + 1.0


X = np.ones((2, 2), dtype=np.float32)


def _with_data(pipe, data=X):
    """``pipe``'s graph with ``data`` spliced in for its source."""
    g, data_id = attach_data(pipe.graph, Dataset.of(data))
    g = g.replace_dependency(pipe.source, data_id)
    return g.remove_source(pipe.source)


def _plan(graph, annotations=()):
    verdicts = {n: lattice.classify(graph.get_operator(n)) for n in graph.nodes}
    return plan_segments(graph, verdicts, {}, annotations=annotations)


def _bound(graph, annotations=()):
    """The bindings the executor would dispatch, in segment order."""
    segments, _ = _plan(graph, annotations)
    return [b for b in (bind_segment(graph, s) for s in segments) if b]


def _ops(binding):
    return [op for op, _ in binding.steps]


def _pull(graph, sink, *, nodes_only=False):
    """``(value, spans)`` of one pull: segment dispatch, or node dispatch
    (``segment_plan={}``: planned, nothing eligible)."""
    executor = GraphExecutor(
        graph, optimize=False, segment_plan={} if nodes_only else None
    )
    tracer = tracer_mod.install(tracer_mod.Tracer())
    try:
        value = executor.execute(sink).get()
        return value, tracer.spans()
    finally:
        tracer_mod.reset()


def _segment_spans(spans):
    return [sp for sp in spans if sp.name == "exec.segment"]


def test_linear_chain_is_one_segment_with_same_output():
    pipe = _Mul(2.0).and_then(_Mul(3.0)).and_then(_Mul(0.5))
    g = _with_data(pipe, np.arange(6, dtype=np.float32).reshape(2, 3))
    (binding,) = _bound(g)
    assert len(binding) == 3
    out, spans = _pull(g, pipe.sink)
    (sp,) = _segment_spans(spans)
    assert sp.attrs["path"] == "compiled" and sp.attrs["nodes"] == 3
    ref, _ = _pull(g, pipe.sink, nodes_only=True)
    want = np.arange(6, dtype=np.float32).reshape(2, 3) * 3.0
    np.testing.assert_allclose(np.asarray(out.to_array()), want)
    assert np.array_equal(np.asarray(out.to_array()), np.asarray(ref.to_array()))


def test_host_node_bounds_segments():
    pipe = _Mul(2.0).and_then(_Mul(3.0)).and_then(_HostOnly()).and_then(_Mul(4.0))
    g = _with_data(pipe)
    _, barriers = _plan(g)
    assert BARRIER_HOST in barriers.values()
    # the upstream pair is one segment; the single node after the host
    # boundary gains nothing from a program of its own and stays a node
    (binding,) = _bound(g)
    assert [op.k for op in _ops(binding)] == [2.0, 3.0]
    out, spans = _pull(g, pipe.sink)
    assert len(_segment_spans(spans)) == 1
    assert any(sp.name == "node._Mul" for sp in spans)
    np.testing.assert_allclose(
        np.asarray(out.to_array()), (X * 6.0 + 1.0) * 4.0
    )


def _diamond(second_branch_tail):
    shared = _Mul(2.0)
    b1 = shared.and_then(_Mul(3.0)).and_then(_Mul(5.0))
    b2 = shared.and_then(second_branch_tail[0])
    for t in second_branch_tail[1:]:
        b2 = b2.and_then(t)
    pipe = Pipeline.gather([b1, b2])
    graph, _ = EquivalentNodeMergeRule().apply(pipe.graph, {})
    return Pipeline(graph, pipe.source, pipe.sink), shared


def test_diamond_with_all_readers_traceable_is_one_segment():
    # shared feeds two traceable branches: one segment holds the prefix
    # and both branches. The gather that rejoins them is the sink's value
    # — a zipped Dataset that must leave — so it stays a barrier, and the
    # branch ends are the segment's two outputs.
    pipe, shared = _diamond([_Mul(7.0), _Mul(11.0)])
    g = _with_data(pipe)
    segments, barriers = _plan(g)
    assert list(barriers.values()).count(BARRIER_GATHER) == 1
    (binding,) = _bound(g)
    assert len(binding) == 5 and len(binding.outputs) == 2
    assert shared in _ops(binding)
    out, spans = _pull(g, pipe.sink)
    assert len(_segment_spans(spans)) == 1
    got = [np.asarray(a) for a in out.payload]
    np.testing.assert_allclose(got[0], X * 30.0)
    np.testing.assert_allclose(got[1], X * 154.0)


def test_member_read_outside_the_segment_is_a_segment_output():
    # shared feeds a traceable chain AND a host-only node: shared is a
    # member whose value also leaves the program, for the host reader
    pipe, shared = _diamond([_HostOnly()])
    g = _with_data(pipe)
    (binding,) = _bound(g)
    members = _ops(binding)
    assert shared in members and len(members) == 3
    shared_id = next(n for n in g.nodes if g.get_operator(n) is shared)
    assert shared_id in binding.outputs and len(binding.outputs) == 2
    out, _ = _pull(g, pipe.sink)
    got = [np.asarray(a) for a in out.payload]
    np.testing.assert_allclose(got[0], X * 30.0)
    np.testing.assert_allclose(got[1], X * 2.0 + 1.0)


def test_sink_consumed_interior_member_is_a_segment_output():
    # two sinks: one at the chain end, one at an interior node — both
    # values leave the one program
    a, b = _Mul(2.0), _Mul(3.0)
    graph, data = attach_data(Graph(), Dataset.of(X))
    graph, na = graph.add_node(a, [data])
    graph, nb = graph.add_node(b, [na])
    graph, sink_mid = graph.add_sink(na)
    graph, sink_end = graph.add_sink(nb)
    (binding,) = _bound(graph)
    assert binding.outputs == [na, nb]
    executor = GraphExecutor(graph, optimize=False)
    mid = executor.execute(sink_mid).get()
    end = executor.execute(sink_end).get()
    np.testing.assert_allclose(np.asarray(mid.to_array()), X * 2.0)
    np.testing.assert_allclose(np.asarray(end.to_array()), X * 6.0)


def test_annotated_node_is_a_barrier():
    pipe = _Mul(2.0).and_then(_Mul(3.0))
    g = _with_data(pipe)
    first = next(n for n in g.nodes if getattr(g.get_operator(n), "k", 0) == 2.0)
    # annotated as if it were a saveable prefix: its result must hit the
    # state table, so no program may hold it as an interior value
    _, barriers = _plan(g, {first: "prefix"})
    assert barriers[first] == BARRIER_SAVED
    assert _bound(g, {first: "prefix"}) == []
    assert len(_bound(g)) == 1


def test_item_dataset_fallback_matches_batched():
    pipe = _Mul(2.0).and_then(_Mul(3.0))
    (binding,) = _bound(_with_data(pipe))
    ragged = Dataset.from_items(
        [np.ones((2,), np.float32), np.zeros((3,), np.float32)]
    )
    (out,), path = binding.run([ragged])
    assert path == "fallback"
    got = out.collect()
    np.testing.assert_allclose(np.asarray(got[0]), np.full((2,), 6.0))
    np.testing.assert_allclose(np.asarray(got[1]), np.zeros((3,)))
    (batched,), path = binding.run([Dataset.of(np.ones((1, 2), np.float32))])
    assert path == "compiled"
    np.testing.assert_allclose(np.asarray(batched.to_array()), [[6.0, 6.0]])


def test_single_datum_pull_is_node_dispatch():
    pipe = _Mul(2.0).and_then(_Mul(3.0))
    fitted = FittedPipeline(pipe.graph, pipe.source, pipe.sink)
    tracer = tracer_mod.install(tracer_mod.Tracer())
    try:
        out = fitted.apply_datum(np.ones((3,), np.float32))
        spans = tracer.spans()
    finally:
        tracer_mod.reset()
    np.testing.assert_allclose(np.asarray(out), np.full((3,), 6.0))
    assert _segment_spans(spans) == []
    assert sum(1 for sp in spans if sp.name == "node._Mul") == 2


def test_gather_and_combiner_are_members_and_agree_with_node_dispatch():
    from keystone_tpu.nodes.util import VectorCombiner
    from keystone_tpu.workflow.operators import GatherTransformerOperator

    branches = [_Mul(float(i + 1)) for i in range(3)]
    pipe = Pipeline.gather(branches).and_then(VectorCombiner())
    g = _with_data(pipe)
    _, barriers = _plan(g)
    assert BARRIER_GATHER not in barriers.values()
    (binding,) = _bound(g)
    assert len(binding) == 5  # 3 muls + gather + combiner
    assert sum(
        isinstance(op, GatherTransformerOperator) for op in _ops(binding)
    ) == 1
    out, spans = _pull(g, pipe.sink)
    (sp,) = _segment_spans(spans)
    assert sp.attrs["path"] == "compiled"
    assert not any(sp.name.startswith("node._Mul") for sp in spans)
    ref, ref_spans = _pull(g, pipe.sink, nodes_only=True)
    assert _segment_spans(ref_spans) == []
    expect = np.concatenate([X * 1, X * 2, X * 3], axis=1)
    np.testing.assert_allclose(np.asarray(out.to_array()), expect)
    assert np.array_equal(np.asarray(out.to_array()), np.asarray(ref.to_array()))


def test_planning_is_deterministic_and_a_fitted_pipeline_pickles():
    def digests():
        pipe = _Mul(2.0).and_then(_Mul(3.0))
        return [b.digest for b in _bound(_with_data(pipe))]

    first = digests()
    assert first == digests() and len(first) == 1 and first[0]

    pipe = _Mul(2.0).and_then(_Mul(3.0))
    fitted = FittedPipeline(pipe.graph, pipe.source, pipe.sink)
    fitted.apply(Dataset.of(X))  # plans and caches the bindings
    clone = pickle.loads(pickle.dumps(fitted))
    np.testing.assert_allclose(
        np.asarray(clone.apply(Dataset.of(X)).to_array()), X * 6.0
    )


def test_no_fuse_marker_is_a_barrier():
    marked = _Mul(3.0)
    marked.no_fuse = True
    pipe = _Mul(2.0).and_then(marked).and_then(_Mul(4.0))
    g = _with_data(pipe)
    _, barriers = _plan(g)
    assert BARRIER_NO_FUSE in barriers.values()
    assert _bound(g) == []  # the singletons around it stay nodes
    out, spans = _pull(g, pipe.sink)
    assert _segment_spans(spans) == []
    np.testing.assert_allclose(np.asarray(out.to_array()), X * 24.0)


def test_unfingerprintable_chain_is_still_one_compiled_segment():
    # a lambda's closure over a jitted callable has no content-stable
    # form: no digest, so no sharing, cost record or export — but still
    # one program, owned by the binding
    import jax

    scale = jax.jit(lambda a: a * 3.0)
    pipe = _Mul(2.0).and_then(FunctionNode(batch_fn=lambda A: scale(A)))
    g = _with_data(pipe)
    (binding,) = _bound(g)
    assert binding.digest is None
    out, spans = _pull(g, pipe.sink)
    (sp,) = _segment_spans(spans)
    assert sp.attrs["path"] == "compiled" and sp.attrs["nodes"] == 2
    np.testing.assert_allclose(np.asarray(out.to_array()), X * 6.0)


def test_batch_coupled_members_over_chunks_raise_typed():
    class _Center(Transformer):
        batch_coupled = True

        def trace_batch(self, A):
            return A - A.mean(axis=0)

    pipe = _Mul(2.0).and_then(_Center())
    (binding,) = _bound(_with_data(pipe))
    assert binding.batch_coupled
    chunked = ChunkedDataset.from_array(np.ones((10, 2), np.float32), 4)
    with pytest.raises(ValueError, match="_Center.*cannot stream per-chunk"):
        binding.run([chunked])
    assert not binding._demoted  # the caller's error, not a failed dispatch
    fitted = FittedPipeline(pipe.graph, pipe.source, pipe.sink)
    with pytest.raises(ValueError, match="materialize the dataset first"):
        fitted.apply(chunked)
    # whole batches still ride the one program
    (out,), path = binding.run([Dataset.of(np.ones((4, 2), np.float32))])
    assert path == "compiled"
    np.testing.assert_allclose(np.asarray(out.to_array()), 0.0)


def test_tiny_timit_job_is_one_segment_a_data_set():
    """No optimizer patching: the default rule stack, the planner, the
    binding. The cosines, the gather and the combiner are ONE program over
    the training set at fit and (with the fitted mapper and the classifier)
    over the test set at apply; none of them runs as a node."""
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
    train = synthetic_timit(96, 4, dim=24, seed=0)
    test = synthetic_timit(24, 4, dim=24, seed=1)
    labels = ClassLabelIndicators(4).apply_batch(train.labels)
    tracer = tracer_mod.install(tracer_mod.Tracer())
    try:
        fitted = (
            build_featurizer(conf)
            .and_then(
                BlockLeastSquaresEstimator(
                    conf.cosine_features, conf.num_epochs, conf.lam
                ),
                train.data,
                labels,
            )
            .and_then(MaxClassifier())
            .fit()
        )
        fit_spans = tracer.spans()
        fitted.apply(test.data).to_array()
        apply_spans = tracer.spans()[len(fit_spans):]
    finally:
        tracer_mod.reset()
    for spans, members in ((fit_spans, 4), (apply_spans, 6)):
        (sp,) = _segment_spans(spans)
        assert sp.attrs["path"] == "compiled"
        assert sp.attrs["nodes"] == members
        assert sp.attrs["label"].startswith(
            "CosineRandomFeatures+CosineRandomFeatures+"
            "GatherTransformerOperator+VectorCombiner"
        )
        for name in (
            "node.CosineRandomFeatures", "node.GatherTransformerOperator",
            "node.VectorCombiner",
        ):
            assert not any(s.name == name for s in spans), name
