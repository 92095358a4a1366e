"""Segment-compiled execution: lowering, dispatch, AOT round trips,
adaptive boundaries, and — above all — answer preservation.

The executor dispatches the SEGMENT graph, so the load-bearing contract is
equality with node dispatch — operator by operator, ``segment_plan={}``,
sharing no program with the segment — on every path (compiled,
chunked/ragged, fallback) plus the warm-boot guarantee: a second process
loads exported segment executables and never re-traces.
"""

import numpy as np
import pytest

import keystone_tpu.compile as cmod
import keystone_tpu.cost as cost
from keystone_tpu.check import lattice
from keystone_tpu.check.segments import plan_segments
from keystone_tpu.compile import ExecutableCache
from keystone_tpu.compile import manifest as manifest_mod
from keystone_tpu.compile.fingerprint import segment_fingerprint
from keystone_tpu.compile.segment import (
    SegmentDispatcher,
    bind_segment,
    lower_segment,
    prewarm_segment_artifacts,
    reset_dispatchers,
)
from keystone_tpu.cost import segments as seg_cost
from keystone_tpu.data.chunked import ChunkedDataset
from keystone_tpu.data.dataset import Dataset
from keystone_tpu.obs import tracer as tracer_mod
from keystone_tpu.workflow.executor import GraphExecutor
from keystone_tpu.workflow.graph import Graph
from keystone_tpu.workflow.operators import DatasetOperator
from keystone_tpu.workflow.pipeline import FittedPipeline, attach_data
from keystone_tpu.workflow.transformer import Transformer


@pytest.fixture(autouse=True)
def _isolate_segment_state():
    """Dispatchers are process-global (keyed by digest + cache root) and
    these tests install a process-global AOT cache; neither may leak."""
    reset_dispatchers()
    yield
    reset_dispatchers()
    cmod.reset()


class _Mul(Transformer):
    def __init__(self, k):
        self.k = k

    def trace_batch(self, X):
        return X * self.k


class _Add(Transformer):
    """Two-input traceable member: only constructible through the raw
    Graph API (and_then chains are unary), but the lowering must handle
    multi-dep members positionally. Asymmetric on purpose — a swapped
    argument order changes the answer."""

    def trace_batch(self, X, Y):
        return X + 2.0 * Y


class _HostOnly(Transformer):
    """No trace_batch — a segment barrier, like Cacher/Shuffler."""

    def apply(self, x):
        return x + 1.0


def _mul_chain_fitted():
    pipe = _Mul(2.0).and_then(_Mul(3.0)).and_then(_Mul(0.5))
    return FittedPipeline(pipe.graph, pipe.source, pipe.sink)


def _apply_by_nodes(fitted, data):
    """``fitted.apply(data)`` through node dispatch: the plan every
    executor accepts as "planned, nothing eligible"."""
    g, data_id = attach_data(fitted.graph, data)
    g = g.replace_dependency(fitted._source, data_id)
    g = g.remove_source(fitted._source)
    executor = GraphExecutor(g, optimize=False, segment_plan={})
    return executor.execute(fitted._sink).get()


def _plan(graph):
    verdicts = {n: lattice.classify(graph.get_operator(n)) for n in graph.nodes}
    segments, barriers = plan_segments(graph, verdicts, {})
    return segments, barriers


def _two_input_graph():
    g = Graph()
    g, a = g.add_node(
        DatasetOperator(Dataset.of(np.ones((4, 3), np.float32))), []
    )
    g, b = g.add_node(
        DatasetOperator(Dataset.of(np.full((4, 3), 2.0, np.float32))), []
    )
    # deps deliberately NOT in graph-id order: the pinned inputs contract
    # must come from linearization, not from dependency iteration
    g, c = g.add_node(_Add(), [b, a])
    g, d = g.add_node(_Mul(3.0), [c])
    g, sink = g.add_sink(d)
    return g, (a, b, c, d)


X10 = np.arange(40, dtype=np.float32).reshape(10, 4)


# ---------------------------------------------------------------------------
# Planning contract + lowering
# ---------------------------------------------------------------------------


def test_segment_inputs_are_pinned_to_linearization_order():
    from keystone_tpu.workflow import analysis

    g, (a, b, c, d) = _two_input_graph()
    segments, _ = _plan(g)
    (seg,) = [s for s in segments if len(s.nodes) == 2]
    assert seg.nodes == [c, d] and seg.outputs == [d]
    assert set(seg.inputs) == {a, b}
    full_pos = {gid: i for i, gid in enumerate(analysis.linearize(g))}
    assert seg.inputs == sorted(seg.inputs, key=lambda i: full_pos[i])
    # the plan (and therefore the lowered signature) is deterministic
    segments2, _ = _plan(g)
    (seg2,) = [s for s in segments2 if len(s.nodes) == 2]
    assert seg2.inputs == seg.inputs and seg2.nodes == seg.nodes


def test_fingerprint_is_stable_and_state_sensitive():
    g, _ = _two_input_graph()
    (seg,) = [s for s in _plan(g)[0] if len(s.nodes) == 2]
    d1 = segment_fingerprint(g, seg)
    g2, _ = _two_input_graph()
    (seg2,) = [s for s in _plan(g2)[0] if len(s.nodes) == 2]
    assert segment_fingerprint(g2, seg2) == d1

    gk = Graph()
    gk, a = gk.add_node(
        DatasetOperator(Dataset.of(np.ones((4, 3), np.float32))), []
    )
    gk, b = gk.add_node(
        DatasetOperator(Dataset.of(np.full((4, 3), 2.0, np.float32))), []
    )
    gk, c = gk.add_node(_Add(), [b, a])
    gk, d = gk.add_node(_Mul(4.0), [c])  # different operator state
    gk, _sink = gk.add_sink(d)
    (segk,) = [s for s in _plan(gk)[0] if len(s.nodes) == 2]
    assert segment_fingerprint(gk, segk) != d1


def test_lower_segment_composes_members_positionally():
    g, (a, b, _c, _d) = _two_input_graph()
    (seg,) = [s for s in _plan(g)[0] if len(s.nodes) == 2]
    fn, steps, out_slots = lower_segment(g, seg)
    assert len(steps) == 2 and len(out_slots) == 1
    # feed by the pinned order: one value per segment input, positionally
    by_node = {
        a: np.ones((4, 3), np.float32),
        b: np.full((4, 3), 2.0, np.float32),
    }
    out = fn(*[by_node[i] for i in seg.inputs])
    # _Add's deps are (b, a): (2 + 2*1) * 3 — a swapped argument order
    # would produce (1 + 2*2) * 3 = 15 instead
    np.testing.assert_allclose(np.asarray(out[0]), 12.0)


def test_binding_dispatches_two_input_segment_compiled():
    g, (a, b, _c, _d) = _two_input_graph()
    (seg,) = [s for s in _plan(g)[0] if len(s.nodes) == 2]
    binding = bind_segment(g, seg)
    assert binding is not None and len(binding) == 2
    ins = {
        a: Dataset.of(np.ones((4, 3), np.float32)),
        b: Dataset.of(np.full((4, 3), 2.0, np.float32)),
    }
    outs, path = binding.run([ins[i] for i in binding.inputs])
    assert path == "compiled"
    np.testing.assert_allclose(np.asarray(outs[0].to_array()), 12.0)


def test_singleton_plain_node_is_not_bound():
    pipe = _Mul(2.0).and_then(_HostOnly()).and_then(_Mul(4.0))
    g, data_id = attach_data(pipe.graph, Dataset.of(X10))
    g = g.replace_dependency(pipe.source, data_id)
    g = g.remove_source(pipe.source)
    segments, barriers = _plan(g)
    # the host node is a barrier; the _Mul singletons around it gain
    # nothing from segment dispatch and must not bind
    assert "host" in barriers.values()
    for seg in segments:
        assert bind_segment(g, seg) is None


# ---------------------------------------------------------------------------
# Executor dispatch: spans, parity with node dispatch
# ---------------------------------------------------------------------------


def test_chain_applies_as_one_segment_span():
    fitted = _mul_chain_fitted()
    tracer = tracer_mod.install(tracer_mod.Tracer())
    try:
        y = np.asarray(fitted.apply(Dataset.of(X10)).to_array())
        spans = tracer.spans()
    finally:
        tracer_mod.reset()
    np.testing.assert_allclose(y, X10 * 3.0)
    seg_spans = [sp for sp in spans if sp.name == "exec.segment"]
    assert len(seg_spans) == 1
    (sp,) = seg_spans
    assert sp.attrs["nodes"] == 3 and sp.attrs["path"] == "compiled"
    assert len(sp.attrs["node_ids"]) == 3
    # member nodes emit NO per-node spans — that is the dispatch saving
    assert not any("_Mul" in s.name for s in spans)

    tracer = tracer_mod.install(tracer_mod.Tracer())
    try:
        y_node = np.asarray(
            _apply_by_nodes(fitted, Dataset.of(X10)).to_array()
        )
        node_spans = tracer.spans()
    finally:
        tracer_mod.reset()
    assert not any(s.name == "exec.segment" for s in node_spans)
    assert sum(1 for s in node_spans if "_Mul" in s.name) == 3
    assert np.array_equal(y, y_node), "one program must not change answers"


def test_ragged_final_chunk_rides_chunk_padder():
    fitted = _mul_chain_fitted()
    chunked = ChunkedDataset.from_array(X10, 4)  # chunks of 4, 4, 2 rows
    tracer = tracer_mod.install(tracer_mod.Tracer())
    try:
        y = np.asarray(fitted.apply(chunked).to_array())
        spans = tracer.spans()
    finally:
        tracer_mod.reset()
    np.testing.assert_allclose(y, X10 * 3.0)
    (sp,) = [s for s in spans if s.name == "exec.segment"]
    assert sp.attrs["path"] == "chunked"

    y_node = np.asarray(
        _apply_by_nodes(fitted, ChunkedDataset.from_array(X10, 4)).to_array()
    )
    assert np.array_equal(y, y_node)


def test_host_callback_chain_degrades_to_node_dispatch():
    pipe = _Mul(2.0).and_then(_HostOnly()).and_then(_Mul(4.0))
    fitted = FittedPipeline(pipe.graph, pipe.source, pipe.sink)
    tracer = tracer_mod.install(tracer_mod.Tracer())
    try:
        y = np.asarray(fitted.apply(Dataset.of(X10)).to_array())
        spans = tracer.spans()
    finally:
        tracer_mod.reset()
    np.testing.assert_allclose(y, (X10 * 2.0 + 1.0) * 4.0)
    # no bindable segment around the host barrier: pure node dispatch,
    # no demotion warnings, no errors
    assert not any(s.name == "exec.segment" for s in spans)
    assert any("_HostOnly" in s.name for s in spans)


# ---------------------------------------------------------------------------
# End-to-end parity on the named pipelines (gather / diamond shapes)
# ---------------------------------------------------------------------------


def test_mnist_random_fft_segment_vs_node_equality(monkeypatch):
    from keystone_tpu.nodes.learning.linear import BlockLeastSquaresEstimator
    from keystone_tpu.nodes.util import ClassLabelIndicators, MaxClassifier
    from keystone_tpu.pipelines.mnist_random_fft import (
        NUM_CLASSES,
        MnistRandomFFTConfig,
        build_featurizer,
        synthetic_mnist,
    )

    conf = MnistRandomFFTConfig(num_ffts=2, block_size=512, lam=10.0)
    train, test = synthetic_mnist(128, 32, seed=7)

    def fit():
        labels = ClassLabelIndicators(NUM_CLASSES).apply_batch(train.labels)
        return (
            build_featurizer(conf)
            .and_then(
                BlockLeastSquaresEstimator(
                    conf.block_size, 1, conf.lam or 0.0
                ),
                train.data,
                labels,
            )
            .and_then(MaxClassifier())
            .fit()
        )

    fitted = fit()
    tracer = tracer_mod.install(tracer_mod.Tracer())
    try:
        y_seg = np.asarray(fitted.apply(test.data).to_array())
        spans = tracer.spans()
    finally:
        tracer_mod.reset()
    assert any(s.name == "exec.segment" for s in spans)

    y_node = np.asarray(_apply_by_nodes(fitted, test.data).to_array())
    assert np.array_equal(y_seg, y_node)

    # a fit run entirely under node dispatch trains the same model
    monkeypatch.setattr(
        GraphExecutor, "_plan_segment_bindings", lambda self: {}
    )
    fitted_off = fit()
    y_off = np.asarray(_apply_by_nodes(fitted_off, test.data).to_array())
    assert np.array_equal(y_seg, y_off)


def test_timit_segment_vs_node_equality():
    """One program against operator-by-operator dispatch of the same
    fitted chain: on the CPU the float32 scores agree to the bit, so the
    predicted labels do. (Before PR 31 this compared the fused operator's
    program with itself.)"""
    from keystone_tpu.nodes.learning.linear import BlockLeastSquaresEstimator
    from keystone_tpu.nodes.util import ClassLabelIndicators, MaxClassifier
    from keystone_tpu.pipelines.timit import (
        TimitConfig,
        build_featurizer,
        synthetic_timit,
    )

    conf = TimitConfig(
        num_cosines=3, cosine_features=64, input_dim=24, num_epochs=1,
        lam=1e-2, num_classes=4,
    )
    train = synthetic_timit(96, 4, dim=24, seed=0)
    test = synthetic_timit(24, 4, dim=24, seed=1)
    labels = ClassLabelIndicators(4).apply_batch(train.labels)

    def fit(*tail):
        pipe = build_featurizer(conf).and_then(
            BlockLeastSquaresEstimator(
                conf.cosine_features, conf.num_epochs, conf.lam
            ),
            train.data,
            labels,
        )
        for node in tail:
            pipe = pipe.and_then(node)
        return pipe.fit()

    for fitted in (fit(), fit(MaxClassifier())):
        y_seg = np.asarray(fitted.apply(test.data).to_array())
        y_node = np.asarray(_apply_by_nodes(fitted, test.data).to_array())
        assert np.array_equal(y_seg, y_node)


# ---------------------------------------------------------------------------
# AOT round trip: cold exports, warm loads, prewarm
# ---------------------------------------------------------------------------


def test_cold_run_exports_and_warm_run_loads_zero_trace(tmp_path):
    from keystone_tpu.compile import segment as segment_mod

    cache = cmod.configure(str(tmp_path))
    assert cache is not None
    y_cold = np.asarray(_mul_chain_fitted().apply(Dataset.of(X10)).to_array())
    (disp,) = list(segment_mod._DISPATCHERS.values())
    assert disp.traced_count == 1 and disp.loaded_count == 0
    digests = manifest_mod.segment_digests(cache)
    assert digests == [disp.digest]
    sigs = manifest_mod.segment_signatures(cache, disp.digest)
    assert sigs == [(((10, 4), "float32"),)]

    # "new process": dispatcher registry dropped, same pipeline rebuilt
    reset_dispatchers()
    y_warm = np.asarray(_mul_chain_fitted().apply(Dataset.of(X10)).to_array())
    (disp2,) = list(segment_mod._DISPATCHERS.values())
    assert disp2.digest == disp.digest
    assert disp2.loaded_count == 1 and disp2.traced_count == 0, (
        "a warm boot must load the exported segment, never re-trace"
    )
    assert np.array_equal(y_cold, y_warm)


def test_prewarm_warms_manifest_indexed_segments(tmp_path):
    cache = cmod.configure(str(tmp_path))
    _mul_chain_fitted().apply(Dataset.of(X10)).to_array()
    assert prewarm_segment_artifacts(cache) >= 1
    # an empty cache prewarms nothing and does not fail
    assert prewarm_segment_artifacts(ExecutableCache(str(tmp_path / "e"))) == 0


def test_dispatcher_without_cache_uses_structural_jit():
    disp = SegmentDispatcher(
        lambda x: (x * 2.0,), "ab" * 32, None, label="t", n_nodes=2
    )
    y = disp(np.ones((2, 2), np.float32))
    np.testing.assert_allclose(np.asarray(y[0]), 2.0)
    y = disp(np.ones((3, 2), np.float32))  # second signature, same jit
    np.testing.assert_allclose(np.asarray(y[0]), 2.0)
    assert disp.loaded_count == 0 and disp.traced_count == 0


def test_manifest_segment_records_roundtrip(tmp_path):
    cache = ExecutableCache(str(tmp_path))
    digest = "c" * 64
    sigs = (((4, 3), "float32"), ((4, 1), "int32"))
    manifest_mod.record_segment(cache, digest, sigs)
    manifest_mod.record_segment(cache, digest, sigs)  # idempotent
    assert manifest_mod.segment_signatures(cache, digest) == [sigs]
    assert manifest_mod.segment_digests(cache) == [digest]
    manifest_mod.record_segment(cache, digest, (((8, 3), "float32"),))
    assert len(manifest_mod.segment_signatures(cache, digest)) == 2


# ---------------------------------------------------------------------------
# Adaptive boundaries: demotion policy + runtime-failure fallback
# ---------------------------------------------------------------------------


def test_compile_exceeding_savings_demotes_unexported_segment(tmp_path):
    cost.configure(str(tmp_path))
    digest = "a" * 64
    assert seg_cost.should_compile(digest, 3)
    seg_cost.record_compile(digest, 1.0, exported=False, n_nodes=3)
    for _ in range(seg_cost.MIN_RUNS_FOR_DEMOTION - 1):
        seg_cost.record_run(digest, 1e-5, n_nodes=3)
    assert seg_cost.should_compile(digest, 3)  # below the evidence floor
    seg_cost.record_run(digest, 1e-5, n_nodes=3)
    assert not seg_cost.should_compile(digest, 3)
    rec = cost.get_store().load("plan/segment/" + digest[:32])
    assert rec["why"] == "compile_exceeds_savings"


def test_exported_segment_never_demotes(tmp_path):
    cost.configure(str(tmp_path))
    digest = "b" * 64
    seg_cost.record_compile(digest, 100.0, exported=True, n_nodes=3)
    for _ in range(seg_cost.MIN_RUNS_FOR_DEMOTION * 2):
        seg_cost.record_run(digest, 1e-6, n_nodes=3)
    # the export amortizes across processes: a sunk compile is never
    # charged against this process's dispatch savings
    assert seg_cost.should_compile(digest, 3)


def test_runtime_failure_demotes_and_next_plan_splits(tmp_path):
    cost.configure(str(tmp_path))
    g, _ = _two_input_graph()
    (seg,) = [s for s in _plan(g)[0] if len(s.nodes) == 2]
    binding = bind_segment(g, seg)
    assert binding is not None
    seg_cost.record_failure(binding.digest)
    assert bind_segment(g, seg) is None, (
        "a demoted digest must split back to node dispatch at plan time"
    )


def test_failed_dispatch_falls_back_to_exact_node_semantics():
    pipe = _Mul(2.0).and_then(_Mul(3.0))
    fitted = FittedPipeline(pipe.graph, pipe.source, pipe.sink)
    g, data_id = attach_data(fitted.graph, Dataset.of(X10))
    g = g.replace_dependency(pipe.source, data_id)
    g = g.remove_source(pipe.source)
    (seg,) = [s for s in _plan(g)[0] if len(s.nodes) == 2]
    binding = bind_segment(g, seg)
    assert binding is not None

    def boom(*xs):
        raise RuntimeError("synthetic trace failure")

    binding.fn = boom
    binding.digest = "f" * 64  # fresh dispatcher, not the cached good one
    outs, path = binding.run([Dataset.of(X10)])
    assert path == "fallback" and binding._demoted
    np.testing.assert_allclose(np.asarray(outs[0].to_array()), X10 * 6.0)
    # subsequent runs stay demoted without retrying the broken program
    outs2, path2 = binding.run([Dataset.of(X10)])
    assert path2 == "fallback"
    np.testing.assert_allclose(np.asarray(outs2[0].to_array()), X10 * 6.0)


def test_cost_recording_is_noop_without_store():
    assert cost.get_store() is None
    digest = "d" * 64
    seg_cost.record_compile(digest, 1.0, exported=False, n_nodes=3)
    seg_cost.record_run(digest, 1.0, n_nodes=3)
    seg_cost.record_failure(digest)
    assert seg_cost.should_compile(digest, 3)
