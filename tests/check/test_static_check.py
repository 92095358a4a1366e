"""Unit tests for the static pipeline checker (keystone_tpu/check/):
abstract spec propagation, the traceability lattice, segment planning,
the zero-execution guarantee, and the construction/fit-entry wiring."""

import numpy as np
import pytest

import keystone_tpu.cost as cost_mod
from keystone_tpu.check import (
    BATCH_COUPLED,
    CheckOnlyExit,
    ContractMismatchError,
    HOST_CALLBACK,
    OPAQUE,
    PipelineCheckError,
    STATEFUL,
    TRACEABLE,
    Spec,
    SpecTuple,
    check_graph,
    classify,
)
from keystone_tpu.data.chunked import ChunkedDataset
from keystone_tpu.data.dataset import Dataset
from keystone_tpu.nodes.learning.linear import (
    BlockLeastSquaresEstimator,
    LinearMapEstimator,
)
from keystone_tpu.nodes.stats import (
    LinearRectifier,
    PaddedFFT,
    RandomSignNode,
    StandardScaler,
)
from keystone_tpu.nodes.util import (
    ClassLabelIndicators,
    MaxClassifier,
    VectorCombiner,
)
from keystone_tpu.workflow.pipeline import Pipeline
from keystone_tpu.workflow.transformer import FunctionNode, Identity


def _small_pipe(d=32, k=4, n=64, est=None):
    X = np.random.RandomState(0).randn(n, d).astype(np.float32)
    y = ClassLabelIndicators(k).apply_batch(
        np.random.RandomState(1).randint(0, k, size=n)
    )
    est = est or LinearMapEstimator(lam=1.0)
    return (
        RandomSignNode.create(d, seed=0)
        .and_then(est, X, y)
        .and_then(MaxClassifier())
    )


# ---------------------------------------------------------------------------
# lattice
# ---------------------------------------------------------------------------


def test_pure_jax_node_traceable():
    assert classify(LinearRectifier(0.0)) == TRACEABLE
    assert classify(PaddedFFT()) == TRACEABLE


def test_host_node_opaque():
    from keystone_tpu.nodes.nlp.hashing import HashingTF

    assert classify(HashingTF(64)) == OPAQUE


def test_pure_callback_detected_statically():
    import functools
    import jax

    def stall(x):
        return x

    def body(X):
        return jax.pure_callback(
            functools.partial(stall),
            jax.ShapeDtypeStruct(X.shape, X.dtype), X,
        )

    assert classify(FunctionNode(batch_fn=body)) == HOST_CALLBACK


def test_callback_detected_through_closure_helper():
    import jax

    def helper(X):
        return jax.pure_callback(
            lambda a: a, jax.ShapeDtypeStruct(X.shape, X.dtype), X
        )

    def body(X):
        return helper(X) * 2.0

    assert classify(FunctionNode(batch_fn=body)) == HOST_CALLBACK


def test_shared_code_object_distinct_closures_not_memo_confused():
    """Two batch_fns from one factory share a code object but close over
    different helpers — a pure-jax one and a callback-routed one. The
    classification memo must not serve one's verdict to the other."""
    import functools
    import jax

    def cb(a):
        return a

    def callback_helper(X):
        return jax.pure_callback(
            functools.partial(cb), jax.ShapeDtypeStruct(X.shape, X.dtype), X
        )

    def pure_helper(X):
        return X * 2.0

    def make(f):
        return FunctionNode(batch_fn=lambda X: f(X), label="made")

    assert classify(make(pure_helper)) == TRACEABLE
    assert classify(make(callback_helper)) == HOST_CALLBACK
    # and in the other evaluation order, from a fresh pair
    assert classify(make(callback_helper)) == HOST_CALLBACK
    assert classify(make(pure_helper)) == TRACEABLE


def test_batch_coupled_verdict_and_instance_mutation():
    class Coupled(Identity):
        batch_coupled = True

    assert classify(Coupled()) == BATCH_COUPLED
    # post-construction instance mutation is seen (tests do this)
    node = Identity()
    node.batch_coupled = True
    assert classify(node) == BATCH_COUPLED


def test_stateful_mutation_detected():
    class Sneaky(Identity):
        def trace_batch(self, X):
            self.count = getattr(self, "count", 0) + 1
            return X

    assert classify(Sneaky()) == STATEFUL


def test_explicit_verdict_pin():
    class Pinned(Identity):
        check_verdict = STATEFUL

    assert classify(Pinned()) == STATEFUL


def test_segment_is_as_coupled_as_its_worst_member():
    from keystone_tpu.check.segments import plan_segments
    from keystone_tpu.compile.segment import bind_segment
    from keystone_tpu.workflow.pipeline import attach_data

    class Coupled(Identity):
        batch_coupled = True

    def binding_of(pipe):
        g, data = attach_data(pipe.graph, np.ones((4, 3), np.float32))
        g = g.replace_dependency(pipe.source, data).remove_source(pipe.source)
        verdicts = {n: classify(g.get_operator(n)) for n in g.nodes}
        (seg,) = [
            s for s in plan_segments(g, verdicts, {})[0] if len(s.nodes) == 2
        ]
        return sorted(verdicts[n] for n in seg.nodes), bind_segment(g, seg)

    verdicts, binding = binding_of(Identity().and_then(LinearRectifier(0.0)))
    assert verdicts == [TRACEABLE, TRACEABLE] and not binding.batch_coupled
    verdicts, binding = binding_of(Identity().and_then(Coupled()))
    assert verdicts == [BATCH_COUPLED, TRACEABLE] and binding.batch_coupled
    assert binding.coupled_labels == ["Coupled"]


# ---------------------------------------------------------------------------
# abstract interpretation
# ---------------------------------------------------------------------------


def test_specs_propagate_from_array_leaf_to_sink():
    pipe = _small_pipe(d=16, k=3)
    rep = check_graph(
        pipe.graph, source=pipe.source, datum_spec=((16,), "float32")
    )
    sink = rep.sink_spec
    assert isinstance(sink, Spec)
    assert sink.item_shape == ()  # MaxClassifier: per-item class index
    assert sink.dtype in ("int32", "int64")
    assert sink.sym  # lead dim symbolic: derived from the per-item hint


def test_gather_produces_tuple_spec_and_combiner_concats():
    branches = [
        RandomSignNode.create(8, seed=i).and_then(LinearRectifier(0.0))
        for i in range(3)
    ]
    pipe = Pipeline.gather(branches).and_then(VectorCombiner())
    rep = check_graph(
        pipe.graph, source=pipe.source, datum_spec=((8,), "float32")
    )
    assert isinstance(rep.sink_spec, Spec)
    assert rep.sink_spec.item_shape == (3 * 8,)


def test_chunked_leaf_carries_item_spec_without_production():
    produced = []

    def chunk(i):
        produced.append(i)
        return np.zeros((16, 8), np.float32)

    ds = ChunkedDataset.from_chunk_fn(chunk, 4, 64)
    ds._item_spec = ((8,), "float32")
    pipe = Identity().and_then(LinearMapEstimator(lam=1.0), ds, np.zeros(
        (64, 2), np.float32
    ))
    rep = check_graph(
        pipe.graph, source=pipe.source, datum_spec=((8,), "float32")
    )
    assert produced == []  # the whole check produced ZERO chunks
    assert isinstance(rep.sink_spec, Spec)
    assert rep.sink_spec.item_shape == (2,)  # labels dim via fitted_out_spec


def test_from_array_records_item_spec():
    ds = ChunkedDataset.from_array(np.zeros((100, 7), np.float32), 32)
    assert ds.item_spec == ((7,), "float32")


def test_shape_mismatch_raises_node_attributed_at_and_then():
    """The acceptance gate: a mismatched composition fails AT
    CONSTRUCTION, names the offending node, and produces zero chunks."""
    produced = []

    def chunk(i):
        produced.append(i)
        return np.zeros((16, 100), np.float32)

    ds = ChunkedDataset.from_chunk_fn(chunk, 4, 64)
    ds._item_spec = ((100,), "float32")  # pipeline expects 784
    labels = np.zeros((64, 10), np.float32)

    feat = (
        RandomSignNode.create(784, seed=0)
        .and_then(PaddedFFT())
        .and_then(LinearRectifier(0.0))
    )
    with pytest.raises(PipelineCheckError) as ei:
        feat.and_then(BlockLeastSquaresEstimator(512, 1, 1.0), ds, labels)
    assert "RandomSignNode" in str(ei.value)
    assert ei.value.node is not None
    assert produced == []  # nothing scanned before the refusal


def test_dtype_mismatch_weaker_than_shape_does_not_false_positive():
    # float64 data through a float32-param chain PROMOTES, it does not
    # error — the checker must not invent failures eval_shape allows
    X = np.random.RandomState(0).randn(32, 16).astype(np.float64)
    y = ClassLabelIndicators(3).apply_batch(
        np.random.RandomState(1).randint(0, 3, size=32)
    )
    pipe = RandomSignNode.create(16, seed=0).and_then(
        LinearMapEstimator(lam=1.0), X, y
    )
    assert pipe is not None


def test_batch_coupled_on_chunked_stream_raises():
    class Coupled(Identity):
        batch_coupled = True

        def trace_batch(self, X):
            return X - X.mean(axis=0)

    ds = ChunkedDataset.from_array(np.zeros((64, 8), np.float32), 16)
    # composition graph: Coupled consumes the chunked leaf on the
    # estimator-data path — refused AT and_then, before any scan
    with pytest.raises(PipelineCheckError, match="batch-coupled"):
        Coupled().and_then(
            LinearMapEstimator(lam=1.0), ds,
            np.zeros((64, 2), np.float32),
        )


def test_cacher_materializes_chunked_stream_for_coupled_node():
    from keystone_tpu.nodes.util import Cacher

    class Coupled(Identity):
        batch_coupled = True

        def trace_batch(self, X):
            return X - X.mean(axis=0)

    ds = ChunkedDataset.from_array(np.zeros((64, 8), np.float32), 16)
    pipe = (
        Cacher()
        .and_then(Coupled())
        .and_then(LinearMapEstimator(lam=1.0), ds, np.zeros(
            (64, 2), np.float32
        ))
    )
    check_graph(pipe.graph, source=pipe.source)  # no error


def test_out_spec_declaration_consumed():
    from keystone_tpu.nodes.util.core import MultiClassLabelIndicators

    node = MultiClassLabelIndicators(7)
    pipe = node.to_pipeline()
    rep = check_graph(pipe.graph, source=pipe.source)
    assert isinstance(rep.sink_spec, Spec)
    assert rep.sink_spec.item_shape == (7,)
    assert rep.sink_spec.dtype == "float32"


def test_vector_splitter_declares_tuple_spec():
    from keystone_tpu.nodes.util.core import VectorSplitter

    pipe = VectorSplitter(3).to_pipeline()
    rep = check_graph(
        pipe.graph, source=pipe.source, datum_spec=((8,), "float32")
    )
    assert isinstance(rep.sink_spec, SpecTuple)
    widths = [e.item_shape[-1] for e in rep.sink_spec.elems]
    assert widths == [3, 3, 2]


def test_standard_scaler_fitted_out_spec_preserves():
    X = np.random.RandomState(0).randn(32, 12).astype(np.float32)
    pipe = Identity().and_then(StandardScaler(), X).and_then(
        MaxClassifier()
    )
    rep = check_graph(
        pipe.graph, source=pipe.source, datum_spec=((12,), "float32")
    )
    assert isinstance(rep.sink_spec, Spec)


# ---------------------------------------------------------------------------
# segments
# ---------------------------------------------------------------------------


def test_segment_plan_splits_at_cacher_and_estimator():
    from keystone_tpu.nodes.util import Cacher

    pipe = _small_pipe(d=16, k=3)
    rep = check_graph(
        pipe.graph, source=pipe.source, datum_spec=((16,), "float32")
    )
    assert rep.segment_count >= 2  # estimator-path + serve-path segments
    assert any(r == "estimator" for r in rep.barriers.values())

    fitted = pipe.fit()
    frep = fitted.check(span=False)
    assert frep.segment_count == 1  # fitted chain: one compilable unit

    # a Cacher in the (unfused) graph splits the plan around it — the
    # raw composition graph keeps the Cacher node (the optimizer may
    # later fuse an unannotated one, which legitimately merges segments)
    capped = (
        RandomSignNode.create(16, seed=0)
        .and_then(Cacher())
        .and_then(LinearRectifier(0.0))
        .to_pipeline()
    )
    crep = check_graph(
        capped.graph, source=capped.source, datum_spec=((16,), "float32")
    )
    assert crep.segment_count == 2
    assert "cacher" in crep.barriers.values()


def test_segment_bytes_priced_from_specs():
    pipe = RandomSignNode.create(16, seed=0).and_then(
        LinearRectifier(0.0)
    ).to_pipeline()
    rep = check_graph(
        pipe.graph, source=pipe.source, datum_spec=((16,), "float32")
    )
    (seg,) = rep.segments
    # two (16,)-float32 node outputs → 64 + 64 bytes per item
    assert seg.est_item_bytes == 16 * 4 * 2


# ---------------------------------------------------------------------------
# zero-execution guarantee + wiring
# ---------------------------------------------------------------------------


def test_check_executes_zero_samples():
    cost_mod.reset_sampling()
    pipe = _small_pipe(d=16, k=3)
    pipe.check(span=False)
    pipe.fit()  # the fit MAY sample (autocache); reset and re-check
    cost_mod.reset_sampling()
    pipe.check(span=False)
    assert cost_mod.sampling_executions()["total"] == 0


def test_kill_switch_disables_implicit_checks(monkeypatch):
    monkeypatch.setenv("KEYSTONE_STATIC_CHECK", "0")
    ds = ChunkedDataset.from_chunk_fn(
        lambda i: np.zeros((16, 100), np.float32), 4, 64
    )
    ds._item_spec = ((100,), "float32")
    feat = RandomSignNode.create(784, seed=0).and_then(PaddedFFT())
    # with the switch off, the bad composition constructs (the defect
    # would surface at execution, as before this subsystem existed)
    pipe = feat.and_then(
        BlockLeastSquaresEstimator(512, 1, 1.0), ds,
        np.zeros((64, 10), np.float32),
    )
    # the EXPLICIT check still runs and still raises
    with pytest.raises(PipelineCheckError):
        pipe.check(span=False)


def test_fit_entry_raises_before_any_chunk(monkeypatch):
    produced = []

    def chunk(i):
        produced.append(i)
        return np.zeros((16, 100), np.float32)

    ds = ChunkedDataset.from_chunk_fn(chunk, 4, 64)
    # no item_spec recorded → and_then cannot prove the mismatch...
    feat = RandomSignNode.create(784, seed=0).and_then(PaddedFFT())
    pipe = feat.and_then(
        BlockLeastSquaresEstimator(512, 1, 1.0), ds,
        np.zeros((64, 10), np.float32),
    )
    # ...but once the spec IS known (say, recorded later), fit() refuses
    ds._item_spec = ((100,), "float32")
    with pytest.raises(PipelineCheckError, match="RandomSignNode"):
        pipe.fit()
    assert produced == []


def test_check_report_span_emitted():
    from keystone_tpu.obs import tracer as obs_tracer

    t = obs_tracer.Tracer()
    installed = obs_tracer.install(t)
    try:
        pipe = _small_pipe(d=16, k=3)
        pipe.check()
        spans = [s for s in t.spans() if s.name == "check.report"]
        assert spans, "no check.report span"
        sp = spans[-1]
        assert sp.attrs["segments"] >= 2
        assert sp.attrs["sampling_total"] == 0
        assert sp.attrs["nodes"] > 0
    finally:
        obs_tracer.uninstall(installed)


def test_check_only_mode_via_cli(capsys):
    from keystone_tpu.__main__ import main as cli_main

    rc = cli_main([
        "mnist", "--backend", "cpu", "--numFFTs", "1",
        "--blockSize", "256", "--lambda", "10", "--check",
    ])
    assert rc == 0
    out = capsys.readouterr().out
    assert "CHECK OK" in out and "0 executions" in out
    # mode must not leak to later fits in this process
    from keystone_tpu import check as check_pkg

    assert not check_pkg.check_only_mode()


# ---------------------------------------------------------------------------
# serving-contract validation
# ---------------------------------------------------------------------------


def test_swap_contract_mismatch_is_typed_with_node_attribution():
    fitted = _small_pipe(d=16, k=3).fit()
    rep = fitted.check(span=False)
    with pytest.raises(ContractMismatchError, match="does not match"):
        rep.require_contract((8,), "float32", verb="swap")
    with pytest.raises(ContractMismatchError, match="does not match"):
        rep.require_contract((16,), "float64", verb="swap")
    rep.require_contract((16,), "float32", verb="swap")  # clean


def test_swap_contract_batch_coupled_names_node():
    fitted = _small_pipe(d=16, k=3).fit()
    node = next(iter(fitted.graph.nodes))
    fitted.graph.get_operator(node).batch_coupled = True
    rep = fitted.check(span=False)
    with pytest.raises(ContractMismatchError) as ei:
        rep.require_contract(None, None, verb="swap")
    assert ei.value.node is not None
    assert ei.value.label is not None


def test_coupling_refused_even_with_worse_lattice_trait():
    """Coupling is orthogonal to the verdict: a batch-coupled node that
    ALSO routes through a host callback classifies host_callback in the
    lattice, but the pad-and-slice serving paths must still refuse it."""
    import functools
    import jax

    def body(X):
        X = jax.pure_callback(
            functools.partial(lambda a: a),
            jax.ShapeDtypeStruct(X.shape, X.dtype), X,
        )
        return X - X.mean(axis=0)

    node = FunctionNode(batch_fn=body, label="coupled_callback")
    node.batch_coupled = True
    assert classify(node) == HOST_CALLBACK  # verdict: the worse trait
    fitted = node.to_pipeline().fit()
    rep = fitted.check(span=False)
    assert rep.batch_coupled_labels() == ["coupled_callback"]
    with pytest.raises(ContractMismatchError, match="batch-coupled"):
        rep.require_contract(None, None, verb="serve")


def test_worker_boot_contract_validation():
    fitted = _small_pipe(d=16, k=3).fit()
    rep = fitted.check(span=False)
    # the worker-boot call shape (cluster/worker.py): spec'd contract
    with pytest.raises(ContractMismatchError, match="boot"):
        rep.require_contract((99,), "float32", verb="boot")


def test_check_error_pickles_with_attribution():
    import pickle

    e = PipelineCheckError("bad spec", node="node[3]", label="PaddedFFT")
    e2 = pickle.loads(pickle.dumps(e))
    assert e2.node == "node[3]" and e2.label == "PaddedFFT"
    assert str(e2) == str(e)
