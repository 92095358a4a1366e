"""``CosineScoresRule``: a fitted chain of cosine branches → gather →
combiner → ``BlockLinearMapper`` becomes ONE ``CosineLinearMapper`` in
``Pipeline.fit``'s graph — the node's kernel on one TPU chip, the same
bodies elsewhere — and every other fitted graph comes back as given."""

import numpy as np
import pytest

from benchmark.program import linear_model
from keystone_tpu.compile.segment import reset_dispatchers
from keystone_tpu.nodes.learning.cosine_linear import (
    CosineLinearMapper,
    CosineScoresRule,
)
from keystone_tpu.nodes.learning.linear import (
    BlockLeastSquaresEstimator,
    BlockLinearMapper,
)
from keystone_tpu.nodes.util import ClassLabelIndicators, MaxClassifier
from keystone_tpu.obs import tracer as tracer_mod
from keystone_tpu.ops import cosine_scores as cs
from keystone_tpu.pipelines.timit import (
    TimitConfig,
    build_featurizer,
    synthetic_timit,
)
from keystone_tpu.workflow.env import PipelineEnv


def _timit_job(features=128):
    PipelineEnv.get_or_create().reset()
    conf = TimitConfig(
        num_cosines=2, cosine_features=features, input_dim=24, num_epochs=1,
        lam=1e-2, num_classes=4,
    )
    train = synthetic_timit(96, 4, dim=24, seed=0)
    test = synthetic_timit(40, 4, dim=24, seed=1)
    labels = ClassLabelIndicators(4).apply_batch(train.labels)
    predictor = (
        build_featurizer(conf)
        .and_then(
            BlockLeastSquaresEstimator(features, 1, conf.lam),
            train.data, labels,
        )
        .and_then(MaxClassifier())
    )
    return predictor, test


def _ops(fitted):
    graph = fitted.graph
    return [type(graph.get_operator(n)).__name__ for n in sorted(graph.nodes)]


@pytest.fixture
def calls(monkeypatch):
    """Every graph the rule was given, with what it gave back."""
    seen = []
    apply = CosineScoresRule.apply

    def recording(self, graph, annotations):
        out = apply(self, graph, annotations)
        seen.append((graph, out[0]))
        return out

    monkeypatch.setattr(CosineScoresRule, "apply", recording)
    return seen


def test_the_rule_fires_on_timits_fitted_graph(calls):
    predictor, test = _timit_job()
    unfitted = np.asarray(predictor(test.data).get().to_array())
    fitted = predictor.fit()
    assert _ops(fitted) == ["CosineLinearMapper", "MaxClassifier"]
    (given, made), = calls
    assert made is not given
    # off the TPU the node runs the bodies the chain wrote: the same labels
    np.testing.assert_array_equal(
        np.asarray(fitted.apply(test.data).to_array()), unfitted
    )


def test_the_model_reads_the_same_before_and_after(monkeypatch):
    """``benchmark/program.py:linear_model`` finds the fused node as it
    found the mapper: the same W, b and mean, to the bit."""
    before = linear_model(_fitted_without_the_rule(monkeypatch))
    after = linear_model(_timit_job()[0].fit())
    for key in ("W", "b", "mean"):
        np.testing.assert_array_equal(after[key], before[key])


def _fitted_without_the_rule(monkeypatch):
    with monkeypatch.context() as m:
        m.setattr(CosineScoresRule, "apply", lambda self, g, a: (g, a))
        fitted = _timit_job()[0].fit()
    assert _ops(fitted) == [
        "CosineRandomFeatures", "CosineRandomFeatures",
        "GatherTransformerOperator", "VectorCombiner", "BlockLinearMapper",
        "MaxClassifier",
    ]
    return fitted


@pytest.mark.parametrize("pipeline", ["random_patch_cifar", "random_patch_cifar_kernel"])
def test_other_fitted_graphs_come_back_as_given(pipeline, calls):
    """``cifar_patch10k``'s and ``cifar_krr``'s pipelines call ``fit()`` in
    their timed job: the rule hands their graph back untouched."""
    import importlib

    from keystone_tpu.pipelines.random_patch_cifar import synthetic_cifar

    module = importlib.import_module(f"keystone_tpu.pipelines.{pipeline}")
    conf_cls = (
        module.KernelCifarConfig if pipeline.endswith("kernel")
        else module.RandomCifarConfig
    )
    conf = conf_cls(num_filters=8, patch_steps=2, whitener_size=200, seed=0)
    if pipeline.endswith("kernel"):
        conf.block_size = 64
    PipelineEnv.get_or_create().reset()
    module.run(synthetic_cifar(128, seed=1), synthetic_cifar(32, seed=2), conf)
    assert calls
    for given, made in calls:
        assert made is given
        assert not any(
            isinstance(given.get_operator(n), CosineLinearMapper)
            for n in given.nodes
        )


def test_a_saved_interior_result_is_left_as_written(monkeypatch):
    fitted = _fitted_without_the_rule(monkeypatch).graph
    combiner = next(
        n for n in fitted.nodes
        if type(fitted.get_operator(n)).__name__ == "VectorCombiner"
    )
    graph, _ = CosineScoresRule().apply(fitted, {combiner: object()})
    assert graph is fitted
    graph, _ = CosineScoresRule().apply(fitted, {})
    assert graph is not fitted


def test_a_mapper_with_refit_state_is_left_as_written(monkeypatch):
    graph = _fitted_without_the_rule(monkeypatch).graph
    (mapper,) = [
        n for n in graph.nodes
        if type(graph.get_operator(n)) is BlockLinearMapper
    ]
    op = graph.get_operator(mapper)
    stateful = BlockLinearMapper(
        op.xs, op.block_size, b=op.b, feature_means=op.feature_means,
        solver_state=object(),
    )
    graph = graph.set_operator(mapper, stateful)
    assert CosineScoresRule().apply(graph, {})[0] is graph


def _segment_facts(apply):
    reset_dispatchers()
    tracer = tracer_mod.install(tracer_mod.Tracer())
    try:
        apply()
        return [
            s.attrs for s in tracer.spans() if s.name == "exec.segment"
        ]
    finally:
        tracer_mod.reset()
        reset_dispatchers()


def test_exec_segment_counts_the_rows_through_the_kernel(monkeypatch):
    """On a fitted apply where the kernel runs (interpret mode here, as
    on one TPU chip compiled): ``cosine_scores_rows``. Off the TPU, and on
    the unfitted pipeline's apply, which holds no fitted graph: absent."""
    predictor, test = _timit_job()
    fitted = predictor.fit()

    def apply_fitted():
        return fitted.apply(test.data).to_array()

    def apply_unfitted():
        return predictor(test.data).get().to_array()

    (off,) = _segment_facts(apply_fitted)
    assert off["label"] == "CosineLinearMapper+MaxClassifier"
    assert off["cosine_bounded_rows"] == 40 and "cosine_scores_rows" not in off
    monkeypatch.setattr(cs, "kernel_mode", lambda: "interpret")
    (on,) = _segment_facts(apply_fitted)
    assert on["cosine_scores_rows"] == on["rows"] == 40
    assert on["path"] == "compiled"
    for facts in _segment_facts(apply_unfitted):
        assert "cosine_scores_rows" not in facts


def test_the_rule_starts_pallas_import_where_the_kernel_will_run(monkeypatch):
    """Made on one TPU chip, the node's Pallas import starts at once, off
    the thread that will trace it; anywhere else nothing starts."""
    started = []
    monkeypatch.setattr(cs, "preload", lambda: started.append(1))
    _timit_job()[0].fit()
    assert not started
    monkeypatch.setattr(cs, "kernel_mode", lambda: "compiled")
    _timit_job()[0].fit()
    assert started == [1]


def _fake_import(monkeypatch, fail_without_interpreter=False):
    """What ``cosine_scores`` imports, each with what ``sys.modules`` held
    for the GPU interpreter at that moment."""
    import sys
    import types

    seen = []

    def import_module(name):
        held = sys.modules.get(cs._GPU_INTERPRETER, "absent")
        seen.append((name, held))
        if fail_without_interpreter and held is None:
            raise ImportError("this Pallas needs its GPU interpreter")

    monkeypatch.setattr(
        cs, "importlib", types.SimpleNamespace(import_module=import_module)
    )
    monkeypatch.delitem(sys.modules, cs._GPU_INTERPRETER, raising=False)
    return seen


def test_pallas_is_imported_once_a_process(monkeypatch):
    """One daemon thread a process imports Pallas for the TPU, its GPU
    interpreter stubbed out for the length of the import and not after."""
    import sys
    import types

    made = []

    class Thread:
        def __init__(self, target, name, daemon):
            made.append((target, name, daemon))

        def start(self):
            made[-1][0]()

    seen = _fake_import(monkeypatch)
    monkeypatch.setattr(cs, "threading", types.SimpleNamespace(Thread=Thread))
    monkeypatch.setattr(cs, "_preloading", False)
    cs.preload()
    cs.preload()
    assert len(made) == 1 and made[0][2]  # one daemon thread
    assert seen[0] == ("jax.experimental.pallas.tpu", None)
    assert cs._GPU_INTERPRETER not in sys.modules


def test_a_pallas_that_needs_its_gpu_interpreter_is_imported_whole(
    monkeypatch,
):
    seen = _fake_import(monkeypatch, fail_without_interpreter=True)
    cs._import_for_tpu()
    assert [held for _, held in seen] == [None, "absent"]
