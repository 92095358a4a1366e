"""``SampledSIFTRule`` (``nodes/images/chain.py``): a sampling pass — SIFT →
column-wise nodes → ``ColumnSampler`` — recognised from the graph and
replaced by the one node that makes only the sampled descriptors; every
other arrangement, and every pipeline without a ``SIFTExtractor``, left as
written; the node dispatched as a compiled segment though it is alone; a
``voc_sift_fisher`` job with its two sampling passes through it."""

import jax.numpy as jnp
import numpy as np
import pytest

from keystone_tpu.compile.segment import reset_dispatchers
from keystone_tpu.data.dataset import Dataset
from keystone_tpu.nodes.images import GrayScaler, PixelScaler, SIFTExtractor
from keystone_tpu.nodes.images.chain import (
    SampledSIFTExtractor,
    SampledSIFTRule,
)
from keystone_tpu.nodes.learning.pca import BatchPCATransformer
from keystone_tpu.nodes.stats import ColumnSampler, NormalizeRows
from keystone_tpu.nodes.util import Cacher
from keystone_tpu.obs import tracer as obs_tracer
from keystone_tpu.pipelines import voc_sift_fisher as voc
from keystone_tpu.workflow.env import PipelineEnv
from keystone_tpu.workflow.executor import GraphExecutor
from keystone_tpu.workflow.optimizers import DefaultOptimizer, clear_memo
from keystone_tpu.workflow.pipeline import Pipeline


def _ops(graph):
    return [graph.get_operator(n) for n in sorted(graph.nodes)]


def _pca(dims=8, seed=0):
    rng = np.random.default_rng(seed)
    return BatchPCATransformer(
        np.linalg.qr(rng.standard_normal((128, dims)))[0].astype(np.float32)
    )


def _gray(n=4, seed=0):
    rng = np.random.default_rng(seed)
    return jnp.asarray(rng.uniform(0, 1, (n, 40, 36, 1)), jnp.float32)


def _node_of(graph, kind):
    return next(
        n for n in graph.nodes if isinstance(graph.get_operator(n), kind)
    )


@pytest.mark.parametrize("projected", [False, True], ids=["sift", "sift_pca"])
def test_a_sampling_pass_becomes_one_node(projected):
    sift, pca, sampler = SIFTExtractor(), _pca(), ColumnSampler(12, seed=4)
    chain = sift.and_then(pca) if projected else sift
    pipeline = GrayScaler().and_then(chain).and_then(sampler)
    graph, _ = SampledSIFTRule().apply(pipeline.graph, {})
    ops = _ops(graph)
    assert [type(op) for op in ops] == [GrayScaler, SampledSIFTExtractor]
    fused = ops[1]
    assert (fused.sift, fused.sampler) == (sift, sampler)
    assert fused.then == ((pca,) if projected else ())
    # the passes are found by the name (featurizer.descriptor_passes_per_fit)
    assert "SIFTExtractor" in fused.label
    # the node stands at the sampler's place and reads what SIFT read
    tail = _node_of(graph, SampledSIFTExtractor)
    assert tail == _node_of(pipeline.graph, ColumnSampler)
    assert graph.get_sink_dependency(pipeline.sink) == tail
    assert graph.get_dependencies(tail) == (_node_of(graph, GrayScaler),)
    # the same sample as the chain written out
    X = jnp.asarray(
        np.random.default_rng(1).uniform(0, 255, (5, 40, 36, 3)), jnp.float32
    )
    want = np.asarray(pipeline(X).get().to_array())
    got = Pipeline(graph, pipeline.source, pipeline.sink)(X).get().to_array()
    assert got.shape == want.shape == (5, 8 if projected else 128, 12)
    np.testing.assert_allclose(np.asarray(got), want, rtol=1e-5, atol=1e-3)


def test_the_default_optimizer_runs_the_rule_last():
    batch = DefaultOptimizer().batches()[-1]
    assert [r.rule_name for r in batch.rules] == [
        "ConvChainRule", "SampledSIFTRule"
    ]
    clear_memo()
    pipeline = SIFTExtractor().and_then(ColumnSampler(5))
    graph, _ = DefaultOptimizer().execute(pipeline.graph)
    assert [type(op) for op in _ops(graph)] == [SampledSIFTExtractor]


def test_a_second_reader_of_the_descriptors_is_left_alone():
    sift = SIFTExtractor()
    both = Pipeline.gather([
        sift.and_then(ColumnSampler(5)), sift.and_then(NormalizeRows()),
    ])
    from keystone_tpu.workflow.rules import EquivalentNodeMergeRule

    merged, _ = EquivalentNodeMergeRule().apply(both.graph, {})
    assert sum(isinstance(op, SIFTExtractor) for op in _ops(merged)) == 1
    graph, _ = SampledSIFTRule().apply(merged, {})
    assert graph is merged


def test_a_second_reader_of_the_projection_is_left_alone():
    projected = SIFTExtractor().and_then(_pca())
    both = Pipeline.gather([
        projected.and_then(ColumnSampler(5)), projected.and_then(Cacher()),
    ])
    from keystone_tpu.workflow.rules import EquivalentNodeMergeRule

    merged, _ = EquivalentNodeMergeRule().apply(both.graph, {})
    assert sum(isinstance(op, BatchPCATransformer) for op in _ops(merged)) == 1
    graph, _ = SampledSIFTRule().apply(merged, {})
    assert graph is merged


@pytest.mark.parametrize("saved", [SIFTExtractor, BatchPCATransformer])
def test_a_saved_interior_result_is_left_alone(saved):
    pipeline = SIFTExtractor().and_then(_pca()).and_then(ColumnSampler(5))
    node = _node_of(pipeline.graph, saved)
    graph, _ = SampledSIFTRule().apply(pipeline.graph, {node: object()})
    assert graph is pipeline.graph


@pytest.mark.parametrize("between", [Cacher, NormalizeRows])
def test_a_node_that_is_not_column_wise_is_not_crossed(between):
    """A cache holds all the columns; a row normalisation reads them all."""
    pipeline = (
        SIFTExtractor().and_then(_pca()).and_then(between())
        .and_then(ColumnSampler(5))
    )
    graph, _ = SampledSIFTRule().apply(pipeline.graph, {})
    assert graph is pipeline.graph


def test_a_sampler_with_no_sift_upstream_is_left_alone():
    pipeline = _pca().and_then(ColumnSampler(5))
    graph, _ = SampledSIFTRule().apply(pipeline.graph, {})
    assert graph is pipeline.graph
    # and descriptors nobody samples
    pipeline = SIFTExtractor().and_then(_pca())
    graph, _ = SampledSIFTRule().apply(pipeline.graph, {})
    assert graph is pipeline.graph


def test_a_pipeline_without_sift_keeps_its_graph(monkeypatch):
    """Node for node: the rule hands back the very graph it was given."""
    from keystone_tpu.pipelines import timit

    seen = []
    real = SampledSIFTRule.apply

    def watched(self, graph, annotations):
        out, ann = real(self, graph, annotations)
        seen.append(out is graph)
        return out, ann

    monkeypatch.setattr(SampledSIFTRule, "apply", watched)
    clear_memo()
    conf = timit.TimitConfig(
        num_cosines=2, cosine_features=32, num_epochs=1, lam=1.0,
        num_classes=5,
    )
    timit.run(
        timit.synthetic_timit(64, 5, seed=1),
        timit.synthetic_timit(32, 5, seed=2), conf,
    )
    assert seen and all(seen)


def _traced(fn):
    PipelineEnv.get_or_create().reset()
    reset_dispatchers()
    clear_memo()
    tracer = obs_tracer.Tracer()
    obs_tracer.install(tracer)
    try:
        out = fn()
    finally:
        obs_tracer.uninstall(tracer)
    return out, tracer.spans()


def test_the_node_alone_is_dispatched_as_a_compiled_segment():
    """Behind a held cache the node is its segment's only member: it binds
    all the same, takes its rows' indices, and counts them; node dispatch
    of the same graph gives the same sample."""
    X = _gray(6)
    pipeline = (
        Cacher().and_then(SIFTExtractor()).and_then(_pca())
        .and_then(ColumnSampler(7, seed=2))
    )
    got, spans = _traced(lambda: pipeline(X).get().to_array())
    segments = [sp.attrs for sp in spans if sp.name == "exec.segment"]
    assert [s["label"] for s in segments] == ["SampledSIFTExtractor"]
    (seg,) = segments
    assert seg["path"] == "compiled" and seg["nodes"] == 1
    assert seg["rows"] == seg["sift_sampled_rows"] == 6
    assert seg["sift_sampled_path"] == SIFTExtractor().sampled_path(40, 36, 7)
    assert not [sp for sp in spans if sp.name == "node.SampledSIFTExtractor"]
    clear_memo()
    graph, _ = DefaultOptimizer().execute(pipeline(X).graph)
    by_nodes = GraphExecutor(graph, optimize=False, segment_plan={})
    sink = next(iter(graph.sinks))
    want = by_nodes.execute(sink).get().to_array()
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))


def test_a_full_sift_segment_counts_no_sampled_rows():
    X = _gray(4)
    pipeline = SIFTExtractor().and_then(_pca()).and_then(Cacher())
    _, spans = _traced(lambda: pipeline(X).get().to_array())
    segments = [sp.attrs for sp in spans if sp.name == "exec.segment"]
    assert segments and all("SIFTExtractor" in s["label"] for s in segments)
    assert not any("sift_sampled_rows" in s for s in segments)
    assert not any("sift_sampled_path" in s for s in segments)


@pytest.mark.parametrize("samples, want", [(60, "grid"), (2, "bins")])
def test_a_sampled_segment_says_which_body_made_its_sample(samples, want):
    """A sample on either side of the rule at 40 × 36 (99 descriptors): the
    span names the body that ran beside the rows it counts."""
    X = _gray(4)
    assert SIFTExtractor().sampled_path(40, 36, samples) == want
    pipeline = (
        Cacher().and_then(SIFTExtractor())
        .and_then(ColumnSampler(samples, seed=3))
    )
    _, spans = _traced(lambda: pipeline(X).get().to_array())
    (seg,) = [sp.attrs for sp in spans if sp.name == "exec.segment"]
    assert seg["label"] == "SampledSIFTExtractor"
    assert seg["sift_sampled_rows"] == 4 and seg["sift_sampled_path"] == want


def test_a_small_job_samples_through_the_node_twice():
    n_train, n_test = 32, 16
    train, train_labels = voc.synthetic_voc(n_train, size=48, seed=1)
    test, test_labels = voc.synthetic_voc(n_test, size=48, seed=2)
    conf = voc.SIFTFisherConfig(
        num_pca_samples=3200, num_gmm_samples=3200, vocab_size=4,
        desc_dim=8, lam=0.5, seed=5,
    )
    (_, aps, _), spans = _traced(
        lambda: voc.run(train, train_labels, test, test_labels, conf)
    )
    assert np.isfinite(aps).all()
    segments = [
        sp.attrs for sp in spans if sp.name == "exec.segment"
        and "SIFTExtractor" in sp.attrs["label"]
    ]
    # three passes over the training images and one over the held-out ones
    assert sorted(s["rows"] for s in segments) == [16, 32, 32, 32]
    sampled = [s for s in segments if "sift_sampled_rows" in s]
    assert [s["sift_sampled_rows"] for s in sampled] == [n_train, n_train]
    assert all(s["label"] == "SampledSIFTExtractor" for s in sampled)
    # 100 of a 48 × 48 image's 247 descriptors: read through the grid
    assert [s["sift_sampled_path"] for s in sampled] == ["grid", "grid"]
    # the codebook's sample is drawn ahead of the cache the device declines
    assert not any("cache_declined_bytes" in s for s in sampled)
    assert all(
        sp.attrs["path"] == "compiled" for sp in spans
        if sp.name == "exec.segment"
    )
    passes = [sp.attrs for sp in spans if sp.name == "voc.sample_descriptors"]
    assert [a["images"] for a in passes] == [n_train, n_train]
    assert [a["columns"] for a in passes] == [3200, 3200]
    assert [a["bytes"] for a in passes] == [3200 * 128 * 4, 3200 * 8 * 4]


def test_the_rule_fires_through_the_signed_root_and_not_through_a_cache():
    """The ImageNet pipeline's SIFT branch: ``SIFTExtractor →
    SignedHellingerMapper → ColumnSampler`` becomes one node whose ``then``
    is the root; with the reference's ``Cacher`` between the root and the
    sampler the chain is left as written (a cache is not column-wise) —
    which is why ``imagenet_sift_lcs_fv`` draws its samples ahead of it."""
    from keystone_tpu.nodes.stats import SignedHellingerMapper

    sift, root = SIFTExtractor(scale_step=1), SignedHellingerMapper()
    sampler = ColumnSampler(9, seed=2)
    pipeline = GrayScaler().and_then(sift).and_then(root).and_then(sampler)
    graph, _ = SampledSIFTRule().apply(pipeline.graph, {})
    ops = _ops(graph)
    assert [type(op) for op in ops] == [GrayScaler, SampledSIFTExtractor]
    assert ops[1].then == (root,) and ops[1].sift is sift
    cached = (
        GrayScaler().and_then(sift).and_then(root).and_then(Cacher())
        .and_then(sampler)
    )
    graph, _ = SampledSIFTRule().apply(cached.graph, {})
    assert graph is cached.graph
    # through the projection too: root, then PCA, then the sampler
    pca = _pca()
    both = sift.and_then(root).and_then(pca).and_then(sampler)
    graph, _ = SampledSIFTRule().apply(both.graph, {})
    (fused,) = _ops(graph)
    assert fused.then == (root, pca)


def test_a_small_imagenet_job_names_the_body_of_its_two_sift_samples():
    """``imagenet_sift_lcs_fv.run``: SIFT's two sampling passes go through
    the node (LCS's two do not) and their spans say which body read the
    columns — 50 of a 48 × 48 image's 188 descriptors at scale step 1."""
    from keystone_tpu.pipelines import imagenet_sift_lcs_fv as imagenet

    n_train, n_test, classes = 32, 16, 6
    train, train_labels = imagenet.synthetic_imagenet(
        n_train, classes, size=48, seed=1
    )
    test, test_labels = imagenet.synthetic_imagenet(
        n_test, classes, size=48, seed=2
    )
    conf = imagenet.ImageNetSiftLcsFVConfig(
        desc_dim=8, vocab_size=2, num_pca_samples=1600, num_gmm_samples=1600,
        num_classes=classes, lam=1e-4,
    )
    (_, err, _), spans = _traced(
        lambda: imagenet.run(train, train_labels, test, test_labels, conf)
    )
    assert 0.0 <= err.top1 <= 100.0
    passes = [
        sp.attrs for sp in spans if sp.name == "imagenet.sample_descriptors"
    ]
    assert [a["branch"] for a in passes] == ["sift", "sift", "lcs", "lcs"]
    sampled = [
        sp.attrs for sp in spans
        if sp.name == "exec.segment" and "sift_sampled_path" in sp.attrs
    ]
    sift = SIFTExtractor(scale_step=conf.sift_scale_step)
    want = sift.sampled_path(48, 48, 1600 // n_train)
    assert want == "grid"
    assert [a["sift_sampled_path"] for a in sampled] == [want, want]
    assert [a["sift_sampled_rows"] for a in sampled] == [n_train, n_train]
    assert all("SampledSIFTExtractor" in a["label"] for a in sampled)
