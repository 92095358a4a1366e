"""``ConvChainRule`` (``nodes/images/chain.py``): the conv → rectify → pool
chain recognised from the graph and replaced by one node; every other
arrangement, and every pipeline without a Convolver, left as written; a
fit through the fused node equal to a fit through the three bodies."""

import jax.numpy as jnp
import numpy as np
import pytest

from keystone_tpu.compile.segment import reset_dispatchers
from keystone_tpu.loaders.cifar import synthetic_cifar
from keystone_tpu.nodes.images.chain import ConvChainRule, ConvRectifyPool
from keystone_tpu.nodes.images.core import (
    Convolver,
    ImageVectorizer,
    Pooler,
    SymmetricRectifier,
)
from keystone_tpu.nodes.learning.linear import BlockLinearMapper
from keystone_tpu.obs import tracer as obs_tracer
from keystone_tpu.ops import conv_rectify_pool as crp
from keystone_tpu.pipelines import cifar_extras, random_patch_cifar
from keystone_tpu.workflow.env import PipelineEnv
from keystone_tpu.workflow.optimizers import DefaultOptimizer, clear_memo
from keystone_tpu.workflow.pipeline import Pipeline
from keystone_tpu.workflow.rules import EquivalentNodeMergeRule

CHAIN = (Convolver, SymmetricRectifier, Pooler)


def _all_ops(graph):
    return [graph.get_operator(node) for node in graph.nodes]


def _optimized(pipeline):
    clear_memo()
    graph, _ = DefaultOptimizer().execute(pipeline.graph)
    return graph


def _tiny_conf(cls=random_patch_cifar.RandomCifarConfig, **kw):
    return cls(num_filters=16, lam=10.0, whitener_size=500, **kw)


def _assert_one_fused_chain(graph):
    ops = _all_ops(graph)
    fused = [op for op in ops if isinstance(op, ConvRectifyPool)]
    assert fused, [op.label for op in ops]
    assert all("Convolver" in op.label for op in fused)
    assert not [op for op in ops if isinstance(op, CHAIN)]
    return fused


def test_random_patch_cifar_chain_becomes_one_node():
    train = synthetic_cifar(48, seed=1)
    pipeline = random_patch_cifar.build_pipeline(train, _tiny_conf())
    before = [op for op in _all_ops(pipeline.graph) if isinstance(op, CHAIN)]
    assert len(before) >= 3
    fused = _assert_one_fused_chain(_optimized(pipeline))
    assert fused[0].pooler.pool_fn == "sum"


def _extras_pipeline(which):
    train = synthetic_cifar(40, seed=1)
    test = synthetic_cifar(16, seed=2)
    if which == "random_cifar":
        return cifar_extras.run_random_cifar(train, test, _tiny_conf())[0]
    if which == "augmented":
        conf = _tiny_conf(
            cifar_extras.AugmentedCifarConfig, num_random_images_augment=2
        )
        return cifar_extras.run_random_patch_cifar_augmented(
            train, test, conf
        )[0]
    conf = _tiny_conf(cifar_extras.KernelCifarConfig, block_size=20)
    return cifar_extras.run_random_patch_cifar_kernel(train, test, conf)[0]


@pytest.mark.parametrize("which", ["random_cifar", "augmented", "kernel"])
def test_cifar_extras_chains_become_one_node(which):
    pipeline = _extras_pipeline(which)
    _assert_one_fused_chain(_optimized(pipeline))


def _chain(pooler, k=8, side=12):
    filters = np.random.default_rng(0).standard_normal(
        (k, 6 * 6 * 3)
    ).astype(np.float32)
    return (
        Convolver(filters, side, side, 3)
        .and_then(SymmetricRectifier(alpha=0.25))
        .and_then(pooler)
    )


@pytest.mark.parametrize("pooler", [
    Pooler(3, 4, None, "max"),
    Pooler(3, 4, None, "mean"),
    Pooler(3, 4, jnp.abs, "sum"),
], ids=["max_pool", "mean_pool", "pixel_fn"])
def test_other_poolers_are_left_alone(pooler):
    pipeline = _chain(pooler)
    graph, _ = ConvChainRule().apply(pipeline.graph, {})
    assert graph is pipeline.graph


def test_second_reader_of_the_rectifier_is_left_alone():
    head = _chain(Pooler(3, 4, None, "sum"))
    rectified = Convolver(
        np.ones((8, 108), np.float32), 12, 12, 3
    ).and_then(SymmetricRectifier(alpha=0.25))
    both = Pipeline.gather([
        rectified.and_then(Pooler(3, 4, None, "sum")),
        rectified.and_then(ImageVectorizer()),
    ])
    # the branches' equal Convolvers and rectifiers become one node each
    merged, _ = EquivalentNodeMergeRule().apply(both.graph, {})
    assert sum(
        isinstance(op, SymmetricRectifier) for op in _all_ops(merged)
    ) == 1
    graph, _ = ConvChainRule().apply(merged, {})
    assert graph is merged
    # and the plain chain beside it is taken
    graph, _ = ConvChainRule().apply(head.graph, {})
    assert [type(op) for op in _all_ops(graph)] == [ConvRectifyPool]


def test_saved_interior_result_is_left_alone():
    pipeline = _chain(Pooler(3, 4, None, "sum"))
    graph = pipeline.graph
    conv_node = next(
        n for n in graph.nodes
        if isinstance(graph.get_operator(n), Convolver)
    )
    out, _ = ConvChainRule().apply(graph, {conv_node: object()})
    assert out is graph


def test_fused_node_takes_the_chains_place_in_the_graph():
    pipeline = _chain(Pooler(3, 4, None, "sum")).and_then(ImageVectorizer())
    graph, _ = ConvChainRule().apply(pipeline.graph, {})
    assert len(graph.nodes) == 2
    X = jnp.asarray(
        np.random.default_rng(1).uniform(0, 255, (3, 12, 12, 3)), jnp.float32
    )
    want = pipeline(X).get().to_array()
    got = Pipeline(graph, pipeline.source, pipeline.sink)(X).get().to_array()
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))


@pytest.mark.parametrize("which", ["timit", "mnist_random_fft"])
def test_pipelines_without_a_convolver_keep_their_graphs(which, monkeypatch):
    """Node for node: the rule hands back the very graph it was given."""
    seen = []
    real = ConvChainRule.apply

    def watched(self, graph, annotations):
        out, ann = real(self, graph, annotations)
        seen.append(out is graph)
        return out, ann

    monkeypatch.setattr(ConvChainRule, "apply", watched)
    clear_memo()
    if which == "timit":
        from keystone_tpu.pipelines import timit

        conf = timit.TimitConfig(
            num_cosines=2, cosine_features=32, num_epochs=1, lam=1.0,
            num_classes=5,
        )
        train = timit.synthetic_timit(64, 5, seed=1)
        test = timit.synthetic_timit(32, 5, seed=2)
        timit.run(train, test, conf)
    else:
        from keystone_tpu.pipelines import mnist_random_fft as mnist

        conf = mnist.MnistRandomFFTConfig(num_ffts=2, block_size=64, lam=10.0)
        train, test = mnist.synthetic_mnist(64, 32, seed=3)
        mnist.run(train, test, conf)
    assert seen and all(seen)


# ---- a fit through the fused node against a fit through the bodies -------


def _fit(n_train=96, n_test=40):
    PipelineEnv.get_or_create().reset()
    reset_dispatchers()
    clear_memo()
    train = synthetic_cifar(n_train, seed=1)
    test = synthetic_cifar(n_test, seed=2)
    conf = random_patch_cifar.RandomCifarConfig(
        num_filters=32, lam=3000.0, whitener_size=2000
    )
    tracer = obs_tracer.Tracer()
    obs_tracer.install(tracer)
    try:
        pipeline, train_err, test_err, _ = random_patch_cifar.run(
            train, test, conf
        )
    finally:
        obs_tracer.uninstall(tracer)
    fitted = pipeline.fit().graph
    mapper = next(
        op for op in _all_ops(fitted) if isinstance(op, BlockLinearMapper)
    )
    segments = [
        sp.attrs for sp in tracer.spans() if sp.name == "exec.segment"
        and "Convolver" in str(sp.attrs.get("label", ""))
    ]
    weights = np.concatenate([np.asarray(x) for x in mapper.xs], axis=0)
    return train_err, test_err, weights, segments


def test_tiny_fit_fused_equals_unfused(bf16_products, monkeypatch):
    n_train, n_test = 96, 40
    plain = _fit(n_train, n_test)
    assert plain[3] and all(s["path"] == "compiled" for s in plain[3])
    assert sum(s["rows"] for s in plain[3]) == 2 * n_train + n_test
    assert not any(s.get("conv_fused_rows") for s in plain[3])

    monkeypatch.setattr(crp, "kernel_mode", lambda: "interpret")
    fused = _fit(n_train, n_test)
    assert all(s["path"] == "compiled" for s in fused[3])
    assert (
        sum(s.get("conv_fused_rows", 0) for s in fused[3])
        == 2 * n_train + n_test
    )
    assert fused[0] == plain[0] and fused[1] == plain[1]
    np.testing.assert_allclose(
        fused[2], plain[2], rtol=1e-5, atol=1e-5 * np.abs(plain[2]).max()
    )
