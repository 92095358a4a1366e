"""What ``voc_fv256`` forced, beside the code: a ``Cacher`` whose value the
device cannot hold is declined — it becomes a member of the segment that
feeds it and the answers are node dispatch's —, one that fits is kept and
its upstream runs once; ``ColumnSampler`` draws the same columns whole, in
row slices of any size, chunk by chunk and item by item; a row is priced
with its members' temporaries; pipelines without a ``Cacher`` or a sampler
plan and lower exactly what they did. The device's memory is what
``compile.segment._device_memory`` reads — the CPU reports none, so the
tests stand a reading in its place."""

import numpy as np
import pytest

from keystone_tpu.compile import segment as seg_mod
from keystone_tpu.compile.segment import reset_dispatchers
from keystone_tpu.data.chunked import ChunkedDataset
from keystone_tpu.data.dataset import Dataset
from keystone_tpu.nodes.stats import ColumnSampler
from keystone_tpu.nodes.util import Cacher
from keystone_tpu.obs import tracer as tracer_mod
from keystone_tpu.workflow.executor import GraphExecutor
from keystone_tpu.workflow.transformer import Transformer


@pytest.fixture(autouse=True)
def _isolate():
    reset_dispatchers()
    yield
    reset_dispatchers()


class _Widen(Transformer):
    """One row in, ``k`` times its width out: the value a cache is asked
    to hold."""

    def __init__(self, k):
        self.k = k

    def trace_batch(self, X):
        import jax.numpy as jnp

        T = jnp.tile(X, (1, self.k))
        return jnp.maximum(T * 1.5, 0.01 * T)


class _Fold(Transformer):
    def __init__(self, k):
        self.k = k

    def trace_batch(self, X):
        return X.reshape(X.shape[0], self.k, -1).sum(axis=1)


def _pipeline():
    return _Widen(64).and_then(Cacher()).and_then(_Fold(64))


def _pull(pipe, X, node_dispatch=False):
    """``pipe(X)`` pulled under a tracer: the result and the spans."""
    result = pipe(Dataset.of(X))
    if node_dispatch:
        result._executor = GraphExecutor(
            result._executor.input_graph, segment_plan={}
        )
    tracer = tracer_mod.start()
    try:
        out = np.asarray(result.get().to_array())
    finally:
        tracer_mod.stop()
    return out, tracer.spans()


def _named(spans, name):
    return [sp for sp in spans if sp.name == name]


X = np.random.default_rng(0).standard_normal((96, 8)).astype(np.float32)
#: the cache is asked for 96 rows of 64 · 8 float32
CACHE_BYTES = 96 * 64 * 8 * 4


def test_a_cache_that_cannot_be_held_becomes_a_member(monkeypatch):
    want, spans = _pull(_pipeline(), X, node_dispatch=True)
    assert not _named(spans, "exec.segment") and _named(spans, "node.Cacher")

    # free: less than twice the cache; the plan declines it
    monkeypatch.setattr(
        seg_mod, "_device_memory", lambda: (2 * CACHE_BYTES - 8, 1 << 40)
    )
    got, spans = _pull(_pipeline(), X)
    (segment,) = _named(spans, "exec.segment")
    assert segment.attrs["label"] == "_Widen+Cacher+_Fold"
    assert segment.attrs["path"] == "compiled"
    assert segment.attrs["cache_declined_bytes"] == CACHE_BYTES
    assert not _named(spans, "node.Cacher")
    np.testing.assert_array_equal(got, want)


def test_a_cache_that_fits_is_kept_and_its_upstream_runs_once(monkeypatch):
    monkeypatch.setattr(
        seg_mod, "_device_memory", lambda: (2 * CACHE_BYTES, 1 << 40)
    )
    want, _ = _pull(_pipeline(), X, node_dispatch=True)
    result = _pipeline()(Dataset.of(X))
    tracer = tracer_mod.start()
    try:
        first = np.asarray(result.get().to_array())
        again = np.asarray(result.get().to_array())
    finally:
        tracer_mod.stop()
    spans = tracer.spans()
    # a barrier, as ever: no segment spans it, nothing is declined, and the
    # second pull finds what the first one kept
    assert len(_named(spans, "node.Cacher")) == 1
    assert not any("cache_declined_bytes" in sp.attrs for sp in spans)
    assert len(_named(spans, "node._Widen")) == 1
    np.testing.assert_array_equal(first, want)
    np.testing.assert_array_equal(again, want)


def test_no_memory_reading_or_no_cache_declines_nothing(monkeypatch):
    from keystone_tpu.check import lattice

    def plan(pipe):
        graph = pipe(Dataset.of(X))._executor.graph
        verdicts = {
            n: lattice.classify(graph.get_operator(n)) for n in graph.nodes
        }
        return seg_mod.unheld_caches(graph, verdicts, {})

    assert plan(_pipeline()) == {}  # the CPU reports no memory
    monkeypatch.setattr(seg_mod, "_device_memory", lambda: (16, 1 << 40))
    assert list(plan(_pipeline()).values()) == [CACHE_BYTES]
    # a graph without a Cacher does not even ask for the reading
    monkeypatch.setattr(
        seg_mod, "_device_memory", lambda: pytest.fail("asked")
    )
    assert plan(_Widen(64).and_then(_Fold(64))) == {}


def test_a_declined_cache_is_cut_by_rows_with_its_segment(monkeypatch):
    want, _ = _pull(_pipeline(), X, node_dispatch=True)
    item = 64 * 8 * 4 * 2 + 8 * 4  # the widened row, the cache's copy, the fold
    monkeypatch.setattr(
        seg_mod, "_device_memory", lambda: (20 * item, 4 * 64 * item)
    )
    got, spans = _pull(_pipeline(), X)
    (segment,) = _named(spans, "exec.segment")
    assert segment.attrs["cache_declined_bytes"] == CACHE_BYTES
    assert segment.attrs["slice_rows"] == 8 and segment.attrs["row_slices"] == 12
    np.testing.assert_array_equal(got, want)


# -- the sampler -------------------------------------------------------------


D = np.random.default_rng(1).standard_normal((23, 6, 50)).astype(np.float32)


def _whole(sampler):
    return np.asarray(sampler.apply_batch(Dataset.of(D)).to_array())


def test_the_sampler_draws_by_seed_and_row_alone():
    a, b = _whole(ColumnSampler(9, seed=4)), _whole(ColumnSampler(9, seed=4))
    np.testing.assert_array_equal(a, b)  # no state between calls
    assert a.shape == (23, 6, 9)
    assert not np.array_equal(a, _whole(ColumnSampler(9, seed=5)))
    cols = np.asarray(ColumnSampler(9, seed=4).columns(np.arange(23), 50))
    assert cols.shape == (23, 9) and cols.min() >= 0 and cols.max() < 50
    np.testing.assert_array_equal(
        a, np.take_along_axis(D, cols[:, None, :], axis=2)
    )
    # row 7's draw is row 7's wherever it stands
    np.testing.assert_array_equal(
        np.asarray(ColumnSampler(9, seed=4).columns([7], 50))[0], cols[7]
    )


@pytest.mark.parametrize("chunk", [1, 5, 23])
def test_the_sampler_draws_the_same_columns_chunk_by_chunk(chunk):
    sampler = ColumnSampler(9, seed=4)
    want = _whole(sampler)
    # through sample_chunk, as the ImageNet branch builder drives it
    parts = [
        np.asarray(sampler.sample_chunk(D[at : at + chunk], at))
        for at in range(0, 23, chunk)
    ]
    np.testing.assert_array_equal(np.concatenate(parts), want)
    # through a chunked data set
    chunked = ChunkedDataset.from_array(D, chunk)
    got = np.asarray(sampler.apply_batch(chunked).to_array())
    np.testing.assert_array_equal(got, want)
    # and item by item
    items = sampler.apply_batch(Dataset.from_items([d for d in D]))
    np.testing.assert_array_equal(np.stack(list(items)), want)


class _Scale(Transformer):
    def trace_batch(self, X):
        return X * 2.0


@pytest.mark.parametrize("free_rows", [3, 10])
def test_the_sampler_is_a_member_of_a_row_sliced_segment(monkeypatch, free_rows):
    pipe = _Scale().and_then(ColumnSampler(9, seed=4))
    want = 2.0 * _whole(ColumnSampler(9, seed=4))
    whole, spans = _pull(pipe, D)
    (segment,) = _named(spans, "exec.segment")
    assert segment.attrs["label"] == "_Scale+ColumnSampler"
    assert segment.attrs["row_slices"] == 1
    np.testing.assert_array_equal(whole, want)

    reset_dispatchers()
    item = 6 * 50 * 4 + 6 * 9 * 4
    monkeypatch.setattr(
        seg_mod, "_device_memory", lambda: (2 * free_rows * item, 1 << 40)
    )
    got, spans = _pull(pipe, D)
    (segment,) = _named(spans, "exec.segment")
    slice_rows = segment.attrs["slice_rows"]
    assert slice_rows == {3: 2, 10: 8}[free_rows]
    assert segment.attrs["row_slices"] == -(-23 // slice_rows)
    assert segment.attrs["path"] == "compiled"
    # the last slice is padded with its first row; the padding is cut
    np.testing.assert_array_equal(got, want)


# -- the price of a row ------------------------------------------------------


def test_a_row_is_priced_with_its_members_temporaries():
    import jax.numpy as jnp

    from keystone_tpu.compile.aot import signature_of
    from keystone_tpu.nodes.images import FisherVector, SIFTExtractor
    from keystone_tpu.nodes.learning import GaussianMixtureModel

    d, k, m = 16, 8, 300
    rng = np.random.default_rng(2)
    fv = FisherVector(GaussianMixtureModel(
        rng.standard_normal((d, k)), rng.uniform(0.5, 1.5, (d, k)),
        np.full(k, 1.0 / k),
    ))
    sigs = (signature_of(jnp.zeros((4, d, m), jnp.float32)),)
    priced = seg_mod._item_bytes(sigs, [(fv, (0,))], (1,))
    # the output (d, 2k) and, declared by the node, the posteriors (m, k)
    # with X*X and the transposed descriptors (m, d)
    assert fv.row_scratch_bytes((4, d, m)) == 4 * m * (k + 2 * d)
    assert priced == 4 * d * 2 * k + 4 * m * (k + 2 * d)
    # at the published widths: 75 MB of posteriors an image, not 164 KB
    big = FisherVector(GaussianMixtureModel(
        np.zeros((80, 256)), np.ones((80, 256)), np.full(256, 1 / 256)
    ))
    assert big.row_scratch_bytes((1, 80, 73505)) == 4 * 73505 * (256 + 160)
    sift = SIFTExtractor()
    assert sift.row_scratch_bytes((1, 500, 375, 1)) == (
        4 * 128 * 73505 + 2 * 500 * 375 * 8 * 4
    )


# -- pipelines the change must not move ----------------------------------------


def test_a_segment_without_a_keyed_member_takes_its_inputs_alone():
    import jax

    pipe = _Widen(4).and_then(_Fold(4))
    result = pipe(Dataset.of(X))
    executor = result._executor
    result.get()
    (binding,) = set(executor.segment_plan.values())
    assert not seg_mod._row_keyed(binding.steps)
    assert binding.cache_declined_bytes == 0
    # the lowered function is the members' composition over its inputs,
    # nothing appended: its text is what it was
    text = str(jax.make_jaxpr(binding.fn)(X))
    assert text == str(jax.make_jaxpr(
        lambda a: (_Fold(4).trace_batch(_Widen(4).trace_batch(a)),)
    )(X))
    keyed = _Scale().and_then(ColumnSampler(3, seed=0))(Dataset.of(D))
    keyed.get()
    (binding,) = set(keyed._executor.segment_plan.values())
    assert seg_mod._row_keyed(binding.steps)


def test_timit_and_random_patch_cifar_plan_what_they_planned(monkeypatch):
    """Neither pipeline has a Cacher or a sampler: with a memory reading in
    place their plans decline nothing, key nothing by row and read no cache
    size — segments, labels and programs are the parent's."""
    from keystone_tpu.loaders.csv_loader import LabeledData
    from keystone_tpu.loaders.cifar import synthetic_cifar
    from keystone_tpu.pipelines import random_patch_cifar, timit
    from keystone_tpu.workflow.env import PipelineEnv

    asked = []
    real = seg_mod.unheld_caches
    monkeypatch.setattr(
        seg_mod, "unheld_caches",
        lambda *a: asked.append(real(*a)) or asked[-1],
    )
    monkeypatch.setattr(seg_mod, "_device_memory", lambda: (1 << 34, 1 << 34))
    tracer = tracer_mod.start()
    try:
        PipelineEnv.get_or_create().reset()
        rng = np.random.default_rng(0)
        conf = timit.TimitConfig(
            num_cosines=2, cosine_features=32, num_epochs=1, lam=1.0,
            num_classes=5,
        )
        timit.run(
            LabeledData(
                rng.integers(0, 5, 64).astype(np.int32),
                rng.standard_normal((64, 440)).astype(np.float32),
            ),
            LabeledData(
                rng.integers(0, 5, 32).astype(np.int32),
                rng.standard_normal((32, 440)).astype(np.float32),
            ),
            conf,
        )
        PipelineEnv.get_or_create().reset()
        train, test = synthetic_cifar(48, seed=1), synthetic_cifar(16, seed=2)
        random_patch_cifar.run(
            train, test, random_patch_cifar.RandomCifarConfig(
                num_filters=8, whitener_size=500, lam=10.0
            ),
        )
    finally:
        tracer_mod.stop()
        PipelineEnv.get_or_create().reset()
    assert asked and all(d == {} for d in asked)
    segments = _named(tracer.spans(), "exec.segment")
    assert segments and all(sp.attrs["path"] == "compiled" for sp in segments)
    assert not any("cache_declined_bytes" in sp.attrs for sp in segments)
    assert not any("Cacher" in sp.attrs["label"] for sp in segments)
