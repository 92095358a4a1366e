"""``SIFTExtractor.sampled_batch`` and ``SampledSIFTExtractor``
(``nodes/images/sift.py``, ``chain.py``): the descriptors at the sampler's
columns, made without the rest, against the chain as written — all the
descriptors, then ``ColumnSampler``. The same columns of the same
descriptors: whole numbers after a floor, equal but for an off-by-one where
two summation orders straddle one (none on the CPU at these sizes; the
tolerance is the benchmark's, ``test_the_sampled_columns_are_the_references``).

``sampled_batch`` reads the columns off the pooled maps by one of two bodies,
chosen from shapes (``SIFTExtractor.sampled_path``): every case here runs on
both — the fixture ``path`` answers for the rule — and the rule itself is
held to the two configurations' shapes.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from keystone_tpu.data.chunked import ChunkedDataset
from keystone_tpu.data.dataset import Dataset
from keystone_tpu.nodes.images.chain import SampledSIFTExtractor
from keystone_tpu.nodes.images.sift import SIFTExtractor
from keystone_tpu.nodes.learning.pca import BatchPCATransformer
from keystone_tpu.nodes.stats import ColumnSampler

X_DIM, Y_DIM = 64, 48  # 406 descriptors over four scales


@pytest.fixture(params=["grid", "bins"])
def path(request, monkeypatch):
    """Both bodies, whatever the rule would choose at these small shapes."""
    monkeypatch.setattr(
        SIFTExtractor, "sampled_path", lambda self, xd, yd, s: request.param
    )
    return request.param


def _images(n, seed=0, x=X_DIM, y=Y_DIM):
    """Textured grayscale images in [0, 1]: gradients everywhere, so
    descriptors pass the contrast test."""
    rng = np.random.default_rng(seed)
    xx, yy = np.meshgrid(np.arange(x), np.arange(y), indexing="ij")
    out = []
    for _ in range(n):
        f, t = rng.uniform(0.1, 0.4), rng.uniform(0, np.pi)
        wave = np.sin(2 * np.pi * f * (np.cos(t) * xx + np.sin(t) * yy))
        out.append(0.5 + 0.3 * wave + 0.1 * rng.standard_normal((x, y)))
    return jnp.asarray(np.clip(out, 0, 1)[..., None], jnp.float32)


def _assert_same_sample(got, want):
    got, want = np.asarray(got), np.asarray(want)
    assert got.shape == want.shape
    assert np.abs(got - want).max() <= 1.0
    assert np.mean(got != want) < 1e-3


def _written(sift, sampler, X, rows, then=()):
    D = sift.trace_batch(X)
    for node in then:
        D = node.trace_batch(D)
    return sampler.trace_batch(D, rows)


def test_the_sampled_body_makes_the_samplers_columns(path):
    sift, sampler = SIFTExtractor(), ColumnSampler(60, seed=3)
    X, rows = _images(6), jnp.arange(6)
    assert sift.num_descriptors(X_DIM, Y_DIM) == 406
    got = jax.jit(SampledSIFTExtractor(sift, (), sampler).trace_batch)(X, rows)
    want = _written(sift, sampler, X, rows)
    assert got.shape == (6, 128, 60)
    _assert_same_sample(got, want)
    assert 5.0 < np.asarray(want).mean() < 100.0  # not the zeros of a flat image


def test_every_column_decodes_to_its_scale_and_keypoint(path):
    """All 406 columns in order, and each scale's first and last twice
    over: the sampled body is the full body where nothing is left out."""
    sift = SIFTExtractor()
    X = _images(2, seed=5)
    full = np.asarray(sift.trace_batch(X))
    columns = jnp.tile(jnp.arange(406, dtype=jnp.int32), (2, 1))
    _assert_same_sample(sift.sampled_batch(X, columns), full)
    # 17·11 + 14·9 + 11·6 + 9·3 keypoints: each scale's first and last
    edges = np.asarray([0, 186, 187, 312, 313, 378, 379, 405], np.int32)
    got = sift.sampled_batch(X, jnp.tile(jnp.asarray(edges), (2, 1)))
    _assert_same_sample(got, full[:, :, edges])


@pytest.mark.parametrize("slice_rows", [4, 32])
def test_row_slices_draw_what_the_whole_data_set_draws(slice_rows, path):
    """The draw is keyed on the data-set row: 32 images in slices of 4, or
    as one batch whose first row is row 100 of its data set."""
    sift, sampler = SIFTExtractor(), ColumnSampler(25, seed=11)
    node = SampledSIFTExtractor(sift, (), sampler)
    X = _images(32, seed=2)
    rows = 100 + jnp.arange(32)
    want = np.asarray(_written(sift, sampler, X, rows))
    fn = jax.jit(node.trace_batch)
    got = np.concatenate([
        np.asarray(fn(X[a : a + slice_rows], rows[a : a + slice_rows]))
        for a in range(0, 32, slice_rows)
    ])
    _assert_same_sample(got, want)
    # and not the columns rows 0..31 would draw
    assert not np.array_equal(got, np.asarray(fn(X, jnp.arange(32))))


def test_a_flat_image_samples_zeros(path):
    node = SampledSIFTExtractor(SIFTExtractor(), (), ColumnSampler(40, seed=1))
    flat = jnp.full((2, X_DIM, Y_DIM, 1), 0.5, jnp.float32)
    assert not np.asarray(node.trace_batch(flat)).any()


def test_the_projection_of_the_sample_is_the_sample_of_the_projection(path):
    rng = np.random.default_rng(4)
    basis = np.linalg.qr(rng.standard_normal((128, 16)))[0].astype(np.float32)
    sift, pca, sampler = (
        SIFTExtractor(), BatchPCATransformer(basis), ColumnSampler(30, seed=8)
    )
    assert pca.column_wise and not sift.column_wise
    X, rows = _images(5, seed=9), 7 + jnp.arange(5)
    got = SampledSIFTExtractor(sift, (pca,), sampler).trace_batch(X, rows)
    want = _written(sift, sampler, X, rows, then=(pca,))
    assert got.shape == (5, 16, 30)
    np.testing.assert_allclose(
        np.asarray(got), np.asarray(want), rtol=1e-5, atol=1e-3
    )


def test_a_scale_that_does_not_fit_has_no_columns(path):
    """A 36 × 30 image holds bins of 4 and 6 only (extents 16, 24, 32, 40
    against 30): two scales' keypoints are all the columns there are."""
    sift, sampler = SIFTExtractor(scale_step=1), ColumnSampler(50, seed=2)
    X = _images(3, seed=6, x=36, y=30)
    n = sift.num_descriptors(36, 30)
    assert n == 7 * 5 + 4 * 2
    got = SampledSIFTExtractor(sift, (), sampler).trace_batch(X)
    _assert_same_sample(got, _written(sift, sampler, X, jnp.arange(3)))


def test_items_and_chunks_draw_by_their_place_in_the_data_set(path):
    """Node dispatch of the one node: an item list and a chunked scan give
    each row the columns of its data-set index, as ``ColumnSampler`` does."""
    sift, sampler = SIFTExtractor(num_scales=2), ColumnSampler(9, seed=5)
    node = SampledSIFTExtractor(sift, (), sampler)
    X = _images(6, seed=7)
    want = np.asarray(node.trace_batch(X, jnp.arange(6)))
    whole = node.apply_batch(Dataset.of(X)).to_array()
    np.testing.assert_array_equal(np.asarray(whole), want)
    items = node.apply_batch(Dataset.from_items([x for x in X])).collect()
    np.testing.assert_array_equal(np.stack([np.asarray(i) for i in items]), want)
    chunked = ChunkedDataset(lambda: iter([X[:4], X[4:]]), 6)
    got = np.concatenate(
        [np.asarray(c) for c in node.apply_batch(chunked).raw_chunks()]
    )
    np.testing.assert_array_equal(got, want)


def test_a_row_is_priced_by_what_its_body_reads_the_columns_from():
    """What segment dispatch is told an image holds (the compiled program's
    own figure: tests/nodes/test_conv_rectify_pool_tpu_compile.py)."""
    shape = (16, 500, 375, 1)
    sift = SIFTExtractor()
    node = SampledSIFTExtractor(sift, (), ColumnSampler(651))
    maps = 2 * 500 * 375 * 8 * 4
    # windows of 6, 9, 12 and 15: the four scales' pooled maps side by side
    joined = (495 * 370 + 492 * 367 + 489 * 364 + 486 * 361) * 8 * 4
    assert node.row_scratch_bytes(shape) == joined + maps == 34_948_992
    assert sift.row_scratch_bytes(shape) == maps + 73505 * 128 * 4
    assert node.row_keyed and node.binds_alone
    assert node.segment_facts(shape, 16) == {
        "sift_sampled_rows": 16, "sift_sampled_path": "bins"
    }
    assert "SIFTExtractor" in node.label
    # imagenet_fv16: the grid's stack of raw bins, not the joined maps
    shape = (256, 256, 256, 1)
    sift = SIFTExtractor(scale_step=1)
    node = SampledSIFTExtractor(sift, (), ColumnSampler(1221))
    assert sift.num_descriptors(256, 256) == 13436
    maps = 2 * 256 * 256 * 8 * 4
    # 81² + 59² + 45² + 37² rows of raw bins joined, and the widest scale's
    # before it is joined
    stack = (13436 + 81 * 81) * 128 * 4
    assert node.row_scratch_bytes(shape) == stack + maps == 14_432_768
    assert node.segment_facts(shape, 256) == {
        "sift_sampled_rows": 256, "sift_sampled_path": "grid"
    }


def test_the_two_bodies_give_the_same_sample():
    """Equal on the CPU, element for element: the same sums of the same
    pooled maps, read by two access patterns."""
    sift = SIFTExtractor(scale_step=1)
    X = _images(4, seed=12)
    columns = ColumnSampler(90, seed=4).columns(
        jnp.arange(4), sift.num_descriptors(X_DIM, Y_DIM)
    )
    gray = X[..., 0]
    grid = np.asarray(sift._sampled_grid(gray, columns))
    bins = np.asarray(sift._sampled_bins(gray, columns))
    assert grid.shape == bins.shape == (4, 90, 128)
    np.testing.assert_array_equal(grid, bins)
    assert grid.any()


@pytest.mark.parametrize(
    "sift, shape, samples, want",
    [
        (SIFTExtractor(scale_step=1), (256, 256), 1221, "grid"),
        (SIFTExtractor(), (500, 375), 651, "bins"),
    ],
    ids=["imagenet_fv16", "voc_fv256"],
)
def test_the_body_is_chosen_from_shapes_alone(
    sift, shape, samples, want, monkeypatch
):
    """The sample's share of the keypoint grid decides — 9.1% of 13,436
    against 0.9% of 73,505 — under ``jax.eval_shape``: no array exists."""
    ran = []

    def watched(name, body):
        def run(self, gray, columns):
            ran.append(name)
            return body(self, gray, columns)
        return run

    for name in ("grid", "bins"):
        attr = "_sampled_" + name
        monkeypatch.setattr(
            SIFTExtractor, attr, watched(name, getattr(SIFTExtractor, attr))
        )
    assert sift.sampled_path(*shape, samples) == want
    out = jax.eval_shape(
        sift.sampled_batch,
        jax.ShapeDtypeStruct((2,) + shape + (1,), jnp.float32),
        jax.ShapeDtypeStruct((2, samples), jnp.int32),
    )
    assert out.shape == (2, 128, samples)
    assert ran == [want]


def test_the_signed_root_is_column_wise_and_samples_through(path):
    """``SignedHellingerMapper`` acts element by element: the root of the
    sample is the sample of the roots (the ImageNet pipeline's SIFT branch
    puts it between the extractor and the sampler)."""
    from keystone_tpu.nodes.stats import SignedHellingerMapper

    root = SignedHellingerMapper()
    assert root.column_wise is True
    sift, sampler = SIFTExtractor(scale_step=1), ColumnSampler(40, seed=5)
    X, rows = _images(5, seed=2), jnp.arange(5)
    got = jax.jit(
        SampledSIFTExtractor(sift, (root,), sampler).trace_batch
    )(X, rows)
    want = _written(sift, sampler, X, rows, then=(root,))
    assert got.shape == (5, 128, 40)
    # roots of whole numbers 0..255, an off-by-one floor on a few of them
    assert np.abs(np.asarray(got) ** 2 - np.asarray(want) ** 2).max() <= 1.001
    assert np.mean(np.asarray(got) != np.asarray(want)) < 1e-3
