"""The fused conv → rectify → pool program compiled for the chip it runs on
(TPU v5e) at ``cifar_patch10k``'s widths, without a chip: the TPU compiler
installed here takes a described device. Nothing runs — this holds what
interpret mode cannot: that Mosaic accepts the kernel's tiling, that the
convolution's (n, 27, 27, 10,000) output is nowhere an HBM buffer, and that
what an image holds ahead of the kernel is what segment dispatch is told.

The sampled SIFT body (``SampledSIFTExtractor``) at ``voc_fv256``'s widths
is compiled here too, in this file because one file's tests go to one xdist
worker and only one process may hold the TPU's library: that an image's
(73,505, 128) descriptor stack is nowhere a buffer, and that what an image
holds while its sample is made is what segment dispatch is told. And at
``imagenet_fv16``'s widths, where the sample is dense enough to be read
through the keypoint grid: the stack of raw bins is the widest buffer, the
pooled maps are never joined, and the scratch is what dispatch is told.
And the fitted TIMIT chain's one node (``CosineLinearMapper``) at
``timit_cos4``'s widths: Mosaic takes the cosine-scores kernel, and no
buffer holds a scoring chunk's features.

The topology is described inside a fixture, never at import: one process at
a time may load the TPU's library, and every xdist worker imports this file.
"""

import os
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from keystone_tpu.nodes.images.chain import (
    ConvRectifyPool,
    SampledSIFTExtractor,
)
from keystone_tpu.nodes.images.core import (
    Convolver,
    ImageVectorizer,
    Pooler,
    SymmetricRectifier,
)
from keystone_tpu.nodes.images.sift import SIFTExtractor
from keystone_tpu.nodes.learning.pca import BatchPCATransformer
from keystone_tpu.nodes.learning.zca import ZCAWhitener
from keystone_tpu.nodes.stats import ColumnSampler, SignedHellingerMapper
from keystone_tpu.ops import conv_rectify_pool as crp
from keystone_tpu.ops import cosine_scores as cs

FILTERS, IMAGES, SIDE = 10000, 1024, 32


@pytest.fixture(scope="module")
def one_chip():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    from jax.sharding import SingleDeviceSharding

    try:
        topo = topologies.get_topology_desc(
            platform="tpu", topology_name="v5e:2x2"
        )
    except Exception as e:  # noqa: BLE001 - no TPU compiler here
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture(scope="module")
def compiled(one_chip):
    """The chain and the vectorizer behind it, as a fit's segment holds
    them, over one row slice. A described device's executable cannot be
    read back from the persistent cache: keep it out of it."""
    from jax.experimental.compilation_cache import compilation_cache

    rng = np.random.default_rng(0)
    m = 6 * 6 * 3
    node = ConvRectifyPool(
        Convolver(
            rng.standard_normal((FILTERS, m)).astype(np.float32),
            SIDE, SIDE, 3,
            whitener=ZCAWhitener(
                np.eye(m, dtype=np.float32), np.zeros(m, np.float32)
            ),
        ),
        SymmetricRectifier(alpha=0.25), Pooler(13, 14, None, "sum"),
    )
    vectorizer = ImageVectorizer()
    images = jax.ShapeDtypeStruct(
        (IMAGES, SIDE, SIDE, 3), jnp.float32, sharding=one_chip
    )
    cache_was_on = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    mode = crp.kernel_mode
    crp.kernel_mode = lambda: "compiled"  # the backend here is the CPU
    try:
        return jax.jit(
            lambda X: vectorizer.trace_batch(node.trace_batch(X))
        ).lower(images).compile()
    finally:
        crp.kernel_mode = mode
        jax.config.update("jax_enable_compilation_cache", cache_was_on)
        compilation_cache.reset_cache()


def test_mosaic_takes_the_kernel_at_10000_filters(compiled):
    text = compiled.as_text()
    assert "tpu_custom_call" in text
    assert re.search(r"%conv_rectify_pool[.\d]* = f32\[1024,8,10000\]", text)


def test_the_convolutions_output_is_no_hbm_buffer(compiled):
    """No array of the program holds 27 × 27 windows by thousands of
    filters; the widest thing a window has is its 128-lane patch row."""
    shapes = re.findall(r"(?:f32|bf16)\[([\d,]+)\]", compiled.as_text())
    widest = max(
        int(np.prod([int(d) for d in s.split(",")])) for s in shapes
    )
    # the patch rows (784 × 128) and the features (80,000) are the widest
    # an image has; the convolution's output would be 7,290,000
    assert widest == IMAGES * crp.pool_plan(27, 27, 13, 14).rows * 128
    assert not [s for s in shapes if re.search(r",27,27,\d{4,}$", s)]


def test_an_images_scratch_is_what_dispatch_is_told(compiled):
    told = crp.scratch_bytes(27, 27, 13, 14)
    held = compiled.memory_analysis().temp_size_in_bytes / IMAGES
    assert 0.8 * told <= held <= 1.1 * told, (told, held)


# -- the sampled SIFT body at voc_fv256's widths ------------------------------

VOC_SLICE, VOC_X, VOC_Y, VOC_COLUMNS = 64, 500, 375, 651


def _compiled_slice(node, one_chip, rows, xd, yd):
    """``node`` over one row slice, its first row's index an argument, as
    segment dispatch runs it — compiled for the described chip."""
    from jax.experimental.compilation_cache import compilation_cache

    images = jax.ShapeDtypeStruct(
        (rows, xd, yd, 1), jnp.float32, sharding=one_chip
    )
    row0 = jax.ShapeDtypeStruct((), jnp.int32, sharding=one_chip)
    cache_was_on = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    try:
        return jax.jit(
            lambda X, r: node.trace_batch(
                X, r + jnp.arange(rows, dtype=jnp.int32)
            )
        ).lower(images, row0).compile()
    finally:
        jax.config.update("jax_enable_compilation_cache", cache_was_on)
        compilation_cache.reset_cache()


@pytest.fixture(scope="module")
def sampled(one_chip):
    """SIFT → projection → sampler as the codebook's sampling pass runs it:
    the one node over one row slice."""
    rng = np.random.default_rng(0)
    basis = np.linalg.qr(rng.standard_normal((128, 80)))[0].astype(np.float32)
    node = SampledSIFTExtractor(
        SIFTExtractor(), (BatchPCATransformer(basis),),
        ColumnSampler(VOC_COLUMNS, seed=1),
    )
    return node, _compiled_slice(node, one_chip, VOC_SLICE, VOC_X, VOC_Y)


def test_the_descriptor_stack_is_no_buffer_of_a_sampling_pass(sampled):
    node, compiled = sampled
    assert node.sift.num_descriptors(VOC_X, VOC_Y) == 73505
    text = compiled.as_text()
    shapes = [
        [int(d) for d in s.split(",")]
        for s in re.findall(r"(?:f32|s32)\[([\d,]+)\]", text)
    ]
    # nothing holds a scale's keypoint grid, let alone all four
    grids = {162 * 120, 159 * 118, 157 * 115, 154 * 112, 73505}
    assert not [s for s in shapes if grids & set(s)]
    # the widest an image has is the four scales' pooled maps side by side
    # for the one gather (5,737,248 values), never 73,505 × 128 (9,408,640)
    widest = max(int(np.prod(s)) for s in shapes) / VOC_SLICE
    assert widest == (495 * 370 + 492 * 367 + 489 * 364 + 486 * 361) * 8
    assert len(re.findall(r" gather\(", text)) == 1
    assert re.search(r"f32\[64,80,651\]", text)


def test_a_sampled_images_scratch_is_what_dispatch_is_told(sampled):
    node, compiled = sampled
    told = node.row_scratch_bytes((VOC_SLICE, VOC_X, VOC_Y, 1))
    held = compiled.memory_analysis().temp_size_in_bytes / VOC_SLICE
    assert 0.9 * told <= held <= 1.1 * told, (told, held)


# -- the sampled SIFT body at imagenet_fv16's widths --------------------------

INET_SLICE, INET_SIDE, INET_COLUMNS = 256, 256, 1220


@pytest.fixture(scope="module")
def sampled_dense(one_chip):
    """SIFT at scale step 1 → signed root → sampler as the PCA's sampling
    pass of ``imagenet_fv16`` runs it: 1,220 of 13,436 columns an image."""
    node = SampledSIFTExtractor(
        SIFTExtractor(scale_step=1), (SignedHellingerMapper(),),
        ColumnSampler(INET_COLUMNS, seed=1),
    )
    compiled = _compiled_slice(node, one_chip, INET_SLICE, INET_SIDE, INET_SIDE)
    return node, compiled


def test_a_dense_sample_is_taken_from_the_grids_stack(sampled_dense):
    node, compiled = sampled_dense
    assert node.sift.num_descriptors(INET_SIDE, INET_SIDE) == 13436
    assert node.sift.sampled_path(INET_SIDE, INET_SIDE, INET_COLUMNS) == "grid"
    text = compiled.as_text()
    shapes = [
        [int(d) for d in s.split(",")]
        for s in re.findall(r"(?:f32|s32)\[([\d,]+)\]", text)
    ]
    # the widest an image has is the (13,436, 128) stack of raw bins; the
    # four scales' pooled maps (251² + 248² + 245² + 242² positions of 8)
    # are never laid side by side, and no (128, 13,436) transpose exists
    widest = max(int(np.prod(s)) for s in shapes) / INET_SLICE
    assert widest == 13436 * 128
    joined = 251 * 251 + 248 * 248 + 245 * 245 + 242 * 242
    assert not [s for s in shapes if joined in s]
    assert not [s for s in shapes if s[-2:] == [128, 13436]]
    assert re.search(r"f32\[256,128,1220\]", text)


def test_a_dense_samples_scratch_is_what_dispatch_is_told(sampled_dense):
    node, compiled = sampled_dense
    told = node.row_scratch_bytes((INET_SLICE, INET_SIDE, INET_SIDE, 1))
    assert told == 14_432_768
    held = compiled.memory_analysis().temp_size_in_bytes / INET_SLICE
    assert 0.9 * told <= held <= 1.1 * told, (told, held)


# -- the patch draw of the filter learning at cifar_krr's 50,000 images ------


def test_the_patch_draw_holds_no_padded_copy_of_the_images(one_chip):
    """100,000 windows drawn from 50,000 images: one gather of whole image
    rows, the draw's temporaries near 3 GB. Sliced from (n, 32, 32, 3)
    itself the compiler lays the images out with C = 3 padded to 128
    lanes, a 26.2 GB copy that no chip holds."""
    from jax.experimental.compilation_cache import compilation_cache

    from keystone_tpu.pipelines.random_patch_cifar import _patches_at

    n, m = 50000, 100000
    images = jax.ShapeDtypeStruct((n, SIDE, SIDE, 3), jnp.float32,
                                  sharding=one_chip)
    index = jax.ShapeDtypeStruct((m,), jnp.int32, sharding=one_chip)
    cache_was_on = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    try:
        compiled = _patches_at.lower(images, index, index, index, 6).compile()
    finally:
        jax.config.update("jax_enable_compilation_cache", cache_was_on)
        compilation_cache.reset_cache()
    memory = compiled.memory_analysis()
    # the windows themselves, in the chip's tiling
    assert memory.output_size_in_bytes < 2 * m * 6 * 6 * 3 * 4
    assert memory.temp_size_in_bytes < 4e9
    # one gather of whole rows (a window cut at its own column offset
    # compiles to no gather at all: a loop of slices, a window a step)
    assert len(re.findall(r" gather\(", compiled.as_text())) == 1


def test_the_bodies_hold_no_more_than_dispatch_is_told(one_chip):
    """The three bodies at ``cifar_krr``'s 100 filters and stated precision
    over 1,024 images: the temporaries the TPU compiler gives the program
    an image stay under what ``row_scratch_bytes`` declares."""
    from jax.experimental.compilation_cache import compilation_cache

    rng = np.random.default_rng(0)
    m = 6 * 6 * 3
    node = ConvRectifyPool(
        Convolver(
            rng.standard_normal((100, m)).astype(np.float32), SIDE, SIDE, 3,
            whitener=ZCAWhitener(
                np.eye(m, dtype=np.float32), np.zeros(m, np.float32)
            ),
            precision="high",
        ),
        SymmetricRectifier(alpha=0.25), Pooler(13, 14, None, "sum"),
    )
    vectorizer = ImageVectorizer()
    images = jax.ShapeDtypeStruct(
        (IMAGES, SIDE, SIDE, 3), jnp.float32, sharding=one_chip
    )
    cache_was_on = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    try:
        compiled = jax.jit(
            lambda X: vectorizer.trace_batch(node.trace_batch(X))
        ).lower(images).compile()
    finally:
        jax.config.update("jax_enable_compilation_cache", cache_was_on)
        compilation_cache.reset_cache()
    told = node.row_scratch_bytes((IMAGES, SIDE, SIDE, 3))
    held = compiled.memory_analysis().temp_size_in_bytes / IMAGES
    assert 0.3 * told <= held <= told, (told, held)


# -- the cosine scores at timit_cos4's widths ---------------------------------

COS_ROWS, COS_DIM, COS_FEATURES, COS_CLASSES = 65536, 440, 4096, 147


def _cosine_scores_program(one_chip, rows):
    """The fitted chain's one node and the classifier over a scoring chunk
    of ``rows``: the guard, the fused kernel and its fallback, compiled for
    the described chip with the parameters as arguments (as the literals a
    fitted program holds they cost the compiler 20 s more, and the same
    program)."""
    from jax.experimental.compilation_cache import compilation_cache

    from keystone_tpu.nodes.learning import cosine_linear

    def spec(shape):
        return jax.ShapeDtypeStruct(shape, jnp.float32, sharding=one_chip)

    branches = 4
    args = (
        spec((rows, COS_DIM)),
        spec((COS_DIM, branches * COS_FEATURES)),
        spec((branches * COS_FEATURES,)),
        spec((branches * COS_FEATURES,)),
        spec((branches * COS_FEATURES, COS_CLASSES)),
        spec((COS_CLASSES,)),
        spec((branches,)), spec((branches,)),
    )
    cache_was_on = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    try:
        return jax.jit(
            lambda *a: jnp.argmax(
                cosine_linear._guarded_scores(*a, interpret=False), axis=-1
            )
        ).lower(*args).compile()
    finally:
        jax.config.update("jax_enable_compilation_cache", cache_was_on)
        compilation_cache.reset_cache()


@pytest.fixture(scope="module")
def cosine_scores(one_chip):
    return {
        rows: _cosine_scores_program(one_chip, rows)
        for rows in (COS_ROWS, 2 * COS_ROWS)
    }


def test_mosaic_takes_the_cosine_scores_kernel(cosine_scores):
    text = cosine_scores[COS_ROWS].as_text()
    assert "tpu_custom_call" in text
    assert re.search(r"%cosine_scores[.\d]* = f32\[65536,147\]", text)


def test_a_chunks_features_are_no_hbm_buffer(cosine_scores):
    """Not in the kernel's branch and not in the guard's fallback: no
    array of the program holds a chunk's features, a branch's or all
    four's (4.3 GB where the chain is written out)."""
    text = cosine_scores[COS_ROWS].as_text()
    shapes = set(re.findall(r"(?:f32|bf16)\[([\d,]+)\]", text))
    assert "65536,4096" not in shapes and "65536,16384" not in shapes
    widest = max(
        int(np.prod([int(d) for d in s.split(",")])) for s in shapes
    )
    assert widest < COS_ROWS * COS_FEATURES


def test_a_scored_rows_scratch_is_what_dispatch_is_told(cosine_scores):
    """What a row adds to the program's temporaries — the guard's fallback
    takes the rows a block at a time — against ``row_scratch_bytes``: no
    more, and not far under."""
    from keystone_tpu.nodes.learning.cosine_linear import CosineLinearMapper
    from keystone_tpu.nodes.learning.linear import BlockLinearMapper
    from keystone_tpu.nodes.stats import CosineRandomFeatures

    rng = np.random.default_rng(0)
    node = CosineLinearMapper(
        [CosineRandomFeatures.create(COS_DIM, COS_FEATURES, 0.05555, seed=i)
         for i in range(4)],
        BlockLinearMapper(
            [rng.standard_normal((COS_FEATURES, COS_CLASSES)).astype(np.float32)
             for _ in range(4)], COS_FEATURES,
        ),
    )
    mode = cs.kernel_mode
    cs.kernel_mode = lambda: "compiled"  # the backend here is the CPU
    try:
        told = node.row_scratch_bytes((COS_ROWS, COS_DIM))
    finally:
        cs.kernel_mode = mode
    temps = {
        rows: c.memory_analysis().temp_size_in_bytes
        for rows, c in cosine_scores.items()
    }
    held = (temps[2 * COS_ROWS] - temps[COS_ROWS]) / COS_ROWS
    # the two branches of the guard share their buffers: what dispatch is
    # told is the larger branch's, an upper bound
    assert 0.5 * told <= held <= told, (told, held)
    assert temps[COS_ROWS] < 0.5e9  # the parent's program: 7.5 GB
