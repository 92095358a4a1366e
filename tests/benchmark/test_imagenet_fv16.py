"""``imagenet_fv16``: the configuration file against the published widths,
its counts against hand arithmetic, its plain reference against the program
part by part at a size a CPU test holds (LCS descriptors, sampled columns of
both branches, PCA bases, codebooks, combined features, the class-weighted
solve, the top-k errors), that a share of the class systems adds up, and its
cell through the harness — all added as files, with no file of the harness
edited."""

import json
import os
import shutil
import types

import numpy as np
import pytest

from benchmark import control, harness
from benchmark.ops import (
    cifar_block_update,
    cifar_conv_chain,
    cifar_fit_job,
    imagenet_fit_job,
    imagenet_shapes,
    imagenet_wls_solve,
)
from benchmark.readers import span_attr_per_job, where_counted
from tests.benchmark import tiny

ROOT = tiny.ROOT
CONFIGS = os.path.join(ROOT, "benchmark", "configs")

#: the sizes of the tests: images of 64 × 64 (484 SIFT and 64 LCS
#: descriptors), 8 dimensions, 4 centres (d = 128: one block of the
#: published 4,096), 6 classes, n + 3 ≥ d so that the primal path runs
SMALL = {
    "image_x": 64, "image_y": 64, "descriptors_per_image": 484,
    "lcs_descriptors_per_image": 64, "n_train": 160, "n_test": 32,
    "num_classes": 6, "vocab_size": 4, "desc_dim": 8, "d": 128,
    "num_pca_samples": 8000, "num_gmm_samples": 8000,
    "reference_slice": 8, "reference_rows": 64,
}

OTHERS = ["timit_cos4", "mnist_fft", "cifar_patch10k", "voc_fv256"]


def _config(name="imagenet_fv16", **over):
    with open(os.path.join(CONFIGS, name + ".json")) as f:
        return dict(json.load(f), **over)


def _adapter(part):
    return harness.load_module(
        os.path.join(CONFIGS, f"imagenet_fv16_{part}.py")
    )


def _labels(y):
    return np.asarray(y).astype(np.int32)


# -- the configuration file ------------------------------------------------


def test_the_file_holds_the_published_widths():
    cfg = _config()
    published = {
        "desc_dim": 64, "vocab_size": 16, "lam": 6e-5,
        "mixture_weight": 0.25, "step": 3, "bin_size": 4, "num_scales": 4,
        "scale_step": 1, "descriptor_width": 128,
        "descriptors_per_image": 13436, "lcs_descriptor_width": 96,
        "lcs_descriptors_per_image": 3136, "num_pca_samples": 10000000,
        "num_gmm_samples": 10000000, "block_size": 4096, "epochs": 1,
        "top_k": 5, "d": 4096, "image_x": 256, "image_y": 256,
        "image_channels": 3,
    }
    for key, value in published.items():
        assert cfg[key] == value == cfg["published"][key], key
    assert cfg["lcs"] == {"stride": 4, "border": 16, "patch": 6}
    assert cfg["gmm"]["max_iterations"] == 20
    assert cfg["gmm"]["min_cluster_size"] == 1
    ref = _adapter("reference")
    assert ref.expected_d(cfg) == cfg["d"] == 2 * 2 * 64 * 16
    assert cfg["d"] == cfg["block_size"]  # one block
    assert ref.sift_descriptors_per_image(cfg) == 13436
    assert ref.lcs_descriptors_per_image(cfg) == 3136
    assert ref.lcs_offsets(cfg) == [-10, -4, 2, 8]
    assert {k: cfg["precision"][k] for k in ("featurizer", "solver", "apply")} == {
        "featurizer": "high", "solver": "highest", "apply": "bf16"
    }
    for key in ("deployment", "assumed", "source"):
        assert cfg[key]
    assert len(cfg["source"]) <= 200 and cfg["architecture"] is None


def test_the_cuts_are_the_rows_and_the_share_of_classes():
    cfg = _config()
    changed = {k for k, v in cfg["published"].items() if cfg[k] != v}
    assert changed == set(cfg["reduced"])
    assert {"n_train", "n_test"} <= changed <= {"n_train", "n_test", "num_classes"}
    assert (cfg["published"]["n_train"], cfg["published"]["n_test"]) == (
        1281167, 50000
    )
    assert cfg["published"]["num_classes"] == 1000
    # the deployment has n >> d: the cut keeps the primal path (n + 3 >= d)
    # and a population covariance of full rank (n >= d + 256)
    assert cfg["n_train"] + 3 >= cfg["d"]
    assert cfg["n_train"] >= 4096 + 256
    # the share of the class systems one chip holds
    assert cfg["num_classes"] in (1000, 500, 250, 125)
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        doc = json.load(f)
    (entry,) = [c for c in doc["configs"] if c["name"] == "imagenet_fv16"]
    assert entry["reduced"] == cfg["reduced"]
    assert entry["source"] == cfg["source"]
    # samples an image as upstream computes them: 1e7 // n_train
    ref = _adapter("reference")
    assert ref.per_image(cfg, "num_pca_samples") == 10000000 // cfg["n_train"]


def test_the_program_is_given_every_width(capsys):
    cfg = _config()
    conf = _adapter("program").conf_of(cfg)
    assert (conf.desc_dim, conf.vocab_size, conf.lam, conf.mixture_weight) == (
        64, 16, 6e-5, 0.25
    )
    assert (conf.sift_scale_step, conf.lcs_stride, conf.lcs_border,
            conf.lcs_patch) == (1, 4, 16, 6)
    assert (conf.num_pca_samples, conf.num_gmm_samples) == (10000000, 10000000)
    assert conf.num_classes == cfg["num_classes"]
    assert conf.seed == cfg["sample_seed"]
    # fitted from the data: no checkpoint file
    assert not any(
        getattr(conf, f) for f in vars(conf) if f.endswith("_file")
    )
    # the grid, the block and the passes are the pipeline's own: a file
    # that asks for another fails
    for other in ({"step": 4}, {"block_size": 2048}, {"top_k": 1}):
        with pytest.raises(SystemExit) as e:
            _adapter("program").conf_of(dict(cfg, **other))
        assert e.value.code == 2
        assert "the program builds" in capsys.readouterr().err


# -- the counts --------------------------------------------------------------


def test_the_descriptors_of_an_image():
    from keystone_tpu.nodes.images import LCSExtractor, SIFTExtractor

    cfg = _config()
    assert imagenet_shapes.sift_scales(cfg) == [
        (4, 3, 81, 81), (6, 4, 59, 59), (8, 5, 45, 45), (10, 6, 37, 37),
    ]
    assert imagenet_shapes.sift_descriptors(cfg) == 13436
    assert SIFTExtractor(scale_step=1).num_descriptors(256, 256) == 13436
    assert imagenet_shapes.lcs_descriptors(cfg) == 56 * 56 == 3136
    assert LCSExtractor(4, 16, 6).num_descriptors(256, 256) == 3136
    small = _config(**SMALL)
    assert imagenet_shapes.sift_descriptors(small) == 484
    assert imagenet_shapes.lcs_descriptors(small) == 64
    ref = _adapter("reference")
    assert ref.descriptors_per_image(small, "sift") == 484
    assert ref.descriptors_per_image(small, "lcs") == 64


def test_one_image_through_both_chains():
    cfg = _config()
    feat = imagenet_shapes.featurize_image(cfg)
    assert feat["gemm_flops"] == (
        2 * 13436 * 128 * 64 + 4 * 2 * 13436 * 64 * 16
        + 2 * 3136 * 96 * 64 + 4 * 2 * 3136 * 64 * 16
    ) == 394428416
    assert feat["bytes"] == 256 * 256 * 3 + 4 * 4096
    # SIFT's maps and LCS's box sums outweigh the 16-centre products
    assert imagenet_shapes.sift_flops(cfg) == pytest.approx(7.89e7, rel=1e-2)
    assert imagenet_shapes.lcs_flops(cfg) == 196608 * 31.0
    assert feat["other_flops"] < feat["gemm_flops"]


def test_the_solves_least_operations():
    cfg = _config()
    n, d, k = cfg["n_train"], 4096, cfg["num_classes"]
    sol = imagenet_shapes.solve(cfg, n)
    # the population Gram and the class Grams from class-sorted rows:
    # 2·n·d² each IN ALL; the cross term and the residual update; the class
    # cross term from sorted rows
    assert sol["gemm_flops"] == 4 * n * d * d + 4 * n * d * k + 2 * n * d
    assert sol["other_flops"] == k * (d**3 / 3 + 2 * d * d)
    assert sol["bytes"] == 4 * (4 * n * d + 2 * k * d * d)
    got = imagenet_wls_solve.count(cfg, {})
    assert got["flops"] == sol["gemm_flops"] + sol["other_flops"]
    # as written the masked Grams read all n rows a class: k times the
    # class-sorted count
    assert 2 * n * k * d * d / (2 * n * d * d) == k


def test_the_fit_job_counts_each_image_once():
    cfg = _config()
    job = imagenet_fit_job.count(cfg, {})
    feat = imagenet_shapes.featurize_image(cfg)
    book = imagenet_shapes.codebooks(cfg)
    sol = imagenet_shapes.solve(cfg, cfg["n_train"])
    images = cfg["n_train"] + cfg["n_test"]
    assert imagenet_shapes.images_featurized(cfg) == images
    whole = lambda p: p["gemm_flops"] + p["other_flops"]  # noqa: E731
    app = imagenet_shapes.apply_row(cfg)
    assert job["flops"] == (
        images * whole(feat) + whole(book) + whole(sol)
        + cfg["n_test"] * whole(app)
    )
    samples = (10000000 // cfg["n_train"]) * cfg["n_train"]
    assert book["gemm_flops"] == pytest.approx(
        2 * ((20 * 4 + 5) * 2 * samples * 64 * 16 + 2 * samples * 64 * 15)
        + 2 * samples * (128 * 128 + 96 * 96), rel=1e-12,
    )


@pytest.mark.parametrize("name", OTHERS)
@pytest.mark.parametrize("ops", [imagenet_fit_job, imagenet_wls_solve])
def test_a_count_does_not_apply_to_another_configuration(name, ops):
    assert ops.count(_config(name), {}) is None
    assert ops.count(_config(), {}) is not None


@pytest.mark.parametrize(
    "ops", [cifar_fit_job, cifar_conv_chain, cifar_block_update]
)
def test_another_configurations_count_does_not_apply_to_this_one(ops):
    # (voc_fv256's counts ask for ``vocab_size`` alone, which this
    # configuration states too: its metrics list voc_fv256.fit, so the
    # harness never hands them this cell — PERF.md section 7)
    assert ops.count(_config(), {}) is None
    assert ops.count(_config(**SMALL), {}) is None


def _run_with(config, facts=None):
    manifest = harness.Manifest(ROOT)
    return types.SimpleNamespace(
        manifest=manifest, config=config, traffic={}, facts=facts or {},
        cell={"chips": 1}, peak=tiny.PEAK, reduction=None,
    )


def test_where_counted_leaves_out_what_is_not_described():
    params = {"reader": "ops_over_time", "ops": "imagenet_fit_job"}
    facts = {"units": 1, "window_s": 30.0}
    for other in OTHERS:
        assert where_counted.read(params, _run_with(_config(other), facts)) is None
    cfg = _config()
    got = where_counted.read(params, _run_with(cfg, facts))
    need = imagenet_fit_job.count(cfg, {})["flops"]
    assert got == 100.0 * need / (30.0 * tiny.PEAK["flops_per_s"])
    params = harness.Manifest(ROOT).metric_file("solver_wls_roofline")["params"]
    assert where_counted.read(params, _run_with(cfg, facts)) is None  # no trace


# -- the reference against the program ---------------------------------------


@pytest.fixture(scope="module")
def small():
    """One job of the program and the reference's codebooks at the small
    size."""
    from keystone_tpu.workflow.env import PipelineEnv

    cfg = _config(**SMALL)
    ref, prog = _adapter("reference"), _adapter("program")
    ref._STATE.clear()
    train = ref.make_rows(cfg, cfg["train_seed"], cfg["n_train"])
    held = ref.make_rows(cfg, 4242, cfg["n_test"])
    handle = prog.fit(
        cfg, train[0], _labels(train[1]), held[0], _labels(held[1])
    )
    out = types.SimpleNamespace(
        cfg=cfg, ref=ref, prog=prog, train=train, held=held, handle=handle,
        model=prog.model(handle), books=ref.learn_codebooks(cfg, train[0]),
    )
    ref._STATE[cfg["n_train"]] = out.books
    yield out
    ref._STATE.clear()
    PipelineEnv.get_or_create().reset()


def _nodes(fitted, cls):
    graph = fitted.graph
    return [
        graph.get_operator(n) for n in sorted(graph.nodes)
        if isinstance(graph.get_operator(n), cls)
    ]


def _branch_of(width: int) -> str:
    return {128: "sift", 96: "lcs"}[int(width)]


def test_the_images_repeat_and_carry_both_signals(small):
    X, y = small.train
    assert X.shape == (160, 64, 64, 3) and str(X.dtype) == "uint8"
    assert y.shape == (160,) and str(y.dtype) == "int32"
    assert 0 <= int(y.min()) and int(y.max()) < 6
    again = small.ref.make_rows(small.cfg, small.cfg["train_seed"], 160)
    np.testing.assert_array_equal(np.asarray(X), np.asarray(again[0]))
    np.testing.assert_array_equal(np.asarray(y), np.asarray(again[1]))
    # a set is the same however it is cut: image i has its own key
    part = small.ref.make_rows(small.cfg, small.cfg["train_seed"], 5)
    np.testing.assert_array_equal(np.asarray(X)[:5], np.asarray(part[0]))
    other = small.ref.make_rows(small.cfg, 99, 160)
    assert not np.array_equal(np.asarray(X), np.asarray(other[0]))
    # colour: the channels differ (LCS's signal); structure: a grating under
    # the noise (SIFT's)
    Xf = np.asarray(X, np.float32)
    levels = Xf.mean(axis=(1, 2))  # (n, 3)
    assert np.abs(levels - levels.mean(axis=1, keepdims=True)).max() > 3.0
    # the class grid: 5 orientations x 5 frequencies x hues
    assert small.ref.class_grid(125) == (5, 5, 5)
    assert small.ref.class_grid(1000) == (5, 5, 40)


def test_the_programs_lcs_descriptors_are_the_references(small):
    from keystone_tpu.nodes.images import LCSExtractor

    X = small.train[0][:6]
    got = np.asarray(LCSExtractor(4, 16, 6).trace_batch(X))
    want = np.asarray(small.ref.lcs(small.cfg, X)).transpose(0, 2, 1)
    assert got.shape == want.shape == (6, 96, 64)
    # both from exact box sums of 8-bit pixels: equal but for the last
    # rounding of a mean and a root
    np.testing.assert_allclose(got, want, atol=2e-3)
    assert want.max() <= 255 and want[:, 1::2].mean() > 1.0  # deviations


def test_the_signed_root_of_sift_is_the_references(small):
    from keystone_tpu.nodes.images import GrayScaler, PixelScaler, SIFTExtractor
    from keystone_tpu.nodes.stats import SignedHellingerMapper

    X = small.train[0][:4]
    gray = GrayScaler().trace_batch(PixelScaler().trace_batch(X))
    D = SIFTExtractor(scale_step=1).trace_batch(gray)
    got = np.asarray(SignedHellingerMapper().trace_batch(D))
    describe, offset = small.ref.BRANCHES["sift"]
    want = np.asarray(describe(small.cfg, X)).transpose(0, 2, 1)
    assert got.shape == want.shape == (4, 128, 484) and offset == 0
    # roots of whole numbers 0..255: equal but where a floor straddles one
    assert np.abs(got**2 - want**2).max() <= 1.0 + 1e-3
    assert np.mean(np.abs(got - want) > 1e-5) < 1e-3


@pytest.mark.parametrize("branch", ["sift", "lcs"])
def test_the_sampled_columns_are_the_references(small, branch):
    from keystone_tpu.data.dataset import Dataset
    from keystone_tpu.nodes.stats import ColumnSampler

    cfg, ref = small.cfg, small.ref
    describe, offset = ref.BRANCHES[branch]
    seed = cfg["sample_seed"] + offset
    count = ref.per_image(cfg, "num_pca_samples")
    total = ref.descriptors_per_image(cfg, branch)
    rows = np.arange(3, 11)
    np.testing.assert_array_equal(
        np.asarray(ColumnSampler(count, seed=seed).columns(rows, total)),
        np.asarray(ref.sampled_columns(seed, rows, count, total)),
    )
    # the reference's sample, block by block, is the sampler's of the
    # reference's descriptors, image after image
    X = small.train[0][:24]
    D = np.asarray(describe(cfg, X)).transpose(0, 2, 1)
    got = ColumnSampler(count, seed=seed).apply_batch(Dataset.of(D))
    got = np.asarray(got.to_array()).transpose(0, 2, 1).reshape(-1, D.shape[1])
    blocks = ref._sample_blocks(dict(cfg, reference_rows=16), branch, X, seed, count)
    want = np.concatenate([np.asarray(b()) for b in blocks], axis=0)
    assert got.shape == want.shape == (24 * count, D.shape[1])
    # the same columns of the same descriptors: what differs is a floor's
    # off-by-one (SIFT) or a deviation's last rounding (LCS) where slices
    # of 8 and a batch of 24 fuse in another order
    assert np.mean(np.abs(got - want) > 2e-3) < 1e-3


def test_the_pca_bases_are_the_references(small):
    from keystone_tpu.nodes.learning import BatchPCATransformer

    pcas = _nodes(small.handle.pipeline, BatchPCATransformer)
    assert sorted(p.pca_mat.shape[0] for p in pcas) == [96, 128]
    for pca in pcas:
        got = np.asarray(pca.pca_mat)
        want = np.asarray(small.books[_branch_of(got.shape[0])]["basis"])
        assert got.shape == want.shape and got.shape[1] == 8
        # float32 eigh of the float32 covariance here, float64 there, the
        # same sign convention
        assert np.abs(got - want).max() < 5e-3
        np.testing.assert_allclose(want.T @ want, np.eye(8), atol=1e-5)


def test_the_codebooks_are_the_references(small):
    from keystone_tpu.nodes.images import FisherVector

    fvs = _nodes(small.handle.pipeline, FisherVector)
    assert len(fvs) == 2
    matched = set()
    for fv in fvs:
        gaps = {}
        for branch, book in small.books.items():
            gaps[branch] = max(
                np.linalg.norm(np.asarray(mine) - np.asarray(theirs))
                / np.linalg.norm(np.asarray(theirs))
                for mine, theirs in (
                    (fv.gmm.means.T, book["means"]),
                    (fv.gmm.variances.T, book["variances"]),
                    (fv.gmm.weights, book["weights"]),
                )
            )
        branch = min(gaps, key=gaps.get)
        # the same 4 seeds, then the same rounds: what is left is the
        # basis's 1e-5 and float32 summation order
        assert gaps[branch] < 5e-3, gaps
        matched.add(branch)
    assert matched == {"sift", "lcs"}


def _program_features(fitted, X):
    """The fitted pipeline's features: its graph applied up to the
    mapper's input."""
    from keystone_tpu.nodes.learning.linear import BlockLinearMapper
    from keystone_tpu.workflow.pipeline import FittedPipeline

    g = fitted.graph
    (mapper,) = [
        n for n in g.nodes
        if isinstance(g.get_operator(n), BlockLinearMapper)
    ]
    g2, sink = g.add_sink(g.get_dependencies(mapper)[0])
    return np.asarray(FittedPipeline(g2, fitted._source, sink).apply(X).to_array())


def test_the_combined_features_are_the_references(small):
    import jax

    apply, params = small.ref.featurizer(small.cfg, "highest")
    want = np.asarray(jax.jit(apply)(params, small.held[0]))
    assert want.shape == (32, 128)
    # two unit-norm Fisher vectors side by side
    np.testing.assert_allclose(np.linalg.norm(want[:, :64], axis=1), 1.0, atol=1e-5)
    np.testing.assert_allclose(np.linalg.norm(want[:, 64:], axis=1), 1.0, atol=1e-5)
    got = _program_features(small.handle.pipeline, small.held[0])
    assert got.shape == want.shape
    assert np.linalg.norm(got - want) / np.linalg.norm(want) < 5e-3


@pytest.fixture(scope="module")
def solved(small):
    """The reference's features of the small training set and the program's
    weighted solve of them."""
    import jax.numpy as jnp

    from keystone_tpu.data.dataset import Dataset
    from keystone_tpu.nodes.learning.weighted import (
        BlockWeightedLeastSquaresEstimator,
    )

    cfg, ref = small.cfg, small.ref
    F = ref.features(cfg, small.books, small.train[0], "highest")
    y = small.train[1]
    Y = 2.0 * np.eye(6, dtype=np.float32)[np.asarray(y)] - 1.0

    def program(Y):
        est = BlockWeightedLeastSquaresEstimator(
            4096, 1, cfg["lam"], cfg["mixture_weight"], num_features=128
        )
        m = est.fit(Dataset.of(F), Dataset.of(jnp.asarray(Y)))
        return (
            np.concatenate([np.asarray(x) for x in m.xs], axis=0),
            np.asarray(m.b),
        )

    return types.SimpleNamespace(F=F, y=y, Y=Y, program=program)


def test_the_weighted_solve_is_the_references(small, solved):
    cfg, ref = small.cfg, small.ref
    W, b = solved.program(solved.Y)
    want = ref.weighted_model(
        solved.F, solved.y, cfg, "highest", solve=ref.solve_direct
    )
    assert W.shape == (128, 6) and b.shape == (6,)
    # float32 pivoted LU at λ = 6e-5 here, float64 there: held to what the
    # two predict, as the cell holds them
    F = np.asarray(solved.F, np.float64)
    S_got = F @ W + b
    S_want = F @ np.asarray(want["W"], np.float64) + np.asarray(want["b"])
    assert np.linalg.norm(S_got - S_want) / np.linalg.norm(S_want) < 2e-3
    assert float(np.abs(np.asarray(want["mean"])).max()) == 0.0


def test_the_woodbury_form_is_the_direct_form(small, solved):
    cfg, ref = small.cfg, small.ref
    stats = ref.class_statistics(
        solved.F, solved.y, num_classes=6, w=0.25, precision="highest"
    )
    kw = dict(w=cfg["mixture_weight"], lam=cfg["lam"])
    direct = ref.solve_direct(solved.F, solved.y, stats, **kw)
    woodbury = ref.solve_woodbury(solved.F, solved.y, stats, **kw)
    assert np.abs(direct - woodbury).max() / np.abs(direct).max() < 1e-9
    # a class with no row at all: both forms give it the same system
    y = np.where(np.asarray(solved.y) == 5, 0, np.asarray(solved.y))
    stats = ref.class_statistics(
        solved.F, y, num_classes=6, w=0.25, precision="highest"
    )
    direct = ref.solve_direct(solved.F, y, stats, **kw)
    woodbury = ref.solve_woodbury(solved.F, y, stats, **kw)
    assert stats["counts"][5] == 0
    assert np.abs(direct - woodbury).max() / np.abs(direct).max() < 1e-9


def test_a_share_of_the_class_systems_adds_up(solved):
    """The class systems are independent — which is what lets a chip hold a
    share of them: over the same rows, two disjoint halves of the classes,
    each solved alone with ``Y`` cut to its columns, give the uncut
    solve's columns and intercepts for those classes."""
    W, b = solved.program(solved.Y)
    for half in ([0, 1, 2], [3, 4, 5]):
        W_half, b_half = solved.program(solved.Y[:, half])
        assert W_half.shape == (128, 3)
        # the same systems, factored in batches of 3 and of 6: float32 at
        # λ = 6e-5 (2e-4 was read)
        scale = np.abs(W[:, half]).max()
        assert np.abs(W_half - W[:, half]).max() / scale < 1e-3
        np.testing.assert_allclose(b_half, b[half], atol=1e-3)


def test_the_comparison_reads_the_program_as_correct(small):
    from benchmark import compare, refmath

    numbers = compare.fit_numbers(
        small.cfg, small.ref, small.train, small.held, small.model,
        small.handle.test_error, rows_per_block=32,
    )
    # float32 everywhere on the CPU: 2e-4 was read
    assert numbers["scores_gap"] < 2e-3
    # the job's top-1 error is the quantity fit_numbers takes of the
    # reference's scores
    assert numbers["test_error"] == small.handle.test_error
    assert numbers["test_error_gap"] <= 1.0 / 32 + 1e-9
    # the top-5 error the job reports is the reference's too
    ref_model = small.ref.fit(
        small.cfg, small.train[0], small.train[1], precision=compare.HIGHEST
    )
    S = refmath.scores(
        small.ref.featurizer(small.cfg, "highest"), small.held[0], ref_model,
        rows_per_block=32, precision="highest",
    )
    assert small.ref.top_k_error(S, small.held[1], 1) == pytest.approx(
        numbers["reference_test_error"]
    )
    top5 = small.ref.top_k_error(S, small.held[1], 5)
    assert small.handle.top5_error == pytest.approx(top5, abs=1.0 / 32 + 1e-9)
    assert top5 <= numbers["reference_test_error"] < 0.8  # chance: 5/6


def test_the_references_top_k_error_is_the_pipelines():
    from keystone_tpu.pipelines.imagenet_sift_lcs_fv import top_k_err_percent

    rng = np.random.default_rng(7)
    S = rng.standard_normal((40, 9))
    labels = rng.integers(0, 9, 40)
    ref = _adapter("reference")
    for k in (1, 5):
        topk = np.argsort(-S, axis=1)[:, :k]
        assert 100.0 * ref.top_k_error(S, labels, k) == pytest.approx(
            top_k_err_percent(topk, labels)
        )


def test_a_job_span_with_its_phases(small):
    from keystone_tpu.obs import tracer as tracer_mod

    cfg, prog = small.cfg, small.prog
    tracer = tracer_mod.start()
    try:
        prog.fit(cfg, small.train[0], _labels(small.train[1]), small.held[0],
                 _labels(small.held[1]))
    finally:
        tracer_mod.stop()
    spans = tracer.spans()
    job = [sp for sp in spans if sp.name == "job"]
    assert len(job) == 1 and job[0].attrs["pipeline"] == "ImageNetSiftLcsFV"
    by_id = {sp.span_id: sp for sp in spans}

    def under(sp, name):
        while sp.parent_id is not None:
            sp = by_id[sp.parent_id]
            if sp.name == name:
                return True
        return False

    def named(name):
        return [sp for sp in spans if sp.name == name]

    for name in ("plan.build", "imagenet.codebook",
                 "imagenet.sample_descriptors", "pca.fit", "kmeans.seed",
                 "gmm_fv.em_fit", "wls.block", "wls.stats", "wls.class_grams",
                 "wls.class_solve", "wls.residual", "eval.top_k"):
        assert named(name) and all(under(sp, "job") for sp in named(name)), name
    for name in ("imagenet.codebook", "imagenet.sample_descriptors",
                 "pca.fit", "kmeans.seed", "gmm_fv.em_fit"):
        assert all(under(sp, "plan.build") for sp in named(name))
    assert [sp.attrs["branch"] for sp in named("imagenet.codebook")] == [
        "sift", "lcs"
    ]
    passes = [sp.attrs for sp in named("imagenet.sample_descriptors")]
    assert [(a["branch"], a["stage"]) for a in passes] == [
        ("sift", "pca"), ("sift", "gmm"), ("lcs", "pca"), ("lcs", "gmm")
    ]
    assert all(a["images"] == 160 and a["columns"] == 8000 for a in passes)
    assert [a["bytes"] for a in passes] == [
        8000 * 128 * 4, 8000 * 8 * 4, 8000 * 96 * 4, 8000 * 8 * 4
    ]
    assert [sp.attrs["samples"] for sp in named("pca.fit")] == [8000, 8000]
    assert [sp.attrs["centres"] for sp in named("gmm_fv.em_fit")] == [4, 4]
    # SIFT's two sampling passes make the sampled descriptors alone, through
    # the signed root
    sampled = [sp.attrs for sp in named("exec.segment")
               if "sift_sampled_rows" in sp.attrs]
    assert [a["sift_sampled_rows"] for a in sampled] == [160, 160]
    assert all("SampledSIFTExtractor" in a["label"] for a in sampled)
    # the solve: one block, the primal path, one d x d system a class
    (block,) = named("wls.block")
    assert block.attrs["path"] == "primal"
    assert block.attrs["class_systems"] == 6 == block.attrs["gram_products"]
    assert block.attrs["class_chunk"] == 8
    assert (block.attrs["rows"], block.attrs["dims"]) == (160, 128)
    for name in ("wls.stats", "wls.class_grams", "wls.class_solve",
                 "wls.residual"):
        assert all(under(sp, "wls.block") for sp in named(name)), name
    assert [sp.attrs["classes"] for sp in named("wls.class_solve")] == [6]
    (top,) = named("eval.top_k")
    assert top.attrs["top5_error"] <= top.attrs["top1_error"] <= 100.0
    assert all(sp.attrs["path"] == "compiled" for sp in named("exec.segment"))


def test_the_class_systems_a_job_are_read_from_the_spans(monkeypatch):
    def sp(name, **attrs):
        return types.SimpleNamespace(name=name, attrs=attrs, instant=False)

    params = harness.Manifest(ROOT).metric_file(
        "solver.wls_class_systems_per_fit"
    )["params"]
    run = _run_with(_config())
    spans = [sp("job"), sp("wls.block", path="primal", class_systems=125),
             sp("job"), sp("wls.block", path="primal", class_systems=125)]
    monkeypatch.setattr(span_attr_per_job.span_idle, "program_spans",
                        lambda: spans)
    assert span_attr_per_job.read(params, run) == 125.0
    # the dual path solves no d x d system
    monkeypatch.setattr(
        span_attr_per_job.span_idle, "program_spans",
        lambda: [sp("job"), sp("wls.block", path="dual", class_systems=0)],
    )
    assert span_attr_per_job.read(params, run) == 0.0
    # a program with no such span (another pipeline, a parent): nothing
    monkeypatch.setattr(span_attr_per_job.span_idle, "program_spans",
                        lambda: [sp("job"), sp("block_ls.solve")])
    assert span_attr_per_job.read(params, run) is None


# -- the cell, through the harness -------------------------------------------


@pytest.fixture(scope="module")
def imagenet_root(tmp_path_factory):
    """``tiny.build``'s benchmark with a small cell of this configuration
    added to it as files and entries."""
    root = tiny.build(str(tmp_path_factory.mktemp("bench_imagenet")))
    bench = os.path.join(root, "benchmark")
    cfg = dict(_config(**SMALL), name="tiny_imagenet")
    with open(os.path.join(bench, "configs", "tiny_imagenet.json"), "w") as f:
        json.dump(cfg, f)
    for part in ("reference", "program"):
        shutil.copy(
            os.path.join(CONFIGS, f"imagenet_fv16_{part}.py"),
            os.path.join(bench, "configs", f"tiny_imagenet_{part}.py"),
        )
    with open(os.path.join(bench, "limits", "tiny_imagenet.fit.json"), "w") as f:
        json.dump({"workload": "tiny_imagenet.fit", "numbers": {
            # between the program's 2e-4 and the smallest control's, both
            # read at this size
            "scores_gap": {"limit": 2e-3},
            # one held-out image of 32
            "test_error_gap": {"limit": 0.04},
        }}, f)
    path = os.path.join(root, "BENCHMARK.json")
    with open(path) as f:
        doc = json.load(f)
    doc["configs"].append({
        "name": "tiny_imagenet", "source": "a test", "reduced": [],
        "file": "benchmark/configs/tiny_imagenet.json", "why": "a test",
    })
    doc["workloads"].append({
        "name": "tiny_imagenet.fit", "config": "tiny_imagenet",
        "traffic": "tiny_fit", "chips": 1, "why": "a test",
    })
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        real = json.load(f)
    mine = {m["name"] for m in real["end_to_end"] + real["per_layer"]
            if "imagenet_fv16.fit" in m.get("workloads", [])}
    for metric in doc["end_to_end"] + doc["per_layer"]:
        if metric["name"] in mine:
            metric["workloads"].append("tiny_imagenet.fit")
    with open(path, "w") as f:
        json.dump(doc, f)
    return root


def test_the_real_cell_is_found_by_name():
    manifest = harness.Manifest(ROOT)
    cell = manifest.cell("imagenet_fv16.fit")
    assert (cell["config"], cell["traffic"], cell["chips"]) == (
        "imagenet_fv16", "fit_loop", 1
    )
    assert len(cell["why"]) <= 200
    assert set(manifest.limits("imagenet_fv16.fit")) == {
        "scores_gap", "test_error_gap"
    }
    names = {m["name"] for m in manifest.metrics_of("imagenet_fv16.fit", "per_layer")}
    assert names == {
        "mfu.fit.imagenet_fv16", "solver_wls_roofline",
        "solver.wls_class_systems_per_fit",
        "workflow.host_gap_share.imagenet_fit", "workflow.idle_ms_per_fit.wls",
    }
    ends = {m["name"] for m in manifest.metrics_of("imagenet_fv16.fit", "end_to_end")}
    assert ends == {"fit_s", "setup_s"}
    for other in ("timit_cos4.fit", "timit_cos4.apply", "cifar_patch10k.fit",
                  "voc_fv256.fit"):
        theirs = {m["name"] for m in manifest.metrics_of(other, "per_layer")}
        assert not names & theirs
    run = harness.Run(
        manifest=manifest, cell=cell, config=manifest.config("imagenet_fv16"),
        traffic=manifest.traffic("fit_loop"), seed=3, seconds=1.0, trace=False,
        device=dict(tiny.DEVICE), peak=tiny.PEAK, phases=harness.Phases(),
    )
    assert run.reference.__file__.endswith("imagenet_fv16_reference.py")
    assert run.program.__file__.endswith("imagenet_fv16_program.py")


def test_the_cell_runs_and_is_correct(imagenet_root, capsys):
    rc, lines = tiny.run_cell(
        imagenet_root, "tiny_imagenet.fit", seed=2**31 + 77, seconds=0.1,
        capsys=capsys,
    )
    assert rc == 0
    result = json.loads(lines[-1])
    assert result["correct"] is True and result["failed"] == 0
    assert set(result["metrics"]) == {"fit_s", "setup_s"}
    assert set(result["compared"]) == {"scores_gap", "test_error_gap"}
    report = json.loads(lines[-2])
    assert 0.0 <= report["compared_all"]["test_error"] < 0.8  # top-1


def _layer_metrics(root, workload, facts):
    manifest = harness.Manifest(root, os.path.join(root, "benchmark"))
    cell = manifest.cell(workload)
    run = harness.Run(
        manifest=manifest, cell=cell, config=manifest.config(cell["config"]),
        traffic=manifest.traffic(cell["traffic"]), seed=3, seconds=1.0,
        trace=True, device=dict(tiny.DEVICE), peak=tiny.PEAK,
        phases=harness.Phases(),
    )
    run.facts.update(facts)
    return harness._read_layer_metrics(manifest, run)


def test_the_new_metrics_are_read_in_their_cell_alone(imagenet_root):
    facts = {"fits": 2, "units": 2, "window_s": 4.0}
    mine = _layer_metrics(imagenet_root, "tiny_imagenet.fit", facts)
    need = imagenet_fit_job.count(_config(**SMALL), {})["flops"]
    assert mine["mfu.fit.imagenet_fv16"]["value"] == (
        100.0 * 2 * need / (4.0 * tiny.PEAK["flops_per_s"])
    )
    for name in ("mfu.fit", "mfu.fit.cifar_patch10k", "mfu.fit.voc_fv256"):
        assert name not in mine
    # handed to a cell of another configuration, they report nothing
    other = _layer_metrics(imagenet_root, "tiny_cos.fit", facts)
    assert "mfu.fit" in other
    for name in ("mfu.fit.imagenet_fv16", "solver_wls_roofline",
                 "solver.wls_class_systems_per_fit"):
        assert name not in other


@pytest.mark.parametrize("fault", [None, "half_rows"])
def test_the_control_is_not_correct(imagenet_root, fault):
    manifest = harness.Manifest(
        imagenet_root, os.path.join(imagenet_root, "benchmark")
    )
    out = control.read(
        manifest, "tiny_imagenet.fit", 5, seconds=0.5,
        device=dict(tiny.DEVICE), peak=tiny.PEAK, fault=fault,
    )
    assert out["correct"] is False
    assert out["compared"]["scores_gap"]["value"] > (
        out["compared"]["scores_gap"]["limit"]
    )


def test_a_program_that_samples_whole_descriptor_sets_fails_at_once(
    monkeypatch, capsys
):
    """The parent of this PR pulled the descriptors of the whole training
    set before it sampled them: at the published widths that is 56 GB. The
    adapter asks the program first."""
    from keystone_tpu.pipelines import imagenet_sift_lcs_fv as pipeline

    prog = _adapter("program")
    prog._require_lazy_sampling()  # this program can
    monkeypatch.delattr(pipeline, "_sample_descriptors")
    with pytest.raises(SystemExit) as e:
        prog.fit(_config(**SMALL), None, None, None, None)
    assert e.value.code == 2
    assert "whole training" in capsys.readouterr().err
