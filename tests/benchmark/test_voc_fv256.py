"""``voc_fv256``: the configuration file against the published widths, its
counts against hand arithmetic, its plain reference against the program part
by part at a size a CPU test holds (descriptors, sampled columns, PCA basis,
codebook, Fisher vectors, the one-pass solve, the average precision), and
its cell through the harness — all added as files, with no file of the
harness edited."""

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
    shapes,
    voc_descriptor_chain,
    voc_fit_job,
    voc_shapes,
)
from benchmark.readers import where_counted
from tests.benchmark import tiny

ROOT = tiny.ROOT
CONFIGS = os.path.join(ROOT, "benchmark", "configs")

#: the sizes of the tests: images of 64 × 48 (406 descriptors), 16
#: dimensions, 8 centres (d = 256: one block of the published 4,096)
SMALL = {
    "image_x": 64, "image_y": 48, "descriptors_per_image": 406,
    "n_train": 32, "n_test": 16, "vocab_size": 8, "desc_dim": 16, "d": 256,
    "num_pca_samples": 6400, "num_gmm_samples": 6400,
    "reference_slice": 4, "reference_rows": 16,
}


def _config(name="voc_fv256", **over):
    with open(os.path.join(CONFIGS, name + ".json")) as f:
        return dict(json.load(f), **over)


def _adapter(part):
    return harness.load_module(os.path.join(CONFIGS, f"voc_fv256_{part}.py"))


# -- the configuration file ------------------------------------------------


def test_the_file_holds_the_published_widths():
    cfg = _config()
    published = {
        "desc_dim": 80, "vocab_size": 256, "lam": 0.5, "step": 3,
        "bin_size": 4, "num_scales": 4, "scale_step": 0,
        "descriptor_width": 128, "descriptors_per_image": 73505,
        "num_pca_samples": 1000000, "num_gmm_samples": 1000000,
        "block_size": 4096, "epochs": 1, "num_classes": 20, "d": 40960,
        "image_x": 500, "image_y": 375, "image_channels": 3,
    }
    for key, value in published.items():
        assert cfg[key] == value == cfg["published"][key], key
    # the cut is images only, both sets by the same factor to multiples
    # of 256, and the file says so
    changed = {k for k, v in cfg["published"].items() if cfg[k] != v}
    assert changed == set(cfg["reduced"]) <= {"n_train", "n_test"}
    assert (cfg["published"]["n_train"], cfg["published"]["n_test"]) == (
        5011, 4952
    )
    if changed:
        assert cfg["n_train"] % 256 == 0 and cfg["n_test"] % 256 == 0
        assert cfg["n_train"] == cfg["n_test"]
    assert cfg["gmm"]["max_iterations"] == 20
    assert cfg["gmm"]["min_cluster_size"] == 1
    ref = _adapter("reference")
    assert ref.expected_d(cfg) == cfg["d"] == 2 * 80 * 256
    assert ref.num_descriptors(cfg) == cfg["descriptors_per_image"]
    assert {k: cfg["precision"][k] for k in ("featurizer", "solver", "apply")} == {
        "featurizer": "high", "solver": "high", "apply": "bf16"
    }
    for key in ("deployment", "assumed", "source"):
        assert cfg[key]
    assert len(cfg["source"]) <= 200


def test_the_program_is_given_every_width(capsys):
    cfg = _config()
    conf = _adapter("program").conf_of(cfg)
    assert (conf.desc_dim, conf.vocab_size, conf.lam, conf.scale_step) == (
        80, 256, 0.5, 0
    )
    assert (conf.num_pca_samples, conf.num_gmm_samples) == (1000000, 1000000)
    assert conf.seed == cfg["sample_seed"]
    assert not (conf.pca_file or conf.gmm_mean_file)  # fitted from the data
    # the grid is the extractor's own: a file that asks for another fails
    with pytest.raises(SystemExit) as e:
        _adapter("program").conf_of(dict(cfg, step=4))
    assert e.value.code == 2 and "the program builds" in capsys.readouterr().err


# -- the counts --------------------------------------------------------------


def test_the_descriptors_of_an_image():
    from keystone_tpu.nodes.images import SIFTExtractor

    cfg = _config()
    assert voc_shapes.scales(cfg) == [
        (4, 3, 162, 120), (6, 3, 159, 118), (8, 3, 157, 115),
        (10, 3, 154, 112),
    ]
    assert voc_shapes.descriptors(cfg) == 73505
    assert SIFTExtractor().num_descriptors(500, 375) == 73505
    small = _config(**SMALL)
    assert voc_shapes.descriptors(small) == 406
    assert _adapter("reference").num_descriptors(small) == 406


def test_one_image_through_the_chain():
    feat = voc_shapes.featurize_image(_config())
    n = 73505
    assert feat["gemm_flops"] == (
        2 * n * 128 * 80 + 4 * 2 * n * 80 * 256
    ) == 13548441600
    # SIFT is a quarter of a GFLOP on the VPU, the posteriors' chain a fifth
    assert voc_shapes.sift_flops(_config()) == pytest.approx(2.57e8, rel=5e-3)
    assert feat["other_flops"] == pytest.approx(4.45e8, rel=5e-3)
    assert feat["bytes"] == 500 * 375 * 3 + 4 * 40960
    # compute binds by the published peaks: 71.0 us an image (68.8 of them
    # the products) against 0.9 us of bytes
    whole = feat["gemm_flops"] + feat["other_flops"]
    assert whole / 197e12 == pytest.approx(71.0e-6, rel=2e-3)
    assert feat["gemm_flops"] / 197e12 == pytest.approx(68.8e-6, rel=2e-3)
    assert feat["bytes"] / 819e9 < 1e-6


def test_the_chain_of_a_job_counts_each_image_once():
    cfg = _config()
    got = voc_descriptor_chain.count(cfg, {})
    feat = voc_shapes.featurize_image(cfg)
    images = cfg["n_train"] + cfg["n_test"]
    assert voc_shapes.images_featurized(cfg) == images
    assert got["flops"] == images * (feat["gemm_flops"] + feat["other_flops"])
    assert got["bytes"] == images * feat["bytes"]


def test_the_solve_is_counted_as_the_scan_solvers():
    cfg = _config()
    mine, theirs = voc_shapes.solve(cfg, 2048), shapes.solve(cfg, 2048)
    assert mine == theirs
    assert mine["gemm_flops"] == 2 * 2048 * 40960 * 4096 + 6 * 2048 * 40960 * 20


def test_the_fit_job_is_mostly_the_chain():
    cfg = _config()
    job = voc_fit_job.count(cfg, {})
    chain = voc_descriptor_chain.count(cfg, {})["flops"]
    book = voc_shapes.codebook(cfg)
    assert 0.88 < chain / job["flops"] < 0.96
    # twenty rounds of EM over 10^6 samples: four 80 x 256 products a round
    samples = (1000000 // cfg["n_train"]) * cfg["n_train"]
    assert book["gemm_flops"] == pytest.approx(
        (20 * 4 + 5) * 2 * samples * 80 * 256 + 2 * samples * 80 * 255
        + 2 * samples * 128 * 128, rel=1e-12,
    )
    solve = voc_shapes.solve(cfg, cfg["n_train"])
    assert (solve["gemm_flops"] + solve["other_flops"]) / job["flops"] < 0.05


@pytest.mark.parametrize("name", ["timit_cos4", "mnist_fft", "cifar_patch10k"])
@pytest.mark.parametrize("ops", [voc_fit_job, voc_descriptor_chain])
def test_a_count_does_not_apply_to_another_configuration(name, ops):
    assert ops.count(_config(name), {}) is None


@pytest.mark.parametrize(
    "ops", [cifar_fit_job, cifar_conv_chain, cifar_block_update]
)
def test_another_configurations_count_does_not_apply_to_this_one(ops):
    assert ops.count(_config(), {}) is None
    assert ops.count(_config(**SMALL), {}) is None


def _run_with(config, facts=None):
    manifest = harness.Manifest(ROOT)
    return types.SimpleNamespace(
        manifest=manifest, config=config, traffic={}, facts=facts or {},
        cell={"chips": 1}, peak=tiny.PEAK, reduction=None,
    )


def test_where_counted_leaves_out_what_is_not_described():
    params = {"reader": "ops_over_time", "ops": "voc_fit_job"}
    facts = {"units": 2, "window_s": 30.0}
    for other in ("timit_cos4", "cifar_patch10k"):
        assert where_counted.read(params, _run_with(_config(other), facts)) is None
    cfg = _config()
    got = where_counted.read(params, _run_with(cfg, facts))
    need = voc_fit_job.count(cfg, {})["flops"]
    assert got == 100.0 * 2 * need / (30.0 * tiny.PEAK["flops_per_s"])
    params = {"reader": "trace_ops_matching", "match": "^jit_fn/",
              "ops": "voc_descriptor_chain"}
    assert where_counted.read(params, _run_with(cfg, facts)) is None


# -- the reference against the program ---------------------------------------


def _masks(y):
    return np.asarray(y).astype(np.int32)


@pytest.fixture(scope="module")
def small():
    """One job of the program and the reference's codebook at the small
    size."""
    from keystone_tpu.workflow.env import PipelineEnv

    cfg = _config(**SMALL)
    ref, prog = _adapter("reference"), _adapter("program")
    ref._STATE.clear()
    train = ref.make_rows(cfg, cfg["train_seed"], cfg["n_train"])
    held = ref.make_rows(cfg, 4242, cfg["n_test"])
    handle = prog.fit(
        cfg, train[0], _masks(train[1]), held[0], _masks(held[1])
    )
    out = types.SimpleNamespace(
        cfg=cfg, ref=ref, prog=prog, train=train, held=held, handle=handle,
        model=prog.model(handle), codebook=ref.learn_codebook(cfg, train[0]),
    )
    ref._STATE[cfg["n_train"]] = out.codebook
    yield out
    ref._STATE.clear()
    PipelineEnv.get_or_create().reset()


def _nodes(fitted, cls):
    graph = fitted.graph
    return [
        graph.get_operator(n) for n in graph.nodes
        if isinstance(graph.get_operator(n), cls)
    ]


def test_the_images_repeat_and_carry_one_to_three_labels(small):
    X, y = small.train
    assert X.shape == (32, 64, 48, 3) and str(X.dtype) == "uint8"
    assert y.shape == (32,) and str(y.dtype) == "int32"
    again = small.ref.make_rows(small.cfg, small.cfg["train_seed"], 32)
    np.testing.assert_array_equal(np.asarray(X), np.asarray(again[0]))
    np.testing.assert_array_equal(np.asarray(y), np.asarray(again[1]))
    # a set is the same however it is cut: image i has its own key
    part = small.ref.make_rows(small.cfg, small.cfg["train_seed"], 5)
    np.testing.assert_array_equal(np.asarray(X)[:5], np.asarray(part[0]))
    other = small.ref.make_rows(small.cfg, 99, 32)
    assert not np.array_equal(np.asarray(X), np.asarray(other[0]))
    sets = [list(s) for s in small.ref.label_sets(y, 20)]
    # the adapter decodes the bitmasks as the reference does
    assert sets == [list(s) for s in small.prog.label_sets(_masks(y), 20)]
    assert {len(s) for s in sets} <= {1, 2, 3}
    Y = np.asarray(small.ref.indicators(y, 20))
    assert Y.shape == (32, 20) and set(np.unique(Y)) == {-1.0, 1.0}
    assert [list(np.flatnonzero(r > 0)) for r in Y] == sets


def test_the_programs_descriptors_are_the_references(small):
    from keystone_tpu.nodes.images import GrayScaler, PixelScaler, SIFTExtractor

    X = small.train[0][:6]
    gray = GrayScaler().trace_batch(PixelScaler().trace_batch(X))
    got = np.asarray(SIFTExtractor().trace_batch(gray))
    want = np.asarray(small.ref.sift(small.cfg, X)).transpose(0, 2, 1)
    assert got.shape == want.shape == (6, 128, 406)
    # whole numbers 0..255 after the floor: equal but for an off-by-one
    # where the two summation orders straddle a whole number
    assert np.abs(got - want).max() <= 1.0
    assert np.mean(got != want) < 1e-3
    assert want.max() <= 255 and 5.0 < want.mean() < 100.0


def test_the_sampled_columns_are_the_references(small):
    from keystone_tpu.data.dataset import Dataset
    from keystone_tpu.nodes.stats import ColumnSampler

    sampler = ColumnSampler(7, seed=3)
    rows = np.arange(4, 14)
    np.testing.assert_array_equal(
        np.asarray(sampler.columns(rows, 406)),
        np.asarray(small.ref.sampled_columns(3, rows, 7, 406)),
    )
    # the program's PCA sample is the reference's, image after image
    cfg = small.cfg
    pca_sample, _ = small.ref.sample_descriptors(cfg, small.train[0])
    D = np.asarray(small.ref.sift(cfg, small.train[0])).transpose(0, 2, 1)
    per = small.ref.per_image(cfg, "num_pca_samples")
    got = ColumnSampler(per, seed=cfg["sample_seed"]).apply_batch(Dataset.of(D))
    got = np.asarray(got.to_array()).transpose(0, 2, 1).reshape(-1, 128)
    assert got.shape == (32 * 200, 128)
    # the same columns of the same descriptors: what differs is a floor's
    # off-by-one where slices of 4 and a batch of 32 sum in another order
    want = np.asarray(pca_sample)
    assert np.abs(got - want).max() <= 1.0 and np.mean(got != want) < 1e-3


def test_the_pca_basis_is_the_references(small):
    from keystone_tpu.nodes.learning import BatchPCATransformer

    (pca,) = _nodes(small.handle.pipeline, BatchPCATransformer)
    got, want = np.asarray(pca.pca_mat), np.asarray(small.codebook["basis"])
    assert got.shape == want.shape == (128, 16)
    # float32 eigh of the float32 covariance here, float64 there; the same
    # sign convention (largest element positive)
    assert np.abs(got - want).max() < 5e-3
    assert np.all(want[np.argmax(np.abs(want), axis=0), np.arange(16)] > 0)
    np.testing.assert_allclose(want.T @ want, np.eye(16), atol=1e-5)


def test_the_codebook_is_the_references(small):
    from keystone_tpu.nodes.images import FisherVector

    (fv,) = _nodes(small.handle.pipeline, FisherVector)
    book = small.codebook
    for mine, theirs in (
        (fv.gmm.means.T, book["means"]), (fv.gmm.variances.T, book["variances"]),
        (fv.gmm.weights, book["weights"]),
    ):
        mine, theirs = np.asarray(mine), np.asarray(theirs)
        assert mine.shape == theirs.shape
        # the same 8 seeds, then the same 20 rounds: what is left is the
        # basis's 1e-3 and float32 summation order
        assert np.linalg.norm(mine - theirs) / np.linalg.norm(theirs) < 5e-3
    assert np.asarray(book["weights"]).sum() == pytest.approx(1.0, abs=1e-5)


def test_the_seeding_draws_the_references_points(small):
    import jax

    from keystone_tpu.nodes.learning.kmeans import _seed_plus_plus

    rng = np.random.default_rng(5)
    X = jax.numpy.asarray(rng.standard_normal((3000, 16)).astype(np.float32))
    key = jax.random.PRNGKey(small.cfg["kmeans_seed"])
    got = np.asarray(_seed_plus_plus(X, key, 8))
    want = np.asarray(small.ref.kmeans_seeds(X, key, 8))
    np.testing.assert_array_equal(got, want)
    assert len({tuple(r) for r in got}) == 8  # eight different points of X


def test_the_fisher_vectors_are_the_references(small):
    import jax

    from keystone_tpu.nodes.learning.linear import BlockLinearMapper
    from keystone_tpu.workflow import analysis
    from keystone_tpu.workflow.graph import NodeId

    apply, params = small.ref.featurizer(small.cfg, "highest")
    want = np.asarray(jax.jit(apply)(params, small.held[0]))
    assert want.shape == (16, 256)
    np.testing.assert_allclose(np.linalg.norm(want, axis=1), 1.0, atol=1e-5)
    graph, x = small.handle.pipeline.graph, small.held[0]
    for n in analysis.linearize(graph):
        if isinstance(n, NodeId) and n in graph.operators:
            op = graph.get_operator(n)
            if isinstance(op, BlockLinearMapper):
                break
            x = op.trace_batch(x)
    got = np.asarray(x)
    assert np.linalg.norm(got - want) / np.linalg.norm(want) < 5e-3
    # the reference's own layout: component after component, the first
    # orders of all of them ahead of the second orders
    book = small.codebook
    D = small.ref.sift(small.cfg, small.held[0][:1])
    one = np.asarray(small.ref.fisher_vectors(small.cfg, book, D, "highest"))
    np.testing.assert_allclose(one, want[:1], atol=2e-4)


def test_a_scan_fit_of_one_epoch_equals_the_references_one_pass():
    import jax.numpy as jnp

    from keystone_tpu.data.dataset import Dataset
    from keystone_tpu.nodes.learning.linear import BlockLeastSquaresEstimator

    rng = np.random.default_rng(3)
    A = rng.standard_normal((96, 256)).astype(np.float32)
    Y = rng.standard_normal((96, 20)).astype(np.float32)
    # the block divides d: the scan solver at num_iter == 1, as 4,096
    # divides 40,960
    model = BlockLeastSquaresEstimator(64, 1, lam=0.5, num_features=256).fit(
        Dataset.of(A), Dataset.of(Y)
    )
    W, means = _adapter("reference").one_pass_block_ridge(
        jnp.asarray(A), jnp.asarray(Y - Y.mean(0)), block_size=64, lam=0.5,
        precision="highest",
    )
    got = np.concatenate([np.asarray(x) for x in model.xs])
    assert got.shape == (256, 20) and len(model.xs) == 4
    assert np.linalg.norm(got - np.asarray(W)) / np.linalg.norm(W) < 1e-4
    np.testing.assert_allclose(
        np.concatenate([np.asarray(m) for m in model.feature_means]),
        np.asarray(means), atol=1e-6,
    )


def test_the_comparison_reads_the_program_as_correct(small):
    from benchmark import compare, refmath

    numbers = compare.fit_numbers(
        small.cfg, small.ref, small.train, small.held, small.model,
        small.handle.test_error, rows_per_block=16,
    )
    # float32 everywhere on the CPU: 1e-4 was read; what is left is the
    # basis (float32 eigh here, float64 there) carried through the codebook
    assert numbers["scores_gap"] < 2e-3
    assert numbers["test_error"] == small.handle.test_error
    # the job's MAP is the reference's: the harness's own error is an
    # argmax mismatch, which a multi-label task does not have, so the cell
    # gives test_error_gap no limit and this test holds the MAP instead
    ref_model = small.ref.fit(
        small.cfg, small.train[0], small.train[1], precision=compare.HIGHEST
    )
    S = refmath.scores(
        small.ref.featurizer(small.cfg, "highest"), small.held[0], ref_model,
        rows_per_block=16, precision="highest",
    )
    ref_map = small.ref.average_precisions(S, small.held[1], 20).mean()
    assert 1.0 - small.handle.test_error == pytest.approx(ref_map, abs=0.02)
    assert 0.1 < ref_map < 0.98  # neither chance (0.1) nor solved


def test_the_references_average_precision_is_the_evaluators(small):
    from keystone_tpu.evaluation.mean_average_precision import (
        MeanAveragePrecisionEvaluator,
    )

    rng = np.random.default_rng(7)
    S = rng.standard_normal((16, 20))
    masks = small.held[1]
    want = MeanAveragePrecisionEvaluator(20).evaluate(
        S, small.ref.label_sets(masks, 20)
    )
    np.testing.assert_allclose(
        small.ref.average_precisions(S, masks, 20), want, atol=1e-12
    )


def test_a_job_span_with_its_phases(small):
    from keystone_tpu.obs import tracer as tracer_mod

    cfg, prog = small.cfg, small.prog
    tracer = tracer_mod.start()
    try:
        prog.fit(cfg, small.train[0], _masks(small.train[1]), small.held[0],
                 _masks(small.held[1]))
    finally:
        tracer_mod.stop()
    spans = tracer.spans()
    job = [sp for sp in spans if sp.name == "job"]
    assert len(job) == 1 and job[0].attrs["pipeline"] == "VOCSIFTFisher"
    by_id = {sp.span_id: sp for sp in spans}

    def under(sp, name):
        while sp.parent_id is not None:
            sp = by_id[sp.parent_id]
            if sp.name == name:
                return True
        return False

    for name in ("plan.build", "voc.sample_descriptors", "pca.fit",
                 "kmeans.seed", "gmm_fv.em_fit", "block_ls.solve", "eval.map"):
        found = [sp for sp in spans if sp.name == name]
        assert found and all(under(sp, "job") for sp in found), name
    for name in ("voc.sample_descriptors", "pca.fit", "kmeans.seed",
                 "gmm_fv.em_fit"):
        assert all(under(sp, "plan.build") for sp in spans if sp.name == name)
    passes = [sp.attrs for sp in spans if sp.name == "voc.sample_descriptors"]
    assert [a["images"] for a in passes] == [32, 32]
    assert [a["columns"] for a in passes] == [6400, 6400]
    assert [a["bytes"] for a in passes] == [6400 * 128 * 4, 6400 * 16 * 4]
    (pca,) = [sp.attrs for sp in spans if sp.name == "pca.fit"]
    assert (pca["samples"], pca["dims"]) == (6400, 16)
    (seed,) = [sp.attrs for sp in spans if sp.name == "kmeans.seed"]
    assert seed["centres"] == 8
    (em,) = [sp.attrs for sp in spans if sp.name == "gmm_fv.em_fit"]
    assert (em["samples"], em["centres"]) == (6400, 8)
    assert 1 <= em["iterations"] <= 20
    # three passes over the training images and one over the held-out ones
    rows = [sp.attrs["rows"] for sp in spans if sp.name == "exec.segment"
            and "SIFTExtractor" in sp.attrs["label"]]
    assert sorted(rows) == [16, 32, 32, 32]
    assert all(sp.attrs["path"] == "compiled" for sp in spans
               if sp.name == "exec.segment")


# -- the cell, through the harness -------------------------------------------


@pytest.fixture(scope="module")
def voc_root(tmp_path_factory):
    """``tiny.build``'s benchmark with a small cell of this configuration
    added to it as files and entries."""
    root = tiny.build(str(tmp_path_factory.mktemp("bench_voc")))
    bench = os.path.join(root, "benchmark")
    cfg = dict(_config(**SMALL), name="tiny_voc")
    with open(os.path.join(bench, "configs", "tiny_voc.json"), "w") as f:
        json.dump(cfg, f)
    for part in ("reference", "program"):
        shutil.copy(
            os.path.join(CONFIGS, f"voc_fv256_{part}.py"),
            os.path.join(bench, "configs", f"tiny_voc_{part}.py"),
        )
    with open(os.path.join(bench, "limits", "tiny_voc.fit.json"), "w") as f:
        json.dump({"workload": "tiny_voc.fit", "numbers": {
            # between the program's 1e-4 and the smallest control's 3e-3
            # (the featurizer at one bf16 pass), both read at this size
            "scores_gap": {"limit": 1e-3},
        }}, f)
    path = os.path.join(root, "BENCHMARK.json")
    with open(path) as f:
        doc = json.load(f)
    doc["configs"].append({
        "name": "tiny_voc", "source": "a test", "reduced": [],
        "file": "benchmark/configs/tiny_voc.json", "why": "a test",
    })
    doc["workloads"].append({
        "name": "tiny_voc.fit", "config": "tiny_voc",
        "traffic": "tiny_fit", "chips": 1, "why": "a test",
    })
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        real = json.load(f)
    mine = {m["name"] for m in real["end_to_end"] + real["per_layer"]
            if "voc_fv256.fit" in m.get("workloads", [])}
    for metric in doc["end_to_end"] + doc["per_layer"]:
        if metric["name"] in mine:
            metric["workloads"].append("tiny_voc.fit")
    with open(path, "w") as f:
        json.dump(doc, f)
    return root


def test_the_real_cell_is_found_by_name():
    manifest = harness.Manifest(ROOT)
    cell = manifest.cell("voc_fv256.fit")
    assert (cell["config"], cell["traffic"], cell["chips"]) == (
        "voc_fv256", "fit_loop", 1
    )
    assert len(cell["why"]) <= 200
    assert set(manifest.limits("voc_fv256.fit")) == {"scores_gap"}
    names = {m["name"] for m in manifest.metrics_of("voc_fv256.fit", "per_layer")}
    assert names == {
        "mfu.fit.voc_fv256", "featurizer_fisher_roofline",
        "featurizer.descriptor_passes_per_fit",
        "workflow.host_gap_share.voc_fit",
        "workflow.idle_ms_per_fit.learn_codebook",
    }
    ends = {m["name"] for m in manifest.metrics_of("voc_fv256.fit", "end_to_end")}
    assert ends == {"fit_s", "setup_s"}
    for other in ("timit_cos4.fit", "timit_cos4.apply", "cifar_patch10k.fit"):
        theirs = {m["name"] for m in manifest.metrics_of(other, "per_layer")}
        assert not names & theirs


def test_the_cell_runs_and_is_correct(voc_root, capsys):
    rc, lines = tiny.run_cell(
        voc_root, "tiny_voc.fit", seed=2**31 + 77, seconds=0.1, capsys=capsys,
    )
    assert rc == 0
    result = json.loads(lines[-1])
    assert result["correct"] is True and result["failed"] == 0
    assert set(result["metrics"]) == {"fit_s", "setup_s"}
    # a multi-label task has no argmax error: scores_gap alone decides
    assert set(result["compared"]) == {"scores_gap"}
    report = json.loads(lines[-2])
    assert 0.0 < report["compared_all"]["test_error"] < 1.0  # 1 − MAP


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


def test_the_rows_through_sift_a_job(monkeypatch):
    from benchmark.readers import span_attr_per_job

    def sp(name, **attrs):
        return types.SimpleNamespace(name=name, attrs=attrs, instant=False)

    spans = [sp("job")] + [
        sp("exec.segment", rows=rows, label=label) for rows, label in (
            (32, "SIFTExtractor+ColumnSampler"),
            (32, "SIFTExtractor+BatchPCATransformer+Cacher+ColumnSampler"),
            (32, "SIFTExtractor+BatchPCATransformer+Cacher+FisherVector+..."),
            (16, "SIFTExtractor+BatchPCATransformer+Cacher+FisherVector+..."),
            (32, "PixelScaler+GrayScaler"),
        )
    ]
    monkeypatch.setattr(span_attr_per_job.span_idle, "program_spans",
                        lambda: spans)
    params = harness.Manifest(ROOT).metric_file(
        "featurizer.descriptor_passes_per_fit"
    )["params"]
    # (3 · 32 + 16) rows through SIFT over the 32 + 16 a job has to
    # featurize; 2.0 where the two sets are of one size
    run = _run_with({"n_train": 32, "n_test": 16})
    assert span_attr_per_job.read(params, run) == pytest.approx(112 / 48)
    spans[4].attrs["rows"] = 32
    assert span_attr_per_job.read(params, _run_with(
        {"n_train": 32, "n_test": 32}
    )) == 2.0
    # a program with no such segment (another pipeline, a parent): nothing
    monkeypatch.setattr(span_attr_per_job.span_idle, "program_spans",
                        lambda: [sp("job"), sp("exec.segment", rows=9,
                                               label="Convolver")])
    assert span_attr_per_job.read(params, run) is None


def test_the_new_metrics_are_read_in_their_cell_alone(voc_root):
    facts = {"fits": 2, "units": 2, "window_s": 4.0}
    mine = _layer_metrics(voc_root, "tiny_voc.fit", facts)
    need = voc_fit_job.count(_config(**SMALL), {})["flops"]
    assert mine["mfu.fit.voc_fv256"]["value"] == (
        100.0 * 2 * need / (4.0 * tiny.PEAK["flops_per_s"])
    )
    assert "mfu.fit" not in mine and "mfu.fit.cifar_patch10k" not in mine
    # handed to a cell of another configuration, they report nothing
    other = _layer_metrics(voc_root, "tiny_cos.fit", facts)
    assert "mfu.fit" in other
    for name in ("mfu.fit.voc_fv256", "featurizer_fisher_roofline",
                 "featurizer.descriptor_passes_per_fit"):
        assert name not in other


@pytest.mark.parametrize("fault", [None, "half_rows"])
def test_the_control_is_not_correct(voc_root, fault):
    manifest = harness.Manifest(voc_root, os.path.join(voc_root, "benchmark"))
    out = control.read(
        manifest, "tiny_voc.fit", 5, seconds=0.5, device=dict(tiny.DEVICE),
        peak=tiny.PEAK, fault=fault,
    )
    assert out["correct"] is False
    assert out["compared"]["scores_gap"]["value"] > (
        out["compared"]["scores_gap"]["limit"]
    )


def test_a_program_that_cannot_decline_a_cache_fails_at_once(monkeypatch, capsys):
    """The parent of this PR kept every cache it was asked for and drew its
    samples from the descriptors of the whole training set: at the published
    widths it exhausts the device. The adapter asks the program first."""
    from keystone_tpu.compile import segment

    prog = _adapter("program")
    prog._require_declined_caches()  # this program can
    monkeypatch.delattr(segment, "unheld_caches")
    with pytest.raises(SystemExit) as e:
        prog.fit(_config(**SMALL), None, None, None, None)
    assert e.value.code == 2
    assert "keeps every cache" in capsys.readouterr().err
