"""``cifar_patch10k``: the configuration file against the published widths,
its counts against hand arithmetic, its plain reference against the program
at a size a CPU test holds, and its cell through the harness — all added as
files, with no file of the harness edited."""

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
    cifar_shapes,
)
from benchmark.readers import span_attr_per_job, where_counted
from tests.benchmark import tiny

ROOT = tiny.ROOT
CONFIGS = os.path.join(ROOT, "benchmark", "configs")

#: the sizes of the tests: 48 filters (d = 384), 384 training images, a
#: whitener on 2,000 patches
SMALL = {
    "num_filters": 48, "n_train": 384, "n_test": 128, "whitener_size": 2000,
    "d": 384, "reference_slice": 16, "reference_rows": 128,
}


def _config(name="cifar_patch10k", **over):
    with open(os.path.join(CONFIGS, name + ".json")) as f:
        return dict(json.load(f), **over)


def _adapter(part):
    return harness.load_module(
        os.path.join(CONFIGS, f"cifar_patch10k_{part}.py")
    )


# -- the configuration file ------------------------------------------------


def test_the_file_holds_the_published_widths():
    cfg = _config()
    published = {
        "num_filters": 10000, "lam": 3000.0, "whitening_epsilon": 1e-5,
        "patch_size": 6, "patch_steps": 1, "pool_size": 14, "pool_stride": 13,
        "alpha": 0.25, "whitener_size": 100000, "block_size": 4096,
        "epochs": 1, "num_classes": 10, "image_side": 32,
        "image_channels": 3, "d": 80000, "var_constant": 10.0,
    }
    for key, value in published.items():
        assert cfg[key] == value == cfg["published"][key], key
    # the cut is rows only, and the held-out set is the published one
    assert cfg["reduced"] == ["n_train"]
    changed = {k for k, v in cfg["published"].items() if cfg[k] != v}
    assert changed == {"n_train"}
    assert cfg["published"]["n_train"] == 50000 and cfg["n_train"] == 16384
    assert cfg["n_test"] == 10000
    assert _adapter("reference").expected_d(cfg) == cfg["d"] == 80000
    assert cfg["precision"] == {
        "featurizer": "bf16", "solver": "high", "apply": "bf16"
    }
    for key in ("deployment", "assumed"):
        assert cfg[key]


def test_the_program_is_given_every_width():
    cfg = _config()
    conf = _adapter("program").conf_of(cfg)
    assert (conf.num_filters, conf.lam, conf.whitening_epsilon) == (
        10000, 3000.0, 1e-5
    )
    assert (conf.patch_size, conf.patch_steps, conf.pool_size,
            conf.pool_stride, conf.alpha, conf.whitener_size) == (
        6, 1, 14, 13, 0.25, 100000
    )


# -- the counts --------------------------------------------------------------


def test_one_image_through_the_chain():
    feat = cifar_shapes.featurize_image(_config())
    # 27·27 windows of 108 numbers against 10,000 filters
    assert feat["gemm_flops"] == 2 * 729 * 108 * 10000 == 1574640000
    other = (4 * 729 * 108 + 2 * 729 * 10000 + 4 * 729 * 10000
             + 4 * 14 * 14 * 2 * 10000)
    assert feat["other_flops"] == other
    assert feat["bytes"] == 4 * (32 * 32 * 3 + 80000)


def test_the_chain_of_a_job():
    cfg = _config()
    got = cifar_conv_chain.count(cfg, {})
    images = 2 * 16384 + 10000
    feat = cifar_shapes.featurize_image(cfg)
    assert got["flops"] == images * (feat["gemm_flops"] + feat["other_flops"])
    assert got["flops"] == pytest.approx(6.99e13, rel=2e-3)
    assert got["bytes"] == images * feat["bytes"] + 3 * 4 * 108 * 10000
    # compute binds by the published peaks: 0.355 s a job against 0.017 s
    assert got["flops"] / 197e12 == pytest.approx(0.355, rel=5e-3)
    assert got["bytes"] / 819e9 < 0.02


def test_the_ragged_block_solve():
    cfg = _config()
    assert cifar_shapes.block_widths(cfg) == [4096] * 19 + [2176]
    n, k = 16384, 10
    gram = 2 * n * (19 * 4096**2 + 2176**2)
    kwide = 6 * n * 80000 * k
    got = cifar_block_update.count(cfg, {})
    assert got["flops"] == gram + kwide
    assert got["flops"] == pytest.approx(1.068e13, rel=1e-3)
    assert got["bytes"] == 4 * 4 * n * 80000
    sol = cifar_shapes.solve(cfg, n)
    assert sol["other_flops"] == (
        19 * (4096**3 / 3 + 2 * 4096**2 * k) + 2176**3 / 3 + 2 * 2176**2 * k
    )
    # a d the block divides counts as ops/shapes.py counts it
    from benchmark.ops import shapes

    even = dict(cfg, d=81920)
    mine, theirs = cifar_shapes.solve(even, n), shapes.solve(even, n)
    assert mine == {k: pytest.approx(v, rel=1e-12) for k, v in theirs.items()}


def test_the_fit_job_is_mostly_the_featurizer():
    cfg = _config()
    job = cifar_fit_job.count(cfg, {})
    chain = cifar_conv_chain.count(cfg, {})["flops"]
    solve = cifar_block_update.count(cfg, {})["flops"]
    assert job["flops"] == pytest.approx(8.10e13, rel=5e-3)
    assert 0.85 < chain / job["flops"] < 0.88
    assert 0.12 < solve / job["flops"] < 0.15


@pytest.mark.parametrize("name", ["timit_cos4", "mnist_fft"])
@pytest.mark.parametrize(
    "ops", [cifar_fit_job, cifar_conv_chain, cifar_block_update]
)
def test_a_count_does_not_apply_to_another_configuration(name, ops):
    assert ops.count(_config(name), {}) is None


def _run_with(config, facts=None):
    manifest = harness.Manifest(ROOT)
    return types.SimpleNamespace(
        manifest=manifest, config=config, traffic={}, facts=facts or {},
        cell={"chips": 1}, peak=tiny.PEAK, reduction=None,
    )


def test_where_counted_leaves_out_what_is_not_described():
    params = {"reader": "ops_over_time", "ops": "cifar_fit_job"}
    facts = {"units": 3, "window_s": 20.0}
    assert where_counted.read(params, _run_with(_config("timit_cos4"), facts)) is None
    cfg = _config()
    got = where_counted.read(params, _run_with(cfg, facts))
    need = cifar_fit_job.count(cfg, {})["flops"]
    assert got == 100.0 * 3 * need / (20.0 * tiny.PEAK["flops_per_s"])
    # and a reader of the trace, with no trace reduced, finds nothing
    params = {"reader": "trace_ops_matching", "match": "^jit_fn/",
              "ops": "cifar_conv_chain"}
    assert where_counted.read(params, _run_with(cfg, facts)) is None


def test_rows_convolved_a_job(monkeypatch):
    def sp(name, **attrs):
        return types.SimpleNamespace(name=name, attrs=attrs, instant=False)

    spans = [
        sp("job"), sp("job"),
        sp("exec.segment", rows=384, label="Fused[Convolver » Pooler]"),
        sp("exec.segment", rows=384, label="Fused[Convolver » Pooler]+Max"),
        sp("exec.segment", rows=128, label="Fused[Convolver » Pooler]+Max"),
        sp("exec.segment", rows=384, label="Fused[Convolver » Pooler]"),
        sp("exec.segment", rows=384, label="Fused[Convolver » Pooler]+Max"),
        sp("exec.segment", rows=128, label="Fused[Convolver » Pooler]+Max"),
        sp("exec.segment", rows=999, label="Cosine"),  # another segment
        sp("exec.segment", label="Fused[Convolver]", path="chunked"),
    ]
    monkeypatch.setattr(span_attr_per_job.span_idle, "program_spans",
                        lambda: spans)
    params = {"span": "exec.segment", "attr": "rows", "label_has": "Convolver",
              "root": "job", "per_job": {"n_train": 2, "n_test": 1}}
    run = _run_with({"n_train": 384, "n_test": 128})
    assert span_attr_per_job.read(params, run) == 1.0
    # a fit that convolves the training images once more reads more
    spans.append(sp("exec.segment", rows=768, label="Convolver"))
    assert span_attr_per_job.read(params, run) == pytest.approx(1 + 384 / 896)
    # no such spans (a parent commit), or another configuration: nothing
    monkeypatch.setattr(span_attr_per_job.span_idle, "program_spans",
                        lambda: [sp("job")])
    assert span_attr_per_job.read(params, run) is None
    monkeypatch.setattr(span_attr_per_job.span_idle, "program_spans",
                        lambda: None)
    assert span_attr_per_job.read(params, run) is None
    monkeypatch.setattr(span_attr_per_job.span_idle, "program_spans",
                        lambda: spans)
    assert span_attr_per_job.read(params, _run_with({"n_train": 4})) is None


# -- the reference against the program ---------------------------------------


@pytest.fixture(scope="module")
def small():
    """One fit of the program and the reference's state at the small size."""
    from keystone_tpu.workflow.env import PipelineEnv

    cfg = _config(**SMALL)
    ref, prog = _adapter("reference"), _adapter("program")
    ref._STATE.clear()
    train = ref.make_rows(cfg, cfg["train_seed"], cfg["n_train"])
    held = ref.make_rows(cfg, 4242, cfg["n_test"])
    handle = prog.fit(
        cfg, train[0], np.asarray(train[1]).astype(np.int32),
        held[0], np.asarray(held[1]).astype(np.int32),
    )
    model = prog.model(handle)
    out = types.SimpleNamespace(
        cfg=cfg, ref=ref, prog=prog, train=train, held=held, handle=handle,
        model=model,
    )
    yield out
    ref._STATE.clear()
    PipelineEnv.get_or_create().reset()


def test_the_images_overlap_and_repeat(small):
    X, y = small.train
    assert X.shape == (384, 32, 32, 3) and y.shape == (384,)
    assert 0.0 <= float(X.min()) and float(X.max()) <= 255.0
    again = small.ref.make_rows(small.cfg, small.cfg["train_seed"], 384)
    np.testing.assert_array_equal(np.asarray(X), np.asarray(again[0]))
    other = small.ref.make_rows(small.cfg, 99, 384)
    assert not np.array_equal(np.asarray(X), np.asarray(other[0]))
    # classes overlap: the held-out error is far from 0 and from chance (0.9)
    assert 0.05 < small.handle.test_error < 0.75


def test_the_program_learns_the_references_filters(small):
    from keystone_tpu.data.dataset import Dataset
    from keystone_tpu.pipelines.random_patch_cifar import (
        learn_filters,
        sample_patches,
    )

    conf = small.prog.conf_of(small.cfg)
    # the same 2,000 patches: the draw made first, only those windows cut
    got = np.asarray(sample_patches(small.train[0], conf))
    want = small.ref.sample_patches(small.cfg, small.train[0])
    np.testing.assert_array_equal(got, want.astype(np.float32))
    filters, _ = learn_filters(Dataset.of(small.train[0]), conf)
    ref = small.ref.learn_filters(small.cfg, small.train[0])["filters"]
    assert filters.shape == ref.shape == (48, 108)
    # A normalised patch has no mean, so the whitener's smallest direction
    # (the constant patch, eigenvalue 0, scaled by eps^-1/2 = 316) carries
    # only rounding — float32 SVD here, float64 eigh there — and no patch
    # ever meets it: the banks are compared with that direction taken out.
    centre = lambda F: F - F.mean(axis=1, keepdims=True)  # noqa: E731
    gap = np.linalg.norm(centre(np.asarray(filters)) - centre(ref))
    assert gap / np.linalg.norm(centre(ref)) < 2e-3


def test_the_scaled_features_agree(small):
    import jax

    from keystone_tpu.data.dataset import Dataset

    apply, params = small.ref.featurizer(small.cfg, "highest")
    want = np.asarray(jax.jit(apply)(params, small.held[0]))
    assert want.shape == (128, 384)
    # through what both predict: the program's labels against its own model
    # on the REFERENCE's features (float32 on both sides on the CPU: they
    # agree but for a near-tie)
    fitted = small.prog.fitted(small.handle)
    labels = np.asarray(fitted.apply(Dataset.of(small.held[0])).to_array())
    scores = (want - small.model["mean"]) @ small.model["W"] + small.model["b"]
    assert np.mean(labels != scores.argmax(axis=1)) <= 1 / 128
    # column standardisation: unit deviation on the training images
    train = np.asarray(jax.jit(apply)(params, small.train[0]))
    np.testing.assert_allclose(train.mean(axis=0), 0.0, atol=1e-4)
    np.testing.assert_allclose(train.std(axis=0, ddof=1), 1.0, atol=1e-3)


def test_the_comparison_reads_the_program_as_correct(small):
    from benchmark import compare

    numbers = compare.fit_numbers(
        small.cfg, small.ref, small.train, small.held, small.model,
        small.handle.test_error, rows_per_block=64,
    )
    # float32 everywhere on the CPU, a float64 factorisation in the
    # reference and a float32 one in the program: 1e-6 was read, and 1e-4
    # leaves room for another BLAS
    assert numbers["scores_gap"] < 1e-4
    assert numbers["test_error_gap"] <= 1 / 128
    assert numbers["test_error"] == small.handle.test_error


def test_a_ragged_fit_equals_the_references_one_pass():
    import jax.numpy as jnp

    from keystone_tpu.data.dataset import Dataset
    from keystone_tpu.nodes.learning.linear import BlockLeastSquaresEstimator

    rng = np.random.default_rng(3)
    A = rng.standard_normal((256, 44)).astype(np.float32)
    Y = rng.standard_normal((256, 3)).astype(np.float32)
    model = BlockLeastSquaresEstimator(16, 1, lam=30.0).fit(
        Dataset.of(A), Dataset.of(Y)
    )
    W, means = _adapter("reference").one_pass_block_ridge(
        jnp.asarray(A), jnp.asarray(Y - Y.mean(0)), block_size=16, lam=30.0,
        precision="highest",
    )
    got = np.concatenate([np.asarray(x) for x in model.xs])
    assert got.shape == (44, 3) and [x.shape[0] for x in model.xs] == [16, 16, 12]
    np.testing.assert_allclose(got, np.asarray(W), atol=2e-6)
    np.testing.assert_allclose(
        np.concatenate([np.asarray(m) for m in model.feature_means]),
        np.asarray(means), atol=1e-6,
    )
    # one pass is not the optimum
    Ac = A - A.mean(0)
    best = np.linalg.solve(Ac.T @ Ac + 30.0 * np.eye(44), Ac.T @ (Y - Y.mean(0)))
    assert np.abs(got - best).max() > 1e-3


def test_a_job_span_with_its_phases(small):
    from keystone_tpu.obs import tracer as tracer_mod

    cfg, prog = small.cfg, small.prog
    tracer = tracer_mod.start()
    try:
        prog.fit(
            cfg, small.train[0], np.asarray(small.train[1]).astype(np.int32),
            small.held[0], np.asarray(small.held[1]).astype(np.int32),
        )
    finally:
        tracer_mod.stop()
    spans = tracer.spans()
    job = [sp for sp in spans if sp.name == "job"]
    assert len(job) == 1 and job[0].attrs["pipeline"] == "RandomPatchCifar"
    by_id = {sp.span_id: sp for sp in spans}

    def under(sp, name):
        while sp.parent_id is not None:
            sp = by_id[sp.parent_id]
            if sp.name == name:
                return True
        return False

    for name in ("plan.build", "cifar.sample_patches", "zca.fit",
                 "cifar.choose_filters", "block_ls.solve", "eval.metrics"):
        found = [sp for sp in spans if sp.name == name]
        assert found and all(under(sp, "job") for sp in found), name
    for name in ("cifar.sample_patches", "zca.fit", "cifar.choose_filters"):
        assert all(under(sp, "plan.build") for sp in spans if sp.name == name)
    # every image convolved once per use: 2 n_train + n_test rows
    rows = [sp.attrs["rows"] for sp in spans if sp.name == "exec.segment"
            and "Convolver" in sp.attrs["label"]]
    assert sorted(rows) == [128, 384, 384]
    solve = [sp for sp in spans if sp.name == "block_ls.solve"][0]
    assert solve.attrs == {"blocks": 1, "ragged_cols": 384}
    assert not [sp for sp in spans if sp.name == "block_ls.stream_solve"]


# -- the cell, through the harness -------------------------------------------


@pytest.fixture(scope="module")
def cifar_root(tmp_path_factory):
    """``tiny.build``'s benchmark with a small cell of this configuration
    added to it as files and entries."""
    root = tiny.build(str(tmp_path_factory.mktemp("bench_cifar")))
    bench = os.path.join(root, "benchmark")
    cfg = dict(_config(**SMALL), name="tiny_cifar")
    with open(os.path.join(bench, "configs", "tiny_cifar.json"), "w") as f:
        json.dump(cfg, f)
    for part in ("reference", "program"):
        shutil.copy(
            os.path.join(CONFIGS, f"cifar_patch10k_{part}.py"),
            os.path.join(bench, "configs", f"tiny_cifar_{part}.py"),
        )
    with open(os.path.join(bench, "limits", "tiny_cifar.fit.json"), "w") as f:
        json.dump({"workload": "tiny_cifar.fit", "numbers": {
            "scores_gap": {"limit": 1e-3}, "test_error_gap": {"limit": 0.02},
        }}, f)
    path = os.path.join(root, "BENCHMARK.json")
    with open(path) as f:
        doc = json.load(f)
    doc["configs"].append({
        "name": "tiny_cifar", "source": "a test", "reduced": [],
        "file": "benchmark/configs/tiny_cifar.json", "why": "a test",
    })
    doc["workloads"].append({
        "name": "tiny_cifar.fit", "config": "tiny_cifar",
        "traffic": "tiny_fit", "chips": 1, "why": "a test",
    })
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        real = json.load(f)
    mine = {m["name"] for m in real["end_to_end"] + real["per_layer"]
            if "cifar_patch10k.fit" in m.get("workloads", [])}
    for metric in doc["end_to_end"] + doc["per_layer"]:
        if metric["name"] in mine:
            metric["workloads"].append("tiny_cifar.fit")
    with open(path, "w") as f:
        json.dump(doc, f)
    return root


def test_the_cell_runs_and_is_correct(cifar_root, capsys):
    rc, lines = tiny.run_cell(
        cifar_root, "tiny_cifar.fit", seed=2**31 + 77, seconds=0.1,
        capsys=capsys,
    )
    assert rc == 0
    result = json.loads(lines[-1])
    assert result["correct"] is True and result["failed"] == 0
    assert set(result["metrics"]) == {"fit_s", "setup_s"}
    assert set(result["compared"]) == {"scores_gap", "test_error_gap"}


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


def test_the_new_metrics_are_read_in_their_cell_alone(cifar_root):
    facts = {"fits": 2, "units": 2, "window_s": 4.0}
    mine = _layer_metrics(cifar_root, "tiny_cifar.fit", facts)
    need = cifar_fit_job.count(_config(**SMALL), {})["flops"]
    assert mine["mfu.fit.cifar_patch10k"]["value"] == (
        100.0 * 2 * need / (4.0 * tiny.PEAK["flops_per_s"])
    )
    # handed to a cell of another configuration, they report nothing
    other = _layer_metrics(cifar_root, "tiny_cos.fit", facts)
    assert "mfu.fit" in other
    for name in ("mfu.fit.cifar_patch10k", "featurizer_conv_roofline",
                 "solver_block_update_roofline",
                 "featurizer.conv_passes_per_fit"):
        assert name not in other


@pytest.mark.parametrize("fault", [None, "half_rows"])
def test_the_control_is_not_correct(cifar_root, fault):
    manifest = harness.Manifest(
        cifar_root, os.path.join(cifar_root, "benchmark")
    )
    out = control.read(
        manifest, "tiny_cifar.fit", 5, seconds=0.5, device=dict(tiny.DEVICE),
        peak=tiny.PEAK, fault=fault,
    )
    assert out["correct"] is False
    assert out["compared"]["scores_gap"]["value"] > (
        out["compared"]["scores_gap"]["limit"]
    )


def test_a_program_without_row_slices_fails_at_once(monkeypatch, capsys):
    """The parent of PR 29 neither ran this cell nor failed: on the chip it
    exhausted the device, fell back to node dispatch and had not ended
    after 600 s. The adapter asks the program first."""
    from keystone_tpu.compile.segment import SegmentBinding

    prog = _adapter("program")
    prog._require_row_slices()  # this program has them
    monkeypatch.delattr(SegmentBinding, "row_plan")
    with pytest.raises(SystemExit) as e:
        prog.fit(_config(**SMALL), None, None, None, None)
    assert e.value.code == 2
    assert "no row slices" in capsys.readouterr().err
