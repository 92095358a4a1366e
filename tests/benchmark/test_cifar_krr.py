"""``cifar_krr``: the configuration file against the published widths, its
counts against hand arithmetic, its plain reference against the program at
a size a CPU test holds, the spans a job leaves, and its cell through the
harness — all added as files, with no file of the harness edited."""

import json
import os
import shutil
import sys
import types

import numpy as np
import pytest

from benchmark import compare, control, harness
from benchmark.ops import krr_fit_job, krr_shapes, krr_solve
from benchmark.readers import span_attr_per_job, where_counted
from tests.benchmark import tiny

ROOT = tiny.ROOT
CONFIGS = os.path.join(ROOT, "benchmark", "configs")

#: the sizes of the tests: 8 filters (d = 64), 640 training images in ten
#: blocks of 64 and 128 held out — the published job's ten blocks and its
#: held-out share — with gamma scaled to d as the published 2e-4 is to 800
#: (gamma · 2d = 0.32: off-diagonal kernel entries near 0.73 in both)
SMALL = {
    "num_filters": 8, "d": 64, "n_train": 640, "n_test": 128,
    "block_size": 64, "gamma": 2.5e-3, "whitener_size": 2000,
    "reference_slice": 16, "reference_rows": 128,
    "kernel_pass_entries": 640 * 640,
}


def _config(name="cifar_krr", **over):
    with open(os.path.join(CONFIGS, name + ".json")) as f:
        return dict(json.load(f), **over)


def _adapter(part):
    return harness.load_module(os.path.join(CONFIGS, f"cifar_krr_{part}.py"))


#: the per-layer metrics this configuration brings, read in its cell alone
OWN = {
    "solver_krr_roofline", "solver.krr_kernel_passes_per_fit",
    "workflow.idle_ms_per_fit.krr", "mfu.fit.cifar_krr",
}
#: the accepted metrics whose readers find this job's spans and trace too
SHARED = {
    "workflow.host_gap_share.fit", "workflow.idle_unattributed_share.fit",
    "workflow.idle_ms_per_fit.learn_filters", "featurizer.conv_passes_per_fit",
}


# -- the configuration file ------------------------------------------------


def test_the_file_holds_the_published_widths():
    cfg = _config()
    published = {
        "num_filters": 100, "whitening_epsilon": 0.1, "patch_size": 6,
        "patch_steps": 1, "pool_size": 14, "pool_stride": 13, "alpha": 0.25,
        "whitener_size": 100000, "var_constant": 10.0, "gamma": 2e-4,
        "block_size": 5000, "epochs": 1, "cache_kernel": True,
        "num_classes": 10, "image_side": 32, "image_channels": 3, "d": 800,
        "n_train": 50000, "n_test": 10000,
    }
    for key, value in published.items():
        assert cfg[key] == value == cfg["published"][key], key
    # nothing cut; every published key stands as published
    assert cfg["reduced"] == []
    assert {k for k, v in cfg["published"].items() if cfg[k] != v} == set()
    assert cfg["published"]["lam"] == 0.0
    assert _adapter("reference").expected_d(cfg) == cfg["d"] == 800
    assert cfg["kernel_pass_entries"] == 50000**2
    parts = {k: v for k, v in cfg["precision"].items() if k != "why"}
    assert parts == {"featurizer": "high", "kernel": "high",
                     "solver": "highest", "apply": "highest"}
    assert cfg["precision"]["why"]
    # the control lowers each stated part by one step
    assert {k: compare.below(compare.stated(cfg))[k] for k in parts} == {
        "featurizer": "bf16", "kernel": "bf16", "solver": "high",
        "apply": "high",
    }
    for key in ("deployment", "assumed"):
        assert cfg[key]


def test_the_program_is_given_every_width():
    cfg = _config()
    conf = _adapter("program").conf_of(cfg)
    assert (conf.num_filters, conf.whitening_epsilon, conf.patch_size,
            conf.patch_steps, conf.pool_size, conf.pool_stride, conf.alpha,
            conf.whitener_size) == (100, 0.1, 6, 1, 14, 13, 0.25, 100000)
    assert (conf.gamma, conf.lam, conf.block_size, conf.num_epochs,
            conf.cache_kernel, conf.seed) == (2e-4, 0.0, 5000, 1, True, 0)


def test_the_program_states_the_configurations_precisions():
    """What the configuration says the program computes at is what the
    program's modules state."""
    from keystone_tpu.nodes.learning import kernel
    from keystone_tpu.pipelines import random_patch_cifar_kernel

    stated = _config()["precision"]
    assert stated["featurizer"] == random_patch_cifar_kernel.FEATURIZER_PRECISION
    assert stated["kernel"] == kernel.KERNEL_PRECISION
    assert stated["solver"] == kernel.SOLVE_PRECISION
    assert stated["apply"] == kernel.APPLY_PRECISION


# -- the counts --------------------------------------------------------------


def test_the_solve_counted_by_hand():
    cfg = _config()
    n, d, k, bs = 50000, 800, 10, 5000
    sol = krr_shapes.solve(cfg)
    assert krr_shapes.block_widths(cfg) == [bs] * 10
    assert sol["gemm_flops"] == 2 * n * n * d + 2 * n * n * k
    assert 2 * n * n * d == 4.0e12
    assert sol["other_flops"] == n * n + 10 * (bs**3 / 3 + 2 * bs * bs * k)
    # the kernel written once and read once, the features read once
    assert sol["bytes"] == 4 * (2 * n * n + n * d)
    got = krr_solve.count(cfg, {})
    assert got["flops"] == pytest.approx(4.4742e12, rel=1e-4)
    # bandwidth binds by the published peaks: 24.6 ms against 22.7
    assert got["bytes"] / 819e9 == pytest.approx(0.0246, rel=5e-3)
    assert got["flops"] / 197e12 == pytest.approx(0.0227, rel=5e-3)


def test_the_job_counted_by_hand():
    cfg = _config()
    from benchmark.ops import cifar_shapes

    feat = cifar_shapes.featurize_image(cfg)
    per_image = feat["gemm_flops"] + feat["other_flops"]
    assert feat["gemm_flops"] == 2 * 729 * 108 * 100
    job = krr_fit_job.count(cfg, {})
    solve = krr_solve.count(cfg, {})["flops"]
    n, nt = 50000, 10000
    assert job["flops"] == pytest.approx(
        60000 * per_image + 2 * n * 800 + solve + nt * n * 1601
        + 2 * 60000 * n * 10, rel=1e-12,
    )
    assert job["flops"] == pytest.approx(6.34e12, rel=5e-3)
    # the kernel ridge solve is most of it, the featurizer a sixth
    assert 0.70 < solve / job["flops"] < 0.72
    assert 0.15 < 60000 * per_image / job["flops"] < 0.17


@pytest.mark.parametrize(
    "name", ["timit_cos4", "mnist_fft", "cifar_patch10k", "imagenet_fv16"]
)
@pytest.mark.parametrize("ops", [krr_solve, krr_fit_job])
def test_a_count_does_not_apply_to_another_configuration(name, ops):
    assert ops.count(_config(name), {}) is None


def _run_with(config, facts=None):
    return types.SimpleNamespace(
        manifest=harness.Manifest(ROOT), config=config, traffic={},
        facts=facts or {}, cell={"chips": 1}, peak=tiny.PEAK, reduction=None,
    )


def test_where_counted_leaves_out_what_is_not_described():
    params = {"reader": "ops_over_time", "ops": "krr_fit_job"}
    facts = {"units": 12, "window_s": 20.0}
    assert where_counted.read(params, _run_with(_config("cifar_patch10k"), facts)) is None
    cfg = _config()
    got = where_counted.read(params, _run_with(cfg, facts))
    need = krr_fit_job.count(cfg, {})["flops"]
    assert got == 100.0 * 12 * need / (20.0 * tiny.PEAK["flops_per_s"])
    params = {"reader": "trace_ops_matching", "ops": "krr_solve",
              "match": "^jit__kernel_block_slice/"}
    assert where_counted.read(params, _run_with(cfg, facts)) is None


# -- the reference against the program ---------------------------------------


@pytest.fixture(scope="module")
def small():
    """One fit job of the program under an installed tracer, and the
    reference's state, at the small size."""
    from keystone_tpu.obs import tracer as tracer_mod
    from keystone_tpu.workflow.env import PipelineEnv

    cfg = _config(**SMALL)
    ref, prog = _adapter("reference"), _adapter("program")
    ref._STATE.clear()
    train = ref.make_rows(cfg, cfg["train_seed"], cfg["n_train"])
    held = ref.make_rows(cfg, 4242, cfg["n_test"])
    tracer = tracer_mod.start()
    try:
        handle = prog.fit(
            cfg, train[0], np.asarray(train[1]).astype(np.int32),
            held[0], np.asarray(held[1]).astype(np.int32),
        )
    finally:
        tracer_mod.stop()
    out = types.SimpleNamespace(
        cfg=cfg, ref=ref, prog=prog, train=train, held=held, handle=handle,
        model=prog.model(handle), spans=tracer.spans(),
    )
    yield out
    ref._STATE.clear()
    PipelineEnv.get_or_create().reset()


def _mapper(small):
    from keystone_tpu.nodes.learning.kernel import KernelBlockLinearMapper

    graph = small.prog.fitted(small.handle).graph
    return next(
        graph.get_operator(n) for n in graph.nodes
        if isinstance(graph.get_operator(n), KernelBlockLinearMapper)
    )


def test_the_model_is_dual(small):
    m = small.model
    assert m["W"].shape == (640, 10)
    assert m["mean"].shape == (640,) and not m["mean"].any()
    assert m["b"].shape == (10,) and not m["b"].any()
    assert np.isfinite(m["W"]).all()


def test_the_program_solves_as_the_reference_does(small):
    """The same coefficients, row for row, and the same held-out scores:
    float32 on both sides but the reference's float64 factor; read 2.3e-4
    and 4.6e-5 (CPU), held to 10 times that."""
    ref_model = small.ref.fit(
        small.cfg, *small.train, precision=compare.HIGHEST
    )
    W, W_ref = small.model["W"], np.asarray(ref_model["W"])
    assert np.linalg.norm(W - W_ref) / np.linalg.norm(W_ref) < 2.5e-3
    numbers = compare.fit_numbers(
        small.cfg, small.ref, small.train, small.held, small.model,
        small.handle.test_error, rows_per_block=64,
    )
    assert numbers["scores_gap"] < 5e-4
    assert numbers["test_error_gap"] <= 1 / 128
    assert numbers["test_error"] == small.handle.test_error


def test_kernel_rows_times_the_coefficients_are_the_programs_scores(small):
    """The reference's kernel rows of the training images times the
    program's coefficients give the scores the program's own mapper gives
    its own features of those images."""
    import jax

    apply, params = small.ref.featurizer(small.cfg, "highest")
    rows = np.asarray(jax.jit(apply)(params, small.train[0]))
    assert rows.shape == (640, 640)
    np.testing.assert_allclose(np.diag(rows), 1.0, atol=1e-5)
    mapper = _mapper(small)
    mine = np.asarray(mapper.trace_batch(mapper.train_X))
    theirs = rows @ small.model["W"]
    assert np.linalg.norm(mine - theirs) / np.linalg.norm(theirs) < 1e-3


def test_the_blocks_are_solved_in_the_references_order(small):
    steps = [sp.attrs["block"] for sp in small.spans
             if sp.name == "krr.block_step"]
    assert steps == small.ref.block_order(small.cfg, 640)
    assert sorted(steps) == list(range(10)) and steps != list(range(10))


def test_a_job_span_with_its_phases(small):
    spans = small.spans
    job = [sp for sp in spans if sp.name == "job"]
    assert len(job) == 1
    assert job[0].attrs["pipeline"] == "RandomPatchCifarKernel"
    by_id = {sp.span_id: sp for sp in spans}

    def under(sp, name):
        while sp.parent_id is not None:
            sp = by_id[sp.parent_id]
            if sp.name == name:
                return True
        return False

    for name in ("plan.build", "cifar.sample_patches", "zca.fit", "krr.fit",
                 "krr.block_step", "krr.kernel", "krr.apply",
                 "eval.metrics"):
        found = [sp for sp in spans if sp.name == name]
        assert found and all(under(sp, "job") for sp in found), name
    (fit,) = [sp for sp in spans if sp.name == "krr.fit"]
    assert fit.attrs == {
        "blocks": 10, "epochs": 1, "block_size": 64,
        # the ten blocks of 640 x 64 float32 the cache held
        "cached_bytes": 10 * 640 * 64 * 4,
    }
    steps = [sp for sp in spans if sp.name == "krr.block_step"]
    assert len(steps) == 10 and all(under(sp, "krr.fit") for sp in steps)
    assert {(sp.attrs["rows"], sp.attrs["cols"]) for sp in steps} == {(640, 64)}
    kernels = [sp for sp in spans if sp.name == "krr.kernel"]
    in_fit = [sp for sp in kernels if under(sp, "krr.fit")]
    assert sum(sp.attrs["entries"] for sp in in_fit) == 640**2
    # the apply: the training images, then the held-out ones, each on a
    # span with the entries it made and one kernel span a block
    applies = [sp for sp in spans if sp.name == "krr.apply"]
    assert [sp.attrs["entries"] for sp in applies] == [640 * 640, 128 * 640]
    for sp in applies:
        mine = [k for k in kernels if k.parent_id == sp.span_id]
        assert len(mine) == 10
        assert sum(k.attrs["entries"] for k in mine) == sp.attrs["entries"]


def test_the_passes_metric_reads_two_point_two(small, monkeypatch):
    monkeypatch.setattr(
        span_attr_per_job.span_idle, "program_spans", lambda: small.spans
    )
    spec = harness.load_json(os.path.join(
        ROOT, "benchmark", "metrics", "solver.krr_kernel_passes_per_fit.json"
    ))
    got = span_attr_per_job.read(spec["params"], _run_with(small.cfg))
    assert got == pytest.approx(2.2, rel=1e-12)
    # a configuration without the key, or no spans: nothing to read
    assert span_attr_per_job.read(
        spec["params"], _run_with(_config("cifar_patch10k"))
    ) is None
    monkeypatch.setattr(
        span_attr_per_job.span_idle, "program_spans", lambda: None
    )
    assert span_attr_per_job.read(spec["params"], _run_with(small.cfg)) is None


def test_the_convolution_passes_metric_reads_one(small, monkeypatch):
    """Every image through the Convolver's segments once per use: the
    training images for the fit and for the training error, the held-out
    ones once (2 n_train + n_test rows)."""
    monkeypatch.setattr(
        span_attr_per_job.span_idle, "program_spans", lambda: small.spans
    )
    spec = harness.load_json(os.path.join(
        ROOT, "benchmark", "metrics", "featurizer.conv_passes_per_fit.json"
    ))
    got = span_attr_per_job.read(spec["params"], _run_with(small.cfg))
    assert got == pytest.approx(1.0, rel=1e-12)


def test_the_float32_factor_stays_finite_at_the_configurations_lambda():
    """A 5,000-row block of the published kernel — 100 filters, d = 800,
    gamma 2e-4 — on the reference's features, factored and solved by the
    program's float32 Cholesky at the published lambda = 0: finite, and the
    system solved to float32's measure of its conditioning (the block's
    eigenvalues run from 1.4e-2 to 3.6e3; read 2.8e-4)."""
    import jax.numpy as jnp

    from keystone_tpu.linalg.row_matrix import solve_spd

    cfg = _config(n_train=5000, reference_rows=1000)
    ref = _adapter("reference")
    X, y = ref.make_rows(cfg, cfg["train_seed"], 5000)
    _, F = ref._PATCH._learn(cfg, X, "highest")
    assert F.shape == (5000, 800)
    K = ref.kernel(F, F, cfg["gamma"], "highest")
    Y = np.asarray(2.0 * (np.arange(10) == np.asarray(y)[:, None]) - 1.0,
                   np.float32)
    W = np.asarray(solve_spd(K, jnp.asarray(Y), cfg["lam"]))
    assert np.isfinite(W).all()
    Kh = np.asarray(K, np.float64)
    residual = np.linalg.norm(Kh @ W - Y) / np.linalg.norm(Y)
    assert residual < 5e-3
    exact = np.linalg.solve(Kh, Y)
    assert np.linalg.norm(W - exact) / np.linalg.norm(exact) < 0.05


# -- the cell, through the harness -------------------------------------------


@pytest.fixture(scope="module")
def krr_root(tmp_path_factory):
    """``tiny.build``'s benchmark with a small cell of this configuration
    added to it as files and entries."""
    root = tiny.build(str(tmp_path_factory.mktemp("bench_krr")))
    bench = os.path.join(root, "benchmark")
    cfg = dict(_config(**SMALL), name="tiny_krr")
    with open(os.path.join(bench, "configs", "tiny_krr.json"), "w") as f:
        json.dump(cfg, f)
    for part in ("reference", "program"):
        shutil.copy(
            os.path.join(CONFIGS, f"cifar_krr_{part}.py"),
            os.path.join(bench, "configs", f"tiny_krr_{part}.py"),
        )
    with open(os.path.join(bench, "limits", "tiny_krr.fit.json"), "w") as f:
        json.dump({"workload": "tiny_krr.fit", "numbers": {
            "scores_gap": {"limit": 1e-3}, "test_error_gap": {"limit": 0.02},
        }}, f)
    path = os.path.join(root, "BENCHMARK.json")
    with open(path) as f:
        doc = json.load(f)
    doc["configs"].append({
        "name": "tiny_krr", "source": "a test", "reduced": [],
        "file": "benchmark/configs/tiny_krr.json", "why": "a test",
    })
    doc["workloads"].append({
        "name": "tiny_krr.fit", "config": "tiny_krr", "traffic": "tiny_fit",
        "chips": 1, "why": "a test",
    })
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        real = json.load(f)
    mine = {m["name"] for m in real["end_to_end"] + real["per_layer"]
            if "cifar_krr.fit" in m.get("workloads", [])}
    for metric in doc["end_to_end"] + doc["per_layer"]:
        # a metric with no list is read in every cell already
        if metric["name"] in mine and "workloads" in metric:
            metric["workloads"].append("tiny_krr.fit")
    with open(path, "w") as f:
        json.dump(doc, f)
    return root


def test_the_cell_runs_and_is_correct(krr_root, capsys):
    rc, lines = tiny.run_cell(
        krr_root, "tiny_krr.fit", seed=2**31 + 91, seconds=0.1,
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


def test_the_new_metrics_are_read_in_their_cell_alone(krr_root):
    facts = {"fits": 2, "units": 2, "window_s": 4.0}
    mine = _layer_metrics(krr_root, "tiny_krr.fit", facts)
    need = krr_fit_job.count(_config(**SMALL), {})["flops"]
    assert mine["mfu.fit.cifar_krr"]["value"] == (
        100.0 * 2 * need / (4.0 * tiny.PEAK["flops_per_s"])
    )
    other = _layer_metrics(krr_root, "tiny_cos.fit", facts)
    assert "mfu.fit" in other
    for name in OWN:
        assert name not in other


@pytest.mark.parametrize("fault", [None, "half_rows"])
def test_the_control_is_not_correct(krr_root, fault):
    manifest = harness.Manifest(krr_root, os.path.join(krr_root, "benchmark"))
    out = control.read(
        manifest, "tiny_krr.fit", 5, seconds=0.5, device=dict(tiny.DEVICE),
        peak=tiny.PEAK, fault=fault,
    )
    assert out["correct"] is False
    assert out["compared"]["scores_gap"]["value"] > (
        out["compared"]["scores_gap"]["limit"]
    )


def test_the_real_cell_is_found_by_name():
    manifest = harness.Manifest(ROOT)
    cell = manifest.cell("cifar_krr.fit")
    assert (cell["config"], cell["traffic"], cell["chips"]) == (
        "cifar_krr", "fit_loop", 1
    )
    assert len(cell["why"]) <= 200
    assert set(manifest.limits("cifar_krr.fit")) == {
        "scores_gap", "test_error_gap"
    }
    names = {m["name"] for m in manifest.metrics_of("cifar_krr.fit", "per_layer")}
    # its own metrics and the generic ones that read this job as they read
    # the cells they came with; a later cell or metric may add to the set
    assert OWN | SHARED <= names
    ends = {m["name"] for m in manifest.metrics_of("cifar_krr.fit", "end_to_end")}
    assert ends == {"fit_s", "setup_s"}


def test_a_program_without_the_kernel_run_fails_at_once(monkeypatch, capsys):
    """The parent of this configuration has no
    ``pipelines.random_patch_cifar_kernel``: the adapter asks the program
    first and exits 2 before any fit."""
    import keystone_tpu.pipelines as pipelines

    prog = _adapter("program")
    prog._require_kernel_run()  # this program has it
    monkeypatch.delattr(pipelines, "random_patch_cifar_kernel")
    monkeypatch.setitem(
        sys.modules, "keystone_tpu.pipelines.random_patch_cifar_kernel", None
    )
    with pytest.raises(SystemExit) as e:
        prog.fit(_config(**SMALL), None, None, None, None)
    assert e.value.code == 2
    assert "random_patch_cifar_kernel" in capsys.readouterr().err


def test_the_first_job_of_a_process_settles_the_chip(monkeypatch):
    """On the TPU a process's first job runs ``SETTLE_JOBS`` whole jobs
    ahead of its own, each later job runs once, and off the TPU none is
    added."""
    prog = _adapter("program")
    assert prog.settle_jobs() == 0  # the CPU
    calls = []
    monkeypatch.setattr(prog, "_job", lambda *a: calls.append(a) or "handle")
    monkeypatch.setattr(prog, "settle_jobs", lambda: 3)
    monkeypatch.setattr(prog, "_settled", False)
    assert prog.fit({}, 1, 2, 3, 4) == "handle"
    assert len(calls) == 4
    assert all(c == ({}, 1, 2, 3, 4) for c in calls)
    prog.fit({}, 1, 2, 3, 4)
    assert len(calls) == 5
