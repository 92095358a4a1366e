"""chip_smoke.py: the script refuses anything but a TPU, and its legs —
plain functions with size arguments — pass at tiny sizes on the CPU mesh
(the rehearsal for the run on the chip)."""

import os
import subprocess
import sys

import numpy as np
import pytest

import chip_smoke
from keystone_tpu import compile as cmod
from keystone_tpu.nodes.images.sift import SIFTExtractor
from keystone_tpu.obs import tracer as tracer_mod
from keystone_tpu.utils import timing

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


@pytest.fixture(autouse=True)
def _clean_observers():
    timing.reset()
    yield
    tracer_mod.reset()
    timing.reset()
    cmod.reset()


def test_script_refuses_the_cpu_and_names_it():
    proc = subprocess.run(
        [sys.executable, os.path.join(ROOT, "chip_smoke.py")],
        env={**os.environ, "JAX_PLATFORMS": "cpu"},
        capture_output=True, text=True, timeout=120,
    )
    assert proc.returncode != 0
    assert "found cpu" in proc.stderr
    assert proc.stdout.strip() == ""  # no result line


def test_script_alone_fails_without_a_result(tmp_path):
    """In a directory that holds the script and nothing else of the repo
    there is no program to drive: non-zero, no result line."""
    import shutil

    shutil.copy(os.path.join(ROOT, "chip_smoke.py"), tmp_path)
    env = {k: v for k, v in os.environ.items() if k != "PYTHONPATH"}
    proc = subprocess.run(
        [sys.executable, "chip_smoke.py"], cwd=tmp_path, env=env,
        capture_output=True, text=True, timeout=120,
    )
    assert proc.returncode != 0
    assert "not here" in proc.stderr
    assert proc.stdout.strip() == ""


def test_verdict_line_has_the_drivers_keys_and_no_others():
    import json

    line = chip_smoke.verdict_line(
        np.bool_(True),
        {"platform": "tpu", "kind": "TPU v5 lite", "count": np.int64(1)},
    )
    assert "\n" not in line
    assert json.loads(line) == {
        "ok": True,
        "device": {"platform": "tpu", "kind": "TPU v5 lite", "count": 1},
    }


def test_fit_then_serve_legs_at_tiny_size(tmp_path):
    cmod.configure(str(tmp_path))
    report, fitted, rows = chip_smoke.fit_leg(
        num_ffts=2, block_size=256, lam=100.0, n_train=512, n_test=128,
        band=(0.0, 0.9), backend="cpu",
    )
    assert report["ok"], report
    assert report["features"] == 1024
    served = chip_smoke.serve_leg(
        fitted, rows, buckets=(4, 16), n_requests=160
    )
    assert served["ok"], served
    assert served["agree"] == 160
    assert served["compiles"] == 2 and not served["compiled_under_traffic"]
    assert chip_smoke.fallbacks_fired() == {}


def test_kernel_leg_interpreted_and_sync_check():
    report = chip_smoke.kernel_leg(n=700, d=128, b=256, interpret=True)
    assert report["ok"], report
    sync = chip_smoke.sync_check(size=64, steps=3)
    assert sync["block_until_ready_seconds"] > 0


def test_conv_chain_leg_interpreted():
    """The leg's control flow at a tiny size. Interpreted on the CPU the
    bodies' product is float32 and the kernel's operands bf16, so the gap
    is one bf16 pass's, far from the compiled leg's 1e-5."""
    report = chip_smoke.conv_chain_leg(filters=100, images=5, interpret=True)
    assert report["ok"] and report["finite"], report
    assert report["shape"] == {"filters": 100, "images": 5, "side": 32}
    assert 0 < report["max_rel_gap_xla"] < 2e-2
    assert "front_door_chose_kernel" not in report


def test_fisher_leg_at_a_tiny_size():
    """The leg's control flow on the CPU, where every product is float32:
    the program's descriptors, basis, codebook and features are the
    reference's well inside the gaps the chip is held to."""
    report = chip_smoke.fisher_leg(
        images=6, x=64, y=48, dims=16, centres=8, per_image=300
    )
    assert report["ok"] and report["finite"], report
    assert report["shape"]["descriptors"] == 406
    assert report["descriptor_max_gap"] <= 1.0
    # the sampled body draws the sampler's columns of the same descriptors
    assert report["sampled_max_gap"] <= 1.0 and report["sampled_share"] < 1e-3
    # both counts are dense at this size; at the chip's, 2,000 and 8,000 of
    # 73,505 columns lie on either side of the rule
    assert report["sampled_paths"] == ["grid"]
    sift = SIFTExtractor()
    assert sift.sampled_path(500, 375, 2000) == "bins"
    assert sift.sampled_path(500, 375, 8000) == "grid"
    assert report["basis"] < 5e-3 and report["features"] < 5e-3, report
    assert chip_smoke.FISHER_SHAPE == dict(
        images=8, x=500, y=375, dims=80, centres=256
    )


def test_weighted_leg_at_a_tiny_size():
    """The leg's control flow on the CPU: the primal path (n + 3 >= d), the
    program's float32 solve against the reference's float64 class systems
    by held-out scores, and LCS against the reference's."""
    report = chip_smoke.weighted_leg(
        rows=96, dims=64, classes=4, held_out=32, images=3, size=64
    )
    assert report["ok"] and report["finite"], report
    assert report["paths"] == ["primal"]
    assert report["scores"] < 1e-3 and report["labels_agree"] == 1.0, report
    assert report["shape"]["lcs_descriptors"] == 64
    assert report["lcs"] < 1e-2 < report["lcs_scale"]
    assert chip_smoke.WEIGHTED_SHAPE["dims"] == 4096
    assert chip_smoke.WEIGHTED_SHAPE["rows"] >= 4096 + 256


def test_a_forced_segment_demotion_is_seen():
    """A segment whose compiled program raises at run time is served node
    by node with a warning (tests/compile/test_segment.py pins that the
    answers stay exact) — the smoke's fallback read must SEE it, both as
    the degrade counter and as the segment span's path."""
    from keystone_tpu.check import lattice
    from keystone_tpu.check.segments import plan_segments
    from keystone_tpu.compile.segment import bind_segment
    from keystone_tpu.data.dataset import Dataset
    from keystone_tpu.workflow.executor import GraphExecutor
    from keystone_tpu.workflow.expressions import DatasetExpression
    from keystone_tpu.workflow.pipeline import FittedPipeline, attach_data
    from keystone_tpu.workflow.transformer import Transformer

    class Mul(Transformer):
        def __init__(self, k):
            self.k = k

        def trace_batch(self, X):
            return X * self.k

    X = np.arange(40, dtype=np.float32).reshape(10, 4)
    pipe = Mul(2.0).and_then(Mul(3.0))
    fitted = FittedPipeline(pipe.graph, pipe.source, pipe.sink)
    g, data_id = attach_data(fitted.graph, Dataset.of(X))
    g = g.replace_dependency(pipe.source, data_id).remove_source(pipe.source)
    verdicts = {n: lattice.classify(g.get_operator(n)) for n in g.nodes}
    (seg,) = [s for s in plan_segments(g, verdicts, {})[0] if len(s.nodes) == 2]
    binding = bind_segment(g, seg)

    def boom(*xs):
        raise RuntimeError("synthetic run-time failure")

    binding.fn = boom
    binding.digest = "e" * 64  # a fresh dispatcher, not a cached good one

    tracer_mod.start()
    assert chip_smoke.fallbacks_fired() == {}
    (out,) = GraphExecutor._segment_bundle(
        binding, [DatasetExpression.now(Dataset.of(X))]
    ).get()
    np.testing.assert_allclose(np.asarray(out.to_array()), X * 6.0)
    assert chip_smoke.fallbacks_fired() == {
        "degrade.segment_demoted": 1, "exec.segment.fallback": 1,
    }
