"""A benchmark of its own in a temporary directory, at sizes a CPU test
holds: a copy of ``benchmark/`` plus a configuration, cells and limits
that exist only as the files written here — no code of the harness is
edited to take them, which is what a later PR is held to."""

from __future__ import annotations

import json
import os
import shutil
import types

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

#: peaks for a device that is no chip: the tests check arithmetic, not speed
PEAK = {"flops_per_s": 1e12, "hbm_bytes_per_s": 1e11}
DEVICE = {"platform": "cpu", "kind": "cpu", "count": 1}

TINY_FFT = {
    "name": "tiny_fft", "num_ffts": 2, "block_size": 1024, "lam": 10.0,
    "epochs": 1, "image_size": 784, "fft_size": 1024, "num_classes": 10,
    "n_train": 2048, "n_test": 512, "d": 1024, "reference_rows": 512,
    "task_seed": 7, "train_seed": 11, "feature_seed": 0, "reduced": [],
    # classes that overlap, so that near-ties exist for a control to flip
    "assumed": {"latent_dim": 8, "proto_radius": 5.0, "latent_sigma": 2.5,
                "ambient_sigma": 0.05},
    "precision": {"featurizer": "float32, no matrix product",
                  "solver": "high", "apply": "bf16"},
}
TINY_COS = {
    "name": "tiny_cos", "num_cosines": 2, "cosine_features": 64,
    "block_size": 64, "input_dim": 24, "num_classes": 5, "epochs": 3,
    "gamma": 0.2, "lam": 1.0, "n_train": 1024, "n_test": 256, "d": 128,
    "reference_rows": 256, "task_seed": 7, "train_seed": 11,
    "feature_seed": 123, "reduced": [],
    "assumed": {"class_scale": 1.0, "noise_sigma": 1.5},
    "precision": {"featurizer": "bf16", "solver": "high", "apply": "bf16"},
}
TRAFFIC = {
    "tiny_fit": {"kind": "fit_loop", "trace_seconds": 1.0},
    "tiny_apply": {"kind": "apply_loop", "score_rows": 1024,
                   "chunk_rows": 256, "fit_rows": 1024, "fit_test_rows": 64,
                   "reference_rows": 512, "trace_seconds": 1.0},
    "tiny_serve": {"kind": "serve_open_loop", "arrivals": "poisson",
                   "rate_per_s": 2000.0, "replicas": 1, "buckets": [4, 16],
                   "warmup_requests": 4, "timeout_s": 30.0,
                   "trace_seconds": 1.0},
}
CELLS = [
    ("tiny_fft.fit", "tiny_fft", "tiny_fit"),
    ("tiny_cos.fit", "tiny_cos", "tiny_fit"),
    ("tiny_cos.apply", "tiny_cos", "tiny_apply"),
    ("tiny_fft.serve", "tiny_fft", "tiny_serve"),
]
LIMITS = {
    "tiny_fft.fit": {"scores_gap": 1e-3, "test_error_gap": 0.02},
    "tiny_cos.fit": {"scores_gap": 1e-3, "test_error_gap": 0.02},
    "tiny_cos.apply": {"label_gap_max": 0.05, "passes_differ": 0},
    "tiny_fft.serve": {"label_gap_max": 0.05, "unanswered": 0},
}


#: the tiny cells of a ``kind`` report the metric; ``setup_s`` every cell
END_TO_END = [
    {"name": "fit_s", "unit": "s", "kind": ".fit"},
    {"name": "apply_rows_per_s", "unit": "rows/s", "kind": ".apply"},
    {"name": "serve_p50_ms", "unit": "ms", "kind": ".serve"},
    {"name": "serve_p95_ms", "unit": "ms", "kind": ".serve"},
    {"name": "setup_s", "unit": "s"},
]


def _dump(path: str, doc) -> None:
    with open(path, "w") as f:
        json.dump(doc, f)


def build(tmp: str) -> str:
    """Write the tiny benchmark under ``tmp`` and return its root."""
    bench = os.path.join(tmp, "benchmark")
    shutil.copytree(
        os.path.join(ROOT, "benchmark"), bench,
        ignore=shutil.ignore_patterns("__pycache__"),
    )
    real = json.load(open(os.path.join(ROOT, "BENCHMARK.json")))
    for config, like in ((TINY_FFT, "mnist_fft"), (TINY_COS, "timit_cos4")):
        _dump(os.path.join(bench, "configs", config["name"] + ".json"), config)
        for part in ("reference", "program"):
            shutil.copy(
                os.path.join(bench, "configs", f"{like}_{part}.py"),
                os.path.join(bench, "configs", f"{config['name']}_{part}.py"),
            )
    for name, doc in TRAFFIC.items():
        _dump(os.path.join(bench, "traffic", name + ".json"), doc)
    for name, numbers in LIMITS.items():
        _dump(os.path.join(bench, "limits", name + ".json"), {
            "workload": name,
            "numbers": {k: {"limit": v} for k, v in numbers.items()},
        })
    def cells_of(moves: str):
        """The tiny cells that report the end-to-end metric ``moves``
        (None: every cell does)."""
        for metric in END_TO_END:
            if metric["name"] == moves and "kind" in metric:
                return [c[0] for c in CELLS if c[0].endswith(metric["kind"])]
        return None

    # every metric file of the benchmark, whether or not a shipped cell
    # reports it yet, for the tiny cells that report the metric it moves
    per_layer = []
    for name in sorted(os.listdir(os.path.join(bench, "metrics"))):
        with open(os.path.join(bench, "metrics", name)) as f:
            spec = json.load(f)
        entry = {k: spec[k] for k in ("name", "unit", "better", "source",
                                      "layer", "moves")}
        if cells_of(spec["moves"]) is not None:
            entry["workloads"] = cells_of(spec["moves"])
        per_layer.append(entry)
    end_to_end = []
    for metric in END_TO_END:
        entry = {"name": metric["name"], "unit": metric["unit"]}
        if "kind" in metric:
            entry["workloads"] = cells_of(metric["name"])
        end_to_end.append(entry)
    _dump(os.path.join(tmp, "BENCHMARK.json"), {
        "command": real["command"], "paths": real["paths"],
        "run_seconds": 1,
        "configs": [
            {"name": c["name"], "source": "a test", "reduced": [],
             "file": f"benchmark/configs/{c['name']}.json", "why": "a test"}
            for c in (TINY_FFT, TINY_COS)
        ],
        "workloads": [
            {"name": n, "config": c, "traffic": t, "chips": 1, "why": "a test"}
            for n, c, t in CELLS
        ],
        "end_to_end": end_to_end, "per_layer": per_layer,
    })
    return tmp


def run_cell(root: str, workload: str, *, seed: int = 7, seconds: float = 0.3,
             capsys=None):
    """Drive one run of ``workload`` through ``harness.execute`` — all of
    the command but its look for a chip — and return ``(rc, lines)`` of
    standard output."""
    import time

    from benchmark import harness

    manifest = harness.Manifest(root, os.path.join(root, "benchmark"))
    cell = manifest.cell(workload)
    traffic = manifest.traffic(cell["traffic"])
    args = types.SimpleNamespace(
        seed=seed, seconds=seconds, trace=0, setup_only=False
    )
    rc = harness.execute(
        manifest, manifest.driver(traffic["kind"]), cell=cell,
        config=manifest.config(cell["config"]), traffic=traffic, args=args,
        device=dict(DEVICE), peak=PEAK, phases=harness.Phases(),
        started=time.perf_counter(),
    )
    out = capsys.readouterr().out if capsys is not None else ""
    return rc, [line for line in out.splitlines() if line.strip()]
