"""A configuration, a traffic mix and a per-layer metric are each added as
new files plus entries, with no code of the harness edited: ``tiny.build``
writes a benchmark of its own that way, and this adds a metric to it."""

import json
import os

from benchmark import harness
from tests.benchmark import tiny


def _run(manifest, workload):
    cell = manifest.cell(workload)
    return harness.Run(
        manifest=manifest, cell=cell, config=manifest.config(cell["config"]),
        traffic=manifest.traffic(cell["traffic"]), seed=3, seconds=1.0,
        trace=True, device=dict(tiny.DEVICE), peak=tiny.PEAK,
        phases=harness.Phases(),
    )


def test_the_added_files_are_found_by_name(tiny_root):
    manifest = harness.Manifest(tiny_root, os.path.join(tiny_root, "benchmark"))
    assert manifest.config("tiny_cos")["d"] == 128
    assert manifest.traffic("tiny_apply")["kind"] == "apply_loop"
    assert manifest.limits("tiny_cos.apply")["passes_differ"] == 0
    run = _run(manifest, "tiny_cos.fit")
    assert run.reference.__file__.endswith("tiny_cos_reference.py")
    assert run.program.__file__.endswith("tiny_cos_program.py")


def test_a_metric_added_as_a_file_is_read(tiny_root):
    bench = os.path.join(tiny_root, "benchmark")
    entry = {
        "name": "fits.done", "unit": "fits", "better": "higher",
        "source": "host_clock", "layer": "Whole fit step", "moves": "fit_s",
        "workloads": ["tiny_cos.fit"],
    }
    with open(os.path.join(bench, "metrics", "fits.done.json"), "w") as f:
        json.dump(dict(entry, reader="driver_fact", params={"key": "fits"}), f)
    path = os.path.join(tiny_root, "BENCHMARK.json")
    with open(path) as f:
        doc = json.load(f)
    doc["per_layer"].append(entry)
    with open(path, "w") as f:
        json.dump(doc, f)

    manifest = harness.Manifest(tiny_root, bench)
    run = _run(manifest, "tiny_cos.fit")
    run.facts.update(fits=4, units=4, window_s=2.0)
    run.counters.update({
        "setup.compile_requests": 10, "setup.persistent_cache_hits": 9,
    })
    got = harness._read_layer_metrics(manifest, run)
    assert got["fits.done"] == {"value": 4.0, "unit": "fits"}
    assert got["compile.cache_hit_share_setup"]["value"] == 90.0
    # 4 fits of the tiny job's operations over 2 s of a 1 TFLOP/s "peak"
    need = manifest.ops("fit_job").count(run.config, run.traffic)["flops"]
    assert got["mfu.fit"]["value"] == 100.0 * 4 * need / (2.0 * 1e12)
    # no trace was reduced: the readers of the trace find nothing to read
    # and return nothing, never a 0
    assert "workflow.host_gap_share.fit" not in got
    assert "solver_gemm_roofline" not in got
    # another cell does not report the metric
    other = _run(manifest, "tiny_fft.fit")
    other.facts.update(fits=1, units=1, window_s=1.0)
    assert "fits.done" not in harness._read_layer_metrics(manifest, other)
