"""The reader of the process's first job (``readers/first_job.py``) on
hand-built spans, the four metrics it serves in ``BENCHMARK.json``, and a
traced run of the tiny benchmark on the CPU that prints them."""

import json
import os
import time
import types

import pytest

from benchmark import harness
from benchmark import trace as trace_mod
from benchmark.readers import first_job
from tests.benchmark import tiny

ROOT = tiny.ROOT
COMPILE = [
    "compile.first_job_trace_s", "compile.first_job_lower_s",
    "compile.first_job_load_s",
]
EXTRA = "workflow.first_job_extra_s"


class _Sp:
    instant = False

    def __init__(self, name, start, end, **counts):
        self.name, self.start, self.end = name, start, end
        self.trace_s = counts.get("trace_s", 0.0)
        self.lower_s = counts.get("lower_s", 0.0)
        self.load_s = counts.get("load_s", 0.0)

    @property
    def seconds(self):
        return self.end - self.start


#: a label upload ahead of the job, then the job: 4 s, of which jax traced
#: 1.5, lowered 0.25 and loaded 0.75
BOOT = [
    _Sp("xfer.h2d", 0.0, 0.1, load_s=0.05),
    _Sp("plan.segments", 1.0, 3.0, trace_s=1.5, lower_s=0.25),
    _Sp("exec.segment", 3.0, 4.5, load_s=0.75),
    _Sp("job", 1.0, 5.0, trace_s=1.5, lower_s=0.25, load_s=0.75),
]
#: the traced window: three warm jobs of 1.0, 1.25 and 3.0 s
WINDOW = [
    _Sp("job", 10.0, 11.0), _Sp("plan.build", 10.0, 10.5),
    _Sp("job", 11.0, 12.25), _Sp("job", 13.0, 16.0),
]


@pytest.fixture
def program(monkeypatch):
    """Stands where the program's boot and session recorders do."""
    from keystone_tpu.obs import tracer

    def put(boot, window):
        monkeypatch.setattr(tracer, "first_job_spans", lambda: boot)
        monkeypatch.setattr(tracer, "session_spans", lambda: window)

    return put


def _read(field):
    return first_job.read({"root": "job", "field": field}, run=None)


@pytest.mark.parametrize("field,want", [
    ("trace_s", 1.5), ("lower_s", 0.25), ("load_s", 0.75),
    ("extra_s", 4.0 - 1.25),  # less the MEDIAN window job
])
def test_each_field_of_the_first_job(program, field, want):
    program(BOOT, WINDOW)
    assert _read(field) == pytest.approx(want)


def test_a_job_that_compiled_nothing_reads_zero_not_none(program):
    program([_Sp("job", 0.0, 2.0)], WINDOW)
    assert [_read(f) for f in first_job.FIELDS] == [0.0, 0.0, 0.0]


@pytest.mark.parametrize("field", list(first_job.FIELDS) + ["extra_s"])
def test_no_first_job_no_number(program, monkeypatch, field):
    program([], WINDOW)  # an installed tracer or a session took it
    assert _read(field) is None
    program([BOOT[0]], WINDOW)  # a lone upload is not the job
    assert _read(field) is None
    # a program from before the boot recorder: the parent commit
    from keystone_tpu.obs import tracer

    monkeypatch.delattr(tracer, "first_job_spans")
    assert _read(field) is None


def test_a_window_with_no_job_has_no_extra(program):
    program(BOOT, [_Sp("pipeline.apply", 10.0, 10.1)])  # an apply cell
    assert _read("extra_s") is None
    assert _read("trace_s") == 1.5
    program(BOOT, [])
    assert _read("extra_s") is None


def test_the_first_span_of_the_name_is_the_job(program):
    program(BOOT + [_Sp("job", 6.0, 7.0, trace_s=9.0)], WINDOW)
    assert _read("trace_s") == 1.5


def test_an_unknown_field_is_an_error(program):
    program(BOOT, WINDOW)
    with pytest.raises(ValueError):
        _read("seconds")


# ---------------------------------------------------------------------------
# the manifest's four entries
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("name", COMPILE + [EXTRA])
def test_the_metric_is_listed_for_the_cells_whose_lists_may_grow(name):
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        doc = json.load(f)
    entry = next(m for m in doc["per_layer"] if m["name"] == name)
    with open(os.path.join(ROOT, "benchmark", "metrics", name + ".json")) as f:
        spec = json.load(f)
    assert spec["reader"] == "first_job" and spec["params"]["root"] == "job"
    assert entry["moves"] == "setup_s" and entry["unit"] == "s"
    # the two cells whose tests leave a list free to grow: the three newer
    # cells' test files hold each to an exact set of per-layer names, and
    # their fixtures append to a ``workloads`` key that the tiny manifest
    # gives no metric that moves ``setup_s`` (PERF.md section 7)
    if name == EXTRA:
        assert entry["workloads"] == ["timit_cos4.fit"]  # a window of jobs
        assert (entry["layer"], entry["source"]) == ("Workflow", "program_span")
    else:
        assert entry["workloads"] == ["timit_cos4.fit", "timit_cos4.apply"]
        assert (entry["layer"], entry["source"]) == (
            "Compile", "program_counter"
        )


# ---------------------------------------------------------------------------
# a traced run of the tiny benchmark on the CPU
# ---------------------------------------------------------------------------


def _traced_run(root, workload, monkeypatch, capsys):
    """``harness.execute`` with ``--trace 1``. The CPU's trace has no device
    plane, so the reduction is stood in for: the first job's readers read
    the program's recorders, not the trace."""
    from keystone_tpu.obs import tracer

    monkeypatch.setattr(trace_mod, "reduce", lambda raw, chips=1: (
        trace_mod.Reduction(
            busy_s=0.0, window_s=1.0, op_seconds={}, idle_seconds={},
            annotations={}, busy=[], chips=1,
        )
    ))
    tracer.reset()  # as a process starts: the boot recorder armed
    manifest = harness.Manifest(root, os.path.join(root, "benchmark"))
    cell = manifest.cell(workload)
    traffic = manifest.traffic(cell["traffic"])
    args = types.SimpleNamespace(
        seed=2147484001, seconds=0.3, trace=1, setup_only=False
    )
    try:
        rc = harness.execute(
            manifest, manifest.driver(traffic["kind"]), cell=cell,
            config=manifest.config(cell["config"]), traffic=traffic,
            args=args, device=dict(tiny.DEVICE), peak=tiny.PEAK,
            phases=harness.Phases(), started=time.perf_counter(),
        )
        boot, window = tracer.first_job_spans(), tracer.session_spans()
    finally:
        tracer.reset()
    lines = [x for x in capsys.readouterr().out.splitlines() if x.strip()]
    return rc, json.loads(lines[-1]), boot, window


def test_a_traced_fit_cell_prints_the_four_metrics(
    tiny_root, monkeypatch, capsys
):
    rc, result, boot, window = _traced_run(
        tiny_root, "tiny_cos.fit", monkeypatch, capsys
    )
    assert rc == 0 and result["correct"]
    metrics = result["metrics"]
    for name in COMPILE + [EXTRA]:
        assert metrics[name]["unit"] == "s", sorted(metrics)
    # set-up's one job, whole, and nothing of the window
    jobs = [sp for sp in boot if sp.name == "job"]
    assert len(jobs) == 1 and boot[-1] is jobs[0]
    job = jobs[0]
    assert {"plan.build", "exec.segment", "block_ls.solve"} <= {
        sp.name for sp in boot
    }
    window_jobs = [sp for sp in window if sp.name == "job"]
    assert window_jobs and all(sp.start >= job.end for sp in window_jobs)
    assert metrics[COMPILE[0]]["value"] == job.trace_s
    assert metrics[COMPILE[1]]["value"] == job.lower_s
    assert metrics[COMPILE[2]]["value"] == job.load_s
    assert job.trace_s + job.lower_s + job.load_s <= job.seconds
    # the metrics the benchmark had read as before
    assert metrics["compile.requests_per_fit"]["value"] == sum(
        sp.compiles for sp in window_jobs
    ) / len(window_jobs)


def test_a_traced_apply_cell_prints_the_three_and_no_extra(
    tiny_root, monkeypatch, capsys
):
    rc, result, boot, _ = _traced_run(
        tiny_root, "tiny_cos.apply", monkeypatch, capsys
    )
    assert rc == 0 and result["correct"]
    for name in COMPILE:
        assert result["metrics"][name]["value"] >= 0.0
    assert EXTRA not in result["metrics"]
    # the fit of set-up is the first job; its warm-up chunk came after it
    assert boot[-1].name == "job"
    assert "pipeline.apply" not in {sp.name for sp in boot}
