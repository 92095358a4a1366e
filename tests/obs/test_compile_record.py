"""What the tracer keeps of ``jax.monitoring``: union seconds a kind, the
table by program, the counts every span carries — and the boot recorder,
which keeps a process's first job whoever else records or not."""

import itertools
import logging
import threading

import pytest
from jax import monitoring

from keystone_tpu.obs import tracer as trace_mod
from keystone_tpu.obs.export import (
    compile_seconds_by_span,
    format_first_job,
    to_chrome_trace,
)

TRACE = "/jax/core/compile/jaxpr_trace_duration"
LOWER = "/jax/core/compile/jaxpr_to_mlir_module_duration"
LOAD = "/jax/core/compile/backend_compile_duration"


#: the process's record keeps every interval it was told of: each test
#: fires its events in a stretch of the clock no other test (or run) used
_STRETCH = itertools.count(1)
_base = 0.0


@pytest.fixture(autouse=True)
def armed():
    """Each test starts as a process does: nobody installed, no session,
    the boot recorder armed."""
    global _base
    _base = 1e4 * next(_STRETCH)
    trace_mod.reset()
    yield
    trace_mod.reset()


def _fire(event, start, end, fun="f"):
    """What ``dispatch.log_elapsed_time`` does when a timed region ends."""
    monitoring.record_event_duration_secs(event, end - start, fun_name=fun)
    monitoring.record_event_time_span(
        event, _base + start, _base + end, fun_name=fun
    )


# ---------------------------------------------------------------------------
# the record
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("intervals,want", [
    ([(0.0, 1.0), (2.0, 3.0)], 2.0),                    # apart
    ([(1.0, 2.0), (0.0, 3.0)], 3.0),                    # inner, then outer
    ([(1.0, 2.0), (3.0, 4.0), (0.0, 5.0)], 5.0),        # outer swallows two
    ([(0.0, 2.0), (1.0, 3.0)], 3.0),                    # overlapping ends
    ([(0.0, 1.0), (0.0, 1.0)], 1.0),                    # the same twice
    ([(0.0, 1.0), (5.0, 6.0), (2.0, 3.0)], 3.0),        # a late arrival
    ([(0.0, 1.0), (5.0, 6.0), (0.5, 5.5)], 6.0),        # ... that bridges
    ([(1.0, 1.0), (2.0, 1.5)], 0.0),                    # empty, reversed
])
def test_seconds_are_the_union_of_the_intervals(intervals, want):
    covered, total = [], 0.0
    for start, end in intervals:
        added = trace_mod._cover(covered, start, end)
        assert added >= 0.0
        total += added
    assert total == pytest.approx(want)
    assert covered == sorted(covered)
    assert all(a[1] <= b[0] for a, b in zip(covered, covered[1:]))
    assert sum(e - s for s, e in covered) == pytest.approx(want)


def test_two_overlapping_traces_count_once_and_kinds_stay_apart():
    rec = trace_mod.CompileRecord()
    rec.on_time_span(TRACE, 11.0, 12.0, fun_name="inner")
    rec.on_time_span(TRACE, 10.0, 14.0, fun_name="outer")
    rec.on_time_span(LOWER, 14.0, 14.5, fun_name="jit(outer)")
    rec.on_time_span(LOAD, 14.5, 16.5, fun_name="jit(outer)")
    rec.on_time_span("/jax/some/other_duration", 0.0, 99.0, fun_name="x")
    assert rec.seconds == pytest.approx(
        {"trace": 4.0, "lower": 0.5, "load": 2.0}  # trace: not 5
    )
    assert rec.requests == {"trace": 2, "lower": 1, "load": 1}


def test_the_table_by_program_and_what_was_added_since():
    rec = trace_mod.CompileRecord()
    rec.on_time_span(TRACE, 0.0, 1.0, fun_name="fn")
    rec.on_time_span(LOAD, 1.0, 3.0, fun_name="jit(fn)")
    before = rec.programs()
    # tracing names the function, lowering and loading its module: one row
    assert before == {
        "fn": {"trace": (1, 1.0), "lower": (0, 0.0), "load": (1, 2.0)},
    }
    rec.on_time_span(TRACE, 5.0, 5.5, fun_name="fn")
    rec.on_time_span(LOWER, 6.0, 6.25, fun_name="jit(_bcd_scan)")
    assert rec.programs()["fn"]["trace"] == (2, 1.5)  # a program's own sum
    assert rec.programs(since=before) == {
        "fn": {"trace": (1, 0.5), "lower": (0, 0.0), "load": (0, 0.0)},
        "_bcd_scan": {"trace": (0, 0.0), "lower": (1, 0.25), "load": (0, 0.0)},
    }
    assert rec.programs(since=rec.programs()) == {}


def test_the_cache_s_hits_and_read_seconds_stand_beside_load():
    rec = trace_mod.CompileRecord()
    rec.on_event("/jax/compilation_cache/cache_hits")
    rec.on_event("/jax/compilation_cache/cache_misses")
    rec.on_duration("/jax/compilation_cache/cache_retrieval_time_sec", 0.25)
    rec.on_duration("/jax/compilation_cache/compile_time_saved_sec", 9.0)
    rec.on_duration(LOAD, 1.0)  # seconds come from the time spans alone
    assert (rec.cache_hits, rec.cache_read_s) == (1, 0.25)
    assert rec.seconds["load"] == 0.0


def test_the_process_record_listens_from_import_on():
    """No ``Tracer`` was ever constructed by this test: the listeners are
    in place all the same."""
    rec = trace_mod.compile_record()
    before = (rec.requests["load"], rec.seconds["load"], rec.cache_hits)
    _fire(LOAD, 100.0, 100.5)
    monitoring.record_event("/jax/compilation_cache/cache_hits")
    assert rec.requests["load"] == before[0] + 1
    assert rec.seconds["load"] == pytest.approx(before[1] + 0.5)
    assert rec.cache_hits == before[2] + 1
    assert not hasattr(trace_mod, "_install_compile_listener")


# ---------------------------------------------------------------------------
# every span carries the change between its entry and its exit
# ---------------------------------------------------------------------------


def test_span_counts_nest_a_childs_inside_its_parents():
    t = trace_mod.install(trace_mod.Tracer(sync=False))
    with trace_mod.span("job"):
        with trace_mod.span("plan.segments"):
            _fire(TRACE, 201.0, 202.0, "inner")
            _fire(TRACE, 200.0, 203.0, "fn")
            _fire(LOWER, 203.0, 203.5, "jit(fn)")
        with trace_mod.span("exec.segment"):
            _fire(LOAD, 204.0, 206.0, "jit(fn)")
            monitoring.record_event("/jax/compilation_cache/cache_hits")
        with trace_mod.span("eval.metrics"):
            pass
    by_name = {sp.name: sp for sp in t.spans()}
    plan, ex = by_name["plan.segments"], by_name["exec.segment"]
    job, ev = by_name["job"], by_name["eval.metrics"]
    assert (plan.trace_s, plan.lower_s, plan.load_s) == pytest.approx(
        (3.0, 0.5, 0.0)
    )
    assert (ex.trace_s, ex.load_s, ex.compiles, ex.cache_hits) == (
        0.0, pytest.approx(2.0), 1, 1
    )
    assert (job.trace_s, job.lower_s, job.load_s) == pytest.approx(
        (3.0, 0.5, 2.0)
    )
    assert (job.compiles, job.cache_hits, plan.compiles) == (1, 1, 0)
    assert (ev.trace_s, ev.lower_s, ev.load_s, ev.compiles) == (0, 0, 0, 0)
    # ... and a span's own seconds are its counts less its children's
    assert compile_seconds_by_span(t.spans()) == pytest.approx({
        "job": 0.0, "plan.segments": 3.5, "exec.segment": 2.0,
        "eval.metrics": 0.0,
    })


def test_the_exported_args_and_the_summary_rows_hold_the_counts():
    t = trace_mod.install(trace_mod.Tracer(sync=False))
    with trace_mod.span("warm"):
        pass
    with trace_mod.span("cold"):
        _fire(TRACE, 300.0, 300.25)
        _fire(LOAD, 301.0, 301.5)
        monitoring.record_event("/jax/compilation_cache/cache_hits")
    args = {
        e["name"]: e["args"] for e in to_chrome_trace(t)["traceEvents"]
        if e["ph"] == "X"
    }
    assert args["cold"] == {
        "compiles": 1, "cache_hits": 1, "trace_s": 0.25, "load_s": 0.5,
    }
    assert args["warm"] == {}  # zeros are left out, as ``compiles`` is
    row = t.span_summary()["cold"]
    assert (row["trace_s"], row["lower_s"], row["load_s"]) == (0.25, 0.0, 0.5)
    assert row["compiles"] == 1 and row["compile_cache_hits"] == 1
    assert row["cache_hits"] == 0  # the memo's: not the persistent cache's


def test_a_real_jit_is_seen_once_and_a_second_call_not_at_all():
    import jax
    import jax.numpy as jnp

    def fresh_for_this_test(x):
        return jnp.tanh(x) * 3.0 + 1.0

    fn = jax.jit(fresh_for_this_test)
    x = jnp.ones((8,), jnp.float32)
    t = trace_mod.install(trace_mod.Tracer(sync=False))
    with trace_mod.span("first"):
        fn(x).block_until_ready()
    with trace_mod.span("second"):
        fn(x).block_until_ready()
    first, second = t.spans()
    assert first.compiles == 1
    assert first.trace_s > 0 and first.lower_s > 0 and first.load_s > 0
    assert first.trace_s + first.lower_s + first.load_s <= first.seconds
    assert (second.compiles, second.cache_hits) == (0, 0)
    assert (second.trace_s, second.lower_s, second.load_s) == (0.0, 0.0, 0.0)
    row = trace_mod.compile_record().programs()["fresh_for_this_test"]
    assert [n for n, _ in row.values()] == [1, 1, 1]


# ---------------------------------------------------------------------------
# the boot recorder: a process's first job
# ---------------------------------------------------------------------------


def _job(children=("plan.build", "exec.segment")):
    with trace_mod.span("job", pipeline="Tiny") as job:
        for name in children:
            with trace_mod.span(name):
                pass
    return job


def test_the_first_job_is_kept_with_all_its_children_and_nothing_after():
    assert trace_mod.first_job_spans() == []
    with trace_mod.span("xfer.h2d", bytes=64) as lone:
        pass
    assert lone is not trace_mod.NULL_SPAN
    # a childless parentless span is kept and ends nothing
    assert [sp.name for sp in trace_mod.first_job_spans()] == ["xfer.h2d"]
    job = _job()
    assert trace_mod.current() is None  # not "the tracer" of anyone
    spans = trace_mod.first_job_spans()
    assert [sp.name for sp in spans] == [
        "xfer.h2d", "plan.build", "exec.segment", "job",
    ]
    assert spans[-1] is job and job.attrs == {"pipeline": "Tiny"}
    assert all(sp.parent_id == job.span_id for sp in spans[1:3])
    # the flag is down for the life of the process
    with trace_mod.span("job") as second:
        with trace_mod.span("plan.build") as child:
            pass
    assert second is trace_mod.NULL_SPAN and child is trace_mod.NULL_SPAN
    assert trace_mod.first_job_spans() == spans


def test_a_second_job_allocates_no_span(monkeypatch):
    _job()
    made = []
    real = trace_mod.Span
    monkeypatch.setattr(
        trace_mod, "Span", lambda *a, **kw: made.append(kw) or real(*a, **kw)
    )
    _job()
    with trace_mod.span("xfer.h2d"):
        pass
    assert made == []


def test_the_boot_recorder_never_syncs_and_never_sizes(monkeypatch):
    import jax
    import jax.numpy as jnp

    value = jnp.ones((4,))
    blocked, sized = [], []
    monkeypatch.setattr(
        jax, "block_until_ready", lambda x: blocked.append(x) or x
    )
    monkeypatch.setattr(
        trace_mod, "cheap_nbytes", lambda x: sized.append(x) or 0
    )
    with trace_mod.span("job"):
        with trace_mod.span("exec.segment") as sp:
            sp.sync_on(value)
    assert blocked == [] and sized == []
    kept = trace_mod.first_job_spans()
    assert [sp.name for sp in kept] == ["exec.segment", "job"]
    assert kept[0].sync_seconds == 0.0 and kept[0].output_bytes is None
    assert kept[0].sync_target is None  # nothing is held for it either


def test_the_boot_recorder_is_bounded(monkeypatch):
    monkeypatch.setattr(trace_mod, "BOOT_MAX_SPANS", 3)
    trace_mod.reset()
    _job(children=("a", "b", "c", "d"))
    assert [sp.name for sp in trace_mod.first_job_spans()] == ["a", "b", "c"]
    assert trace_mod._boot.dropped == 2  # "d" and the job itself
    # a recorder that overflowed still comes down with its root
    with trace_mod.span("after") as sp:
        assert sp is trace_mod.NULL_SPAN


def test_an_overflowed_recorder_comes_down_with_a_childless_root(monkeypatch):
    """A process with no job — parentless spans that never have children —
    stops paying for a ``Span`` once the recorder is full."""
    monkeypatch.setattr(trace_mod, "BOOT_MAX_SPANS", 2)
    trace_mod.reset()
    for _ in range(3):
        with trace_mod.span("serve.batch") as sp:
            assert sp is not trace_mod.NULL_SPAN
    with trace_mod.span("serve.batch") as sp:
        assert sp is trace_mod.NULL_SPAN
    assert len(trace_mod.first_job_spans()) == 2


def test_an_installed_tracer_takes_the_first_job_and_leaves_it_armed():
    t = trace_mod.install(trace_mod.Tracer(sync=False))
    _job()
    assert [sp.name for sp in t.spans()] == ["plan.build", "exec.segment", "job"]
    assert trace_mod.first_job_spans() == []
    trace_mod.stop()
    _job(children=("eval.metrics",))  # the first job nobody else took
    assert [sp.name for sp in trace_mod.first_job_spans()] == [
        "eval.metrics", "job",
    ]


def test_a_profiler_session_takes_precedence_too(tmp_path):
    import jax

    options = jax.profiler.ProfileOptions()
    options.python_tracer_level = 0
    jax.profiler.start_trace(str(tmp_path), profiler_options=options)
    try:
        _job()
    finally:
        jax.profiler.stop_trace()
    assert [sp.name for sp in trace_mod.session_spans()][-1] == "job"
    assert trace_mod.first_job_spans() == []


def test_a_suspended_thread_records_nothing_and_ends_nothing():
    with trace_mod.suspended():
        with trace_mod.span("job") as sp:
            with trace_mod.span("profiling.run"):
                pass
        assert sp is trace_mod.NULL_SPAN
    assert trace_mod.first_job_spans() == []
    _job()
    assert trace_mod.first_job_spans()[-1].name == "job"


def test_workers_adopted_under_the_first_root_land_in_it():
    done = []

    def work(token, name):
        with trace_mod.adopt(token):
            with trace_mod.span(name):
                pass
        done.append(name)

    with trace_mod.span("job") as job:
        with trace_mod.span("pipeline.pull") as pull:
            token = trace_mod.handoff()
            threads = [
                threading.Thread(target=work, args=(token, f"node.w{i}"))
                for i in range(3)
            ]
            for th in threads:
                th.start()
            for th in threads:
                th.join(timeout=10)
    assert sorted(done) == ["node.w0", "node.w1", "node.w2"]
    spans = trace_mod.first_job_spans()
    workers = [sp for sp in spans if sp.name.startswith("node.w")]
    assert len(workers) == 3
    assert all(sp.parent_id == pull.span_id for sp in workers)
    assert job.tid not in {sp.tid for sp in workers}
    # a worker that outlives the job finds nobody recording
    with trace_mod.adopt(token):
        with trace_mod.span("node.late") as sp:
            assert sp is trace_mod.NULL_SPAN


def test_reset_arms_the_recorder_anew():
    _job()
    assert trace_mod.first_job_spans()
    trace_mod.reset()
    assert trace_mod.first_job_spans() == []
    assert trace_mod.first_job_programs() == {}
    with trace_mod.span("job") as sp:
        assert sp is not trace_mod.NULL_SPAN


def test_the_first_job_is_explained_once_at_info(caplog):
    with caplog.at_level(logging.INFO, logger="keystone_tpu.obs.tracer"):
        with trace_mod.span("xfer.h2d"):
            _fire(LOAD, 399.0, 399.5, "jit(upload)")  # ahead of the job
        with trace_mod.span("job"):
            with trace_mod.span("plan.segments"):
                _fire(TRACE, 400.0, 401.0, "fn")
                _fire(LOWER, 401.0, 401.5, "jit(fn)")
            with trace_mod.span("block_ls.solve"):
                _fire(LOAD, 402.0, 404.0, "jit(_bcd_scan)")
                monitoring.record_event("/jax/compilation_cache/cache_hits")
        _job()
    lines = [r.getMessage() for r in caplog.records if "first job" in r.message]
    assert len(lines) == 1
    line = lines[0]
    assert line.startswith("first job: job ")
    assert "traced 1.000 s, lowered 0.500 s" in line
    assert "loaded 2.000 s (1 requests, 1 from the cache)" in line
    assert "block_ls.solve 2.000, plan.segments 1.500" in line
    assert "_bcd_scan 2.000, fn 1.500" in line and "upload" not in line
    # the table by program covers the root span alone
    assert trace_mod.first_job_programs() == {
        "fn": {"trace": (1, 1.0), "lower": (1, 0.5), "load": (0, 0.0)},
        "_bcd_scan": {"trace": (0, 0.0), "lower": (0, 0.0), "load": (1, 2.0)},
    }


def test_the_line_on_a_job_that_compiled_nothing():
    job = _job()
    line = format_first_job(trace_mod.first_job_spans(), {}, job)
    assert "most of it under spans none; in programs none" in line


def test_a_tiny_timit_job_is_the_first_job_of_its_process():
    """The acceptance case: nobody installed, no session — the first ``job``
    with its layers' spans is kept, its counts say what jax did, and the
    second job leaves nothing."""
    from keystone_tpu.pipelines.timit import TimitConfig, run, synthetic_timit
    from keystone_tpu.workflow.env import PipelineEnv

    conf = TimitConfig(
        num_cosines=2, cosine_features=32, num_classes=4, num_epochs=1
    )
    train, test = synthetic_timit(128, 4, seed=1), synthetic_timit(32, 4, seed=2)
    for _ in range(2):
        PipelineEnv.get_or_create().reset()
        run(train, test, conf)
    spans = trace_mod.first_job_spans()
    jobs = [sp for sp in spans if sp.name == "job"]
    assert len(jobs) == 1 and spans[-1] is jobs[0]
    job = jobs[0]
    names = {sp.name for sp in spans}
    assert {"plan.build", "plan.segments", "exec.segment", "block_ls.solve",
            "eval.metrics"} <= names
    assert job.trace_s + job.lower_s + job.load_s <= job.seconds
    assert all(sp.trace_s <= job.trace_s + 1e-9 for sp in spans)
    assert all(job.start <= sp.start <= sp.end <= job.end for sp in spans)


def test_span_skew_prints_the_first_job_by_span_and_by_program():
    """``tools/span_skew.py``'s table: a segment is told apart by its
    label, a rule by its name, and a span's own compile seconds are its
    counts less its children's."""
    import importlib.util
    import os

    path = os.path.join(
        os.path.dirname(__file__), "..", "..", "tools", "span_skew.py"
    )
    spec = importlib.util.spec_from_file_location("span_skew_tool", path)
    tool = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(tool)

    with trace_mod.span("job"):
        with trace_mod.span("plan.optimize"):
            with trace_mod.span("plan.rule", rule="MergeRule"):
                _fire(TRACE, 1.0, 1.5, "eval_shape_fn")
        for label in ("SIFT+PCA", "SIFT+PCA", "Fisher"):
            with trace_mod.span("exec.segment", label=label, path="compiled"):
                _fire(LOAD, 2.0, 3.0, "jit(fn)")  # the same second thrice
    table = tool.first_job_table(
        trace_mod.first_job_spans(), trace_mod.first_job_programs()
    )
    rows = table["by_span"]
    assert list(rows)[0] == "job"  # the longest first
    assert rows["exec.segment:SIFT+PCA"]["calls"] == 2
    assert rows["exec.segment:SIFT+PCA"]["compiles"] == 2
    assert rows["exec.segment:SIFT+PCA"]["load_s"] == pytest.approx(1.0)
    assert rows["exec.segment:Fisher"]["load_s"] == 0.0  # the union rule
    assert rows["plan.rule:MergeRule"]["own_compile_s"] == pytest.approx(0.5)
    assert rows["plan.optimize"]["trace_s"] == pytest.approx(0.5)
    assert rows["plan.optimize"]["own_compile_s"] == 0.0
    assert rows["job"]["own_compile_s"] == 0.0
    assert table["programs"]["fn"]["load"] == {"requests": 3, "seconds": 3.0}
    assert list(table["programs"]) == ["fn", "eval_shape_fn"]
    assert table["programs_in_all"] == 2
