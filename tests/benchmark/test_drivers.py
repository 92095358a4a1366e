"""Each traffic kind's driver at a tiny size, through the functions the
command calls: all of a run but its look for a chip."""

import gc
import json
import os
import statistics
import types
import weakref

import pytest

from tests.benchmark import tiny

E2E = {
    "tiny_fft.fit": {"fit_s", "setup_s"},
    "tiny_cos.fit": {"fit_s", "setup_s"},
    "tiny_cos.apply": {"apply_rows_per_s", "setup_s"},
    "tiny_fft.serve": {"serve_p50_ms", "serve_p95_ms", "setup_s"},
}


@pytest.mark.parametrize("workload", sorted(E2E))
def test_a_run_ends_in_the_result_line(tiny_root, workload, capsys):
    rc, lines = tiny.run_cell(tiny_root, workload, seconds=0.1, capsys=capsys)
    assert rc == 0
    report, result = json.loads(lines[-2]), json.loads(lines[-1])
    # the last line: the keys the driver reads, and ``compared`` last
    assert list(result)[:5] == [
        "correct", "attempted", "failed", "metrics", "device"
    ]
    assert list(result)[-1] == "compared"
    assert result["correct"] is True and result["failed"] == 0
    assert result["attempted"] >= 1
    assert set(result["metrics"]) == E2E[workload]
    for row in result["metrics"].values():
        assert row["value"] > 0 and set(row) == {"value", "unit"}
    assert set(result["device"]) == {
        "platform", "kind", "count", "memory_peak_bytes"
    }
    for row in result["compared"].values():
        assert row["value"] <= row["limit"]
    # the line before it: set-up's phases and the compile counts
    assert report["phases_s"] and "datagen" in report["phases_s"]
    for part in ("setup", "window"):
        assert {"compile_requests", "persistent_cache_hits",
                "cache_entries_added"} <= set(report[part])


#: the stub programs below take no data
NO_DATA = dict.fromkeys(("X_train", "y_train", "X_test", "y_test"))


def _fit_loop(root):
    from benchmark import harness

    return harness.Manifest(root, os.path.join(root, "benchmark")).driver(
        "fit_loop"
    )


@pytest.mark.parametrize("workload", ["tiny_cos.fit", "tiny_fft.fit"])
def test_fit_s_is_the_window_over_its_jobs(tiny_root, workload, capsys,
                                           monkeypatch):
    driver = _fit_loop(tiny_root)
    seen, window = {}, driver.window

    def spy(run, state, seconds):
        produced = window(run, state, seconds)
        seen.update(run.facts)
        return produced

    monkeypatch.setattr(driver, "window", spy)
    rc, lines = tiny.run_cell(tiny_root, workload, seconds=0.3, capsys=capsys)
    assert rc == 0
    report, result = json.loads(lines[-2]), json.loads(lines[-1])
    jobs = seen["job_ms"]
    assert jobs and all(j > 0 for j in jobs)
    # every job of the window and all of its time: nothing is discarded
    assert 1e3 * seen["window_s"] == pytest.approx(sum(jobs), abs=0.01 * len(jobs))
    assert seen["fits"] == result["attempted"] == len(jobs)
    assert result["metrics"]["fit_s"]["value"] == seen["window_s"] / len(jobs)
    assert 1e3 * seen["fit_median_s"] == pytest.approx(
        statistics.median(jobs), abs=0.01
    )
    # the report line shows the driver's own numbers beside the metric,
    # each job's duration among them
    shown = report["window"]["facts"]
    assert shown["fit_s"] == seen["fit_s"] and shown["job_ms"] == jobs
    assert shown["fit_median_s"] == seen["fit_median_s"]


def _stub_run(fit):
    """A run whose program is ``fit`` and whose model is empty."""
    return types.SimpleNamespace(
        program=types.SimpleNamespace(fit=fit, model=lambda handle: {}),
        config={}, facts={},
    )


def _timed_driver(tiny_root, monkeypatch, slow_job=None, slow_s=0.25):
    """The fit driver on a clock of the test's own, and a program that
    takes 2 ms of it a job, job ``slow_job`` ``slow_s``."""
    driver = _fit_loop(tiny_root)
    now, calls = [100.0], []
    monkeypatch.setattr(
        driver, "time", types.SimpleNamespace(perf_counter=lambda: now[0])
    )

    def fit(config, *data):
        calls.append(1)
        now[0] += slow_s if len(calls) - 1 == slow_job else 0.002
        return types.SimpleNamespace(test_error=0.0)

    return driver, _stub_run(fit)


def test_one_slow_job_is_paid_by_fit_s_and_not_by_the_median(
        tiny_root, monkeypatch):
    driver, steady = _timed_driver(tiny_root, monkeypatch)
    driver.window(steady, NO_DATA, 0.301)
    driver, stalled = _timed_driver(tiny_root, monkeypatch, slow_job=3)
    driver.window(stalled, NO_DATA, 0.301)
    a, b = steady.facts, stalled.facts
    assert a["fits"] == 151 and b["fits"] == 27
    assert b["job_ms"][3] == 250 and max(a["job_ms"]) == 2
    # the stall is in the metric: all the work over all the time
    assert a["fit_s"] == pytest.approx(0.002)
    assert b["fit_s"] == pytest.approx((0.25 + 26 * 0.002) / 27)
    assert b["fit_s"] == pytest.approx(b["window_s"] / b["fits"])
    # and not in the statistics that stand beside it: the median, and the
    # highest percentile with ten jobs beyond it
    assert b["fit_median_s"] == pytest.approx(a["fit_median_s"])
    assert b["fit_p_high_s"] == pytest.approx(0.002)
    assert 1e3 * a["fit_p_high_s"] == pytest.approx(sorted(a["job_ms"])[-11])


def test_a_job_starts_with_the_last_jobs_garbage_collected(tiny_root):
    class Node:
        pass

    left = []  # a weak reference to each job's cyclic garbage

    def fit(config, *data):
        # the job before this one left a cycle: it is gone by now
        assert not left or left[-1]() is None
        a, b = Node(), Node()
        a.other, b.other = b, a
        left.append(weakref.ref(a))
        return types.SimpleNamespace(test_error=0.0)

    run = _stub_run(fit)
    gc.disable()  # the driver's own collection, not a pass that happens by
    try:
        _fit_loop(tiny_root).window(run, NO_DATA, 0.02)
    finally:
        gc.enable()
    assert run.facts["fits"] == len(left) >= 2


def test_a_short_window_reports_no_high_percentile(tiny_root, monkeypatch):
    driver, run = _timed_driver(tiny_root, monkeypatch)
    driver.window(run, NO_DATA, 0.0199)
    assert run.facts["fits"] == 10 and "fit_p_high_s" not in run.facts
    driver.window(run, NO_DATA, 0.0219)
    assert run.facts["fits"] == 11 and "fit_p_high_s" in run.facts


def test_a_large_seed_is_taken(tiny_root, capsys):
    rc, lines = tiny.run_cell(
        tiny_root, "tiny_cos.fit", seed=2**31 + 12345, seconds=0.05,
        capsys=capsys,
    )
    assert rc == 0 and json.loads(lines[-1])["correct"] is True


def test_every_seed_offers_the_same_load():
    from benchmark.drivers import serve_open_loop as d

    traffic = tiny.TRAFFIC["tiny_serve"]
    due_a, picks_a = d.schedule(traffic, 1, 2.0, 256)
    due_b, picks_b = d.schedule(traffic, 2, 2.0, 256)
    assert len(due_a) == len(due_b) == 4000
    assert due_a[-1] == pytest.approx(due_b[-1])  # the same gaps, reordered
    assert not (due_a == due_b).all() and not (picks_a == picks_b).all()
    assert due_a[-1] == pytest.approx(2.0, rel=0.02)
    again, _ = d.schedule(traffic, 1, 2.0, 256)
    assert (again == due_a).all()


def test_a_failed_request_counts_as_the_slowest():
    from benchmark.drivers.serve_open_loop import percentile

    assert percentile([1.0] * 94 + [float("inf")] * 6, 95) == float("inf")
    assert percentile([1.0] * 96 + [float("inf")] * 4, 95) == 1.0
    assert percentile(list(range(1, 101)), 50) == 50
