"""Each traffic kind's driver at a tiny size, through the functions the
command calls: all of a run but its look for a chip."""

import json

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
