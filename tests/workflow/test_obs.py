"""Observability configuration (utils/obs.py + CLI --logLevel/--trace)."""

import logging
import threading

import pytest

from keystone_tpu.obs import tracer
from keystone_tpu.utils import obs, timing


def test_configure_sets_level_and_format(capsys):
    obs.configure("info")
    logging.getLogger("keystone_tpu.test").info("hello obs")
    err = capsys.readouterr().err
    assert "hello obs" in err
    assert "keystone_tpu.test" in err
    obs.configure("warning")
    logging.getLogger("keystone_tpu.test").info("hidden")
    assert "hidden" not in capsys.readouterr().err


def test_configure_rejects_unknown_level():
    with pytest.raises(ValueError):
        obs.configure("loud")


@pytest.fixture
def no_tracer(past_first_job):
    """The four span tests install a tracer: leave none behind."""


def test_trace_records_span_totals_and_logs(no_tracer, tmp_path, caplog):
    """``configure(trace=...)`` is the switch now: spans land in the
    installed tracer (totals and calls in ``span_summary``) and the
    export logs them by name."""
    path = str(tmp_path / "trace.json")
    obs.configure("warning", trace=path)
    with tracer.span("obs.test_phase"):
        pass
    with tracer.span("obs.test_phase"):
        pass
    row = tracer.current().span_summary()["obs.test_phase"]
    assert row["calls"] == 2 and row["seconds"] >= 0.0
    with caplog.at_level(logging.INFO, logger="keystone_tpu.obs.tracer"):
        assert obs.export_trace() == path
    assert "obs.test_phase" in caplog.text


@pytest.mark.parametrize("raw,want", [("", False), ("trace.json", True)])
def test_trace_env_parsing(no_tracer, monkeypatch, tmp_path, raw, want):
    """``KEYSTONE_TRACE`` alone decides whether ``configure(None)``
    installs a tracer; ``KEYSTONE_PROFILE`` is gone and switches nothing."""
    monkeypatch.setenv("KEYSTONE_PROFILE", "1")
    monkeypatch.setenv("KEYSTONE_TRACE", raw and str(tmp_path / raw))
    obs.configure("warning")
    assert (tracer.current() is not None) is want
    with tracer.span("obs.env") as sp:
        assert (sp is not tracer.NULL_SPAN) is want


def test_bad_env_level_falls_back(monkeypatch, capsys):
    monkeypatch.setenv("KEYSTONE_LOG", "trace")
    obs.configure(None)  # must not raise
    import logging

    assert logging.getLogger().level == logging.WARNING


def test_configure_is_idempotent_one_handler():
    """Repeated configure() must re-level, not stack stream handlers
    (stacked handlers double every log line)."""
    obs.configure("info")
    root = logging.getLogger()
    n_handlers = len(root.handlers)
    obs.configure("debug")
    obs.configure("warning")
    assert len(root.handlers) == n_handlers
    assert root.level == logging.WARNING


def test_every_under_concurrent_callers():
    """N threads racing one key: exactly one winner per window."""
    key = "test.concurrent.every"
    obs.reset_rate_limits()
    results = []
    barrier = threading.Barrier(8)

    def hit():
        barrier.wait(timeout=5)
        results.append(obs.every(key, 60.0))

    threads = [threading.Thread(target=hit) for _ in range(8)]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    assert sum(results) == 1


def test_timing_reset_clears_rate_limits():
    """Back-to-back bench runs in one process: timing.reset() must give
    the new run its FIRST periodic log instead of inheriting the old
    run's suppression window."""
    key = "test.reset.every"
    assert obs.every(key, 3600.0) is True
    assert obs.every(key, 3600.0) is False  # suppressed within the window
    timing.reset()
    assert obs.every(key, 3600.0) is True  # fresh epoch logs immediately


def test_span_sync_on_blocks_at_exit(no_tracer):
    """The value handed to ``sync_on`` is what an installed tracer blocks
    on (and sizes) at span exit — the async-dispatch attribution contract."""
    import jax.numpy as jnp

    t = tracer.install(tracer.Tracer())
    with tracer.span("obs.holder_sync") as sp:
        sp.sync_on(jnp.ones((4,)) * 2.0)
    (recorded,) = t.spans()
    assert recorded.sync_target is None and recorded.output_bytes == 16
    row = t.span_summary()["obs.holder_sync"]
    assert row["calls"] == 1 and row["sync_seconds"] >= 0.0


def test_span_sync_failure_is_logged_not_swallowed(no_tracer, caplog):
    """A REAL device error during the span-exit sync must surface at
    WARNING while the span still records; non-blockable values stay
    silent."""

    class _Boom:
        def block_until_ready(self):
            raise RuntimeError("sync exploded")

    t = tracer.install(tracer.Tracer())
    with caplog.at_level(logging.WARNING, logger="keystone_tpu.obs.span"):
        with tracer.span("obs.sync_fail") as sp:
            sp.sync_on(_Boom())
    assert "block_until_ready failed" in caplog.text
    assert t.span_summary()["obs.sync_fail"]["calls"] == 1

    caplog.clear()
    with caplog.at_level(logging.WARNING, logger="keystone_tpu.obs.span"):
        with tracer.span("obs.sync_plain") as sp:
            sp.sync_on(object())  # plain objects pass through jax untouched
    assert "block_until_ready failed" not in caplog.text
