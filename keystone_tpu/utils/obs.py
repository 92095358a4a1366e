"""Observability configuration: one switch for logs, tracer and caches.

Parity: the reference inherits its observability from Spark — log4j
config, per-stage timing in the Spark UI, and ad-hoc ``logInfo`` phase
logs in the hot solvers (e.g. KernelRidgeRegression.scala:216-224). The
counterparts here:

* ``configure(level)`` — process-wide stdlib logging with a timestamped
  single-line format (the log4j analogue). Every module already logs
  through ``logging.getLogger(__name__)``; this makes those logs visible
  and uniform.
* spans — every layer boundary and hot-solver phase is an
  ``obs.tracer.span``: a ``ks:`` annotation in any profiler session (the
  Spark-UI-stage-timing analogue is the profile's own timeline), synced
  and kept in memory under an installed tracer.

Environment switches (read by the CLI and by ``configure(None)``):

* ``KEYSTONE_LOG=debug|info|warning|error`` — log level.
* ``KEYSTONE_TRACE=/path/trace.json`` — install the pipeline tracer
  (``keystone_tpu.obs``) and export a Chrome-trace/Perfetto JSON at
  process exit (or explicitly via :func:`export_trace`).
* ``KEYSTONE_AOT_CACHE=/path/dir`` — install the persistent AOT
  executable cache (``keystone_tpu.compile``): fitted-pipeline compiles
  load previously exported executables instead of re-tracing, and jax's
  persistent compilation cache is layered underneath.
* ``KEYSTONE_PROFILE_DIR=/path/dir`` — install the persistent operator
  profile store (``keystone_tpu.cost``): fits learn per-operator
  throughput from traced runs and the second fit of any pipeline plans
  its solver choice + cache plan from evidence with zero sampling.
"""

from __future__ import annotations

import logging
import os
import threading
import time
from typing import Dict, Optional

_FORMAT = "%(asctime)s %(levelname)-7s %(name)s: %(message)s"
_DATEFMT = "%H:%M:%S"

_configured = False

_every_lock = threading.Lock()
_every_last: Dict[str, float] = {}


def every(key: str, seconds: float) -> bool:
    """Process-wide rate limiter for periodic logs: True at most once per
    ``seconds`` for a given ``key`` (first call always True). Lets hot
    loops (the serving engine's worker, long solver scans) emit periodic
    INFO summaries without flooding at per-iteration rate."""
    now = time.monotonic()
    with _every_lock:
        last = _every_last.get(key)
        if last is not None and now - last < seconds:
            return False
        _every_last[key] = now
        return True


def reset_rate_limits() -> None:
    """Forget every :func:`every` key so the next call logs immediately.
    ``timing.reset()`` calls this: a new measurement epoch must not
    inherit the previous run's suppression windows (back-to-back bench
    runs in one process were losing their first periodic summary)."""
    with _every_lock:
        _every_last.clear()


def configure(
    level: Optional[str] = None,
    trace: Optional[str] = None,
    aot_cache: Optional[str] = None,
    profiles: Optional[str] = None,
) -> None:
    """Configure logging (and optionally the tracer and caches) process-wide.

    ``level=None`` reads ``KEYSTONE_LOG`` (default: warning, stdlib's
    default visibility; unknown env values warn and fall back rather than
    crash the CLI). ``trace`` is a Chrome-trace
    output path enabling the pipeline tracer (``keystone_tpu.obs``);
    ``None`` follows ``KEYSTONE_TRACE`` (off unless set). ``aot_cache``
    is a directory path enabling the persistent AOT executable cache
    (``keystone_tpu.compile``); ``None`` follows ``KEYSTONE_AOT_CACHE``
    (off unless set). ``profiles`` is a directory path enabling the
    persistent operator profile store (``keystone_tpu.cost``); ``None``
    follows ``KEYSTONE_PROFILE_DIR`` (off unless set). Idempotent; later
    calls re-level the root handler, and an already-installed tracer is
    kept (spans survive).
    """
    global _configured
    from_env = level is None
    if from_env:
        level = os.environ.get("KEYSTONE_LOG", "warning")
    lvl = getattr(logging, str(level).upper(), None)
    if not isinstance(lvl, int):
        if not from_env:
            raise ValueError(f"unknown log level: {level!r}")
        # a bad env var should not crash the CLI — warn and fall back
        logging.getLogger(__name__).warning(
            "ignoring unknown KEYSTONE_LOG=%r (use debug|info|warning|error)",
            level,
        )
        lvl = logging.WARNING
    root = logging.getLogger()
    if not _configured:
        handler = logging.StreamHandler()
        handler.setFormatter(logging.Formatter(_FORMAT, _DATEFMT))
        root.addHandler(handler)
        _configured = True
    root.setLevel(lvl)

    if trace is None:
        trace = os.environ.get("KEYSTONE_TRACE") or None
    if trace:
        from ..obs import tracer as _obs_tracer

        _obs_tracer.start(path=trace)

    # an explicit aot_cache path (or "" to disable) reconfigures the AOT
    # executable cache; aot_cache=None only ensures the KEYSTONE_AOT_CACHE
    # env default is honored — like the tracer, an already-installed cache
    # is KEPT, so a later configure("debug") call to re-level logging
    # cannot silently uninstall it
    from .. import compile as _compile_mod

    if aot_cache is not None:
        _compile_mod.configure(aot_cache)
    else:
        _compile_mod.get_cache()

    # profile store: same keep-unless-explicit contract as the AOT cache
    from .. import cost as _cost_mod

    if profiles is not None:
        _cost_mod.configure(profiles)
    else:
        _cost_mod.get_store()


def export_trace(path: Optional[str] = None) -> Optional[str]:
    """Write the configured trace NOW (Chrome-trace JSON + top-N summary
    log + autocache audit log). Returns the path written, or None when
    tracing was never configured — callers (the CLI's ``finally``) can
    invoke it unconditionally."""
    from ..obs import tracer as _obs_tracer

    return _obs_tracer.export(path)
