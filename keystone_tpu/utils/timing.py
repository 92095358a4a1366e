"""Per-phase wall-clock instrumentation for the hot solvers.

Parity: the reference logs per-block phase times in its hot loops —
kernelGen/residual/collect/localSolve/modelUpdate in
``nodes/learning/KernelRidgeRegression.scala:216-224`` and pipeline totals in
``MnistRandomFFT.scala:31,66-67``. Here a process-global registry accumulates
named phase durations; solvers wrap their phases in :func:`phase`, the bench
reads :func:`snapshot`, and everything logs at INFO.

jax dispatch is asynchronous, so each phase exit synchronizes on the phase's
result (``block_until_ready``) when given one — otherwise device time would
be misattributed to whichever later phase first blocks.
"""

from __future__ import annotations

import contextlib
import logging
import threading
import time
from collections import defaultdict
from typing import Any, Dict, Optional

logger = logging.getLogger(__name__)

_lock = threading.Lock()
_totals: Dict[str, float] = defaultdict(float)
_counts: Dict[str, int] = defaultdict(int)

# Profiling is OFF by default: a phase exit then only reads the wall clock
# (async dispatch keeps running ahead, so attribution is approximate but the
# hot loops stay sync-free). Enabling (KEYSTONE_PROFILE=1 or enable()) adds a
# block_until_ready per phase for accurate attribution + INFO logs.
import os as _os

_profiling = bool(_os.environ.get("KEYSTONE_PROFILE"))


def enable(on: bool = True) -> None:
    global _profiling
    _profiling = on


@contextlib.contextmanager
def phase(name: str, sync: Optional[Any] = None):
    """Time a named phase. Under profiling, ``sync`` (or a value appended to
    the yielded holder) is blocked on at exit so asynchronously-dispatched
    device work lands in the right bucket."""
    t0 = time.perf_counter()
    holder: list = []
    try:
        yield holder
    finally:
        if _profiling:
            target = holder[0] if holder else sync
            if target is not None:
                try:
                    import jax

                    jax.block_until_ready(target)
                except (ImportError, TypeError):
                    pass  # no jax / non-blockable value: nothing to sync
                except Exception:
                    # a REAL device error (stream failure, dead backend):
                    # swallowing it would silently misattribute every
                    # later phase — surface it, keep timing
                    logger.warning(
                        "phase %s: device sync failed", name, exc_info=True
                    )
        dt = time.perf_counter() - t0
        with _lock:
            _totals[name] += dt
            _counts[name] += 1
        if _profiling:
            logger.info("phase %-28s %8.4f s", name, dt)


def record(name: str, seconds: float) -> None:
    with _lock:
        _totals[name] += seconds
        _counts[name] += 1


def degraded(site: str) -> None:
    """A catch-and-degrade site fired: the program kept running, on a
    slower or older path (a demoted segment, a refused export, a skipped
    warm-up). Counted as ``degrade.<site>`` so a smoke or benchmark can
    refuse a run that quietly fell back — ``snapshot("degrade.")``."""
    record("degrade." + site, 0.0)


def reset() -> None:
    """Clear phase totals AND the obs rate-limiter state: a fresh
    measurement epoch (back-to-back bench runs in one process) must get
    its first periodic log, not inherit the previous run's suppression
    window."""
    with _lock:
        _totals.clear()
        _counts.clear()
    from . import obs

    obs.reset_rate_limits()


def snapshot(prefix: Optional[str] = None) -> Dict[str, Dict[str, float]]:
    """{phase: {"seconds": total, "calls": n}} — what the bench embeds.

    ``prefix`` filters to one subsystem's phases (e.g. ``"serve."`` for
    the serving engine's metric snapshots), so a service's metrics export
    doesn't drag every solver phase of the process along."""
    with _lock:
        return {
            k: {"seconds": round(_totals[k], 4), "calls": _counts[k]}
            for k in sorted(_totals)
            if prefix is None or k.startswith(prefix)
        }
