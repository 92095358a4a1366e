"""Named counters of seconds and calls, process-wide.

What is left of the per-phase timer: timed REGIONS are spans now
(``obs.tracer.span``, which lands in the device trace and in memory);
this registry keeps the plain counters that are read by name — the
serving replica's ``serve.batch`` (``serving/metrics.py`` embeds
``snapshot("serve.")``) and the catch-and-degrade sites
(``snapshot("degrade.")``, which ``chip_smoke.py`` refuses a run over).
"""

from __future__ import annotations

import threading
from collections import defaultdict
from typing import Dict, Optional

_lock = threading.Lock()
_totals: Dict[str, float] = defaultdict(float)
_counts: Dict[str, int] = defaultdict(int)


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
    """Clear the counters AND the obs rate-limiter state: a fresh
    measurement epoch (back-to-back bench runs in one process) must get
    its first periodic log, not inherit the previous run's suppression
    window."""
    with _lock:
        _totals.clear()
        _counts.clear()
    from . import obs

    obs.reset_rate_limits()


def snapshot(prefix: Optional[str] = None) -> Dict[str, Dict[str, float]]:
    """``{name: {"seconds": total, "calls": n}}``; ``prefix`` filters to
    one subsystem's counters (``"serve."``, ``"degrade."``)."""
    with _lock:
        return {
            k: {"seconds": round(_totals[k], 4), "calls": _counts[k]}
            for k in sorted(_totals)
            if prefix is None or k.startswith(prefix)
        }
