"""The span record: one timed region of pipeline execution.

A :class:`Span` is what the :class:`~keystone_tpu.obs.tracer.Tracer`
collects — name, DAG node identity, operator type, wall-clock interval,
device-sync time, materialized output bytes, cache hit/miss, and what jax
compiled, traced, lowered and loaded across the region. Spans form a tree
per thread (``parent_id``/``depth`` come from the tracer's thread-local
stack).

The helpers here size and synchronize values WITHOUT side effects: sizing
never forces a lazy dataset to materialize, and syncing only blocks on
device-resident arrays (host values pass through untouched).
"""

from __future__ import annotations

import logging
from dataclasses import dataclass, field
from typing import Any, Dict, Optional

logger = logging.getLogger(__name__)


@dataclass
class Span:
    """One traced region. ``start``/``end`` are ``time.perf_counter``
    readings; the exporter rebases them onto the tracer's epoch."""

    name: str
    start: float
    end: float = 0.0
    span_id: int = 0
    parent_id: Optional[int] = None
    depth: int = 0
    tid: int = 0
    thread_name: str = ""
    #: DAG node identity (stringified NodeId.id), None for non-node spans
    node_id: Optional[str] = None
    #: operator class name (Cacher, Segment, ...)
    op_type: Optional[str] = None
    #: "hit" (memoized result returned) | "miss" (computed this pull) | None
    cache: Optional[str] = None
    #: seconds spent blocking on the device stream at span exit
    sync_seconds: float = 0.0
    #: materialized result size, when cheaply knowable (see cheap_nbytes)
    output_bytes: Optional[int] = None
    #: what jax.monitoring reported inside this span, process-wide
    #: (``obs.tracer.CompileRecord``): XLA compile requests, those of them
    #: the persistent cache answered, and the seconds — union of the
    #: events' intervals a kind — in which jax traced Python to a jaxpr,
    #: lowered a jaxpr to an MLIR module, compiled or loaded an executable
    compiles: int = 0
    cache_hits: int = 0
    trace_s: float = 0.0
    lower_s: float = 0.0
    load_s: float = 0.0
    #: bytes ``utils/params.content_digest`` hashed inside this span, and
    #: the digests it answered from memory there
    digest_bytes: int = 0
    digest_hits: int = 0
    instant: bool = False
    #: free-form attributes; the concurrent executor adds
    #: ``queue_wait_seconds`` (ready-to-started scheduler latency) and
    #: ``worker`` (pool thread name) to node spans it forced
    attrs: Dict[str, Any] = field(default_factory=dict)
    #: value to block on at span exit (cleared once synced); not exported
    sync_target: Any = field(default=None, repr=False)

    @property
    def seconds(self) -> float:
        return max(self.end - self.start, 0.0)

    def sync_on(self, value: Any) -> None:
        """Ask the tracer to block on ``value`` when this span closes, so
        asynchronously-dispatched device work is attributed here and not to
        whichever later span first synchronizes."""
        self.sync_target = value


def _device_payload(value: Any) -> Any:
    """What to block on for ``value`` — device arrays / batched payloads.
    Returns None when syncing would force work (item lists, chunked
    datasets) or there is nothing device-resident to wait for."""
    from ..data.dataset import Dataset

    if isinstance(value, Dataset):
        # batched payloads are (pytrees of) arrays already dispatched;
        # item-list / chunked datasets would have to MATERIALIZE to sync
        return value.payload if value.is_batched else None
    return value


def sync_value(value: Any) -> bool:
    """``jax.block_until_ready`` on the device-resident part of ``value``.

    Returns True when a sync was attempted. Missing jax or non-blockable
    values are expected (ImportError/TypeError pass silently); anything
    else is a REAL device error and is logged at WARNING rather than
    swallowed."""
    target = _device_payload(value)
    if target is None:
        return False
    try:
        import jax

        jax.block_until_ready(target)
        return True
    except (ImportError, TypeError):
        return False
    except Exception:
        logger.warning("span sync: block_until_ready failed", exc_info=True)
        return False


def cheap_nbytes(value: Any) -> Optional[int]:
    """Best-effort materialized size of ``value`` in bytes, WITHOUT forcing
    computation, host transfer, or chunk materialization. None when the
    size is not cheaply knowable."""
    import numpy as np

    try:
        from ..data.dataset import Dataset

        if isinstance(value, Dataset):
            if not value.is_batched:
                return None  # sizing would force collect()
            import jax

            return int(
                sum(
                    int(np.prod(a.shape)) * a.dtype.itemsize
                    for a in jax.tree_util.tree_leaves(value.payload)
                    if hasattr(a, "shape") and hasattr(a, "dtype")
                )
            )
        nbytes = getattr(value, "nbytes", None)
        if nbytes is not None:
            return int(nbytes)
        if hasattr(value, "shape") and hasattr(value, "dtype"):
            return int(np.prod(value.shape)) * value.dtype.itemsize
    except Exception:
        # sizing is best-effort by contract: a value that cannot report
        # its bytes must never break the span that carries it
        logger.debug("cheap_nbytes probe failed", exc_info=True)
        return None
    return None
