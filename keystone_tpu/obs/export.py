"""Trace exporters: Chrome-trace JSON, cross-process stitching, and a
plain-text top-N summary.

The JSON form is the ``chrome://tracing`` / Perfetto "Trace Event Format"
(https://ui.perfetto.dev opens it directly): one ``"X"`` complete event
per span (``ts``/``dur`` in microseconds, rebased to the tracer's epoch),
``"i"`` instant events for cache hits, and ``"M"`` metadata events naming
the process and its threads — every export carries a ``process_name``
metadata event and its real ``pid``, so multi-process traces render as
DISTINCT process tracks instead of flattening into one. Events are
sorted by ``ts`` so consumers that stream (and ``bin/trace-smoke.sh``'s
monotonicity check) see ordered time.

Cross-process stitching (:func:`stitch_chrome_trace`): each process
serializes its spans with :func:`wire_spans` — rebased onto the shared
unix clock, because perf_counter epochs are process-local — and the
router merges N processes' span sets into ONE document with per-pid
process tracks. Span identity never collides across the merge: events
carry no raw span ids, and the ``trace_id`` attr that ties one request's
hops together is already namespaced by the originating pid
(``obs/context.py``).
"""

from __future__ import annotations

import json
import os
from typing import Dict, Iterable, List, Optional, Sequence, Tuple

from .tracer import Tracer


def _json_safe(v):
    if v is None or isinstance(v, (bool, int, float, str)):
        return v
    return str(v)


def _span_args(sp) -> dict:
    """One span's exported args dict (typed fields + free-form attrs)."""
    args = {
        k: _json_safe(v)
        for k, v in (
            ("node", sp.node_id),
            ("op_type", sp.op_type),
            ("cache", sp.cache),
            ("sync_ms", round(sp.sync_seconds * 1e3, 3) or None),
            ("output_bytes", sp.output_bytes),
            ("compiles", sp.compiles or None),
            ("cache_hits", sp.cache_hits or None),
            ("trace_s", round(sp.trace_s, 6) or None),
            ("lower_s", round(sp.lower_s, 6) or None),
            ("load_s", round(sp.load_s, 6) or None),
            ("digest_bytes", sp.digest_bytes or None),
            ("digest_hits", sp.digest_hits or None),
        )
        if v is not None
    }
    args.update({k: _json_safe(v) for k, v in sp.attrs.items()})
    return args


def _process_meta(pid: int, process_name: Optional[str]) -> List[dict]:
    if not process_name:
        return []
    return [
        {
            "name": "process_name",
            "ph": "M",
            "ts": 0.0,
            "pid": pid,
            "tid": 0,
            "args": {"name": process_name},
        }
    ]


def default_process_name() -> str:
    """``keystone:<argv0-basename>/<pid>`` — distinct per process even
    when every tier runs the same entry point."""
    import sys

    base = os.path.basename(sys.argv[0] or "python") or "python"
    return f"keystone:{base}/{os.getpid()}"


def to_chrome_trace(
    tracer: Tracer, process_name: Optional[str] = None
) -> Dict[str, object]:
    """The trace as a Chrome-trace dict: ``{"traceEvents": [...], ...}``."""
    pid = os.getpid()
    events: List[dict] = []
    thread_names = {}
    for sp in tracer.spans():
        ev = {
            "name": sp.name,
            "cat": "keystone",
            "ph": "i" if sp.instant else "X",
            "ts": round((sp.start - tracer.epoch) * 1e6, 3),
            "pid": pid,
            "tid": sp.tid,
            "args": _span_args(sp),
        }
        if sp.instant:
            ev["s"] = "t"  # thread-scoped instant marker
        else:
            ev["dur"] = round(sp.seconds * 1e6, 3)
        events.append(ev)
        thread_names.setdefault(sp.tid, sp.thread_name)
    events.sort(key=lambda e: e["ts"])
    meta = _process_meta(pid, process_name or default_process_name()) + [
        {
            "name": "thread_name",
            "ph": "M",
            "ts": 0.0,
            "pid": pid,
            "tid": tid,
            "args": {"name": name},
        }
        for tid, name in sorted(thread_names.items())
    ]
    return {
        "traceEvents": meta + events,
        "displayTimeUnit": "ms",
        "otherData": {
            "producer": "keystone_tpu.obs",
            "epoch_unix_seconds": tracer.epoch_unix,
        },
    }


def write_chrome_trace(tracer: Tracer, path: str) -> str:
    with open(path, "w") as f:
        json.dump(to_chrome_trace(tracer), f)
    return path


# -- cross-process stitching --------------------------------------------------


def wire_spans(
    spans: Iterable, epoch: float, epoch_unix: float,
    pid: Optional[int] = None, process_name: Optional[str] = None,
) -> List[dict]:
    """Serialize spans for shipping across a process boundary: start
    times rebased from the process-local perf_counter epoch onto the
    HOST-shared unix clock (``epoch_unix + (start - epoch)``), plus the
    pid/thread identity the stitcher needs for per-process tracks. The
    wire form is plain JSON-safe dicts (they ride pickled stats replies
    today, but nothing in them requires pickle)."""
    pid = os.getpid() if pid is None else pid
    out = []
    for sp in spans:
        out.append({
            "name": sp.name,
            "start_unix": epoch_unix + (sp.start - epoch),
            "dur_s": sp.seconds,
            "instant": bool(sp.instant),
            "pid": pid,
            "tid": sp.tid,
            "thread_name": sp.thread_name,
            "process_name": process_name,
            "args": _span_args(sp),
        })
    return out


def stitch_chrome_trace(
    span_sets: Sequence[List[dict]],
    base_unix: Optional[float] = None,
) -> Dict[str, object]:
    """Merge N processes' :func:`wire_spans` outputs into ONE
    Chrome-trace document with real per-pid process tracks.

    ``ts`` is rebased to ``base_unix`` (default: the earliest span seen)
    so the merged timeline starts near 0. Each distinct pid contributes
    its own ``process_name``/``thread_name`` metadata events — the fix
    for the flattened single-process rendering the in-process exporter
    used to produce for multi-process runs."""
    all_spans = [s for spans in span_sets for s in spans]
    if base_unix is None:
        base_unix = min(
            (s["start_unix"] for s in all_spans), default=0.0
        )
    events: List[dict] = []
    proc_names: Dict[int, str] = {}
    thread_names: Dict[Tuple[int, int], str] = {}
    for s in all_spans:
        pid = int(s.get("pid") or 0)
        ev = {
            "name": s["name"],
            "cat": "keystone",
            "ph": "i" if s.get("instant") else "X",
            "ts": round((s["start_unix"] - base_unix) * 1e6, 3),
            "pid": pid,
            "tid": s.get("tid", 0),
            "args": dict(s.get("args") or {}),
        }
        if s.get("instant"):
            ev["s"] = "t"
        else:
            ev["dur"] = round(float(s.get("dur_s") or 0.0) * 1e6, 3)
        events.append(ev)
        if s.get("process_name"):
            proc_names.setdefault(pid, str(s["process_name"]))
        if s.get("thread_name"):
            thread_names.setdefault(
                (pid, s.get("tid", 0)), str(s["thread_name"])
            )
    events.sort(key=lambda e: e["ts"])
    meta: List[dict] = []
    for pid, name in sorted(proc_names.items()):
        meta.extend(_process_meta(pid, name))
    meta.extend(
        {
            "name": "thread_name",
            "ph": "M",
            "ts": 0.0,
            "pid": pid,
            "tid": tid,
            "args": {"name": name},
        }
        for (pid, tid), name in sorted(thread_names.items())
    )
    return {
        "traceEvents": meta + events,
        "displayTimeUnit": "ms",
        "otherData": {
            "producer": "keystone_tpu.obs (stitched)",
            "epoch_unix_seconds": base_unix,
            "processes": sorted(proc_names.values()),
        },
    }


def write_stitched_trace(
    span_sets: Sequence[List[dict]], path: str
) -> str:
    with open(path, "w") as f:
        json.dump(stitch_chrome_trace(span_sets), f)
    return path


def format_top_spans(tracer: Tracer, n: int = 10, prefix: Optional[str] = None) -> str:
    """Plain-text top-``n`` span names by total seconds — the quick look
    that doesn't need a trace viewer."""
    summary = tracer.span_summary(prefix=prefix)
    rows = sorted(
        summary.items(), key=lambda kv: kv[1]["seconds"], reverse=True
    )[:n]
    if not rows:
        return "(no spans)"
    width = min(max(len(name) for name, _ in rows), 64)
    lines = [
        f"{'span':<{width}} {'seconds':>9} {'calls':>6} {'sync_s':>8} "
        f"{'hits':>5} {'MB':>9} {'compiles':>8} {'trace_s':>8} "
        f"{'lower_s':>8} {'load_s':>8}"
    ]
    for name, row in rows:
        mb = (row["bytes"] or 0) / 2**20
        lines.append(
            f"{name[:width]:<{width}} {row['seconds']:>9.4f} "
            f"{row['calls']:>6} {row['sync_seconds']:>8.4f} "
            f"{row['cache_hits']:>5} {mb:>9.2f} {row['compiles']:>8} "
            f"{row['trace_s']:>8.4f} {row['lower_s']:>8.4f} "
            f"{row['load_s']:>8.4f}"
        )
    return "\n".join(lines)


def compile_seconds_by_span(
    spans: Iterable, key=lambda sp: sp.name
) -> Dict[str, float]:
    """By span name (or ``key(span)``), the ``trace_s + lower_s + load_s``
    that are a span's OWN: its counts less its direct children's (never
    below 0: children on two threads at once each see what either
    compiled)."""
    spans = list(spans)
    inside: Dict[int, float] = {}
    for sp in spans:
        if sp.parent_id is not None:
            inside[sp.parent_id] = (
                inside.get(sp.parent_id, 0.0)
                + sp.trace_s + sp.lower_s + sp.load_s
            )
    out: Dict[str, float] = {}
    for sp in spans:
        own = sp.trace_s + sp.lower_s + sp.load_s - inside.get(sp.span_id, 0.0)
        name = key(sp)
        out[name] = out.get(name, 0.0) + max(own, 0.0)
    return out


def format_first_job(
    spans: Iterable, programs: Dict[str, dict], root, n: int = 3
) -> str:
    """One line on a process's first job (``obs.tracer.first_job_spans``):
    what ``root`` took, how much of it jax spent tracing, lowering and
    loading, and the ``n`` spans and programs that hold most of that."""

    def top(table: Dict[str, float]) -> str:
        rows = sorted(table.items(), key=lambda kv: -kv[1])[:n]
        return ", ".join(f"{k} {v:.3f}" for k, v in rows if v > 0) or "none"

    by_fun = {
        fun: sum(seconds for _, seconds in row.values())
        for fun, row in programs.items()
    }
    return (
        f"first job: {root.name} {root.seconds:.3f} s; jax traced "
        f"{root.trace_s:.3f} s, lowered {root.lower_s:.3f} s, compiled or "
        f"loaded {root.load_s:.3f} s ({root.compiles} requests, "
        f"{root.cache_hits} from the cache); most of it under spans "
        f"{top(compile_seconds_by_span(spans))}; in programs {top(by_fun)}"
    )
