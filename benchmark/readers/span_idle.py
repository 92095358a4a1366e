"""The chip's idle time under one host annotation, put down to the program's
own spans: milliseconds a job (or a share of all of it) under the spans
whose name starts with one of ``prefixes``.

The program's spans (``keystone_tpu.obs.tracer.session_spans()``: recorded
for the length of the profiler session, on ``time.perf_counter``) and the
trace (``run.reduction``: the chip's busy intervals and the ``anchor``
annotation's intervals, on the profiler's clock) share no clock, so the
spans are shifted by one offset, taken where both clocks saw the same
moment: each ``root`` span ends as its ``anchor`` interval does. The offset
is the median over the window's steps of (anchor end − root end), and it is
CHECKED: every step's own offset agrees with the median within
``TOLERANCE_S``, and no root span opens before its anchor does. Where the
check fails — or the program has no such spans, as a parent commit has
not — the reader finds nothing to read.

An idle instant goes to the innermost span over it: of the spans of every
thread that cover it, the one that opened last. ``"_none_"`` as a prefix
stands for idle time under no span at all.
"""

from __future__ import annotations

import statistics
from typing import Dict, List, Optional, Tuple

from benchmark import trace

#: (name, start_s, end_s) on one clock
Span = Tuple[str, float, float]
Interval = Tuple[float, float]

TOLERANCE_S = 1e-3
NO_SPAN = "_none_"


def offset_of(
    roots: List[Span], anchors: List[Interval],
    tolerance_s: float = TOLERANCE_S,
) -> Optional[float]:
    """What to add to the spans' clock to get the anchors', or None where
    roots and anchors do not pair up one to one inside the tolerance."""
    roots = sorted(roots, key=lambda s: s[1])
    anchors = sorted(anchors)
    if not roots or len(roots) != len(anchors):
        return None
    offsets = [a_end - end for (_, _, end), (_, a_end) in zip(roots, anchors)]
    offset = statistics.median(offsets)
    if any(abs(o - offset) > tolerance_s for o in offsets):
        return None
    for (_, start, _), (a_start, _) in zip(roots, anchors):
        if start + offset < a_start - tolerance_s:
            return None
    return offset


def innermost(spans: List[Span]) -> List[Span]:
    """The spans flattened to disjoint pieces ``(name, t0, t1)``, each
    piece named by the span over it that opened last."""
    edges = sorted({t for _, start, end in spans for t in (start, end)})
    by_start = sorted(spans, key=lambda s: s[1])
    out: List[Span] = []
    active: List[Span] = []
    i = 0
    for t0, t1 in zip(edges, edges[1:]):
        while i < len(by_start) and by_start[i][1] <= t0:
            active.append(by_start[i])
            i += 1
        active = [s for s in active if s[2] > t0]
        if not active:
            continue
        # opened last; of two that opened together, the one that ends first
        name = max(active, key=lambda s: (s[1], -s[2]))[0]
        if out and out[-1][0] == name and out[-1][2] == t0:
            out[-1] = (name, out[-1][1], t1)
        else:
            out.append((name, t0, t1))
    return out


def idle_by_span(
    spans: List[Span], anchors: List[Interval], busy: List[Interval],
    root: str, tolerance_s: float = TOLERANCE_S,
) -> Optional[Dict[str, float]]:
    """Idle seconds under the anchors by innermost span name, ``NO_SPAN``
    for what no span covers; None where the clocks cannot be joined."""
    offset = offset_of(
        [s for s in spans if s[0] == root], anchors, tolerance_s
    )
    if offset is None:
        return None
    idle = []
    for a_start, a_end in trace.union(anchors):
        at = a_start
        for start, end in trace.clip(busy, (a_start, a_end)):
            if start > at:
                idle.append((at, start))
            at = max(at, end)
        if a_end > at:
            idle.append((at, a_end))
    shifted = [(n, s + offset, e + offset) for n, s, e in spans if e > s]
    pieces = innermost(shifted)
    out: Dict[str, float] = {}
    i = j = 0
    while i < len(pieces) and j < len(idle):  # both sorted and disjoint
        name, p_start, p_end = pieces[i]
        lo, hi = max(p_start, idle[j][0]), min(p_end, idle[j][1])
        if hi > lo:
            out[name] = out.get(name, 0.0) + hi - lo
        if p_end < idle[j][1]:
            i += 1
        else:
            j += 1
    none = sum(e - s for s, e in idle) - sum(out.values())
    out[NO_SPAN] = max(none, 0.0)
    return out


def program_spans() -> Optional[list]:
    """The program's spans of the traced window (its ``Span`` records,
    instants left out), or None where the program keeps none: a commit
    from before the span primitive."""
    try:
        from keystone_tpu.obs import tracer
    except ImportError:
        return None
    read = getattr(tracer, "session_spans", None)
    if read is None:
        return None
    return [sp for sp in read() if not sp.instant]


def read(params: dict, run):
    if run.reduction is None:
        return None
    spans = program_spans()
    anchors = run.reduction.annotations.get(params["anchor"], [])
    if not spans or not anchors:
        return None
    spans = [(sp.name, sp.start, sp.end) for sp in spans]
    # one split a run serves every metric that reads it
    memo = run.__dict__.setdefault("_span_idle", {})
    key = (params["anchor"], params["root"])
    if key not in memo:
        memo[key] = idle_by_span(
            spans, anchors, run.reduction.busy, params["root"]
        )
    split = memo[key]
    if split is None:
        return None
    prefixes = tuple(params["prefixes"])
    seconds = sum(s for name, s in split.items() if name.startswith(prefixes))
    if params.get("share"):
        total = sum(split.values())
        return 100.0 * seconds / total if total > 0 else None
    return 1e3 * seconds / len(anchors)
