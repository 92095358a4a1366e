"""From the profiler's trace to numbers: device busy and idle time, time
by operation, and idle gaps by what the host was doing.

``load`` reads the ``.xplane.pb`` that ``jax.profiler`` wrote with
nothing but jax; ``reduce`` works on plain tuples, so the tests hand it a
small hand-built list. Times are seconds on the trace's own clock.

* busy: the union of the intervals in which an operation ran on a chip
  (its "XLA Ops" line), averaged over the chips used;
* an operation's time is its SELF time: a ``while`` or a ``call`` spans
  its body's operations on the same line, and what they cover is theirs;
* an idle gap is a stretch of the traced window with no operation on the
  chip; it is attributed to the ``bench:`` host annotations that overlap
  it (``jax.profiler.TraceAnnotation`` from the benchmark's drivers).
"""

from __future__ import annotations

import contextlib
import dataclasses
import glob
import os
import re
from typing import Dict, List, Tuple

#: (name, start_s, duration_s)
Event = Tuple[str, float, float]
#: sorted, disjoint (start_s, end_s)
Spans = List[Tuple[float, float]]

#: ``%fusion.9 = f32[2048,2048]{...} fusion(...), kind=kOutput, calls=...``:
#: the profiler names a device operation by its whole HLO line
_HLO_NAME = re.compile(r"^%?([^\s=]+)\s*=")
_MODULE_HASH = re.compile(r"\(\d+\)$")

ANNOTATION_PREFIX = "bench:"
OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"
NO_ANNOTATION = "_no_annotation_"


@dataclasses.dataclass
class Raw:
    """What ``load`` found: per chip the operations' events, the host's
    ``bench:`` annotations, and the traced window on the same clock."""

    device_ops: Dict[str, List[Event]]
    annotations: List[Event]
    window: Tuple[float, float]


@contextlib.contextmanager
def traced(trace_dir: str):
    """Trace the body. The window is the body itself, marked by a host
    annotation so that ``load`` finds it on the trace's clock."""
    import jax

    os.makedirs(trace_dir, exist_ok=True)
    options = jax.profiler.ProfileOptions()
    # the Python tracer records every call of the host's Python: millions
    # of events in a few seconds, and a window slowed by a quarter
    options.python_tracer_level = 0
    jax.profiler.start_trace(trace_dir, profiler_options=options)
    try:
        with jax.profiler.TraceAnnotation(ANNOTATION_PREFIX + "window"):
            yield
    finally:
        jax.profiler.stop_trace()


def _events(line) -> List[Event]:
    return [
        (ev.name, ev.start_ns * 1e-9, ev.duration_ns * 1e-9)
        for ev in line.events
    ]


def load(trace_dir: str) -> Raw:
    from jax.profiler import ProfileData

    paths = sorted(glob.glob(
        os.path.join(trace_dir, "plugins", "profile", "*", "*.xplane.pb")
    ))
    if not paths:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    data = ProfileData.from_file(paths[-1])
    device_ops: Dict[str, List[Event]] = {}
    annotations: List[Event] = []
    for plane in data.planes:
        if plane.name.startswith("/device:TPU:"):
            lines = {line.name: line for line in plane.lines}
            if OPS_LINE in lines:
                device_ops[plane.name] = name_ops(
                    _events(lines[OPS_LINE]),
                    _events(lines[MODULES_LINE]) if MODULES_LINE in lines
                    else [],
                )
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                annotations.extend(
                    ev for ev in _events(line)
                    if ev[0].startswith(ANNOTATION_PREFIX)
                )
    window = [a for a in annotations if a[0] == ANNOTATION_PREFIX + "window"]
    if not window:
        raise LookupError("the trace holds no bench:window annotation")
    _, start, dur = window[0]
    return Raw(device_ops, annotations, (start, start + dur))


def name_ops(ops: List[Event], modules: List[Event]) -> List[Event]:
    """Each operation renamed ``<module>/<op>|<its HLO line>``: the jitted
    program it ran in (``jit__bcd_scan_impl``, without the run's hash), its
    HLO name, and — after the bar, for the readers that match on shapes —
    the line as the profiler gave it. ``short`` cuts a name at the bar."""
    import bisect

    modules = sorted(modules, key=lambda m: m[1])
    starts = [m[1] for m in modules]
    out = []
    for name, start, dur in ops:
        module = "_"
        i = bisect.bisect_right(starts, start) - 1
        if i >= 0 and start < modules[i][1] + modules[i][2]:
            module = _MODULE_HASH.sub("", modules[i][0])
        found = _HLO_NAME.match(name)
        op = found.group(1) if found else name
        out.append((f"{module}/{op}|{name}", start, dur))
    return out


def short(name: str) -> str:
    return name.split("|", 1)[0]


def union(intervals: List[Tuple[float, float]]) -> List[Tuple[float, float]]:
    """Sorted, disjoint intervals covering the same points."""
    out: List[Tuple[float, float]] = []
    for start, end in sorted(intervals):
        if out and start <= out[-1][1]:
            if end > out[-1][1]:
                out[-1] = (out[-1][0], end)
        else:
            out.append((start, end))
    return out


def covered(intervals: List[Tuple[float, float]]) -> float:
    return sum(end - start for start, end in union(intervals))


def overlap(a: Spans, b: Spans) -> float:
    """Seconds covered by both of two sorted, disjoint interval lists."""
    total, i, j = 0.0, 0, 0
    while i < len(a) and j < len(b):
        lo, hi = max(a[i][0], b[j][0]), min(a[i][1], b[j][1])
        if hi > lo:
            total += hi - lo
        if a[i][1] < b[j][1]:
            i += 1
        else:
            j += 1
    return total


def clip(intervals, window):
    lo, hi = window
    return [
        (max(s, lo), min(e, hi)) for s, e in intervals
        if min(e, hi) > max(s, lo)
    ]


def self_times(events: List[Event]) -> Dict[str, float]:
    """Seconds by operation name, each event counted for the part of its
    interval that no event nested inside it covers."""
    out: Dict[str, float] = {}
    stack: List[list] = []  # [name, end, self]

    def close(until: float):
        while stack and stack[-1][1] <= until:
            name, _, own = stack.pop()
            out[name] = out.get(name, 0.0) + max(own, 0.0)

    for name, start, dur in sorted(events, key=lambda e: (e[1], -e[2])):
        close(start)
        if stack:
            stack[-1][2] -= dur
        stack.append([name, start + dur, dur])
    close(float("inf"))
    return out


@dataclasses.dataclass
class Reduction:
    busy_s: float
    window_s: float
    #: seconds by operation (self time), summed over the chips used
    op_seconds: Dict[str, float]
    #: idle seconds by ``bench:`` annotation, on the first chip
    idle_seconds: Dict[str, float]
    #: per annotation name, its intervals clipped to the window
    annotations: Dict[str, List[Tuple[float, float]]]
    #: the first chip's busy intervals clipped to the window
    busy: List[Tuple[float, float]]
    chips: int

    def breakdown(self, top: int = 10) -> dict:
        def ranked(table):
            rows = sorted(table.items(), key=lambda kv: -kv[1])[:top]
            return [[name, seconds] for name, seconds in rows if seconds > 0]

        by_short: Dict[str, float] = {}
        for name, seconds in self.op_seconds.items():
            by_short[short(name)] = by_short.get(short(name), 0.0) + seconds
        return {
            "device_ops": ranked(by_short),
            "idle_gaps": ranked(self.idle_seconds),
        }

    def idle_share_under(self, annotation: str):
        """The share of the time under ``annotation`` with no operation on
        the chip, or None where the window has none of it."""
        spans = union(self.annotations.get(annotation, []))
        total = sum(e - s for s, e in spans)
        if total <= 0:
            return None
        return 1.0 - overlap(spans, self.busy) / total

    def seconds_matching(self, pattern) -> float:
        return sum(
            s for name, s in self.op_seconds.items() if pattern.search(name)
        )


def reduce(raw: Raw, chips: int = 1) -> Reduction:
    lo, hi = raw.window
    planes = sorted(raw.device_ops)[: max(chips, 1)]
    if not planes:
        raise LookupError("the trace holds no device operations")
    op_seconds: Dict[str, float] = {}
    busy_by_chip = []
    for plane in planes:
        inside = [
            (n, max(s, lo), min(s + d, hi) - max(s, lo))
            for n, s, d in raw.device_ops[plane] if min(s + d, hi) > max(s, lo)
        ]
        for name, seconds in self_times(inside).items():
            op_seconds[name] = op_seconds.get(name, 0.0) + seconds
        busy_by_chip.append(union([(s, s + d) for _, s, d in inside]))
    busy = busy_by_chip[0]
    gaps, at = [], lo
    for start, end in busy:
        if start > at:
            gaps.append((at, start))
        at = max(at, end)
    if hi > at:
        gaps.append((at, hi))

    annotations: Dict[str, List[Tuple[float, float]]] = {}
    for name, start, dur in raw.annotations:
        if name == ANNOTATION_PREFIX + "window":
            continue
        annotations.setdefault(name, []).extend(
            clip([(start, start + dur)], (lo, hi))
        )
    idle: Dict[str, float] = {}
    everything = []
    for name, spans in annotations.items():
        spans = union(spans)
        everything.extend(spans)
        idle[name] = overlap(spans, gaps)
    idle[NO_ANNOTATION] = (
        sum(e - s for s, e in gaps) - overlap(union(everything), gaps)
    )
    return Reduction(
        busy_s=sum(covered(b) for b in busy_by_chip) / len(planes),
        window_s=hi - lo,
        op_seconds=op_seconds,
        idle_seconds=idle,
        annotations=annotations,
        busy=busy,
        chips=len(planes),
    )
