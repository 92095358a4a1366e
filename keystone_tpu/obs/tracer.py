"""The tracer: thread-safe span collection with a process-global switch.

Parity motivation: KeystoneML's optimizer is profile-guided but its
EXECUTION is blind — per-stage attribution lives in the Spark UI, outside
the system. Here the :class:`Tracer` is that attribution layer: every DAG
node pull, autocache decision, and serving micro-batch lands in one span
registry, exportable as Chrome-trace JSON (``obs/export.py``) and audited
against the cache planner's estimates (``obs/audit.py``).

The one primitive is the module-level :func:`span`. It always enters a
``jax.profiler.TraceAnnotation("ks:" + name)``, so whoever takes a profile
(``jax.profiler.trace``, a capture through ``start_server``, the
benchmark's ``--trace 1``) sees the program's spans on the device's own
timeline. It records a :class:`Span` in memory while SOMEONE IS RECORDING:

* an installed :class:`Tracer` (``KEYSTONE_TRACE=path`` / ``--trace PATH``
  through ``utils/obs.configure``, or a fit's own under a profile store):
  each span costs one dataclass + two clock reads + a device sync at exit,
  which is the point there — ``cost.finalize`` and ``obs/audit.py`` learn
  from synced spans;
* else a profiler session (``TraceAnnotation.is_enabled()``): the spans go
  to one process-wide session recorder that NEVER syncs and never sizes
  outputs (the device trace already knows when the chip ran) and keeps at
  most :data:`SESSION_MAX_SPANS`; :func:`session_spans` returns them after
  the session has ended;
* else, from the start of the process until its first job has closed — the
  first parentless span that has children — a boot recorder, unsynced and
  bounded like the session's: a process that runs ONE job pays its tracing,
  lowering and cache loads there and nowhere else, so that job is always
  kept (:func:`first_job_spans`) and explained in one INFO line.

With none of them — every span after the first job — the annotation (half a
microsecond) is the only cost: no ``Span`` is allocated and the body gets
:data:`NULL_SPAN`.

Every recorded span carries what jax.monitoring said happened between its
entry and its exit (:class:`CompileRecord`): ``compiles``, ``trace_s``,
``lower_s``, ``load_s``, ``cache_hits``.

:func:`current` returns an INSTALLED tracer only: the session recorder is
not "the tracer" to ``fit_instrumentation``, ``AutoCacheRule`` or
``cost.finalize`` (unsynced spans are not costs to learn from).
"""

from __future__ import annotations

import atexit
import contextlib
import itertools
import logging
import threading
import time
from typing import Any, Dict, Iterator, List, Optional

from jax import monitoring as _monitoring
from jax.profiler import TraceAnnotation as _Annotation

from .span import Span, cheap_nbytes, sync_value

logger = logging.getLogger(__name__)

# -- what jax says of its own tracing, lowering and compiling ---------------

#: jax.monitoring's three timed events (their last path segment) and the
#: kind each is kept as. ``load`` is ``compile_or_get_cached``: the compile
#: when cold; the persistent cache's read, deserialise and load when warm.
_KINDS = {
    "jaxpr_trace_duration": "trace",
    "jaxpr_to_mlir_module_duration": "lower",
    "backend_compile_duration": "load",
}


def _cover(covered: List[tuple], start: float, end: float) -> float:
    """Add ``[start, end)`` to the sorted, disjoint ``covered`` and return
    the seconds it adds to their union. Events arrive as they END, so what
    an interval overlaps sits at the list's tail: an outer trace arrives
    after the inner traces it holds and swallows them."""
    if end <= start:
        return 0.0
    added = end - start
    later = []
    while covered and covered[-1][1] > start:
        s, e = covered.pop()
        if s >= end:  # another thread's, which began after this one ended
            later.append((s, e))
            continue
        added -= min(e, end) - max(s, start)
        start, end = min(s, start), max(e, end)
    covered.append((start, end))
    covered.extend(reversed(later))
    return added


class CompileRecord:
    """The seconds and requests jax.monitoring reports, a kind: Python
    traced to a jaxpr (``trace``), the jaxpr lowered to an MLIR module
    (``lower``), the module compiled or answered by the persistent cache
    (``load``). A nested ``jit`` fires its own event inside the outer
    one's interval, so a kind's seconds are the UNION of its events'
    intervals, never their sum. Every span carries the change of these
    between its entry and its exit; they count the whole process, so two
    spans open at once on two threads both see a compile either made."""

    def __init__(self) -> None:
        self._lock = threading.Lock()
        #: a kind's seconds (the union) and its events;
        #: ``requests["load"]`` is every span's ``compiles``
        self.seconds = dict.fromkeys(_KINDS.values(), 0.0)
        self.requests = dict.fromkeys(_KINDS.values(), 0)
        #: requests the persistent cache answered, and its seconds reading
        #: them (inside ``load_s``)
        self.cache_hits = 0
        self.cache_read_s = 0.0
        #: a kind's disjoint intervals: one entry a top-level trace, lower
        #: or compile — they grow with what jax compiles, not with jobs
        self._covered: Dict[str, List[tuple]] = {
            kind: [] for kind in _KINDS.values()
        }
        #: ``{fun_name: {kind: [requests, seconds]}}``, a program's own
        #: events summed (an outer function's holds what it traced inside)
        self._by_fun: Dict[str, Dict[str, list]] = {}

    def on_time_span(
        self, event: str, start: float, end: float, fun_name: str = "", **kw
    ) -> None:
        kind = _KINDS.get(event.rsplit("/", 1)[-1])
        if kind is None:
            return
        # tracing names the function ``f``, lowering and compiling its
        # module ``jit(f)``: one row a program
        fun = str(fun_name)
        if fun.endswith(")") and "(" in fun:
            fun = fun[fun.index("(") + 1 : -1]
        with self._lock:
            self.requests[kind] += 1
            self.seconds[kind] += _cover(self._covered[kind], start, end)
            row = self._by_fun.get(fun)
            if row is None:
                row = self._by_fun[fun] = {
                    k: [0, 0.0] for k in _KINDS.values()
                }
            row[kind][0] += 1
            row[kind][1] += end - start

    def on_duration(self, event: str, duration: float, **kw) -> None:
        if event.endswith("compilation_cache/cache_retrieval_time_sec"):
            with self._lock:
                self.cache_read_s += duration

    def on_event(self, event: str, **kw) -> None:
        if event.endswith("compilation_cache/cache_hits"):
            with self._lock:
                self.cache_hits += 1

    def programs(self, since: Optional[dict] = None) -> Dict[str, dict]:
        """The table by ``fun_name`` as ``{fun: {kind: (requests,
        seconds)}}``: all of it, or what was added since an earlier
        reading of it."""
        with self._lock:
            table = {
                fun: {kind: tuple(cell) for kind, cell in row.items()}
                for fun, row in self._by_fun.items()
            }
        if since is None:
            return table
        out = {}
        for fun, row in table.items():
            was = since.get(fun)
            if was is not None:
                row = {
                    kind: (n - was[kind][0], s - was[kind][1])
                    for kind, (n, s) in row.items()
                }
            if any(n for n, _ in row.values()):
                out[fun] = row
        return out


#: the process's one record. Listeners cannot be told apart once
#: registered, so it listens from import on, for the process's life; they
#: run only when jax traces or compiles — nothing on a warm job's path.
_record = CompileRecord()
_monitoring.register_event_time_span_listener(_record.on_time_span)
_monitoring.register_event_duration_secs_listener(_record.on_duration)
_monitoring.register_event_listener(_record.on_event)


def compile_record() -> CompileRecord:
    """The process's record of what jax traced, lowered and compiled."""
    return _record


# -- content-digest counting ------------------------------------------------

#: process-wide counts of ``utils/params.content_digest``'s work: the bytes
#: it hashed and the digests it answered from memory. Like the compile
#: count, every span carries the delta between its entry and its exit.
_digest_lock = threading.Lock()
_digest_bytes = 0
_digest_hits = 0


def count_digest(nbytes: int = 0, hits: int = 0) -> None:
    global _digest_bytes, _digest_hits
    with _digest_lock:
        _digest_bytes += nbytes
        _digest_hits += hits


# -- the tracer -------------------------------------------------------------


class Tracer:
    """Collects a span tree per thread; thread-safe for concurrent writers
    (the serving worker and N pipeline threads trace into one registry)."""

    def __init__(
        self, *, sync: bool = True, max_spans: Optional[int] = None
    ) -> None:
        #: block on a span's ``sync_on`` target (and size it) at exit
        self.sync = sync
        #: keep at most this many spans; later ones count as ``dropped``
        self.max_spans = max_spans
        self.dropped = 0
        self._lock = threading.Lock()
        self._spans: List[Span] = []
        self._ids = itertools.count(1)
        self._local = threading.local()
        self.epoch = time.perf_counter()
        self.epoch_unix = time.time()
        #: node_id -> estimate row recorded by the autocache planner
        #: (see obs/audit.py for the estimate-vs-observed feedback loop)
        self._estimates: Dict[str, dict] = {}
        #: bumped at each optimizer pass (RuleExecutor.execute): NodeIds
        #: are small per-graph ints, so a long-lived tracer must not merge
        #: a NEW pass's estimate for id "3" into a PREVIOUS pipeline's row
        self._plan_epoch = 0
        #: spans discarded below _spans[0] (discard_through): cursors
        #: from spans_since stay valid GLOBAL indices across compaction
        self._span_offset = 0

    # -- span recording -------------------------------------------------

    def _stack(self) -> List[Span]:
        stack = getattr(self._local, "stack", None)
        if stack is None:
            stack = self._local.stack = []
        return stack

    def current_span(self) -> Optional[Span]:
        """This thread's innermost open span, or None. The concurrent
        executor captures it as the explicit parent for worker threads."""
        stack = self._stack()
        return stack[-1] if stack else None

    @contextlib.contextmanager
    def adopt(self, parent: Optional[Span]) -> Iterator[None]:
        """Explicit cross-thread parent linking: make ``parent`` (a span
        opened on ANOTHER thread) the current parent on THIS thread. The
        per-thread stacks give a correct tree only for same-thread nesting;
        a scheduler worker forcing a DAG node starts with an empty stack,
        so without adoption its node spans would all be roots. ``parent``
        is pushed but never recorded here — its opener owns its exit."""
        if parent is None:
            yield
            return
        stack = self._stack()
        stack.append(parent)
        try:
            yield
        finally:
            stack.pop()

    def _keep(self, sp: Span) -> None:
        with self._lock:
            if self.max_spans is None or len(self._spans) < self.max_spans:
                self._spans.append(sp)
            else:
                self.dropped += 1

    def _open(
        self,
        name: str,
        *,
        node_id: Optional[str] = None,
        op_type: Optional[str] = None,
        cache: Optional[str] = None,
        **attrs,
    ) -> Span:
        stack = self._stack()
        thread = threading.current_thread()
        sp = Span(
            name=name,
            start=time.perf_counter(),
            span_id=next(self._ids),
            parent_id=stack[-1].span_id if stack else None,
            depth=len(stack),
            tid=thread.ident or 0,
            thread_name=thread.name,
            node_id=node_id,
            op_type=op_type,
            cache=cache,
            attrs=attrs,
        )
        # the counts at entry, negated: _close adds the counts at exit,
        # which leaves what happened inside the span (``compiles``: the
        # compile REQUESTS, those the persistent cache answered included)
        rec, seconds = _record, _record.seconds
        sp.compiles, sp.cache_hits = -rec.requests["load"], -rec.cache_hits
        sp.trace_s, sp.lower_s, sp.load_s = (
            -seconds["trace"], -seconds["lower"], -seconds["load"]
        )
        sp.digest_bytes, sp.digest_hits = -_digest_bytes, -_digest_hits
        stack.append(sp)
        return sp

    def _close(self, sp: Span) -> None:
        self._stack().pop()
        target = sp.sync_target
        if target is not None:
            sp.sync_target = None
            if self.sync:
                t0 = time.perf_counter()
                if sync_value(target):
                    sp.sync_seconds = time.perf_counter() - t0
                if sp.output_bytes is None:
                    sp.output_bytes = cheap_nbytes(target)
        sp.end = time.perf_counter()
        rec, seconds = _record, _record.seconds
        sp.compiles += rec.requests["load"]
        sp.cache_hits += rec.cache_hits
        sp.trace_s += seconds["trace"]
        sp.lower_s += seconds["lower"]
        sp.load_s += seconds["load"]
        sp.digest_bytes += _digest_bytes
        sp.digest_hits += _digest_hits
        self._keep(sp)

    @contextlib.contextmanager
    def span(self, name: str, **kw) -> Iterator[Span]:
        """Open a span IN THIS TRACER (``node_id``, ``op_type``, ``cache``
        and free attrs as keywords); the yielded handle takes extra attrs
        and an optional ``sync_on(value)`` target blocked on at exit.
        Instrumentation sites use the module-level :func:`span`."""
        sp = self._open(name, **kw)
        try:
            yield sp
        finally:
            self._close(sp)

    def instant(
        self,
        name: str,
        *,
        node_id: Optional[str] = None,
        op_type: Optional[str] = None,
        cache: Optional[str] = None,
        **attrs,
    ) -> Span:
        """A zero-duration event (e.g. a memo-cache hit)."""
        stack = self._stack()
        thread = threading.current_thread()
        now = time.perf_counter()
        sp = Span(
            name=name,
            start=now,
            end=now,
            span_id=next(self._ids),
            parent_id=stack[-1].span_id if stack else None,
            depth=len(stack),
            tid=thread.ident or 0,
            thread_name=thread.name,
            node_id=node_id,
            op_type=op_type,
            cache=cache,
            instant=True,
            attrs=dict(attrs),
        )
        self._keep(sp)
        return sp

    def record_complete(self, sp: Span) -> None:
        """Append an externally-built, already-finished span (used by the
        executor for eagerly-computed expressions). Fills in identity and
        tree position from the calling thread's open span, if any."""
        stack = self._stack()
        thread = threading.current_thread()
        sp.span_id = next(self._ids)
        if sp.parent_id is None and stack:
            sp.parent_id = stack[-1].span_id
            sp.depth = len(stack)
        sp.tid = thread.ident or 0
        sp.thread_name = thread.name
        self._keep(sp)

    # -- reads ----------------------------------------------------------

    def spans(self) -> List[Span]:
        with self._lock:
            return list(self._spans)

    def spans_since(self, cursor: int):
        """``(spans[cursor:], new_cursor)`` — the incremental read the
        cluster worker uses to ship each recorded span back to the
        router exactly once. Cursors are GLOBAL indices (monotonic
        across :meth:`discard_through` compaction), so a bookmark taken
        before a discard still resolves to only-unshipped spans."""
        with self._lock:
            n = self._span_offset + len(self._spans)
            start = max(cursor - self._span_offset, 0)
            return self._spans[start:], n

    def discard_through(self, cursor: int) -> int:
        """Drop spans below global index ``cursor`` (they were shipped to
        another process that now owns them). This is what keeps a
        long-lived ALWAYS-ON traced worker bounded: without it the
        append-only registry grows one Span per hop forever. Returns the
        count discarded. Local reads (``spans()``/``span_summary``) see
        only the retained window afterwards — the shipper is the
        archive."""
        with self._lock:
            k = min(max(cursor - self._span_offset, 0), len(self._spans))
            if k:
                del self._spans[:k]
                self._span_offset += k
            return k

    def span_summary(
        self, prefix: Optional[str] = None
    ) -> Dict[str, Dict[str, object]]:
        """``{name: {"seconds", "calls", ...}}`` — the SAME shape as
        ``utils.timing.snapshot`` and ``MetricsRegistry.snapshot()["phases"]``
        so span, phase, and metrics exports concatenate without schema
        mismatches. ``prefix`` filters to one subsystem (e.g. ``"serve."``)."""
        agg: Dict[str, dict] = {}
        for sp in self.spans():
            if prefix is not None and not sp.name.startswith(prefix):
                continue
            row = agg.setdefault(
                sp.name,
                {
                    "seconds": 0.0,
                    "calls": 0,
                    "sync_seconds": 0.0,
                    "bytes": 0,
                    "compiles": 0,
                    "trace_s": 0.0,
                    "lower_s": 0.0,
                    "load_s": 0.0,
                    # the persistent cache's; ``cache_hits`` is the memo's
                    "compile_cache_hits": 0,
                    "cache_hits": 0,
                    "cache_misses": 0,
                },
            )
            row["calls"] += 1
            if sp.cache == "hit":
                row["cache_hits"] += 1
                continue
            if sp.cache == "miss":
                row["cache_misses"] += 1
            row["seconds"] += sp.seconds
            row["sync_seconds"] += sp.sync_seconds
            row["compiles"] += sp.compiles
            row["trace_s"] += sp.trace_s
            row["lower_s"] += sp.lower_s
            row["load_s"] += sp.load_s
            row["compile_cache_hits"] += sp.cache_hits
            if sp.output_bytes:
                row["bytes"] = max(row["bytes"], sp.output_bytes)
        for row in agg.values():
            for key in ("seconds", "sync_seconds", "trace_s", "lower_s",
                        "load_s"):
                row[key] = round(row[key], 4)
        return dict(sorted(agg.items()))

    # -- autocache estimates (see obs/audit.py) -------------------------

    def record_node_estimate(
        self,
        node_id: str,
        label: str,
        est_seconds: Optional[float] = None,
        est_bytes: Optional[float] = None,
        cacher: bool = False,
        **extras,
    ) -> None:
        """Record one planner estimate for a DAG node. ``extras`` carry
        planner-specific context into the audit rows verbatim — e.g. the
        solver chooser's ``kind="solver"``, chosen class, pricing
        ``source``, and per-option ``alternatives``. Re-recording the
        same node id within ONE planning pass overwrites (last planner
        wins), preserving any prior extras the new record doesn't name;
        a row left over from an EARLIER pass (same small-int node id,
        different graph) is replaced wholesale so stale solver extras
        can't leak into the new pipeline's audit."""
        with self._lock:
            row = self._estimates.get(str(node_id), {})
            if row.get("_epoch") != self._plan_epoch:
                row = {}
            row.update(
                {
                    "label": label,
                    "est_seconds": est_seconds,
                    "est_bytes": est_bytes,
                    "cacher": bool(cacher),
                    "_epoch": self._plan_epoch,
                    **extras,
                }
            )
            self._estimates[str(node_id)] = row

    def begin_plan_epoch(self) -> None:
        """Mark the start of a new optimizer planning pass (see
        :meth:`record_node_estimate`)."""
        with self._lock:
            self._plan_epoch += 1

    @property
    def estimates(self) -> Dict[str, dict]:
        with self._lock:
            return {
                k: {kk: vv for kk, vv in row.items() if kk != "_epoch"}
                for k, row in self._estimates.items()
            }


# -- process-global wiring --------------------------------------------------

_current: Optional[Tracer] = None
_export_path: Optional[str] = None
_atexit_registered = False
#: spans already written by an explicit export — lets the atexit backstop
#: skip the rewrite (and the duplicate summary/audit logs) when nothing
#: new was recorded since
_exported_span_count: Optional[int] = None
_suspend = threading.local()


def current() -> Optional[Tracer]:
    """The installed tracer, or None (tracing disabled — the fast path).
    Thread-locally None inside a :func:`suspended` block."""
    if getattr(_suspend, "depth", 0):
        return None
    return _current


# -- the span primitive ------------------------------------------------------

#: the session recorder's bound: a fit job leaves about a hundred spans, so
#: this holds minutes of back-to-back jobs and a forgotten session stays small
SESSION_MAX_SPANS = 65536
#: the boot recorder's: the largest job of the benchmark leaves a few hundred
BOOT_MAX_SPANS = 8192

_session: Optional[Tracer] = None
_session_live = False
_session_lock = threading.Lock()


class _NullAttrs(dict):
    """``sp.attrs[...] = v`` / ``sp.attrs.update(...)`` on a span nobody
    records: accepted and dropped."""

    def __setitem__(self, key, value) -> None:
        pass

    def update(self, *args, **kw) -> None:
        pass


class _NullSpan:
    """What :func:`span` hands its body when nothing records."""

    __slots__ = ()
    attrs = _NullAttrs()

    def sync_on(self, value: Any) -> None:
        pass


NULL_SPAN = _NullSpan()


class _BootRecorder(Tracer):
    """Keeps a process's first job: every span from the start of the
    process until the first parentless span that has children has closed.
    Like the session recorder it never syncs, never sizes outputs and is
    bounded. It learns no span name: a lone parentless span ahead of the
    job (a label upload) is kept and ends nothing."""

    def __init__(self) -> None:
        super().__init__(sync=False, max_spans=BOOT_MAX_SPANS)
        #: the compile record's table by ``fun_name`` over the root span
        self.programs: Dict[str, dict] = {}
        self._programs_at_open: Dict[int, dict] = {}

    def _open(self, name: str, **kw) -> Span:
        sp = super()._open(name, **kw)
        if sp.parent_id is None:
            self._programs_at_open[sp.span_id] = _record.programs()
        return sp

    def _close(self, sp: Span) -> None:
        super()._close(sp)
        if sp.parent_id is not None:
            return
        at_open = self._programs_at_open.pop(sp.span_id, {})
        spans = self.spans()
        # a recorder that overflowed ends with its next root, children seen
        # or not: the flag must come down in a process that has no job
        if self.dropped or any(c.parent_id == sp.span_id for c in spans):
            _end_boot(self, sp, _record.programs(since=at_open))


def _end_boot(boot: _BootRecorder, root: Span, programs: dict) -> None:
    """The first job has closed: the flag comes down for the life of the
    process, and the job is explained once, at INFO."""
    global _boot_live
    with _session_lock:
        if boot is not _boot or not _boot_live:
            return
        _boot_live = False
    boot.programs = programs
    if logger.isEnabledFor(logging.INFO):
        from .export import format_first_job

        logger.info("%s", format_first_job(boot.spans(), programs, root))


_boot = _BootRecorder()
_boot_live = True


def _recorder() -> Optional[Tracer]:
    """Who keeps a span opened now on this thread: the installed tracer,
    else the session recorder while a profiler session records, else the
    boot recorder until the process's first job has closed, else nobody.
    A session that begins after one has ended starts a new list."""
    global _session, _session_live
    if getattr(_suspend, "depth", 0):
        return None
    if _current is not None:
        return _current
    if not _Annotation.is_enabled():
        _session_live = False
        return _boot if _boot_live else None
    if not _session_live:
        with _session_lock:
            if not _session_live:
                _session = Tracer(sync=False, max_spans=SESSION_MAX_SPANS)
                _session_live = True
    return _session


def session_spans() -> List[Span]:
    """The spans of the newest profiler session (readable after it has
    ended; ``[]`` before the first)."""
    return [] if _session is None else _session.spans()


def first_job_spans() -> List[Span]:
    """What the boot recorder kept: the process's first parentless span
    that had children, with all of them, and any childless parentless span
    ahead of it; nothing after it. While an installed tracer or a profiler
    session records, the boot recorder waits: it keeps the first job that
    nobody else took."""
    return _boot.spans()


def first_job_programs() -> Dict[str, dict]:
    """``{fun_name: {kind: (requests, seconds)}}`` of what jax traced,
    lowered and compiled inside the first job's root span; ``{}`` until it
    has closed."""
    return _boot.programs


class span:
    """``with span("block_ls.solve", n=n) as sp:`` — the program's one way
    to mark a region: a ``ks:<name>`` annotation in whatever profile is
    being taken, and a :class:`Span` with whoever records (module doc).
    ``sp.sync_on(value)`` names what an installed tracer blocks on at
    exit; a no-op where nothing syncs. Not for per-item or per-request
    loops (those build finished spans: :meth:`Tracer.record_complete`)."""

    __slots__ = ("_name", "_kw", "_annotation", "_tracer", "_span")

    def __init__(self, name: str, **kw) -> None:
        self._name = name
        self._kw = kw

    def __enter__(self):
        self._annotation = _Annotation("ks:" + self._name)
        self._annotation.__enter__()
        self._tracer = _recorder()
        if self._tracer is None:
            return NULL_SPAN
        self._span = self._tracer._open(self._name, **self._kw)
        return self._span

    def __exit__(self, *exc) -> bool:
        if self._tracer is not None:
            self._tracer._close(self._span)
        self._annotation.__exit__(*exc)
        return False


#: what a suspended thread hands its workers
_SUSPENDED = object()


def handoff() -> Any:
    """What a thread that starts workers hands them so that their spans
    nest under its open span — and stay out of the record where the
    caller is :func:`suspended`: the recorder with the caller's innermost
    open span, or None when nothing records. Opaque: for :func:`adopt`."""
    if getattr(_suspend, "depth", 0):
        return _SUSPENDED
    tracer = _recorder()
    if tracer is None:
        return None
    return tracer, tracer.current_span()


@contextlib.contextmanager
def adopt(token: Any) -> Iterator[None]:
    """Run the body on THIS thread under the :func:`handoff` of another."""
    if token is None:
        yield
    elif token is _SUSPENDED:
        with suspended():
            yield
    else:
        tracer, parent = token
        with tracer.adopt(parent):
            yield


def install(tracer: Tracer) -> Tracer:
    global _current
    _current = tracer
    return tracer


_install_lock = threading.Lock()


def install_if_absent(tracer: Tracer) -> Optional[Tracer]:
    """Install ``tracer`` only if no tracer is currently installed;
    returns it if installed, None if another tracer already holds the
    slot. Lets concurrent fit-local observation windows (Pipeline.fit
    with a profile store) race safely: exactly one wins the slot."""
    global _current
    with _install_lock:
        if _current is not None:
            return None
        _current = tracer
        return tracer


def uninstall(tracer: Tracer) -> bool:
    """Remove ``tracer`` only if it is still the installed one; returns
    whether it was removed. The safe inverse of :func:`install_if_absent`
    — never tears down a tracer some other thread installed later."""
    global _current
    with _install_lock:
        if _current is not tracer:
            return False
        _current = None
        return True


def start(path: Optional[str] = None) -> Tracer:
    """Install a process tracer (idempotent: an existing tracer is kept so
    repeated ``configure`` calls don't drop collected spans). ``path``
    arms the atexit Chrome-trace export."""
    global _current, _export_path, _atexit_registered
    if _current is None:
        _current = Tracer()
    if path:
        _export_path = path
        if not _atexit_registered:
            _atexit_registered = True
            atexit.register(_atexit_export)
    return _current


def stop() -> Optional[Tracer]:
    """Uninstall and return the tracer (spans stay readable on the
    returned object)."""
    global _current
    tracer, _current = _current, None
    return tracer


def reset() -> None:
    """Drop the installed tracer, the session recorder AND the export
    path, and arm the boot recorder anew (test hygiene)."""
    global _current, _export_path, _exported_span_count
    global _session, _session_live, _boot, _boot_live
    _current = None
    _session, _session_live = None, False
    _boot, _boot_live = _BootRecorder(), True
    _export_path = None
    _exported_span_count = None


@contextlib.contextmanager
def suspended() -> Iterator[None]:
    """Temporarily disable span recording ON THIS THREAD, for the installed
    tracer and the session recorder alike (the ``ks:`` annotations stay) —
    used around the autocache PROFILING runs so sampled-scale executions
    don't pollute the real trace (their node ids would collide with the
    production pull's). Thread-local so a serving worker tracing
    micro-batches is unaffected by a concurrent fit's profiling window;
    workers a suspended thread starts inherit it through :func:`handoff`."""
    _suspend.depth = getattr(_suspend, "depth", 0) + 1
    try:
        yield
    finally:
        _suspend.depth -= 1


def _atexit_export() -> None:
    """The exit backstop: write only if spans arrived since the last
    explicit export — a CLI run that already exported in its ``finally``
    must not rewrite the file and double-log the summary + audit."""
    if _current is None:
        return
    if _exported_span_count == len(_current.spans()):
        return
    export()


def export(path: Optional[str] = None) -> Optional[str]:
    """Write the Chrome trace for the installed tracer to ``path`` (or the
    path ``start`` armed), log the top-N span summary and the autocache
    estimate-vs-observed audit. No-op (returns None) when tracing is off
    or no path is configured. Safe under atexit: IO failures log a
    warning instead of raising into interpreter shutdown."""
    global _exported_span_count
    tracer = _current
    path = path or _export_path
    if tracer is None or path is None:
        return None
    _exported_span_count = len(tracer.spans())
    from .audit import log_cache_audit
    from .export import format_top_spans, write_chrome_trace

    try:
        write_chrome_trace(tracer, path)
    except OSError:
        logger.warning("trace export to %s failed", path, exc_info=True)
        return None
    logger.info(
        "trace: %d spans -> %s\n%s",
        len(tracer.spans()),
        path,
        format_top_spans(tracer),
    )
    log_cache_audit(tracer)
    return path
