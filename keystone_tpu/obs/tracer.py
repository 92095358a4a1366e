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
  the session has ended.

With neither, the annotation (half a microsecond) is the only cost: no
``Span`` is allocated and the body gets :data:`NULL_SPAN`.

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

from jax.profiler import TraceAnnotation as _Annotation

from .span import Span, cheap_nbytes, sync_value

logger = logging.getLogger(__name__)

# -- XLA compile counting ---------------------------------------------------

#: process-wide count of XLA backend compile requests (persistent-cache
#: hits included), fed by jax.monitoring.
#: Listeners cannot be unregistered individually, so this installs once
#: (lazily, on first Tracer construction) and stays for the process life;
#: the increment is negligible and only spans read the counter.
_compiles = itertools.count()
_compiles_seen = 0
_compile_listener_lock = threading.Lock()
_compile_listener_installed = False


def _compile_count() -> int:
    return _compiles_seen


def _install_compile_listener() -> None:
    global _compile_listener_installed
    with _compile_listener_lock:
        if _compile_listener_installed:
            return
        _compile_listener_installed = True
    try:
        from jax import monitoring

        def _on_duration(event: str, duration: float, **kw) -> None:
            # one /jax/core/compile/backend_compile_duration per compile
            # REQUEST: jax times compile_or_get_cached, so a request the
            # persistent cache answers fires it too (and additionally a
            # /jax/compilation_cache/cache_hits event)
            if event.endswith("backend_compile_duration"):
                global _compiles_seen
                _compiles_seen = next(_compiles) + 1

        monitoring.register_event_duration_secs_listener(_on_duration)
    except Exception:
        logger.debug("jax compile-event listener unavailable", exc_info=True)


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
        _install_compile_listener()

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
        # the count at entry, negated: _close adds the count at exit, which
        # leaves the compile REQUESTS inside the span (cache hits included)
        sp.compiles = -_compile_count()
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
        sp.compiles += _compile_count()
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
            if sp.output_bytes:
                row["bytes"] = max(row["bytes"], sp.output_bytes)
        for row in agg.values():
            row["seconds"] = round(row["seconds"], 4)
            row["sync_seconds"] = round(row["sync_seconds"], 4)
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


def _recorder() -> Optional[Tracer]:
    """Who keeps a span opened now on this thread: the installed tracer,
    else the session recorder while a profiler session records, else
    nobody. A session that begins after one has ended starts a new list."""
    global _session, _session_live
    if getattr(_suspend, "depth", 0):
        return None
    if _current is not None:
        return _current
    if not _Annotation.is_enabled():
        _session_live = False
        return None
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
    path (test hygiene)."""
    global _current, _export_path, _exported_span_count
    global _session, _session_live
    _current = None
    _session, _session_live = None, False
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
