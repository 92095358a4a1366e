"""Pipeline tracing & per-node profiling.

The observability subsystem the source paper's optimizer implies but
never ships: a :class:`~keystone_tpu.obs.tracer.Tracer` collecting a span
tree across the three execution layers (graph executor pulls, autocache
planning, serving micro-batches), Chrome-trace/Perfetto export, a
plain-text top-N summary, and the estimate-vs-observed autocache audit.

Every instrumentation site is ``with obs.tracer.span(name) as sp:`` (not
re-exported here: ``obs.span`` is the record's module): a
``ks:<name>`` annotation in any profile being taken, and a span in memory
with an installed tracer (``KEYSTONE_TRACE=/path/trace.json`` or the CLI's
``--trace PATH``) or, unsynced, for the length of a profiler session
(``session_spans()``) or of the process's first job (``first_job_spans()``).
With none, the annotation is the whole cost.
"""

from .audit import cache_audit, log_cache_audit
from .context import Sampler, TraceContext, new_trace_id, sample_rate
from .export import (
    format_top_spans,
    stitch_chrome_trace,
    to_chrome_trace,
    wire_spans,
    write_chrome_trace,
    write_stitched_trace,
)
from .flight import FlightRecorder, SITE_INSTANTS
from .flight import recorder as flight_recorder
from .scan import SCAN_LANE_SPAN, SCAN_SPAN, record_scan_span
from .span import Span, cheap_nbytes
from .tracer import (
    Tracer,
    current,
    export,
    first_job_spans,
    install,
    reset,
    session_spans,
    start,
    stop,
    suspended,
)

__all__ = [
    "SCAN_LANE_SPAN",
    "SCAN_SPAN",
    "SITE_INSTANTS",
    "FlightRecorder",
    "Sampler",
    "Span",
    "TraceContext",
    "Tracer",
    "cache_audit",
    "cheap_nbytes",
    "current",
    "flight_recorder",
    "new_trace_id",
    "record_scan_span",
    "export",
    "first_job_spans",
    "format_top_spans",
    "install",
    "log_cache_audit",
    "reset",
    "sample_rate",
    "session_spans",
    "start",
    "stitch_chrome_trace",
    "stop",
    "suspended",
    "to_chrome_trace",
    "wire_spans",
    "write_chrome_trace",
    "write_stitched_trace",
]
