"""One module a reader. ``read(params, run)`` takes a per-layer metric
from what the run gathered — the trace's reduction, the program's spans,
its counters, the driver's own facts — and returns a number, or None
where it finds nothing to read: the harness then leaves the metric out of
the line. A reader never returns 0 for a share of a roofline or a peak."""
