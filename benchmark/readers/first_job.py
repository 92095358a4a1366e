"""A number of the process's first job: set-up's warm-up fit, which runs
before the traced window and which the program's boot recorder keeps
(``keystone_tpu.obs.tracer.first_job_spans()``). The job is the first span
named ``root`` there. ``field`` is one of the seconds a span carries of
what ``jax.monitoring`` reported inside it — ``trace_s``, ``lower_s``,
``load_s``, the union of the events' intervals a kind — or ``extra_s``: the
job's seconds less the median seconds of the traced window's ``root`` spans
(``session_spans()``), what a process that runs one job pays over a warm
one. Where the program keeps no first job — a commit from before the boot
recorder — or, for ``extra_s``, the window holds no ``root`` span, the
reader finds nothing to read."""

from __future__ import annotations

import statistics

from benchmark.readers import span_idle

FIELDS = ("trace_s", "lower_s", "load_s")


def first_job(root: str):
    """The first span named ``root`` that the boot recorder kept, or None."""
    try:
        from keystone_tpu.obs import tracer
    except ImportError:
        return None
    read = getattr(tracer, "first_job_spans", None)
    if read is None:
        return None
    return next((sp for sp in read() if sp.name == root), None)


def read(params: dict, run):
    field = params["field"]
    if field != "extra_s" and field not in FIELDS:
        raise ValueError(f"first_job: no field {field!r}")
    job = first_job(params["root"])
    if job is None:
        return None
    if field != "extra_s":
        return float(getattr(job, field))
    window = [
        sp.seconds for sp in span_idle.program_spans() or []
        if sp.name == params["root"]
    ]
    if not window:
        return None
    return job.seconds - statistics.median(window)
