"""A total over the program's spans of one name in the traced window
(``keystone_tpu.obs.tracer.session_spans()``: the session's recorder holds
exactly that window): ``field`` is ``seconds`` (a span's duration) or
``compiles`` (the compile requests inside it, cache hits included);
``how`` is ``mean`` (the sum over the spans, a span) or ``median``.
Durations need no second clock, so nothing is aligned here. Where the
program keeps no such spans the reader finds nothing to read."""

from __future__ import annotations

import statistics
from typing import List, Optional

from benchmark.readers import span_idle


def total(values: List[float], how: str) -> Optional[float]:
    if not values:
        return None
    if how == "median":
        return float(statistics.median(values))
    if how == "mean":
        return sum(values) / len(values)
    raise ValueError(f"span_total: how is 'mean' or 'median', not {how!r}")


def read(params: dict, run):
    spans = span_idle.program_spans()
    if spans is None:
        return None
    field = params["field"]
    if field not in ("seconds", "compiles"):
        raise ValueError(f"span_total: no field {field!r}")
    values = [
        float(getattr(sp, field)) for sp in spans if sp.name == params["span"]
    ]
    value = total(values, params["how"])
    return None if value is None else params.get("scale", 1.0) * value
