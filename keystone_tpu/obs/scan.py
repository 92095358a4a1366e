"""Scan-pipeline spans: tracer schema for pipelined out-of-core scans.

One ``scan.pipeline`` span per :func:`~keystone_tpu.data.pipeline_scan.
scan_pipeline` scan, covering the whole iteration (first chunk requested
to exhaustion or early close), with the pipeline's counters as span
attrs: host production seconds inside the producer thread, producer-stall
(buffer full — consumer-bound) vs consumer-stall (buffer empty —
producer-bound) seconds, staged H2D bytes, peak buffer occupancy, and
chunk count. The overlap a scan achieved is readable straight off the
span: ``seconds`` ≈ max(producer, consumer) work rather than their sum
when the pipeline is doing its job, and the stall counters say which side
bounded it. ``bin/trace-smoke.sh`` consumes these spans.

Mesh-distributed scans (``lanes > 1``) additionally carry the sharding
schedule: ``lanes``, per-lane chunk/byte totals (``lane_chunks`` /
``lane_bytes`` — skew here is the straggler signal, summarized as
``lane_imbalance`` = max/mean staged bytes), the per-lane ``devices``,
and ``collectives`` — the consumer-reported count of cross-mesh
accumulator reductions and model broadcasts attributed to the scan (the
PAPERS.md #3 gate: O(blocks), never O(chunks); finalize-time reductions
are stamped onto the span after it is recorded). One ``scan.pipeline.lane``
child span per lane nests under the scan span with that lane's device
attribution, so a straggling lane is visible in the trace tree."""

from __future__ import annotations

from .span import Span
from .tracer import current

#: the span name every pipelined scan records
SCAN_SPAN = "scan.pipeline"
#: per-lane child spans of a mesh-distributed scan
SCAN_LANE_SPAN = "scan.pipeline.lane"


def record_scan_span(stats):
    """Record one finished scan's counters as a complete span (plus one
    child span per lane on sharded scans). Returns the scan span so the
    pipeline can stamp late collective counts, or None when tracing is
    off (the usual single ``current() is None`` check)."""
    # scan completion is an allocation peak (staged chunks + accumulator
    # state all live): the memory-watermark seam samples here whether or
    # not tracing is on
    from . import resource as _resource

    _resource.sample_memory()
    tracer = current()
    if tracer is None:
        return None
    attrs = {
        "label": stats.label,
        "chunks": stats.chunks,
        "depth": stats.depth,
        "producer_seconds": round(stats.producer_seconds, 6),
        "producer_stall_seconds": round(stats.producer_stall_seconds, 6),
        "consumer_stall_seconds": round(stats.consumer_stall_seconds, 6),
        "staged_bytes": stats.staged_bytes,
        "occupancy_max": stats.occupancy_max,
    }
    if getattr(stats, "retries", 0):
        # transient-failure retries the scan's budget absorbed — stamped
        # only when nonzero so fault-free traces keep their schema
        attrs["retries"] = stats.retries
    if getattr(stats, "shards", 1) > 1:
        # producer shards (host-side production split over the chunk
        # index space, data/shards.py); per-shard chunk counts are the
        # production-skew signal, same role lane_bytes plays for staging
        attrs["shards"] = stats.shards
        attrs["shard_chunks"] = list(stats.shard_chunks)
    if stats.lanes > 1:
        attrs.update(
            lanes=stats.lanes,
            collectives=stats.collectives,
            lane_chunks=list(stats.lane_chunks),
            lane_bytes=list(stats.lane_bytes),
            devices=list(stats.lane_devices),
        )
        total = sum(stats.lane_bytes)
        if total > 0:
            attrs["lane_imbalance"] = round(
                max(stats.lane_bytes) * stats.lanes / total, 3
            )
    sp = Span(
        name=SCAN_SPAN,
        start=stats.start,
        end=stats.end,
        op_type="ScanPipeline",
        attrs=attrs,
    )
    tracer.record_complete(sp)
    if stats.lanes > 1:
        for lane in range(stats.lanes):
            child = Span(
                name=SCAN_LANE_SPAN,
                start=stats.start,
                end=stats.end,
                parent_id=sp.span_id,
                depth=sp.depth + 1,
                op_type="ScanPipeline",
                attrs={
                    "label": stats.label,
                    "lane": lane,
                    "device": (
                        stats.lane_devices[lane]
                        if lane < len(stats.lane_devices)
                        else ""
                    ),
                    "chunks": (
                        stats.lane_chunks[lane]
                        if lane < len(stats.lane_chunks)
                        else 0
                    ),
                    "staged_bytes": (
                        stats.lane_bytes[lane]
                        if lane < len(stats.lane_bytes)
                        else 0
                    ),
                },
            )
            tracer.record_complete(child)
    return sp
