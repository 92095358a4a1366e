"""Profile-guided-caching feedback loop: estimate vs. observed.

The reference's AutoCacheRule plans caching from EXTRAPOLATED per-node
profiles (linear time/memory-vs-scale fits) and then never checks whether
the estimates held — a mis-extrapolated node silently skews every future
plan. Here the planner records its per-node estimated seconds/bytes into
the tracer (``AutoCacheRule.apply``), the executor's spans record what
each node actually cost, and :func:`cache_audit` joins the two: one row
per estimated node with estimate, observation, and the ratio between
them. ``observed=False`` rows are themselves a finding — the node never
executed under its planned identity (typically a segment absorbed it: its
members run as one ``exec.segment`` span, not as nodes).
"""

from __future__ import annotations

import logging
from typing import Dict, List, Optional

from .tracer import Tracer

logger = logging.getLogger(__name__)


def observed_by_node(tracer: Tracer, start: int = 0) -> Dict[str, dict]:
    """Aggregate executor spans per DAG node id: observed EXCLUSIVE compute
    seconds, max materialized bytes, and hit/miss counts.

    Exclusive matters: evaluation is lazy, so a node's span contains the
    child spans of every upstream thunk it forced — but the planner's
    estimates are per-node. Comparing inclusive observations against
    exclusive estimates would flag every downstream node as
    mis-extrapolated, so each span's direct-children time is subtracted
    first.

    ``start`` restricts the join to spans recorded at index >= start —
    a long-lived process tracer holds every fit's spans, and NodeIds are
    small per-graph ints, so an unwindowed join would merge observations
    across fits and pipelines."""
    spans = tracer.spans()[start:]
    child_seconds: Dict[int, float] = {}
    for sp in spans:
        if sp.parent_id is not None:
            child_seconds[sp.parent_id] = (
                child_seconds.get(sp.parent_id, 0.0) + sp.seconds
            )
    out: Dict[str, dict] = {}
    for sp in spans:
        if sp.node_id is None:
            continue
        row = out.setdefault(
            sp.node_id,
            {
                "label": sp.name,
                "seconds": 0.0,
                "bytes": None,
                "computes": 0,
                "hits": 0,
            },
        )
        if sp.cache == "hit":
            row["hits"] += 1
            continue
        row["seconds"] += max(
            sp.seconds - child_seconds.get(sp.span_id, 0.0), 0.0
        )
        row["computes"] += 1
        if sp.output_bytes is not None:
            row["bytes"] = max(row["bytes"] or 0, sp.output_bytes)
    return out


def segment_member_ids(tracer: Tracer, start: int = 0) -> set:
    """Node ids dispatched INSIDE a compiled segment (``exec.segment``
    spans carry their member ``node_ids``): these nodes never emit their
    own executor span, by design — the audit must not report them as
    mis-planned just because segment dispatch subsumed them."""
    out: set = set()
    for sp in tracer.spans()[start:]:
        if sp.name != "exec.segment":
            continue
        for nid in sp.attrs.get("node_ids") or ():
            out.add(str(nid))
    return out


def _ratio(observed: Optional[float], estimated: Optional[float]) -> Optional[float]:
    if observed is None or not estimated:
        return None
    return round(observed / estimated, 3)


def cache_audit(tracer: Optional[Tracer] = None) -> List[dict]:
    """One row per node the cache planner estimated: estimated vs observed
    seconds/bytes, plus whether the node got a Cacher and whether it was
    observed executing at all. Rows are sorted Cacher-annotated first,
    then by estimated seconds descending."""
    if tracer is None:
        from . import tracer as tracer_mod

        tracer = tracer_mod.current()
    if tracer is None:
        return []
    observed = observed_by_node(tracer)
    in_segment = segment_member_ids(tracer)
    rows = []
    for node_id, est in tracer.estimates.items():
        obs = observed.get(node_id)
        row = {
            "node": node_id,
            "label": est["label"],
            "cacher": est["cacher"],
            # "node" rows come from the cache planner; "solver" rows from
            # the cost-model chooser (solver/estimator nodes are audited
            # too — their estimate is the chooser's predicted fit time)
            "kind": est.get("kind", "node"),
            "est_seconds": est["est_seconds"],
            "obs_seconds": None if obs is None else round(obs["seconds"], 4),
            "seconds_ratio": _ratio(
                None if obs is None else obs["seconds"], est["est_seconds"]
            ),
            "est_bytes": est["est_bytes"],
            "obs_bytes": None if obs is None else obs["bytes"],
            "bytes_ratio": _ratio(
                None if obs is None else obs["bytes"], est["est_bytes"]
            ),
            "cache_hits": 0 if obs is None else obs["hits"],
            "observed": obs is not None,
            # unobserved because a whole-segment dispatch subsumed it —
            # an expected outcome of segment compilation, not a finding
            "segment": obs is None and node_id in in_segment,
        }
        if est.get("kind") == "solver":
            row["solver"] = est.get("solver")
            row["source"] = est.get("source")
            row["alternatives"] = est.get("alternatives")
            solver_est = est.get("solver_est_seconds")
            row["solver_est_seconds"] = solver_est
            row["solver_seconds_ratio"] = _ratio(
                None if obs is None else obs["seconds"], solver_est
            )
        rows.append(row)
    rows.sort(
        key=lambda r: (not r["cacher"], -(r["est_seconds"] or 0.0))
    )
    return rows


def log_cache_audit(tracer: Optional[Tracer] = None) -> List[dict]:
    """Emit the audit at INFO, one line per row; returns the rows."""
    rows = cache_audit(tracer)
    if not rows:
        return rows
    logger.info(
        "autocache audit: %d estimated node(s), %d Cacher-annotated",
        len(rows),
        sum(1 for r in rows if r["cacher"]),
    )
    for r in rows:
        fmt = lambda v, suffix="": "?" if v is None else f"{v:.4g}{suffix}"
        logger.info(
            "  node %-4s %-40s %s est %ss/%sB observed %ss/%sB "
            "(ratio t=%s mem=%s, hits=%d)%s",
            r["node"],
            (
                f"[solver:{r.get('source', '?')}] {r['label']}"
                if r["kind"] == "solver" else r["label"]
            )[:40],
            "[cached]" if r["cacher"] else "        ",
            fmt(r["est_seconds"]),
            fmt(r["est_bytes"]),
            fmt(r["obs_seconds"]),
            fmt(r["obs_bytes"]),
            fmt(r["seconds_ratio"]),
            fmt(r["bytes_ratio"]),
            r["cache_hits"],
            "" if r["observed"] else (
                " SUBSUMED BY SEGMENT (dispatched inside a compiled segment)"
                if r.get("segment")
                else " NEVER OBSERVED (fused away or unexecuted)"
            ),
        )
    return rows
