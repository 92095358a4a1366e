"""Segment planning: partition the DAG into maximal traceable segments
between materialization barriers.

This is the compilation-unit plan the ROADMAP's whole-DAG native
compilation item needs: each segment is a connected sub-DAG every node of
which could lower into ONE fused XLA program, and each barrier is a point
where data must materialize — a Cacher (the result must hit the state
table / HBM pin; one whose value the device cannot hold is ``declined``
and is a member, an identity inside the program: the value is computed
again where it is read, as an RDD's dropped partition is), an out-of-core
scan seam (chunked leaves produce data
chunk-at-a-time), a host-side node (opaque / callback / stateful), an
estimator boundary (fit-time solve), a saveable prefix (its result must
hit the state table), a ``no_fuse`` node (dataset-sized operands must not
become a program's literals), or a gather join whose zipped value must
leave the program (a reader that is no member, or a sink).

This is the ONE grouping decision: ``Pipeline.check()`` / ``--check``
report the plan, and the executor lowers and dispatches exactly these
segments (``compile/segment.py``).
"""

from __future__ import annotations

import logging
from dataclasses import dataclass, field
from typing import Any, Dict, List, Optional, Tuple

from . import lattice
from .abstract import Spec, SpecTuple

logger = logging.getLogger(__name__)

#: barrier reasons
BARRIER_CACHER = "cacher"
BARRIER_SCAN_SEAM = "scan_seam"
BARRIER_HOST = "host"
BARRIER_ESTIMATOR = "estimator"
BARRIER_GATHER = "gather_join"
BARRIER_SAVED = "saved_state"
BARRIER_DATA = "data_leaf"
BARRIER_NO_FUSE = "no_fuse"


@dataclass
class Segment:
    """One maximal traceable sub-DAG between barriers."""

    index: int
    nodes: List[Any] = field(default_factory=list)  # topo order
    #: external inputs (barrier nodes / sources) this segment reads, in
    #: topological (linearization) order of the producing node — a PINNED
    #: contract: segment fingerprints and lowered-function signatures are
    #: positional over this list, so the order must be stable across
    #: processes (insertion order over members was not, since member
    #: iteration depends on union-find grouping)
    inputs: List[Any] = field(default_factory=list)
    #: nodes whose value leaves the segment (consumed outside / by a sink)
    outputs: List[Any] = field(default_factory=list)
    #: estimated bytes ONE item generates across this segment's node
    #: outputs (per-item pricing: specs first, cost-model evidence where
    #: the spec is unknown); None when nothing was estimable
    est_item_bytes: Optional[int] = None

    def __len__(self) -> int:
        return len(self.nodes)


def barrier_reason(
    op: Any, verdict: str, *, is_chunked_leaf: bool = False
) -> Optional[str]:
    """Why ``op`` is a materialization barrier, or None (segment-eligible).

    Barrier-ness is orthogonal to the verdict for Cachers (their traced
    form is identity — traceable — but their *purpose* is to
    materialize). A gather join is a barrier HERE, where only the operator
    is seen; :func:`plan_segments`, which sees its readers, makes it a
    member (a tuple inside the program) where every reader is one."""
    from ..workflow.operators import (
        DatasetOperator,
        DatumOperator,
        DelegatingOperator,
        EstimatorOperator,
        ExpressionOperator,
        GatherTransformerOperator,
    )

    if isinstance(op, (DatasetOperator, DatumOperator)):
        return BARRIER_SCAN_SEAM if is_chunked_leaf else BARRIER_DATA
    if isinstance(op, ExpressionOperator):
        return BARRIER_SAVED
    if isinstance(op, (DelegatingOperator, EstimatorOperator)):
        return BARRIER_ESTIMATOR
    if isinstance(op, GatherTransformerOperator):
        return BARRIER_GATHER
    if type(op).__name__ == "Cacher":
        return BARRIER_CACHER
    if getattr(op, "no_fuse", False):
        return BARRIER_NO_FUSE
    if lattice.blocks_jit(verdict) or verdict == lattice.HOST_CALLBACK:
        return BARRIER_HOST
    return None


def _spec_item_bytes(av: Any) -> Optional[int]:
    if isinstance(av, Spec):
        return av.item_bytes()
    if isinstance(av, SpecTuple):
        parts = [_spec_item_bytes(e) for e in av.elems]
        known = [p for p in parts if p is not None]
        return sum(known) if known else None
    return None


def plan_segments(
    graph: Any,
    verdicts: Dict[Any, str],
    specs: Dict[Any, Any],
    *,
    cost_estimator: Any = None,
    materialized: Any = (),
    annotations: Any = (),
    declined: Any = (),
) -> Tuple[List[Segment], Dict[Any, str]]:
    """Partition ``graph`` into maximal traceable segments.

    Returns ``(segments, barriers)`` where ``barriers`` maps each
    non-segment node to its reason. Segments are connected components of
    the segment-eligible node set under graph edges, numbered in
    topological order of their first node. ``materialized`` are nodes whose
    value the caller already holds (an executor's memo): data, like saved
    state — a segment through one would compute it again from its inputs.
    ``annotations`` are the optimizer's saveable prefixes: such a node's
    result must hit the state table, so it bounds segments too.
    ``declined`` are Cachers whose value the caller cannot hold
    (``compile/segment.py:unheld_caches``): no barrier, a member.
    """
    from ..workflow import analysis
    from ..workflow.graph import NodeId

    full_order = list(analysis.linearize(graph))
    #: covers sources too — segment inputs may be SourceIds and their
    #: ordering contract (see :class:`Segment`) needs a position for every
    #: graph id a member can depend on
    full_pos = {gid: i for i, gid in enumerate(full_order)}
    order = [
        n for n in full_order
        if isinstance(n, NodeId) and n in graph.operators
    ]
    barriers: Dict[Any, str] = {}
    eligible = set()
    from .abstract import leaf_is_chunked

    consumers: Dict[Any, set] = {}
    for n in order:
        for d in graph.get_dependencies(n):
            consumers.setdefault(d, set()).add(n)
    sink_deps = set(graph.sink_dependencies.values())

    # readers first: whether a gather join is a member depends on them
    for n in reversed(order):
        op = graph.get_operator(n)
        if n in materialized or n in annotations:
            reason = BARRIER_SAVED
        else:
            reason = barrier_reason(
                op, verdicts.get(n, lattice.OPAQUE),
                is_chunked_leaf=leaf_is_chunked(op),
            )
            if reason == BARRIER_CACHER and n in declined:
                reason = None
            readers = consumers.get(n)
            if (
                reason == BARRIER_GATHER
                and readers
                and readers <= eligible
                and n not in sink_deps
            ):
                # the join's zipped value never leaves the program: inside
                # it the join is a tuple of its branches
                reason = None
        if reason is None:
            eligible.add(n)
        else:
            barriers[n] = reason
    barriers = {n: barriers[n] for n in order if n in barriers}

    # union-find over edges between eligible nodes
    parent: Dict[Any, Any] = {n: n for n in eligible}

    def find(x):
        while parent[x] is not x:
            parent[x] = parent[parent[x]]
            x = parent[x]
        return x

    def union(a, b):
        ra, rb = find(a), find(b)
        if ra is not rb:
            parent[ra] = rb

    for n in eligible:
        for d in graph.get_dependencies(n):
            if d in eligible:
                union(n, d)

    groups: Dict[Any, List[Any]] = {}
    for n in order:
        if n in eligible:
            groups.setdefault(find(n), []).append(n)

    topo_pos = {n: i for i, n in enumerate(order)}
    segments: List[Segment] = []
    for i, members in enumerate(
        sorted(groups.values(), key=lambda ms: topo_pos[ms[0]])
    ):
        mset = set(members)
        seen = set()
        inputs: List[Any] = []
        for n in members:
            for d in graph.get_dependencies(n):
                if d not in mset and d not in seen:
                    seen.add(d)
                    inputs.append(d)
        # the pinned inputs contract: topological order of the producer,
        # NOT insertion order over members (which varies with grouping)
        inputs.sort(key=lambda d: full_pos[d])
        outputs = [
            n for n in members
            if n in sink_deps or (consumers.get(n, set()) - mset)
        ]
        seg = Segment(
            index=i, nodes=list(members), inputs=inputs, outputs=outputs
        )
        seg.est_item_bytes = _estimate_item_bytes(
            graph, members, specs, cost_estimator
        )
        segments.append(seg)
    return segments, barriers


def _estimate_item_bytes(
    graph, members, specs, cost_estimator
) -> Optional[int]:
    total = 0
    any_known = False
    for n in members:
        b = _spec_item_bytes(specs.get(n))
        if b is None and cost_estimator is not None:
            priced = cost_estimator.node_profile_ns(
                type(graph.get_operator(n)).__name__, 1
            )
            if priced is not None:
                b = int(priced[1])
        if b is not None:
            total += b
            any_known = True
    return total if any_known else None
