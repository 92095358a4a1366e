"""Pull-based memoized graph executor with dependency-scheduled concurrency.

Parity target: ``workflow/GraphExecutor.scala``. The executor optimizes its
graph lazily on first use, then ``execute(graph_id)`` recursively pulls
dependency expressions, memoizing one expression per graph id. Results of
saveable prefixes (annotated by the optimizer) are written into the global
:class:`PipelineEnv` state so later executions skip the work entirely.

Concurrency model: the reference gets branch parallelism for free from
Spark's scheduler — ``Pipeline.gather``'s N featurizer branches run as
independent stages. Here the recursive pull BUILDS the expression web
serially (cheap thunk construction), and when the pending work has genuine
width (two or more nodes simultaneously ready), the pull root's thunk is
armed with a dependency-counted scheduler: ready nodes are submitted to a
bounded worker pool in topological order (``KEYSTONE_EXEC_WORKERS``, default
``min(8, cpu)``), each node forcing only after all of its dependencies have
been forced. Host-bound stages of one branch overlap device compute of
another; laziness is preserved because nothing runs until the root is
``.get()``. ``KEYSTONE_PAR_EXEC=0`` is the kill switch, and single-chain
pulls never pay for a pool or a lock acquisition beyond the expression
once-latches.

Failure semantics: the first branch exception wins — scheduling stops (not
yet-started siblings are abandoned), in-flight siblings drain, and the
original exception propagates with its original traceback.
"""

from __future__ import annotations

import heapq
import logging
import os
import threading
import time
from concurrent.futures import ThreadPoolExecutor
from typing import Any, Dict, List, Optional, Tuple

from ..obs import tracer as _obs_tracer
from ..obs.tracer import current as _trace_current
from ..obs.tracer import span as _span
from ..utils.timing import degraded
from .env import PipelineEnv
from .expressions import DatasetExpression, Expression
from .graph import Graph, GraphId, NodeId, SinkId, SourceId
from .rules import Annotations

logger = logging.getLogger(__name__)

# -- concurrency knobs -------------------------------------------------------


def parallel_enabled() -> bool:
    """``KEYSTONE_PAR_EXEC`` kill switch (default on). Read per pull so
    tests and benches can flip it without rebuilding executors."""
    from ..utils import env_flag

    return env_flag("KEYSTONE_PAR_EXEC", True)


def exec_workers() -> int:
    """Worker-pool width for scheduled pulls: ``KEYSTONE_EXEC_WORKERS``,
    default ``min(8, cpu)``. One pool per pull, sized to the pending work —
    the scan pipeline's ``KEYSTONE_MAP_WORKERS`` pool lives INSIDE a node's
    thunk, so keep the two bounded rather than multiplying them."""
    from ..utils import env_int

    return env_int("KEYSTONE_EXEC_WORKERS", min(8, os.cpu_count() or 1))


# -- retention lookup (hoisted out of the per-node hot path) -----------------

#: lazily-resolved (autocache annotation key, retained operator types).
#: ``_retain`` runs under the scheduler for every node of every pull, so the
#: previous function-local imports would re-enter the import machinery per
#: node; resolved once here instead (lazily — both modules import this one).
_RETENTION_TABLES: Optional[Tuple[str, tuple]] = None


def _retention_tables() -> Tuple[str, tuple]:
    global _RETENTION_TABLES
    if _RETENTION_TABLES is None:
        from ..nodes.util.core import Cacher
        from .autocache import AUTOCACHE_ACTIVE
        from .operators import (
            DatasetOperator,
            DatumOperator,
            EstimatorOperator,
            ExpressionOperator,
        )

        _RETENTION_TABLES = (
            AUTOCACHE_ACTIVE,
            (Cacher, DatasetOperator, DatumOperator, EstimatorOperator,
             ExpressionOperator),
        )
    return _RETENTION_TABLES


#: per-thread scheduler task context: the worker forcing a node publishes
#: queue-wait and worker identity here so the node's span (opened inside the
#: traced thunk, which was built long before scheduling) can pick them up.
_TASK_CTX = threading.local()


class GraphExecutor:
    def __init__(
        self,
        graph: Graph,
        optimize: bool = True,
        parallel: Optional[bool] = None,
        segment_plan: Optional[Dict[NodeId, Any]] = None,
    ):
        self._input_graph = graph
        self._optimize = optimize
        self._optimized: Optional[Graph] = None
        self._annotations: Annotations = {}
        self._state: Dict[GraphId, Expression] = {}
        #: None = follow KEYSTONE_PAR_EXEC; False pins serial (profiling
        #: executors, where per-node wall-clock attribution must not be
        #: polluted by sibling work on other cores)
        self._parallel = parallel
        #: guards expression-web construction + memo writes so concurrent
        #: pulls (serving threads) see a consistent ``_state``
        self._build_lock = threading.Lock()
        #: segment-compiled dispatch plan: output NodeId → SegmentBinding,
        #: planned once per executor on its first pull (None = not yet
        #: planned; {} = planned, nothing eligible: node dispatch, operator
        #: by operator — the reference path the tests compare against).
        #: ``segment_plan`` seeds it with a caller-cached plan — a
        #: FittedPipeline splices an identical graph per apply (node ids
        #: are deterministic, operators are shared objects), so the plan
        #: from apply #1's executor is valid for every later apply and
        #: replanning per pull would pay fingerprint + lattice work on
        #: the request path
        self._seg_bindings: Optional[Dict[NodeId, Any]] = segment_plan

    @property
    def segment_plan(self) -> Optional[Dict[NodeId, Any]]:
        """The planned segment-dispatch table (None until the first pull
        plans it) — cacheable across executors over
        identically-spliced graphs; see ``__init__``."""
        return self._seg_bindings

    @property
    def input_graph(self) -> Graph:
        """The graph as handed in, WITHOUT forcing the lazy optimize —
        the composition seam (``attach_data`` splices this, so building
        an L-stage pipeline never runs the rule stack; ``fit``/``get``
        optimize the composed graph exactly once)."""
        return self._input_graph

    @property
    def graph(self) -> Graph:
        """The optimized graph (optimization happens once, lazily)."""
        if self._optimized is None:
            if self._optimize:
                optimizer = PipelineEnv.get_or_create().optimizer
                self._optimized, self._annotations = optimizer.execute(self._input_graph)
            else:
                self._optimized = self._input_graph
        return self._optimized

    def _retain(self, graph: Graph, graph_id: NodeId) -> bool:
        """Whether this node's result stays resident across pulls.

        Default: everything (the HBM-memoizing fast path). After the
        AutoCacheRule has planned caching, only Cacher / estimator / source
        dataset results are retained — other intermediates recompute per
        pull, exactly like unpersisted RDDs in the reference, so the cache
        budget genuinely bounds resident bytes. Concurrency does not widen
        RETENTION (scheduled pulls share the same per-pull transient table,
        drop it at pull end, and the scheduler releases each node's
        expression as it completes) — but peak TRANSIENT memory can grow by
        up to the worker count, since in-flight branches hold their
        intermediates simultaneously; ``KEYSTONE_EXEC_WORKERS`` bounds
        that factor."""
        autocache_key, retained_types = _retention_tables()
        if not self._annotations.get(autocache_key):
            return True
        op = graph.get_operator(graph_id)
        return isinstance(op, retained_types)

    def _use_parallel(self) -> bool:
        if self._parallel is not None:
            return self._parallel
        return parallel_enabled()

    def execute(self, graph_id: GraphId) -> Expression:
        with self._build_lock:
            if self._seg_bindings is None:
                self._seg_bindings = self._plan_segment_bindings()
            segments = self._seg_bindings or None
            built: Dict[NodeId, Expression] = {}
            expr = self._execute(
                graph_id, transient={}, built=built, segments=segments
            )
            if self._use_parallel():
                self._arm_concurrent(expr, built, segments=segments)
        return expr

    def _execute(
        self,
        graph_id: GraphId,
        transient: Dict,
        built: Dict[NodeId, Expression],
        segments: Optional[Dict[NodeId, Any]] = None,
    ) -> Expression:
        graph = self.graph  # force optimization before anything runs
        if isinstance(graph_id, SourceId):
            raise ValueError(f"cannot execute unconnected {graph_id}")
        if isinstance(graph_id, SinkId):
            return self._execute(
                graph.get_sink_dependency(graph_id), transient, built,
                segments=segments,
            )
        # memo hits are instants of an INSTALLED tracer only; a node that
        # computes runs under obs.tracer.span whoever records
        tracer = _trace_current()
        if graph_id in self._state:
            expr = self._state[graph_id]
            built.setdefault(graph_id, expr)
            if tracer is not None:
                self._trace_hit(tracer, graph, graph_id, store="state")
            return expr
        if graph_id in transient:
            if tracer is not None:
                self._trace_hit(tracer, graph, graph_id, store="transient")
            return transient[graph_id]
        if segments is not None:
            binding = segments.get(graph_id)
            if binding is not None:
                expr = self._execute_segment(
                    binding, graph_id, transient, built, segments
                )
                if expr is not None:
                    return expr
                # else: this pull cannot ride the segment (datum inputs) —
                # fall through to plain node dispatch
        deps = [
            self._execute(d, transient, built, segments=segments)
            for d in graph.get_dependencies(graph_id)
        ]
        op = graph.get_operator(graph_id)
        retained = self._retain(graph, graph_id)
        expr = self._traced_execute(graph_id, op, deps, retained=retained)
        # ``built`` records every node of this pull in dependencies-first
        # order — the scheduler's topological order comes straight from it
        built[graph_id] = expr
        if retained:
            self._state[graph_id] = expr
        else:
            # shared within this pull (diamonds compute once), dropped after
            transient[graph_id] = expr
        prefix = self._annotations.get(graph_id)
        if prefix is not None:
            PipelineEnv.get_or_create().state[prefix] = expr
        return expr

    # -- segment-compiled dispatch --------------------------------------

    def _plan_segment_bindings(self) -> Dict[NodeId, Any]:
        """Plan this executor's segment-dispatch table: run the segment
        planner over the (optimized) graph, lower every eligible segment
        through ``compile/segment.py``, and key each binding by its OUTPUT
        nodes (interiors are subsumed — they never get their own thunk).
        Planning must never break execution: any failure degrades to an
        empty table, i.e. plain node dispatch."""
        try:
            from ..check import lattice
            from ..check.segments import plan_segments
            from ..compile.segment import bind_segment, unheld_caches

            graph = self.graph
            with _span("plan.segments", nodes=len(graph.nodes)) as sp:
                verdicts = {
                    n: lattice.classify(graph.get_operator(n))
                    for n in graph.nodes
                }
                # what this executor already holds (a fit hands each
                # estimator's executor the upstream results of the last)
                # is data: a segment through it would featurize again
                held = {n: e for n, e in self._state.items() if e.computed}
                # a cache request the device cannot honour is no barrier:
                # the Cacher joins the segment that feeds it and its value
                # is computed again where it is read
                declined = unheld_caches(graph, verdicts, held)
                planned, _barriers = plan_segments(
                    graph, verdicts, {}, materialized=held,
                    annotations=self._annotations, declined=declined,
                )
                table: Dict[NodeId, Any] = {}
                for seg in planned:
                    binding = bind_segment(graph, seg, declined)
                    if binding is None:
                        continue
                    for out in binding.outputs:
                        table[out] = binding
                sp.attrs["segments"] = len(planned)
            return table
        except Exception:
            logger.warning(
                "segment planning failed — node dispatch for this executor",
                exc_info=True,
            )
            degraded("segment_planning")
            return {}

    def _execute_segment(
        self,
        binding: Any,
        graph_id: NodeId,
        transient: Dict,
        built: Dict[NodeId, Expression],
        segments: Dict[NodeId, Any],
    ) -> Optional[Expression]:
        """Build (or reuse) the ONE bundle expression for ``binding`` and
        return the output expression for ``graph_id``. Returns None when
        this pull's inputs are not dataset expressions (a datum pull) —
        the caller falls back to node dispatch."""
        outs_key = ("__segment_outs__", binding.index)
        out_exprs = transient.get(outs_key)
        if out_exprs is None:
            in_exprs = [
                self._execute(d, transient, built, segments=segments)
                for d in binding.inputs
            ]
            if not all(isinstance(e, DatasetExpression) for e in in_exprs):
                return None
            bundle = self._segment_bundle(binding, in_exprs)
            graph = self.graph
            out_exprs = {}
            for j, out in enumerate(binding.outputs):
                oe = DatasetExpression(lambda j=j: bundle.get()[j])
                out_exprs[out] = oe
                built[out] = oe
                if self._retain(graph, out):
                    self._state[out] = oe
                else:
                    transient[out] = oe
            transient[outs_key] = out_exprs
        return out_exprs.get(graph_id)

    @staticmethod
    def _segment_bundle(binding: Any, in_exprs: List[Expression]) -> Expression:
        """The segment's single lazy thunk: force the input expressions
        (OUTSIDE the segment span, so upstream node spans keep their own
        attribution), then dispatch the whole segment as one program under
        an ``exec.segment`` span — the span that replaces the N per-node
        spans the members would have emitted."""

        def run_bundle():
            xs = [e.get() for e in in_exprs]
            with _span(
                "exec.segment",
                op_type="Segment",
                segment=binding.index,
                nodes=len(binding.node_ids),
                node_ids=list(binding.node_ids),
                digest=(binding.digest or "")[:16],
                label=binding.label,
            ) as sp:
                outs, path = binding.run(xs, facts=sp.attrs)
                sp.attrs["path"] = path
                if path == "compiled":
                    # chunked outputs are lazy scans — syncing them here
                    # would force the whole out-of-core pass eagerly
                    sp.sync_on(tuple(d.payload for d in outs))
            return outs

        return Expression(run_bundle)

    # -- concurrent scheduling ------------------------------------------

    def _arm_concurrent(
        self,
        root_expr: Expression,
        built: Dict[NodeId, Expression],
        segments: Optional[Dict[NodeId, Any]] = None,
    ) -> None:
        """Wrap the pull root's thunk so its first forcing runs every other
        pending node of this pull through the dependency-counted worker
        pool, then computes the root itself on the calling thread. Arming
        (not running) keeps the pull lazy; single-chain pulls are detected
        here and left untouched — no pool, no extra wrapping."""
        if getattr(root_expr, "_sched_armed", False):
            return
        pending = {n: e for n, e in built.items() if not e.computed}
        root_node = next(
            (n for n, e in built.items() if e is root_expr), None
        )
        sched = [n for n in pending if n != root_node]
        if len(sched) < 2:
            return

        graph = self.graph
        in_sched = set(sched)
        deps_of: Dict[NodeId, List[NodeId]] = {}
        children: Dict[NodeId, List[NodeId]] = {n: [] for n in sched}
        for n in sched:
            ds = []
            # a segment output's graph dependencies are the segment's
            # INTERIOR nodes — absent from ``built`` entirely; its true
            # scheduling edges are the segment's external inputs
            if segments is not None and n in segments:
                dep_src = segments[n].inputs
            else:
                dep_src = graph.get_dependencies(n)
            for d in dep_src:
                if isinstance(d, NodeId) and d in in_sched and d not in ds:
                    ds.append(d)
            deps_of[n] = ds
            for d in ds:
                children[d].append(n)

        # width probe (Kahn waves over the pending subgraph): a strict chain
        # never has two nodes ready at once — keep it on the serial path
        indeg = {n: len(deps_of[n]) for n in sched}
        wave = [n for n in sched if indeg[n] == 0]
        width = 0
        while wave:
            width = max(width, len(wave))
            nxt: List[NodeId] = []
            for n in wave:
                for c in children[n]:
                    indeg[c] -= 1
                    if indeg[c] == 0:
                        nxt.append(c)
            wave = nxt
        if width < 2:
            return

        # ``built`` insertion order is dependencies-first: submitting ready
        # nodes lowest-index-first keeps device dispatch in the same order
        # the serial executor would have used
        topo = {n: i for i, n in enumerate(built) if n in in_sched}
        exprs = {n: built[n] for n in sched}

        def wrap(thunk):
            def scheduled_pull():
                _force_scheduled(exprs, deps_of, children, topo)
                return thunk()

            return scheduled_pull

        root_expr.map_thunk(wrap)
        root_expr._sched_armed = True

    # -- tracing hooks ---------------------------------------------------

    @staticmethod
    def _trace_hit(tracer, graph: Graph, graph_id: NodeId, store: str) -> None:
        """A memoized result was returned instead of recomputed — the
        Cacher/memo hit the span tree records against the recompute case."""
        op = graph.get_operator(graph_id)
        tracer.instant(
            f"node.{op.label}",
            node_id=str(graph_id.id),
            op_type=type(op).__name__,
            cache="hit",
            store=store,
        )

    @staticmethod
    def _traced_execute(graph_id: NodeId, op, deps, retained: bool):
        """Build the node's expression with its eventual EVALUATION wrapped
        in a span. Evaluation is lazy (``Expression`` thunks), so the span
        opens when ``.get()`` first forces this node — upstream thunks
        forced from inside it become child spans, giving the pull's true
        tree. Under an installed tracer, exit blocks on the result so
        async-dispatched device time is attributed here (recorded as
        ``sync_seconds``); a profiler session records without. When the
        concurrent scheduler forces this node, the worker's task context
        adds ``queue_wait_seconds`` (ready-to-started latency) and
        ``worker``."""
        name = f"node.{op.label}"
        op_type = type(op).__name__
        node_id = str(graph_id.id)
        t0 = time.perf_counter()
        expr = op.execute(deps)
        if expr.computed:
            # eager operator (Dataset/Datum leaves, saved state): the work
            # happened inside op.execute — an installed tracer records it
            # directly (a finished span: no annotation, no session record)
            tracer = _trace_current()
            if tracer is not None:
                from ..obs.span import Span, cheap_nbytes

                tracer.record_complete(Span(
                    name=name,
                    start=t0,
                    end=time.perf_counter(),
                    node_id=node_id,
                    op_type=op_type,
                    cache="miss",
                    output_bytes=cheap_nbytes(expr.get()),
                    attrs={"retained": retained, "eager": True},
                ))
            return expr

        def _wrap(thunk):
            def traced_thunk():
                extra = {}
                if getattr(_TASK_CTX, "node_id", None) == node_id:
                    # one-shot consume: a nested pull forced inside this
                    # thunk may reuse the same node-id string (ids are
                    # per-graph counters) and must not inherit these attrs
                    _TASK_CTX.node_id = None
                    extra = {
                        "queue_wait_seconds": round(_TASK_CTX.queue_wait, 6),
                        "worker": _TASK_CTX.worker,
                    }
                with _span(
                    name,
                    node_id=node_id,
                    op_type=op_type,
                    cache="miss",
                    retained=retained,
                    **extra,
                ) as sp:
                    value = thunk()
                    sp.sync_on(value)
                return value

            return traced_thunk

        expr.map_thunk(_wrap)
        return expr


def _force_scheduled(
    exprs: Dict[NodeId, Expression],
    deps_of: Dict[NodeId, List[NodeId]],
    children: Dict[NodeId, List[NodeId]],
    topo: Dict[NodeId, int],
) -> None:
    """Force every expression in ``exprs`` on a bounded worker pool, each
    node only after its scheduled dependencies. All mutable state is local
    to this call: a memoized armed root re-forced by a later pull re-plans
    against what is ALREADY computed (usually nothing left to do).

    On a branch exception: stop submitting (unstarted siblings are
    cancelled), drain in-flight workers, re-raise the first exception with
    its original traceback.
    """
    # a dependency absent from ``exprs`` was either computed at arm time or
    # completed (and released) by an earlier run of this scheduler — a
    # failed run leaves the root armed, so a retry re-enters here
    remaining = [n for n, e in exprs.items() if not e.computed]
    if not remaining:
        return
    spans_to = _obs_tracer.handoff()

    # init-only snapshot; live ready-tracking is indeg/children below
    in_remaining = set(remaining)
    indeg = {
        n: sum(1 for d in deps_of[n] if d in in_remaining)
        for n in remaining
    }
    now = time.perf_counter()
    # heap entries carry the instant the node became READY — queue wait is
    # ready-to-started, including time parked here while workers are busy
    ready = [(topo[n], n, now) for n in remaining if indeg[n] == 0]
    heapq.heapify(ready)
    cond = threading.Condition()
    state = {"pending": len(remaining), "inflight": 0}
    failures: List[BaseException] = []

    def run_one(node: NodeId, expr: Expression, ready_since: float) -> None:
        err: Optional[BaseException] = None
        _TASK_CTX.node_id = str(node.id)
        _TASK_CTX.queue_wait = time.perf_counter() - ready_since
        _TASK_CTX.worker = threading.current_thread().name
        try:
            with _obs_tracer.adopt(spans_to):
                expr.get()
        except BaseException as e:  # noqa: BLE001 — must reach the caller
            err = e
        finally:
            _TASK_CTX.node_id = None
        with cond:
            state["inflight"] -= 1
            if err is not None:
                failures.append(err)
            else:
                state["pending"] -= 1
                # release the scheduler's reference: consumers hold their
                # own refs through their thunk closures, so a non-retained
                # intermediate frees as soon as its last consumer runs —
                # same residency profile as the serial recursive pull
                exprs.pop(node, None)
                t_ready = time.perf_counter()
                for c in children[node]:
                    if c in indeg:
                        indeg[c] -= 1
                        if indeg[c] == 0:
                            heapq.heappush(ready, (topo[c], c, t_ready))
            cond.notify_all()

    # one pool PER PULL, deliberately: a process-shared bounded pool would
    # deadlock when a scheduled node's thunk runs a nested pull (outer
    # workers block holding slots the inner schedule needs); the create/
    # join cost is microseconds against pulls worth scheduling at all
    workers = min(exec_workers(), len(remaining))
    pool = ThreadPoolExecutor(
        max_workers=workers, thread_name_prefix="keystone-exec"
    )
    try:
        with cond:
            while state["pending"] and not failures:
                while ready and state["inflight"] < workers and not failures:
                    _, node, since = heapq.heappop(ready)
                    state["inflight"] += 1
                    pool.submit(run_one, node, exprs[node], since)
                if state["pending"] and not failures:
                    cond.wait()
            while state["inflight"]:
                cond.wait()
    finally:
        pool.shutdown(wait=True)
    if failures:
        raise failures[0]
