"""The typed pipeline layer: Chainable / Pipeline / lazy results / FittedPipeline.

Parity targets: ``workflow/Chainable.scala``, ``Pipeline.scala``,
``PipelineDataset.scala``, ``PipelineDatum.scala``, ``PipelineResult.scala``,
``FittedPipeline.scala``, ``TransformerGraph.scala``.

The TPU-first twist: once a pipeline is ``fit()``, the transformer-only chain
can be *compiled* — every node that exposes a pure-jax ``trace_batch`` is
composed into a single function and jitted, so the whole ``andThen`` chain
becomes one fused XLA computation instead of N kernel launches
(see :meth:`FittedPipeline.compile`).
"""

from __future__ import annotations

import logging
from typing import Any, Callable, List, Optional, Sequence, Union

from ..data.dataset import Dataset
from ..obs.tracer import current as _trace_current
from ..obs.tracer import span as _span
from ..utils.timing import degraded
from .env import PipelineEnv
from .executor import GraphExecutor
from .expressions import DatasetExpression, DatumExpression, Expression
from .graph import Graph, NodeId, NodeOrSourceId, SinkId, SourceId
from .operators import (
    DatasetOperator,
    DatumOperator,
    DelegatingOperator,
    EstimatorOperator,
    ExpressionOperator,
    GatherTransformerOperator,
    Operator,
    TransformerOperator,
)
from . import analysis

logger = logging.getLogger(__name__)


class NotTraceableError(ValueError):
    """A pipeline contains nodes without ``trace_batch`` and therefore cannot
    compile to one XLA computation. Carries the offending node labels so a
    caller (e.g. the serving engine) can report exactly which stage blocks
    compilation. Subclasses :class:`ValueError` so pre-existing
    ``except ValueError`` callers of :meth:`FittedPipeline.compile` keep
    working."""

    def __init__(self, labels: Sequence[str]):
        self.labels = list(labels)
        super().__init__(
            "pipeline not traceable: "
            + ", ".join(self.labels)
            + " lack(s) trace_batch"
        )

    def __reduce__(self):
        # default exception reduction would re-call __init__ with the
        # formatted message, turning .labels into a list of characters
        return (NotTraceableError, (self.labels,))


# ---------------------------------------------------------------------------
# Lazy results
# ---------------------------------------------------------------------------


class PipelineResult:
    """A lazy handle on the output of a pipeline execution
    (parity: ``PipelineResult.scala``)."""

    def __init__(self, executor: GraphExecutor, sink: SinkId):
        self._executor = executor
        self._sink = sink

    @property
    def graph(self) -> Graph:
        return self._executor.graph

    @property
    def spliced_graph(self) -> Graph:
        """The graph composition splices: CSE-canonicalized, NOT fully
        optimized. Reading :attr:`graph` instead would force the
        executor's lazy optimize — the full rule stack (saved-state
        loads, node-implementation sampling, autocache planning, trace
        fusion) re-run on the prefix subgraph at every ``and_then``
        step. Only the structural merge is load-bearing for composition:
        the serving-path and estimator-data copies of a prefix both root
        at the same data leaf here, and merging them is what keeps an
        L-stage chain's graph linear instead of 2^L. Everything else
        waits for the one ``fit``/``get`` pass over the composed graph.
        A result that already paid its full optimize splices that
        (strictly more canonical, ids stable)."""
        if self._executor._optimized is not None:
            return self._executor._optimized
        cached = getattr(self._executor, "_cse_graph", None)
        if cached is None:
            from .rules import EquivalentNodeMergeRule

            cached, _ = EquivalentNodeMergeRule().apply(
                self._executor.input_graph, {}
            )
            self._executor._cse_graph = cached
        return cached

    @property
    def sink(self) -> SinkId:
        return self._sink

    def expression(self) -> Expression:
        return self._executor.execute(self._sink)

    def get(self) -> Any:
        # the pull root: every node span of this execution nests under it —
        # including spans from scheduler worker threads, which the executor
        # explicitly links under this thread's open span (obs.tracer.adopt)
        with _span("pipeline.pull", op_type=type(self).__name__) as sp:
            value = self.expression().get()
            sp.sync_on(value)
        return value


class PipelineDataset(PipelineResult):
    """Lazy dataset result; also usable as the data input of another
    pipeline/estimator (its graph is spliced in, preserving laziness)."""

    def get(self) -> Dataset:
        return super().get()

    def collect(self) -> List[Any]:
        return self.get().collect()

    def to_array(self):
        return self.get().to_array()

    def __iter__(self):
        return iter(self.get())


class PipelineDatum(PipelineResult):
    """Lazy single-datum result."""


# ---------------------------------------------------------------------------
# Fit instrumentation: the tracer + cost-model loop around any fit
# ---------------------------------------------------------------------------


import contextlib


@contextlib.contextmanager
def fit_instrumentation(op_type: str, span_name: str = "pipeline.fit"):
    """The observe-and-learn wrapper every fit runs under — a root span,
    and (with a profile store configured) a pending re-plan joined against
    the fit's observed per-node costs afterwards. Shared by
    :meth:`Pipeline.fit` and the multi-query sweep
    (:mod:`keystone_tpu.sweep`), whose merged DAG earns its own plan
    records through exactly this loop."""
    from .. import cost as cost_mod
    from ..obs import tracer as obs_tracer_mod

    store = cost_mod.get_store()
    tracer = _trace_current()
    own_tracer = None
    if store is not None and tracer is None:
        # install-if-absent: two concurrent fits race for the global
        # slot. The loser must NOT learn: joining the winner's tracer
        # would merge both fits' spans per small-int node id and
        # persist cross-fit sums into both evidence records — so the
        # loser runs a plain fit (no tracer, no pending plan) and the
        # winner's tracer is never torn down mid-fit.
        own_tracer = obs_tracer_mod.install_if_absent(
            obs_tracer_mod.Tracer()
        )
        tracer = own_tracer
        if own_tracer is None:
            store = None
    try:
        with cost_mod.pending_plan(store) as plan:
            if plan is not None and tracer is not None:
                plan.span_watermark = len(tracer.spans())
            with _span(span_name, op_type=op_type):
                yield
            # after the fit span closes: every node span is complete,
            # so the estimate-vs-observed join sees the whole run
            cost_mod.finalize(plan, tracer)
    finally:
        if own_tracer is not None:
            obs_tracer_mod.uninstall(own_tracer)


# ---------------------------------------------------------------------------
# Static checking (keystone_tpu/check/)
# ---------------------------------------------------------------------------


def _static_check(pipeline: "Pipeline", where: str):
    """The implicit construction/fit-entry static check: zero executions,
    raises a node-attributed PipelineCheckError on a PROVEN defect, and
    never fails a pipeline for any other reason (internal checker faults
    log and pass). ``KEYSTONE_STATIC_CHECK=0`` disables."""
    from .. import check as check_mod

    if not check_mod.check_enabled():
        return None
    try:
        return pipeline.check(span=False)
    except check_mod.PipelineCheckError:
        raise
    except Exception:
        logger.warning(
            "static check failed internally at %s; continuing unchecked",
            where, exc_info=True,
        )
        return None


def _emit_check_span(report, op_type: str) -> None:
    """Record the ``check.report`` span (attrs carry the summary plus the
    process sampling counter, so a trace can PROVE the check executed no
    samples)."""
    tracer = _trace_current()
    if tracer is None or report is None:
        return
    from .. import cost as cost_mod

    s = report.summary()
    with tracer.span("check.report", op_type=op_type) as sp:
        sp.attrs.update(
            nodes=s["nodes"],
            segments=s["segments"],
            barriers=s["barriers"],
            jit_compilable=s["jit_compilable"],
            exportable=s["exportable"],
            verdicts=dict(s["verdicts"]),
            sampling_total=cost_mod.sampling_executions()["total"],
        )


# ---------------------------------------------------------------------------
# Graph-building helpers
# ---------------------------------------------------------------------------


def datum_spec_of(data: Any) -> Optional[tuple]:
    """Best-effort per-item ``(shape, dtype)`` of a batch-shaped value —
    the serving contract implied by feeding ``data`` at a pipeline's
    source. None when it is not CHEAPLY knowable (lazy results, item
    lists, chunked scans): this is a hint recorded at fit time, never a
    reason to materialize anything."""
    try:
        if isinstance(data, PipelineResult):
            return None  # lazy; forcing it here would execute the graph
        payload = data
        if isinstance(payload, Dataset):
            if not payload.is_batched:
                return None
            payload = payload.payload
        shape = getattr(payload, "shape", None)
        dtype = getattr(payload, "dtype", None)
        if shape is None or dtype is None or len(shape) < 1:
            return None
        return (tuple(int(d) for d in shape[1:]), str(dtype))
    except Exception:
        # the hint is best-effort by contract: never fail a fit over it
        logger.debug("datum spec probe failed", exc_info=True)
        return None


def attach_data(graph: Graph, data: Any) -> tuple:
    """Add ``data`` to ``graph`` as a dependency-able id.

    Raw datasets/arrays become :class:`DatasetOperator` leaves. Lazy
    :class:`PipelineDataset` / :class:`PipelineDatum` results have their whole
    graph spliced in (so shared prefixes merge + stay lazy).
    Returns ``(graph, dep_id)``.
    """
    if isinstance(data, PipelineResult):
        # splice the CSE-canonicalized (not fully optimized) graph:
        # forcing data.graph here would run the full optimizer stack on
        # the prefix subgraph at every composition step (L rule-stack
        # runs for an L-stage and_then chain) — the composed pipeline's
        # own fit/get optimizes once; see PipelineResult.spliced_graph
        other = data.spliced_graph
        merged, _, sink_map = graph.add_graph(other)
        dep = merged.get_sink_dependency(sink_map[data.sink])
        # drop the imported sinks; keep everything else
        for old_sink, new_sink in sink_map.items():
            merged = merged.remove_sink(new_sink)
        return merged, dep
    if isinstance(data, Dataset):
        op: Operator = DatasetOperator(data)
    else:
        op = DatasetOperator(Dataset.of(data))
    graph, node = graph.add_node(op, [])
    return graph, node


def attach_datum(graph: Graph, datum: Any) -> tuple:
    if isinstance(datum, PipelineResult):
        return attach_data(graph, datum)
    graph, node = graph.add_node(DatumOperator(datum), [])
    return graph, node


# ---------------------------------------------------------------------------
# Chainable
# ---------------------------------------------------------------------------


class Chainable:
    """Anything composable with ``and_then`` into a :class:`Pipeline`
    (parity: ``Chainable.scala``). Subclasses: :class:`Pipeline` and
    :class:`~keystone_tpu.workflow.transformer.Transformer`."""

    def to_pipeline(self) -> "Pipeline":
        raise NotImplementedError

    def and_then(self, nxt: Any, *fit_data: Any) -> "Pipeline":
        """``self`` then ``nxt``.

        * ``and_then(transformer_or_pipeline)`` — plain composition.
        * ``and_then(estimator, data)`` — fit ``estimator`` on ``self(data)``
          and append the fitted model.
        * ``and_then(label_estimator, data, labels)`` — ditto with labels.
        """
        if isinstance(nxt, EstimatorOperator):
            if not hasattr(nxt, "with_data"):
                raise TypeError(
                    f"{type(nxt).__name__} is a bare EstimatorOperator; chainable "
                    "estimators must subclass the typed Estimator/LabelEstimator "
                    "(which provide with_data)"
                )
            if not fit_data:
                raise ValueError(
                    "and_then(estimator) needs training data: and_then(est, data[, labels])"
                )
            trained_input = self(fit_data[0])
            fitted = nxt.with_data(trained_input, *fit_data[1:])
            composed = self.to_pipeline()._compose(fitted)
            # fit_data[0] is fed at the chain's SOURCE (self is the whole
            # prefix), so its per-item spec is the serving datum contract —
            # recorded as a hint for warm-up/AOT consumers of the fit
            if composed._datum_hint is None:
                composed._datum_hint = datum_spec_of(fit_data[0])
            # static entry check: the estimator-data path's leaf specs are
            # known NOW, so a shape/dtype-incompatible composition raises
            # here — at the and_then call — not minutes into the fit scan
            _static_check(composed, where="and_then")
            return composed
        if isinstance(nxt, Chainable):
            if fit_data:
                raise ValueError("fit data only applies when chaining an estimator")
            return self.to_pipeline()._compose(nxt.to_pipeline())
        raise TypeError(f"cannot chain {type(nxt).__name__}")

    # ``a >> b`` sugar for and_then
    def __rshift__(self, nxt: Any) -> "Pipeline":
        return self.and_then(nxt)

    def __call__(self, data: Any) -> PipelineResult:
        return self.to_pipeline().apply(data)


# ---------------------------------------------------------------------------
# Pipeline
# ---------------------------------------------------------------------------


class Pipeline(Chainable):
    """A graph with exactly one unbound source and one sink
    (parity: ``Pipeline.scala``)."""

    def __init__(self, graph: Graph, source: SourceId, sink: SinkId):
        self._graph = graph
        self._source = source
        self._sink = sink
        #: per-item ``(shape, dtype)`` of data this chain's source has been
        #: fed (recorded by ``and_then(estimator, data)``); carried into
        #: the FittedPipeline so serving can warm up without being told
        #: the datum shape again
        self._datum_hint: Optional[tuple] = None

    # -- structure ------------------------------------------------------

    @property
    def graph(self) -> Graph:
        return self._graph

    @property
    def source(self) -> SourceId:
        return self._source

    @property
    def sink(self) -> SinkId:
        return self._sink

    def to_pipeline(self) -> "Pipeline":
        return self

    def to_dot(self, name: str = "pipeline") -> str:
        return self._graph.to_dot(name)

    def _compose(self, nxt: "Pipeline") -> "Pipeline":
        """Splice self's sink into nxt's source (the ``andThen`` core)."""
        merged, source_map, sink_map = self._graph.connect_graph(
            nxt._graph, {self._sink: nxt._source}
        )
        composed = Pipeline(merged, self._source, sink_map[nxt._sink])
        # the composed source IS self's source, so only self's hint applies
        # (nxt's hint described nxt's own source, now an interior edge)
        composed._datum_hint = self._datum_hint
        return composed

    # -- application ----------------------------------------------------

    def apply(self, data: Any) -> PipelineDataset:
        """Lazily apply to a dataset; nothing executes until ``.get()``."""
        graph, data_id = attach_data(self._graph, data)
        graph = graph.replace_dependency(self._source, data_id)
        graph = graph.remove_source(self._source)
        executor = GraphExecutor(graph)
        return PipelineDataset(executor, self._sink)

    def apply_datum(self, datum: Any) -> PipelineDatum:
        """Lazily apply to a single datum."""
        graph, datum_id = attach_datum(self._graph, datum)
        graph = graph.replace_dependency(self._source, datum_id)
        graph = graph.remove_source(self._source)
        executor = GraphExecutor(graph)
        return PipelineDatum(executor, self._sink)

    def __call__(self, data: Any) -> PipelineResult:
        return self.apply(data)

    # -- static checking ------------------------------------------------

    def check(self, datum_spec: Optional[tuple] = None, *, span: bool = True):
        """Run the static pipeline checker (:mod:`keystone_tpu.check`)
        over this graph: abstract shape/dtype propagation from the data
        leaves, per-node traceability verdicts, and the
        traceable-segment plan — in milliseconds, executing ZERO chunks
        and ZERO samples. Raises a node-attributed
        :class:`~keystone_tpu.check.PipelineCheckError` on any
        statically-proven defect; returns the
        :class:`~keystone_tpu.check.CheckReport` otherwise.

        ``datum_spec`` is the per-item ``(shape, dtype)`` fed at the
        unbound source; defaults to the recorded fit-data hint."""
        from .. import check as check_mod
        from .. import cost as cost_mod

        spec = datum_spec if datum_spec is not None else self._datum_hint
        report = check_mod.check_graph(
            self._graph,
            source=self._source,
            datum_spec=spec,
            cost_estimator=cost_mod.get_estimator(),
        )
        if span:
            _emit_check_span(report, type(self).__name__)
        return report

    # -- fitting --------------------------------------------------------

    def fit(self) -> "FittedPipeline":
        """Fit every estimator NOW and return a serializable transformer-only
        pipeline (parity: ``Pipeline.scala:38-65``). This is the jit boundary:
        the returned :class:`FittedPipeline` contains no estimators and can be
        compiled to a single XLA computation.

        Fit-time featurization rides the concurrent executor: each
        estimator pull below goes through ``GraphExecutor.execute``, so the
        N gather branches feeding an estimator featurize on the worker pool
        (``KEYSTONE_EXEC_WORKERS``) exactly as ``apply`` does —
        ``KEYSTONE_PAR_EXEC=0`` serializes both.

        With a profile store configured (``KEYSTONE_PROFILE_DIR``) the fit
        closes the cost-model loop: the optimizer's solver choice and cache
        plan are deposited into a pending plan, the fit's observed per-node
        costs are joined against it afterwards (``cost/replan.py``), and the
        evidence persists so the NEXT fit of this pipeline plans with zero
        sampling executions. A fit-local tracer is installed when none is
        active — observations are what the loop learns from.

        Fit entry runs the static checker first
        (:mod:`keystone_tpu.check`): a proven shape/dtype mismatch or
        chunk-incompatible composition raises a node-attributed
        :class:`~keystone_tpu.check.PipelineCheckError` BEFORE the
        optimizer samples anything or a chunk is produced. In ``--check``
        mode the fit stops there by design
        (:class:`~keystone_tpu.check.CheckOnlyExit`)."""
        from .. import check as check_mod

        if check_mod.check_only_mode():
            report = self.check()  # raises on proven defects, spans
            print(report.render())
            raise check_mod.CheckOnlyExit(report)
        _static_check(self, where="fit")
        with fit_instrumentation(type(self).__name__):
            return self._fit()

    def _fit(self) -> "FittedPipeline":
        optimizer = PipelineEnv.get_or_create().optimizer
        graph, annotations = optimizer.execute(self._graph)
        executor = GraphExecutor(graph, optimize=False)
        executor._annotations = annotations

        for node in list(analysis.linearize(graph)):
            if not isinstance(node, NodeId) or node not in graph.operators:
                continue
            op = graph.get_operator(node)
            if isinstance(op, DelegatingOperator):
                deps = graph.get_dependencies(node)
                est_dep, data_deps = deps[0], deps[1:]
                fitted = executor.execute(est_dep).get()
                if not isinstance(fitted, TransformerOperator):
                    raise TypeError(
                        f"estimator at {est_dep} produced {type(fitted).__name__}, "
                        "expected a TransformerOperator"
                    )
                graph = graph.set_operator(node, fitted)
                graph = graph.set_dependencies(node, list(data_deps))
                # Re-point the executor at the edited graph but keep memoized
                # upstream results — only the edited node and its descendants
                # are stale. Without this, fitting K chained estimators
                # re-executes shared featurization K times.
                stale = {node} | analysis.get_descendants(graph, node)
                fresh = GraphExecutor(graph, optimize=False)
                fresh._annotations = annotations
                fresh._state = {
                    gid: expr
                    for gid, expr in executor._state.items()
                    if gid not in stale
                }
                executor = fresh

        from .rules import UnusedBranchRemovalRule

        graph, _ = UnusedBranchRemovalRule().apply(graph, {})
        for node in graph.nodes:
            op = graph.get_operator(node)
            if not isinstance(op, (TransformerOperator, ExpressionOperator, DatasetOperator, DatumOperator)):
                raise TypeError(f"fit() left a non-transformer operator in the graph: {op.label}")
        hint = self._datum_hint
        return FittedPipeline(
            graph, self._source, self._sink,
            datum_shape=hint[0] if hint else None,
            datum_dtype=hint[1] if hint else None,
        )

    # -- combinators ----------------------------------------------------

    @staticmethod
    def gather(branches: Sequence[Chainable]) -> "Pipeline":
        """Fan one input through every branch and zip the outputs into a
        per-item sequence (parity: ``Pipeline.scala:119-154``)."""
        if not branches:
            raise ValueError("gather of zero branches")
        graph = Graph()
        graph, source = graph.add_source()
        branch_outs: List[NodeOrSourceId] = []
        for branch in branches:
            bp = branch.to_pipeline()
            merged, source_map, sink_map = graph.add_graph(bp.graph)
            merged = merged.replace_dependency(source_map[bp.source], source)
            merged = merged.remove_source(source_map[bp.source])
            out = merged.get_sink_dependency(sink_map[bp.sink])
            merged = merged.remove_sink(sink_map[bp.sink])
            graph = merged
            branch_outs.append(out)
        graph, gather_node = graph.add_node(GatherTransformerOperator(), branch_outs)
        graph, sink = graph.add_sink(gather_node)
        return Pipeline(graph, source, sink)

    @staticmethod
    def identity() -> "Pipeline":
        graph = Graph()
        graph, source = graph.add_source()
        graph, sink = graph.add_sink(source)
        return Pipeline(graph, source, sink)


# ---------------------------------------------------------------------------
# FittedPipeline
# ---------------------------------------------------------------------------


class FittedPipeline(Chainable):
    """An estimator-free pipeline: pure transformer application, serializable,
    and compilable to a single jitted function
    (parity: ``FittedPipeline.scala`` + the XLA-fusion north star)."""

    def __init__(
        self,
        graph: Graph,
        source: SourceId,
        sink: SinkId,
        *,
        datum_shape: Optional[tuple] = None,
        datum_dtype: Optional[str] = None,
    ):
        self._graph = graph
        self._source = source
        self._sink = sink
        #: per-item input contract recorded at fit time (from the data the
        #: chain's estimators were fed) — lets a serving engine warm up
        #: without being handed the shape again; None when not knowable
        self.datum_shape: Optional[tuple] = (
            tuple(int(d) for d in datum_shape) if datum_shape is not None else None
        )
        self.datum_dtype: Optional[str] = (
            str(datum_dtype) if datum_dtype is not None else None
        )
        self._compiled: Optional[Callable] = None
        #: one entry per XLA trace of the compiled function — ``(shape, dtype)``
        #: of the stacked input. len() == number of compiles paid so far.
        self._compiled_signatures: List[tuple] = []
        #: memoized content fingerprint (the graph is immutable post-fit)
        self._fingerprint: Optional[str] = None
        #: segment-dispatch plan cached across applies: every apply
        #: splices an IDENTICAL graph (deterministic node ids, shared
        #: operator objects), so apply #1's executor plan transfers and
        #: later applies skip the fingerprint + lattice replanning work
        self._segment_plan: Optional[dict] = None

    @property
    def graph(self) -> Graph:
        return self._graph

    def to_pipeline(self) -> Pipeline:
        p = Pipeline(self._graph, self._source, self._sink)
        if self.datum_shape is not None and self.datum_dtype is not None:
            p._datum_hint = (self.datum_shape, self.datum_dtype)
        return p

    # -- application (no optimizer pass: parity with the reference, which
    #    applies FittedPipelines without re-optimizing. Which nodes make one
    #    XLA program is the segment planner's decision, taken per executor:
    #    at fit over the graph with its estimators (each a barrier), here
    #    over the fitted chain — so the apply program holds the fitted
    #    mapper and the featurizer together, which no fit-time program did.
    #    Float32 results may differ in the last bits between the two
    #    partitionings; tests/compile/test_segment.py holds them to node
    #    dispatch.)

    def apply(self, data: Any) -> Dataset:
        graph, data_id = attach_data(self._graph, data)
        graph = graph.replace_dependency(self._source, data_id)
        graph = graph.remove_source(self._source)
        # the cached plan transfers only to the single-leaf splice: a
        # PipelineResult splices its whole prefix graph, so node ids no
        # longer line up with the plan's
        plain_splice = not isinstance(data, PipelineResult)
        executor = GraphExecutor(
            graph, optimize=False,
            segment_plan=self._segment_plan if plain_splice else None,
        )
        with _span("pipeline.apply", op_type=type(self).__name__) as sp:
            value = executor.execute(self._sink).get()
            sp.sync_on(value)
        if plain_splice and self._segment_plan is None:
            self._segment_plan = executor.segment_plan
        return value

    def apply_datum(self, datum: Any) -> Any:
        graph, datum_id = attach_datum(self._graph, datum)
        graph = graph.replace_dependency(self._source, datum_id)
        graph = graph.remove_source(self._source)
        executor = GraphExecutor(
            graph, optimize=False, segment_plan=self._segment_plan
        )
        value = executor.execute(self._sink).get()
        if self._segment_plan is None:
            self._segment_plan = executor.segment_plan
        return value

    def __call__(self, data: Any) -> Any:
        return self.apply(data)

    # -- compilation ----------------------------------------------------

    def batch_coupled_nodes(self) -> List[str]:
        """Labels of nodes whose ``trace_batch`` couples rows (whole-batch
        statistics). Such chains must not be served through any
        pad-and-slice path (:meth:`apply_chunked`, the serving engine's
        bucket padding) — padded rows would silently fold into every real
        row's answer."""
        labels = []
        for node in self._graph.nodes:
            op = self._graph.get_operator(node)
            if getattr(op, "batch_coupled", False):
                labels.append(op.label)
        return labels

    def check(self, datum_spec: Optional[tuple] = None, *, span: bool = True):
        """Static check of the fitted chain (see :meth:`Pipeline.check`).
        Not memoized: tests and tools may mutate operator flags post-fit,
        and the whole pass costs milliseconds."""
        from .. import check as check_mod
        from .. import cost as cost_mod

        spec = datum_spec
        if spec is None and self.datum_shape is not None:
            spec = (self.datum_shape, self.datum_dtype or "float32")
        report = check_mod.check_graph(
            self._graph,
            source=self._source,
            datum_spec=spec,
            cost_estimator=cost_mod.get_estimator(),
        )
        if span:
            _emit_check_span(report, type(self).__name__)
        return report

    def untraceable_nodes(self) -> List[str]:
        """Labels of nodes that block whole-chain compilation — the
        STATIC verdict (``keystone_tpu/check/``: ``opaque`` — no
        ``trace_batch`` — or ``stateful``), not a try-trace probe. Empty
        list ⇒ the pipeline jit-compiles."""
        return self.check(span=False).untraceable_labels()

    @property
    def is_traceable(self) -> bool:
        return not self.untraceable_nodes()

    def trace_fn(self) -> Optional[Callable]:
        """Build one pure function (stacked-array in → stacked-array out)
        from the transformer DAG, if the static checker clears every node.

        Returns None when any node is untraceable (host-side, ragged, ...);
        :meth:`untraceable_nodes` names the blockers.
        """
        blockers = self.untraceable_nodes()
        if blockers:
            logger.debug("pipeline not traceable: %s", ", ".join(blockers))
            return None
        return self._build_trace_fn()

    def _build_trace_fn(self) -> Callable:
        """The raw chain builder — callers must have cleared
        :meth:`untraceable_nodes` first."""
        from ..check.segments import Segment
        from ..compile.segment import lower_segment

        graph = self._graph
        whole = Segment(
            index=0,
            nodes=[
                n for n in analysis.linearize(graph) if isinstance(n, NodeId)
            ],
            inputs=[self._source],
            outputs=[graph.get_sink_dependency(self._sink)],
        )
        chain, _steps, _out_slots = lower_segment(graph, whole)

        def fn(x):
            return chain(x)[0]

        return fn

    def fingerprint(self) -> str:
        """Canonical content digest of this pipeline — graph topology +
        operator identities + fitted-parameter digests; stable across
        processes (see ``compile/fingerprint.py``). Raises
        :class:`~keystone_tpu.compile.FingerprintError` when some operator
        state has no content-stable form. Memoized: the graph is immutable
        after fit."""
        if self._fingerprint is None:
            from ..compile import pipeline_fingerprint

            self._fingerprint = pipeline_fingerprint(self)
        return self._fingerprint

    def compile(
        self,
        strict: bool = True,
        on_trace: Optional[Callable[[tuple], None]] = None,
        cache: Any = "auto",
    ) -> Optional[Callable]:
        """Compile the composed transformer chain into one XLA computation.

        ``strict=True`` (default) raises :class:`NotTraceableError` naming the
        blocking nodes, so a service can fail fast at construction instead of
        discovering per-call degradation under traffic. ``strict=False`` is
        the escape hatch for callers that probe-and-fall-back: returns None.

        Every XLA *trace* of the compiled function (one per distinct input
        shape/dtype — i.e. one per compile actually paid) appends the input's
        ``(shape, dtype)`` signature to :attr:`compiled_signatures` and fires
        ``on_trace(signature)`` — the hook callers use to count compiles and
        assert shape-stability invariants. (The serving engine keeps its own
        private jit with equivalent per-trace accounting so that direct use
        of this method cannot pollute a live engine's counters.)

        ``cache`` selects the AOT executable cache
        (:mod:`keystone_tpu.compile`): ``"auto"`` (default) uses the
        process-configured cache (``KEYSTONE_AOT_CACHE`` / ``--aot-cache``)
        when the pipeline fingerprints; an :class:`ExecutableCache` uses
        that cache; ``None`` forces the legacy in-process jit. With a cache,
        each input signature first tries to LOAD a previously exported
        executable — a hit pays zero traces (``compiled_signatures`` stays
        empty for it) — and a miss traces once, exports, and persists for
        every future process.
        """
        import jax

        # one static check drives the whole compile decision: blockers
        # raise typed BEFORE any tracing, and the export verdict steers
        # the AOT path (a host-callback chain jits but cannot export —
        # attempting the export would only fail after a full trace)
        report = self.check(span=False)
        blockers = report.untraceable_labels()
        if blockers:
            if strict:
                raise NotTraceableError(blockers)
            return None
        fn = self._build_trace_fn()
        # counts are per-live-jit (same contract __getstate__ enforces):
        # a recompile replaces the executable, so stale signatures from the
        # discarded jit would report phantom recompiles
        self._compiled_signatures = []
        signatures = self._compiled_signatures

        def note_trace(sig):
            signatures.append(sig)
            if on_trace is not None:
                on_trace(sig)

        aot = self._aot_dispatcher(
            fn, cache, note_trace, exportable=report.exportable
        )
        if aot is not None:
            self._compiled = aot
            return self._compiled

        def traced(x):
            # runs only while jax traces, i.e. exactly once per compile;
            # bound to THIS jit's list so a superseded executable that
            # retraces can't pollute the replacement's accounting
            note_trace((tuple(x.shape), str(x.dtype)))
            return fn(x)

        self._compiled = jax.jit(traced)
        return self._compiled

    def _aot_dispatcher(
        self,
        fn: Callable,
        cache: Any,
        note_trace: Callable,
        exportable: Optional[bool] = None,
    ) -> Optional[Callable]:
        """Build the cache-aware per-signature dispatcher, or None when AOT
        caching is off / the pipeline cannot be content-keyed / the static
        checker proved the chain cannot export (host callbacks)."""
        from .. import compile as compile_mod

        if cache == "auto":
            cache = compile_mod.get_cache()
        if cache is None:
            return None
        if exportable is False:
            logger.info(
                "aot cache skipped (static checker: chain is not "
                "exportable — host-callback/stateful nodes); using "
                "in-process jit"
            )
            return None
        try:
            digest = self.fingerprint()
        except compile_mod.FingerprintError as e:
            logger.info("aot cache skipped (pipeline not fingerprintable): %s", e)
            degraded("aot_fingerprint")
            return None
        except Exception:
            # a fingerprint walk blowing up (self-referential state, exotic
            # objects) must cost the cache, never the compile
            logger.warning("aot cache skipped (fingerprinting failed)", exc_info=True)
            degraded("aot_fingerprint")
            return None
        return compile_mod.AotDispatcher(
            fn, digest, cache, on_trace=note_trace,
            label="pipeline.compile",
            expected_exportable=bool(exportable),
        )

    @property
    def compiled_signatures(self) -> List[tuple]:
        """``(shape, dtype)`` of every trace paid so far, in compile order."""
        return list(self._compiled_signatures)

    @property
    def compile_count(self) -> int:
        return len(self._compiled_signatures)

    def apply_compiled(self, data: Any) -> Any:
        if self._compiled is None:
            self.compile()
        arr = Dataset.of(data).to_array() if not hasattr(data, "shape") else data
        return self._compiled(arr)

    def apply_chunked(self, data: Any, chunk_size: int = 64) -> Dataset:
        """Serve ANY batch size through one fixed-shape executable.

        XLA specializes each program to its input shapes, so applying a
        fitted pipeline to a new batch size recompiles the whole serve
        program — tens of seconds for the image stacks, paid again for
        every distinct size. Here the input is split into ``chunk_size``
        row blocks (the tail padded by repeating its first row, sliced
        off after), so every call after the first reuses one compiled
        program regardless of input size.

        Valid ONLY for row-wise chains — each output row a function of
        its input row alone — which holds for every serve-path
        transformer in this library's pipelines (fitted normalizers,
        featurizers, linear models, classifiers). Nodes declaring
        ``batch_coupled = True`` are rejected here (the padded tail
        chunk would silently change their output) and must go through
        :meth:`apply`.
        """
        if chunk_size <= 0:
            raise ValueError("chunk_size must be positive")
        coupled = self.batch_coupled_nodes()
        if coupled:
            raise ValueError(
                f"apply_chunked on a batch-coupled chain ({coupled[0]}): "
                "the padded tail chunk would corrupt batch statistics — "
                "use apply() instead"
            )
        if self._compiled is None:
            self.compile()
        arr = Dataset.of(data).to_array() if not hasattr(data, "shape") else data
        n = int(arr.shape[0])
        if n == 0:  # zero chunks would be produced; apply() handles empty
            return self.apply(data)
        import jax
        import jax.numpy as jnp
        import numpy as np

        host_resident = isinstance(arr, np.ndarray)
        outs = []

        def run(dev_chunk, pad):
            out = self._compiled(dev_chunk)
            if not hasattr(out, "shape"):
                raise TypeError(
                    "apply_chunked needs a single-array output; use apply() "
                    "for gathered/tuple sinks"
                )
            outs.append(out[: chunk_size - pad] if pad else out)

        if host_resident:
            # Ingest-to-prediction double buffering: uploading a batch of
            # images serially before its compute leaves the chip idle for
            # the upload. Start chunk i+1's H2D BEFORE dispatching chunk i's
            # compute — the upload streams while the device works, and the
            # queue never blocks the host until the final fetch.
            prev = None
            for i in range(0, n, chunk_size):
                chunk = arr[i : i + chunk_size]
                pad = chunk_size - int(chunk.shape[0])
                if pad:  # host input: pad on host, no device round trip
                    chunk = np.concatenate(
                        [chunk, np.repeat(chunk[:1], pad, axis=0)], axis=0
                    )
                dev = jax.device_put(chunk)
                if prev is not None:
                    run(*prev)
                prev = (dev, pad)
            run(*prev)
        else:
            for i in range(0, n, chunk_size):
                chunk = arr[i : i + chunk_size]
                pad = chunk_size - int(chunk.shape[0])
                if pad:
                    # pad on device — a host round trip here would add the
                    # transport's blocking-fetch latency to every call
                    chunk = jnp.concatenate(
                        [chunk, jnp.repeat(chunk[:1], pad, axis=0)], axis=0
                    )
                run(chunk, pad)
        return Dataset(
            outs[0] if len(outs) == 1 else jnp.concatenate(outs, axis=0),
            batched=True,
        )

    # -- incremental refit ----------------------------------------------

    def absorbable_nodes(self) -> List[NodeId]:
        """Nodes carrying a snapshot-able solver state (see
        ``linalg/accumulators.py``) — the models :meth:`absorb` can fold
        appended chunks into."""
        return [
            n
            for n in self._graph.nodes
            if getattr(self._graph.get_operator(n), "solver_state", None)
            is not None
        ]

    def _absorb_node(self):
        """The unique solver-state node absorb folds into, or a typed
        refusal. Returns ``(node, mapper)``."""
        from ..linalg.accumulators import NotAbsorbable

        nodes = self.absorbable_nodes()
        if not nodes:
            raise NotAbsorbable(
                "absorb needs a model fit with a snapshot-able solver "
                "state — fit with LinearMapEstimator(snapshot=True), "
                "PerClassWeightedLeastSquaresEstimator(snapshot=True), "
                "or a GridSweep Gram-family member (the BCD-iterated "
                "families have no associative state and cannot absorb)"
            )
        if len(nodes) > 1:
            labels = [self._graph.get_operator(n).label for n in nodes]
            raise ValueError(
                f"absorb is ambiguous: {len(nodes)} solver-state nodes "
                f"({', '.join(labels)})"
            )
        (node,) = nodes
        return node, self._graph.get_operator(node)

    def _prefix_executor(self, node, data):
        """Executor over this pipeline's frozen prefix (everything
        upstream of the model node), with ``data`` attached — executed
        WITHOUT re-optimizing (same invariant as apply(): re-fusing a
        fitted graph can change float32 program partitioning vs what
        the solver trained on). Returns ``(executor, sink)``."""
        deps = self._graph.get_dependencies(node)
        if len(deps) != 1:
            raise ValueError(
                f"absorb expects a single-input model node, got {len(deps)} deps"
            )
        prefix_graph, prefix_sink = self._graph.add_sink(deps[0])
        prefix_graph, data_id = attach_data(prefix_graph, data)
        prefix_graph = prefix_graph.replace_dependency(self._source, data_id)
        prefix_graph = prefix_graph.remove_source(self._source)
        return GraphExecutor(prefix_graph, optimize=False), prefix_sink

    def prefix_features(self, data: Any):
        """Run ``data`` through the frozen featurizer prefix (everything
        upstream of the absorbable model node) and return the featurized
        value — what the model node would see at fit time. The trainer
        daemon's drift monitor compares these features against the
        fitted solver state's :meth:`~keystone_tpu.linalg.accumulators.
        GramSolverState.moments` snapshot, and applies the model mapper
        to them for streaming residual error, without paying a full
        pipeline apply per monitored chunk."""
        node, _ = self._absorb_node()
        executor, sink = self._prefix_executor(node, data)
        return executor.execute(sink).get()

    def absorb(
        self,
        new_data: Any,
        new_labels: Any,
        *,
        checkpoint: Optional[str] = None,
        checkpoint_key: Optional[str] = None,
        checkpoint_every: int = 1,
        on_chunk: Optional[Callable[[int, Any], None]] = None,
    ) -> "FittedPipeline":
        """Fold appended training chunks into the fitted model WITHOUT a
        from-scratch refit.

        The terminal solver must have been fit with a snapshot-able
        accumulator (``LinearMapEstimator(snapshot=True)``, any sweep
        Gram-family member, or the per-class weighted family's
        ``snapshot=True``): its saved state
        (:class:`~keystone_tpu.linalg.accumulators.GramSolverState` /
        :class:`~keystone_tpu.linalg.weighted.WeightedSolverState`)
        holds the raw sums of everything seen so far, so the update is
        (a) featurize ONLY the new chunks through this pipeline's frozen
        prefix, (b) fold them into the accumulators, (c) re-solve at the
        recorded λ — O(new chunks + solve) total. The old training data
        is never touched. Models without such a state raise the typed
        :class:`~keystone_tpu.linalg.accumulators.NotAbsorbable`.

        ``checkpoint`` (a directory) makes a chunked absorb RESUMABLE:
        the folding state persists atomically every ``checkpoint_every``
        chunks (:class:`~keystone_tpu.faults.FitCheckpoint`), so an
        absorb killed mid-fold and retried with the same arguments
        resumes from the last completed block — folding bit-identical
        state — and never re-produces the already-folded prefix (the
        trainer daemon's crash-survival contract). ``checkpoint_key``
        overrides the identity the checkpoint is keyed by (callers that
        retry a specific chunk batch pass a stable batch id); the
        default derives from the base state and the appended length.
        The checkpoint is removed when the absorb completes.

        ``on_chunk(chunk_index, feat_chunk)`` runs before each chunk is
        folded — the trainer's seam for the ``trainer.absorb`` fault
        point and drift bookkeeping. It fires only for chunks actually
        produced this call (a resumed absorb skips the folded prefix).

        Upstream fitted transformers (scalers, PCA, ...) stay FROZEN:
        refitting them would change the featurization of every
        previously-absorbed row, which only a full refit can do
        consistently. Returns a NEW FittedPipeline (this one is
        unchanged) — publish it to a live engine with
        ``ServingEngine.swap`` / ``ServingFleet.swap``.
        """
        from ..data.chunked import ChunkedDataset
        from ..data.dataset import Dataset as _Dataset

        node, mapper = self._absorb_node()
        state = mapper.solver_state.snapshot()
        prefix_exec, prefix_sink = self._prefix_executor(node, new_data)

        with _span(
            "pipeline.absorb",
            op_type=type(self).__name__,
            prior_rows=int(state.n),
        ) as sp:
            import jax.numpy as jnp

            feats = prefix_exec.execute(prefix_sink).get()
            y = jnp.asarray(
                _Dataset.of(new_labels).to_array(), dtype=jnp.float32
            )
            if isinstance(feats, ChunkedDataset):
                ckpt = None
                start_chunk = 0
                offset = 0
                if checkpoint is not None:
                    import hashlib

                    import numpy as _np_mod

                    from ..faults import FitCheckpoint

                    # the default key binds the APPENDED DATA's identity
                    # through a digest of the labels (already resident —
                    # no extra chunk production): a crashed absorb's
                    # checkpoint must never be resumed by a later absorb
                    # of DIFFERENT same-shaped data. Callers retrying a
                    # specific batch pass checkpoint_key for an explicit
                    # identity (features differing under identical
                    # labels still need it).
                    y_digest = hashlib.sha256(
                        _np_mod.asarray(y).tobytes()
                    ).hexdigest()[:16]
                    key = checkpoint_key or (
                        f"absorb|base={state.n}|new={len(feats)}"
                        f"|y={tuple(int(s) for s in y.shape)}"
                        f"|ydig={y_digest}|lam={state.lam}"
                    )
                    ckpt = FitCheckpoint(checkpoint, key)
                    loaded = ckpt.load()
                    if loaded is not None:
                        state, start_chunk, offset = loaded
                        logger.info(
                            "absorb: resuming at chunk %d (row %d) "
                            "from %s", start_chunk, offset, ckpt.path,
                        )
                every = max(1, int(checkpoint_every))
                i = start_chunk
                for chunk in feats.raw_chunks(skip=start_chunk):
                    if on_chunk is not None:
                        on_chunk(i, chunk)
                    rows = int(chunk.shape[0])
                    state.update(chunk, y[offset : offset + rows])
                    offset += rows
                    i += 1
                    if ckpt is not None and i % every == 0:
                        ckpt.save(state, i, offset)
                if offset != int(y.shape[0]):
                    raise ValueError(
                        f"new chunks have {offset} rows, labels {y.shape[0]}"
                    )
                if ckpt is not None:
                    ckpt.complete()
            else:
                if on_chunk is not None:
                    on_chunk(0, feats)
                state.update(_Dataset.of(feats).to_array(), y)
            new_mapper = state.rebuild_mapper(mapper)
            sp.attrs["absorbed_rows"] = int(state.rows_folded)
            sp.attrs["total_rows"] = int(state.n)
            solved_w = getattr(new_mapper, "W", None)
            if solved_w is not None:
                sp.sync_on(solved_w)
        updated = FittedPipeline(
            self._graph.set_operator(node, new_mapper),
            self._source,
            self._sink,
            datum_shape=self.datum_shape,
            datum_dtype=self.datum_dtype,
        )
        return updated

    # -- persistence ----------------------------------------------------

    def save(self, path: str) -> None:
        from ..utils.serialization import save_pickle

        save_pickle(self, path)

    @staticmethod
    def load(path: str) -> "FittedPipeline":
        from ..utils.serialization import load_pickle

        obj = load_pickle(path)
        if not isinstance(obj, FittedPipeline):
            raise TypeError(f"{path} does not contain a FittedPipeline")
        return obj

    def __getstate__(self):
        state = dict(self.__dict__)
        state["_compiled"] = None  # jitted callables don't pickle
        state["_compiled_signatures"] = []  # counts are per-live-jit
        state["_segment_plan"] = None  # lowered closures don't pickle
        return state

    def __setstate__(self, state):
        self.__dict__.update(state)
        # pickles from before compile-signature tracking / datum hints /
        # AOT fingerprinting / segment planning
        self.__dict__.setdefault("_compiled_signatures", [])
        self.__dict__.setdefault("datum_shape", None)
        self.__dict__.setdefault("datum_dtype", None)
        self.__dict__.setdefault("_fingerprint", None)
        self.__dict__.setdefault("_segment_plan", None)
