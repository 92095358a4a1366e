"""Whole-chain trace fusion: collapse traceable transformer subgraphs into
one jit-compiled operator.

The reference leans on Spark to pipeline narrow transformations within a
stage; the TPU-native equivalent is *compilation* — a chain of pure
``trace_batch`` nodes is one XLA program, not N eager dispatches. This rule
is where that happens for every execution path (fit-time featurization,
``Pipeline.apply``, ``FittedPipeline.apply``), not just the explicit
``FittedPipeline.compile`` front door.

Why it matters on real hardware: each eager op dispatch pays a first-call
XLA compile and its own launch; one fused program pays ONE compile
(persisted across processes via the jax compilation cache) and keeps every
intermediate in HBM.

No reference counterpart file: this rule exists because the execution
substrate is XLA; the closest analogue is Spark stage pipelining, which the
reference gets implicitly (SURVEY §2.7 "data parallelism").
"""

from __future__ import annotations

from typing import List, Sequence, Tuple

from ..data.dataset import Dataset
from . import analysis
from .expressions import DatasetExpression, DatumExpression
from .graph import Graph, NodeId
from .operators import (
    GatherTransformerOperator,
    TransformerOperator,
)
from .rules import Annotations, Rule


#: process-global jitted-callable cache keyed by the fused chain's
#: structural content (see FusedTransformerOperator._jitted). Holds the
#: first instance's ops (and their params) alive — the price of executable
#: reuse, same order of memory as the fitted pipelines themselves. LRU:
#: a long-lived sweep process re-fitting many distinct pipelines gets a
#: fresh key per fit (param digests differ), so without a bound every
#: discarded pipeline's weights would stay pinned for the process life.
from collections import OrderedDict

_FUSED_JIT_CACHE: "OrderedDict" = OrderedDict()
_FUSED_JIT_CACHE_MAX = 64


class FusedTransformerOperator(TransformerOperator):
    """A linearized traceable sub-DAG executing as one jitted XLA program.

    ``steps`` is a topologically-ordered list of ``(op, dep_indices)``; value
    index space is ``[0, n_inputs)`` for the fused node's inputs followed by
    one slot per step. The last step is the output.
    """

    #: ``_jit`` is derived memo state — a warm operator must AOT-fingerprint
    #: identically to a fresh one (see ``compile/fingerprint.py``)
    aot_fingerprint_exclude = ("_jit",)

    def __init__(self, steps: Sequence[Tuple[TransformerOperator, Tuple[int, ...]]],
                 n_inputs: int):
        self.steps = list(steps)
        self.n_inputs = n_inputs
        self._jit = None

    @property
    def label(self) -> str:
        inner = " » ".join(op.label for op, _ in self.steps)
        return f"Fused[{inner}]"

    @property
    def batch_coupled(self) -> bool:
        return any(
            getattr(op, "batch_coupled", False) for op, _ in self.steps
        )

    # -- traced path ----------------------------------------------------

    def trace_batch(self, *xs):
        values: List = list(xs)
        for op, deps in self.steps:
            args = [values[i] for i in deps]
            if isinstance(op, GatherTransformerOperator):
                values.append(tuple(args))
            else:
                values.append(op.trace_batch(*args))
        return values[-1]

    def _jitted(self):
        if self._jit is None:
            import jax

            from .operators import structural_key

            # Share the jitted callable across STRUCTURALLY EQUAL fused
            # chains: every fresh Pipeline instance builds fresh
            # FusedTransformerOperators, and a per-instance jax.jit means a
            # re-trace + executable re-load per instance, many times the
            # cost of running the program. Content-keyed reuse makes the
            # Nth structurally-identical pipeline hit jax.jit's own
            # executable cache. Ops with uncanonicalizable state key by
            # object identity (safe: reuse only within the same instance).
            op_keys = [structural_key(op) for op, _ in self.steps]
            if any(k is op for k, (op, _) in zip(op_keys, self.steps)):
                # identity-fallback key (closure/uncanonicalizable state):
                # a global entry could never be hit by another instance and
                # would pin the chain forever — keep the jit per-instance
                key = None
            else:
                try:
                    key = (
                        self.n_inputs,
                        tuple(
                            (k, tuple(deps))
                            for k, (_, deps) in zip(op_keys, self.steps)
                        ),
                    )
                    hash(key)
                except TypeError:
                    key = None
            if key is None:
                self._jit = jax.jit(self.trace_batch)
            else:
                cached = _FUSED_JIT_CACHE.get(key)
                if cached is None:
                    cached = _FUSED_JIT_CACHE[key] = jax.jit(self.trace_batch)
                    while len(_FUSED_JIT_CACHE) > _FUSED_JIT_CACHE_MAX:
                        _FUSED_JIT_CACHE.popitem(last=False)
                else:
                    _FUSED_JIT_CACHE.move_to_end(key)
                self._jit = cached
        return self._jit

    # -- operator glue --------------------------------------------------

    def batch_transform(self, inputs: Sequence[DatasetExpression]) -> Dataset:
        from ..data.chunked import ChunkedDataset, align_and_zip

        datasets = [d.get() for d in inputs]
        if any(isinstance(ds, ChunkedDataset) for ds in datasets):
            # out-of-core inputs: the fused program runs chunk-by-chunk,
            # lazily — one compiled executable per chunk shape, intermediates
            # bounded by one chunk (the whole point of data/chunked.py)
            if self.batch_coupled:
                coupled = [
                    op.label
                    for op, _ in self.steps
                    if getattr(op, "batch_coupled", False)
                ]
                raise ValueError(
                    f"batch-coupled node(s) {coupled} cannot stream "
                    "per-chunk: batch statistics would be computed per "
                    "chunk — materialize the dataset first"
                )
            # shape-bucket ragged (tail) chunks: pad up to a small static
            # ladder derived from the lead chunk and slice the pad off the
            # result, so the fused program compiles once per bucket instead
            # of once per distinct chunk shape (serving/batching.py's trick
            # applied to out-of-core scans). The padder is captured by the
            # lazy factory, so lineage re-scans reuse the same compiles.
            # shard=True: on a >1-wide data axis the padder rounds every
            # bucket to a lane multiple and commits the padded chunk with
            # batch_sharding before the call, so the fused program computes
            # SPMD across the whole mesh per chunk — featurization spans
            # the chips, not just the solver (ROADMAP "shard the whole fit
            # end-to-end"). A 1-lane mesh keeps this inert.
            from ..data.pipeline_scan import ChunkPadder

            fn = self._jitted()
            if len(datasets) == 1:
                return datasets[0].map_batch(ChunkPadder(fn, shard=True))
            zipped = align_and_zip(datasets)
            return zipped.map_batch(
                ChunkPadder(lambda t: fn(*t), shard=True)
            )
        if all(ds.is_batched for ds in datasets):
            arrays = [ds.to_array() for ds in datasets]
            return Dataset(self._jitted()(*arrays), batched=True)
        # Ragged/item-list inputs: fall back to the per-op Dataset semantics
        # the unfused graph would have used (correct, just not one program).
        values = list(datasets)
        for op, deps in self.steps:
            args = [DatasetExpression.now(values[i]) for i in deps]
            values.append(op.batch_transform(args))
        return values[-1]

    def single_transform(self, inputs: Sequence[DatumExpression]):
        values = [d.get() for d in inputs]
        for op, deps in self.steps:
            args = [DatumExpression.now(values[i]) for i in deps]
            values.append(op.single_transform(args))
        return values[-1]

    def __getstate__(self):
        state = dict(self.__dict__)
        state["_jit"] = None  # jitted callables don't pickle
        return state


class TraceFusionRule(Rule):
    """Replace maximal traceable transformer subgraphs (≥2 nodes) with
    :class:`FusedTransformerOperator` nodes.

    A node joins a group only if every consumer of its result is inside the
    group (so no fused intermediate is needed elsewhere) and it carries no
    saveable-prefix annotation (those results must hit the state table).
    Cachers, estimators, and host-side nodes have no ``trace_batch`` and
    bound the groups naturally.
    """

    name = "TraceFusionRule"

    @staticmethod
    def _traceable(op) -> bool:
        if getattr(op, "no_fuse", False):
            return False
        if isinstance(op, GatherTransformerOperator):
            return True
        return (
            isinstance(op, TransformerOperator)
            and getattr(op, "trace_batch", None) is not None
        )

    def apply(self, graph: Graph, annotations: Annotations) -> Tuple[Graph, Annotations]:
        consumers = {}
        for node in graph.nodes:
            for d in graph.get_dependencies(node):
                if isinstance(d, NodeId):
                    consumers.setdefault(d, set()).add(node)
        sink_consumed = set()
        for sink in graph.sinks:
            d = graph.get_sink_dependency(sink)
            if isinstance(d, NodeId):
                sink_consumed.add(d)

        order = [n for n in analysis.linearize(graph) if isinstance(n, NodeId)]
        assigned = set()
        groups: List[Tuple[NodeId, set]] = []
        for out in reversed(order):
            if (
                out in assigned
                or out in annotations
                or not self._traceable(graph.get_operator(out))
            ):
                continue
            group = {out}
            changed = True
            while changed:
                changed = False
                for member in list(group):
                    for d in graph.get_dependencies(member):
                        if (
                            isinstance(d, NodeId)
                            and d not in group
                            and d not in assigned
                            and d not in annotations
                            and d not in sink_consumed
                            and self._traceable(graph.get_operator(d))
                            and consumers.get(d, set()) <= group
                        ):
                            group.add(d)
                            changed = True
            if len(group) >= 2:
                groups.append((out, group))
                assigned |= group

        for out, group in groups:
            inner_order = [n for n in order if n in group]
            pos = {n: i for i, n in enumerate(inner_order)}
            ext: List = []
            for n in inner_order:
                for d in graph.get_dependencies(n):
                    if (not isinstance(d, NodeId) or d not in group) and d not in ext:
                        ext.append(d)
            ext_index = {d: i for i, d in enumerate(ext)}
            steps = []
            for n in inner_order:
                dep_idx = tuple(
                    len(ext) + pos[d]
                    if isinstance(d, NodeId) and d in group
                    else ext_index[d]
                    for d in graph.get_dependencies(n)
                )
                steps.append((graph.get_operator(n), dep_idx))
            fused = FusedTransformerOperator(steps, len(ext))

            rep = Graph()
            src_ids = []
            for _ in ext:
                rep, s = rep.add_source()
                src_ids.append(s)
            rep, fused_node = rep.add_node(fused, src_ids)
            rep, rep_sink = rep.add_sink(fused_node)
            graph = graph.replace_nodes(
                frozenset(group),
                rep,
                dep_splice={s: d for s, d in zip(src_ids, ext)},
                out_splice={out: rep_sink},
            )

        ann = {n: p for n, p in annotations.items() if n in graph.operators}
        return graph, ann
