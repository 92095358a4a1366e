"""Named optimizer rule stacks (parity: ``workflow/DefaultOptimizer.scala``).

Optimization is memoized process-wide by graph fingerprint: the rule
stack is deterministic in (optimizer config, graph structure, the
operator objects themselves, the saved-state table), so running it twice
on the same inputs is pure waste — an L-stage composition or a re-applied
pipeline pays the stack once. The memo key includes the
:class:`~keystone_tpu.workflow.env.VersionedState` version because
``SavedStateLoadRule`` bakes saved expressions INTO the optimized graph:
any state mutation (a fit saving a prefix, a test reset) invalidates
every cached plan. A fit that is LEARNING (an open cost-model pending
plan) bypasses the memo entirely — its rules must re-deposit their
decisions for the re-planning loop to join against.
``KEYSTONE_OPT_MEMO=0`` is the kill switch.
"""

from __future__ import annotations

import threading
from collections import OrderedDict
from typing import List, Optional, Tuple

from .rules import (
    Annotations,
    Batch,
    EquivalentNodeMergeRule,
    ExtractSaveablePrefixes,
    Rule,
    RuleExecutor,
    SavedStateLoadRule,
    Strategy,
    UnusedBranchRemovalRule,
)

#: bounded process-wide memo: key -> (input_graph, optimized_graph, ann).
#: The input graph rides in the entry so the operator objects its key
#: hashes by identity stay alive for the life of the entry (a GC'd
#: operator's id could otherwise be reused by a structurally-equal twin).
_MEMO_MAX = 32
#: entries pin their graphs — and a graph's Dataset/Datum leaves pin
#: their PAYLOADS. Entry count bounds entries, not bytes: a graph whose
#: in-memory leaf payloads exceed this is not memoized at all, so a
#: long-lived process cannot accumulate 32 multi-GB training arrays
#: behind dropped pipelines. (Chunked datasets hold factories, not
#: arrays — they memoize freely.)
_MEMO_MAX_PAYLOAD_BYTES = 64 << 20
_memo: "OrderedDict[tuple, tuple]" = OrderedDict()
_memo_lock = threading.Lock()
#: observability for tests and the bench: hits/misses/bypasses
memo_stats = {"hits": 0, "misses": 0, "bypasses": 0}


def _payload_bytes(graph) -> int:
    """In-memory bytes the graph's data leaves would pin (materialized
    array payloads only; factories/lazy sources count 0)."""
    from .operators import DatasetOperator, DatumOperator

    total = 0
    for node in graph.nodes:
        op = graph.get_operator(node)
        payload = None
        if isinstance(op, DatasetOperator):
            payload = op.dataset.payload
        elif isinstance(op, DatumOperator):
            payload = op.datum
        if payload is not None:
            total += int(getattr(payload, "nbytes", 0) or 0)
    return total


def memo_enabled() -> bool:
    from ..utils import env_flag

    return env_flag("KEYSTONE_OPT_MEMO", True)


def clear_memo() -> None:
    """Drop every memoized plan (test isolation)."""
    with _memo_lock:
        _memo.clear()
        memo_stats.update(hits=0, misses=0, bypasses=0)


def _memo_key(optimizer: "Optimizer", graph) -> Optional[tuple]:
    """The cache identity of one optimize run, or None when the graph
    cannot be fingerprinted. Operators participate as OBJECTS (identity-
    hashed, except the payload-identity Dataset/Datum leaves) — two
    structurally-equal graphs over different estimator instances must
    never share a plan, or the wrong instances would be fitted."""
    from ..cost.replan import graph_fingerprint
    from . import analysis
    from .env import PipelineEnv
    from .graph import NodeId

    try:
        ops = tuple(
            graph.get_operator(gid)
            for gid in analysis.linearize(graph)
            if isinstance(gid, NodeId) and gid in graph.operators
        )
        return (
            type(optimizer),
            optimizer.memo_config(),
            PipelineEnv.get_or_create().state.version,
            graph_fingerprint(graph),
            ops,
        )
    except Exception:
        # an unkeyable graph bypasses the memo — correct, just slower
        import logging

        logging.getLogger(__name__).debug(
            "optimize memo key not derivable; bypassing", exc_info=True
        )
        return None


class Optimizer(RuleExecutor):
    """Base optimizer type registered in :class:`PipelineEnv`."""

    def memo_config(self) -> tuple:
        """Hashable configuration participating in the memo key —
        subclasses with knobs that change the produced plan must include
        them (see :class:`AutoCachingOptimizer`)."""
        return ()

    def execute(
        self, graph, annotations: Optional[Annotations] = None
    ) -> Tuple[object, Annotations]:
        from ..cost import current_plan

        key = None
        if (
            memo_enabled()
            and not annotations
            and current_plan() is None
            and _payload_bytes(graph) <= _MEMO_MAX_PAYLOAD_BYTES
        ):
            key = _memo_key(self, graph)
        if key is None:
            memo_stats["bypasses"] += 1
            return super().execute(graph, annotations)
        with _memo_lock:
            entry = _memo.get(key)
            if entry is not None:
                _memo.move_to_end(key)
                memo_stats["hits"] += 1
                # annotations are copied out: callers attach them to
                # executors that may extend them in place
                return entry[1], dict(entry[2])
        memo_stats["misses"] += 1
        out_graph, ann = super().execute(graph, annotations)
        with _memo_lock:
            _memo[key] = (graph, out_graph, dict(ann))
            while len(_memo) > _MEMO_MAX:
                _memo.popitem(last=False)
        return out_graph, ann


class DefaultOptimizer(Optimizer):
    """Load saved state, then CSE, then node-level implementation choice."""

    def batches(self) -> List[Batch]:
        from .node_optimization import NodeOptimizationRule

        return self._head_batches() + [
            Batch("Node Level Optimization", Strategy.ONCE, [NodeOptimizationRule()]),
            self._chains_batch(),
        ]

    def _head_batches(self) -> List[Batch]:
        return [
            Batch(
                "Load Saved State",
                Strategy.ONCE,
                [ExtractSaveablePrefixes(), SavedStateLoadRule(), UnusedBranchRemovalRule()],
            ),
            Batch(
                "Common Sub-expression Elimination",
                Strategy.FIXED_POINT,
                [EquivalentNodeMergeRule()],
            ),
        ]

    def _chains_batch(self) -> Batch:
        """Last batch always: a chain that one node computes with less
        becomes that node (``nodes/images/chain.py``) — a convolution chain
        the one that keeps the convolution's output on the chip, a sampling
        pass over SIFT descriptors the one that makes only the sampled
        ones. Which nodes make one XLA program is not the optimizer's
        decision: the executor's segment planner groups what is left
        (``check/segments.py``)."""
        from ..nodes.images.chain import ConvChainRule, SampledSIFTRule

        return Batch(
            "Chains As One Node", Strategy.ONCE,
            [ConvChainRule(), SampledSIFTRule()],
        )


class AutoCachingOptimizer(DefaultOptimizer):
    """DefaultOptimizer plus profile-guided cache/materialization planning
    (parity: ``DefaultOptimizer.scala:19-26``)."""

    def __init__(self, strategy: str = "greedy", mem_budget_bytes: int = None):
        self.strategy = strategy
        self.mem_budget_bytes = mem_budget_bytes

    def memo_config(self) -> tuple:
        return (self.strategy, self.mem_budget_bytes)

    def batches(self) -> List[Batch]:
        from .autocache import AutoCacheRule
        from .node_optimization import NodeOptimizationRule

        return self._head_batches() + [
            Batch("Node Level Optimization", Strategy.ONCE, [NodeOptimizationRule()]),
            Batch(
                "Auto Cache",
                Strategy.ONCE,
                [AutoCacheRule(self.strategy, self.mem_budget_bytes)],
            ),
            self._chains_batch(),
        ]
