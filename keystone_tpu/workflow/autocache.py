"""Profile-guided cache insertion (parity: ``workflow/AutoCacheRule.scala``).

In the reference, RDDs recompute per action unless a ``Cacher`` node persists
them; AutoCacheRule profiles nodes at several sample scales, fits linear
time/memory-vs-scale models (``generalizeProfiles``,
AutoCacheRule.scala:104-135), estimates per-node run counts from downstream
weights (``getRuns`` :57-81), and inserts Cacher nodes — either around
everything reused (``aggressiveCache`` :503-518) or greedily maximizing saved
time under a memory budget (``greedyCache`` :559-602).

Here the same algorithm runs over HBM: the executor retains only results
under a Cacher (plus datasets/fitted estimators) across pulls once this rule
has run — see ``GraphExecutor`` — so the budget genuinely bounds resident
bytes, and uncached intermediates recompute exactly like unpersisted RDDs.
The budget defaults to 75%% of free device memory when the platform reports
it (parity: 0.75 × cluster free storage, AutoCacheRule.scala:572-585).
"""

from __future__ import annotations

import logging
import time
from dataclasses import dataclass
from typing import Dict, List, Optional, Sequence, Tuple

import jax
import numpy as np

from ..data.dataset import Dataset
from ..obs import tracer as obs_tracer
from .executor import GraphExecutor
from .graph import Graph, NodeId
from .node_optimization import _sampled_graph
from .operators import DatasetOperator
from .rules import Annotations, Rule
from . import analysis

logger = logging.getLogger(__name__)

#: string key in the annotations dict marking that cache planning ran (the
#: executor switches from memoize-everything to Cacher-only retention).
AUTOCACHE_ACTIVE = "autocache_active"


@dataclass
class Profile:
    """Per-node cost estimate (parity: ``AutoCacheRule.scala:12``)."""

    ns: float  # nanoseconds to compute
    mem_bytes: float  # size of the materialized result

    def __add__(self, other: "Profile") -> "Profile":
        return Profile(self.ns + other.ns, self.mem_bytes + other.mem_bytes)


def _result_bytes(value) -> float:
    from ..obs.span import cheap_nbytes

    if isinstance(value, Dataset) and not value.is_batched:
        # profiling MAY force materialization (that's its job, unlike the
        # tracer's no-side-effect sizing): item lists collect and sum
        return float(
            sum(getattr(np.asarray(x), "nbytes", 64) for x in value.collect())
        )
    n = cheap_nbytes(value)
    return 64.0 if n is None else float(n)


def _profile_at_scale(graph: Graph, sample_size: int) -> Dict[NodeId, Profile]:
    sampled = _sampled_graph(graph, sample_size)
    # parallel=False: the fitted time-vs-scale model needs each node's own
    # wall-clock — sibling branches running on other cores during a timed
    # pull would inflate (contention) or hide (overlap) per-node cost.
    # Production pulls still run concurrently; retention is unchanged
    # (uncached intermediates stay in the per-pull transient table and the
    # scheduler drops each expression as it completes), though peak
    # transient memory under concurrency can reach worker-count in-flight
    # branches' intermediates at once — KEYSTONE_EXEC_WORKERS bounds it.
    executor = GraphExecutor(sampled, optimize=False, parallel=False)
    profiles: Dict[NodeId, Profile] = {}
    from .. import cost as cost_mod

    # profiling pulls run at sampled scale over a TRUNCATED graph whose
    # node ids collide with the production graph's — suspend tracing so
    # they can't pollute the real span registry / audit observations
    with obs_tracer.suspended():
        for gid in analysis.linearize(sampled):
            if not isinstance(gid, NodeId):
                continue
            try:
                t0 = time.perf_counter_ns()
                cost_mod.count_sampling("autocache")
                value = executor.execute(gid).get()
                elapsed = time.perf_counter_ns() - t0
            except Exception as e:
                logger.debug("profiling skipped %s: %s", gid, e)
                continue
            profiles[gid] = Profile(float(elapsed), _result_bytes(value))
    return profiles


def profile_nodes(
    graph: Graph,
    sample_sizes: Sequence[int] = (8, 16, 24),
    full_size: Optional[int] = None,
    calibration: Optional[Dict[NodeId, float]] = None,
) -> Dict[NodeId, Profile]:
    """Profile at several sample scales and fit a linear model per node,
    extrapolated to the full input size (parity: ``generalizeProfiles``,
    AutoCacheRule.scala:104-135 — same least-squares-in-scale idea, with
    jit warmup noise damped by taking the *minimum* time per scale).

    ``calibration`` holds per-node observed/estimated seconds ratios
    measured by a previous traced run of the same pipeline
    (``cost.replan.stored_calibration``): each node's extrapolation is
    scaled by ITS OWN measured sample-to-full ratio rather than trusting
    one global linear-in-n factor — nodes whose per-item cost shifts
    between the 24-item sample scale and the real run (compile overhead
    amortization, cache effects, batching cliffs) were the audit's worst
    estimate-vs-observed ratios. Ratios are clamped to [1/64, 64] so one
    corrupt observation cannot zero out or explode a plan."""
    input_size = _full_input_size(graph)
    # the truncated leaf size actually run: requested scale capped by the
    # real dataset size (otherwise the fitted slope uses a wrong Δx)
    scales = sorted({min(s, input_size) for s in sample_sizes})
    per_scale = [(s, _profile_at_scale(graph, s)) for s in scales]
    nodes = set().union(*[set(p.keys()) for _, p in per_scale]) if per_scale else set()
    out: Dict[NodeId, Profile] = {}
    for n in nodes:
        xs, ts, bs = [], [], []
        for s, profs in per_scale:
            if n in profs:
                xs.append(float(s))
                ts.append(profs[n].ns)
                bs.append(profs[n].mem_bytes)
        if not xs:
            continue
        target = float(full_size if full_size is not None else max(xs))
        if len(xs) >= 2 and len(set(xs)) >= 2:
            A = np.stack([np.ones(len(xs)), np.asarray(xs)], axis=1)
            t_coef, *_ = np.linalg.lstsq(A, np.asarray(ts), rcond=None)
            b_coef, *_ = np.linalg.lstsq(A, np.asarray(bs), rcond=None)
            ns = max(t_coef[0] + t_coef[1] * target, min(ts))
            mem = max(b_coef[0] + b_coef[1] * target, 0.0)
        else:
            scale = target / xs[-1]
            ns, mem = ts[-1] * scale, bs[-1] * scale
        ratio = (calibration or {}).get(n)
        if ratio is not None:
            ns *= float(np.clip(ratio, 1.0 / 64.0, 64.0))
        out[n] = Profile(float(ns), float(mem))
    return out


def estimate_runs(
    graph: Graph, weights: Dict[NodeId, int], cached: set
) -> Dict[NodeId, int]:
    """Times each node runs given which nodes are cached: a node reruns once
    per (weighted) downstream consumer path that is not cut by a cached node
    (parity: ``AutoCacheRule.getRuns``)."""
    runs: Dict[NodeId, int] = {}

    def runs_of(gid) -> int:
        if gid in runs:
            return runs[gid]
        children = analysis.get_children(graph, gid)
        if not children:
            total = 1
        else:
            total = 0
            for c in children:
                if isinstance(c, NodeId):
                    w = weights.get(c, 1)
                    total += w * (1 if c in cached else runs_of(c))
                else:  # sink
                    total += 1
        runs[gid] = max(total, 1)
        return runs[gid]

    for n in graph.nodes:
        runs_of(n)
    return runs


def _device_budget_bytes() -> int:
    """75% of free device memory when the backend reports it, else 4 GiB."""
    try:
        stats = jax.devices()[0].memory_stats() or {}
        limit = stats.get("bytes_limit")
        in_use = stats.get("bytes_in_use", 0)
        if limit:
            return int(0.75 * (limit - in_use))
    except Exception:
        logger.debug(
            "device memory_stats unavailable; using the 4 GiB default "
            "cache budget", exc_info=True,
        )
    return 4 << 30


def _is_cacher(op) -> bool:
    from ..nodes.util.core import Cacher

    return isinstance(op, Cacher)


def insert_cachers(graph: Graph, nodes: Sequence[NodeId]) -> Graph:
    """Splice a Cacher after each selected node, rerouting every consumer
    (parity: ``addCachesToPipeline``, AutoCacheRule.scala:492-501)."""
    from ..nodes.util.core import Cacher

    for n in nodes:
        children = analysis.get_children(graph, n)
        existing = [
            c for c in children
            if isinstance(c, NodeId) and _is_cacher(graph.get_operator(c))
        ]
        if existing:
            # reuse the existing Cacher: reroute any consumer that bypasses it
            cacher = existing[0]
        else:
            graph, cacher = graph.add_node(Cacher(), [n])
        for c in children:
            if c == cacher:
                continue
            if isinstance(c, NodeId):
                if _is_cacher(graph.get_operator(c)):
                    continue  # a second cacher; leave it alone
                deps = [
                    cacher if d == n else d for d in graph.get_dependencies(c)
                ]
                graph = graph.set_dependencies(c, deps)
            else:  # SinkId
                graph = graph.set_sink_dependency(c, cacher)
    return graph


class AutoCacheRule(Rule):
    """Insert Cacher nodes by the aggressive or greedy policy; the executor
    then retains only cached results across pulls."""

    def __init__(
        self,
        strategy: str = "greedy",
        mem_budget_bytes: Optional[int] = None,
        profiles: Optional[Dict[NodeId, Profile]] = None,
    ):
        self.strategy = strategy
        self.mem_budget_bytes = mem_budget_bytes
        self.profiles = profiles  # injectable for tests (parity: suite)

    def _select_aggressive(self, graph: Graph) -> set:
        """Cache every node whose result is consumed along >1 downstream
        path (parity: ``aggressiveCache``, AutoCacheRule.scala:503-518)."""
        return {
            n
            for n in graph.nodes
            if len(analysis.get_children(graph, n)) > 1
            and not _is_cacher(graph.get_operator(n))
        }

    def _select_greedy(
        self, graph: Graph, profiles: Dict[NodeId, Profile], budget: float
    ) -> set:
        weights = {
            n: getattr(graph.get_operator(n), "weight", 1) for n in graph.nodes
        }
        # Existing Cacher nodes already cut recomputation: seed the run
        # estimator with them so their upstreams' savings aren't double
        # counted (parity: the reference seeds getRuns with cached nodes).
        preexisting = {
            n for n in graph.nodes if _is_cacher(graph.get_operator(n))
        }
        cached: set = set(preexisting)
        spent = 0.0
        while True:
            runs = estimate_runs(graph, weights, cached)
            best, best_save = None, 0.0
            for n, p in profiles.items():
                if n not in graph.nodes or n in cached:
                    continue
                if _is_cacher(graph.get_operator(n)):
                    continue
                if spent + p.mem_bytes > budget:
                    continue
                save = (runs[n] - 1) * p.ns
                if save > best_save:
                    best, best_save = n, save
            if best is None:
                break
            cached.add(best)
            spent += profiles[best].mem_bytes
        return cached - preexisting

    def apply(
        self, graph: Graph, annotations: Annotations
    ) -> Tuple[Graph, Annotations]:
        from .. import cost as cost_mod
        from ..cost import replan as cost_replan

        store = cost_mod.get_store()
        # fingerprint/topo-index once per apply: stored_profiles,
        # calibration, persistence, and the pending-plan deposit all
        # address the same graph identity
        fp = cost_mod.graph_fingerprint(graph) if store is not None else None
        index = (
            cost_replan.topo_node_index(graph) if store is not None else None
        )
        plan_rec = (
            cost_replan.load_plan_record(store, fp)
            if store is not None else None
        )
        profiles: Optional[Dict[NodeId, Profile]] = None
        source = "none"
        budget: Optional[float] = None
        if self.strategy == "aggressive":
            selected = self._select_aggressive(graph)
        else:
            full_n = _full_input_size(graph)
            profiles = self.profiles
            source = "injected" if profiles is not None else source
            if profiles is None and store is not None:
                # a previous traced run of this pipeline left per-node
                # OBSERVED costs — plan from evidence, zero sampling
                profiles = cost_replan.stored_profiles(
                    store, graph, full_n, fp=fp, index=index, rec=plan_rec
                )
                if profiles is not None:
                    source = "profiles"
                    logger.info(
                        "auto-cache: planning %d nodes from stored "
                        "profiles (no sampling)", len(profiles),
                    )
            if profiles is None:
                calibration = cost_replan.stored_calibration(
                    store, graph, fp=fp, index=index, rec=plan_rec
                )
                profiles = profile_nodes(
                    graph, full_size=full_n, calibration=calibration
                )
                source = "sampled+calibrated" if calibration else "sampled"
                self._fill_from_class_throughput(graph, profiles, full_n)
                if store is not None:
                    # persist the sampled estimates NOW: graphs optimized
                    # outside a fit (a prefix spliced at construction, an
                    # apply-path plan) never reach the re-plan hook, and
                    # without a record they would re-sample on every run.
                    # A traced fit of the same graph later overwrites this
                    # with observed evidence (cost/replan.py).
                    self._persist_sampled_plan(
                        store, graph, profiles, full_n, source, fp, index
                    )
            budget = float(
                self.mem_budget_bytes
                if self.mem_budget_bytes is not None
                else _device_budget_bytes()
            )
            selected = self._select_greedy(graph, profiles, budget)
        self._record_plan(graph, profiles, selected)
        self._record_pending(
            graph, profiles, selected, source, budget, fp, index
        )
        self._record_estimate_span(graph, profiles, selected, source)
        if selected:
            logger.info(
                "auto-cache (%s): inserting Cacher after %d nodes (%s)",
                self.strategy,
                len(selected),
                ", ".join(
                    graph.get_operator(n).label for n in sorted(selected)
                ),
            )
            graph = insert_cachers(graph, sorted(selected))
        annotations = dict(annotations)
        annotations[AUTOCACHE_ACTIVE] = True  # type: ignore[index]
        return graph, annotations

    @staticmethod
    def _record_plan(
        graph: Graph,
        profiles: Optional[Dict[NodeId, Profile]],
        selected: set,
    ) -> None:
        """Log the planner's per-node estimates into the trace so the
        estimate-vs-observed audit (obs/audit.py) can close the
        profile-guided-caching feedback loop after execution. Node ids are
        recorded BEFORE Cacher insertion (insert_cachers preserves the
        planned nodes' ids) and match the executor's span ``node`` field
        where the node runs as a node — the audit flags the ones that a
        segment dispatched instead."""
        tracer = obs_tracer.current()
        if tracer is None:
            return
        estimated = set(profiles or ())
        for n in estimated | set(selected):
            if n not in graph.nodes:
                continue
            p = (profiles or {}).get(n)
            tracer.record_node_estimate(
                str(n.id),
                graph.get_operator(n).label,
                est_seconds=None if p is None else p.ns / 1e9,
                est_bytes=None if p is None else p.mem_bytes,
                cacher=n in selected,
            )

    @staticmethod
    def _fill_from_class_throughput(
        graph: Graph, profiles: Dict[NodeId, Profile], full_n: int
    ) -> None:
        """Price nodes the sampled profiling skipped (an upstream failure
        at sample scale, an estimator that cannot run truncated) from the
        store's per-operator-class throughput records — measured evidence
        from OTHER pipelines on this backend/device kind."""
        from .. import cost as cost_mod

        estimator = cost_mod.get_estimator()
        for n in graph.nodes:
            if n in profiles:
                continue
            op = graph.get_operator(n)
            if isinstance(op, DatasetOperator) or _is_cacher(op):
                continue
            priced = estimator.node_profile_ns(type(op).__name__, full_n)
            if priced is not None:
                profiles[n] = Profile(priced[0], priced[1])
                logger.info(
                    "auto-cache: priced unprofiled %s from class "
                    "throughput evidence", op.label,
                )

    @staticmethod
    def _persist_sampled_plan(
        store, graph: Graph, profiles: Dict[NodeId, Profile],
        full_n: int, source: str, fp: str, index: Dict[NodeId, int],
    ) -> None:
        from ..cost.replan import PLAN_VERSION

        nodes = {}
        for n in graph.nodes:
            p = profiles.get(n)
            if p is None:
                continue
            op = graph.get_operator(n)
            nodes[str(index[n])] = {
                "idx": index[n],
                "label": op.label,
                "op_class": type(op).__name__,
                "n": max(int(full_n), 1),
                "observed": False,
                "seconds": round(p.ns / 1e9, 9),
                "bytes": float(p.mem_bytes),
            }
        if len(nodes) != len(graph.nodes):
            return  # partial coverage would force a re-sample anyway
        store.update(
            f"plan/{fp}",
            lambda rec: {
                "version": PLAN_VERSION,
                "strategy": "greedy",
                "budget": None,
                "full_n": max(int(full_n), 1),
                "source": source,
                "nodes": nodes,
            },
        )

    @staticmethod
    def _record_pending(
        graph: Graph,
        profiles: Optional[Dict[NodeId, Profile]],
        selected: set,
        source: str,
        budget: Optional[float],
        fp: Optional[str],
        index: Optional[Dict[NodeId, int]],
    ) -> None:
        """Deposit the cache plan into the pending re-plan (see
        ``cost/replan.py``): graph identity, budget, every node's estimate
        and the selection — what `finalize` joins against observations."""
        from .. import cost as cost_mod
        from ..cost.replan import topo_node_index

        plan = cost_mod.current_plan()
        # first deposit wins — see NodeOptimizationRule: a sub-pipeline
        # optimized while the outer fit executes must not replace the
        # outer fit's plan
        if plan is None or plan.autocache is not None:
            return
        if index is None:
            index = topo_node_index(graph)
        nodes = {}
        for n in graph.nodes:
            op = graph.get_operator(n)
            p = (profiles or {}).get(n)
            nodes[str(n.id)] = {
                "idx": index[n],
                "label": op.label,
                "op_class": type(op).__name__,
                "est_ns": None if p is None else p.ns,
                "est_bytes": None if p is None else p.mem_bytes,
                "cacher": n in selected,
                "leaf": isinstance(op, DatasetOperator),
            }
        plan.autocache = {
            "fp": fp if fp is not None else cost_mod.graph_fingerprint(graph),
            "graph": graph,
            "strategy": "greedy" if budget is not None else "aggressive",
            "budget": budget if budget is not None else 0.0,
            "full_n": _full_input_size(graph),
            "selected": set(selected),
            "source": source,
            "nodes": nodes,
        }

    @staticmethod
    def _record_estimate_span(
        graph: Graph,
        profiles: Optional[Dict[NodeId, Profile]],
        selected: set,
        source: str,
    ) -> None:
        tracer = obs_tracer.current()
        if tracer is None:
            return
        with tracer.span(
            "cost.estimate",
            op_type="AutoCacheRule",
            source=source,
            nodes=0 if profiles is None else len(profiles),
            cachers=len(selected),
        ):
            pass


def _full_input_size(graph: Graph) -> int:
    n = 1
    for node in graph.nodes:
        op = graph.get_operator(node)
        if isinstance(op, DatasetOperator):
            n = max(n, len(op.dataset))
    return n
