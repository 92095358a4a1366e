"""Segment compilation: lower a planned traceable segment into ONE jitted
function and dispatch it through the AOT export/cache/manifest plane.

``check/segments.py`` partitions the optimized DAG into maximal traceable
segments between materialization barriers — the one grouping decision.
This module is the one lowering and the one dispatch:
:func:`lower_segment` composes the member operators' ``trace_batch``
bodies in topo order into a single function over the segment's pinned
``inputs`` → ``outputs`` tuple (a gather join among them is a tuple of its
branches), and
:class:`SegmentDispatcher` resolves one executable per input-signature
tuple exactly the way :class:`~keystone_tpu.compile.aot.AotDispatcher`
does for serving buckets — cache hit ⇒ deserialize, zero traces; miss ⇒
trace once via ``jax.export``, persist, index in the segment manifest so
a warm boot (``ServingFleet.start()``, cluster workers) pre-warms it.
A warm FIT therefore boots zero-trace.

:class:`SegmentBinding` is the executor-facing handle: it owns the
lowered steps, the content digest, and the three runtime paths —

* **compiled** — all-batched array inputs dispatch the whole segment as
  one program (one Python dispatch for N nodes); where the members'
  outputs over the whole batch outgrow what the device has free, the ROWS
  are dispatched in fixed-size slices through one program of the slice's
  shape and the outputs joined (:meth:`SegmentBinding.row_plan`);
* **chunked** — a single-output segment over chunked data rides the
  out-of-core scan per chunk through :class:`ChunkPadder` (ragged final
  chunks pad to the bucket ladder, results slice back);
* **fallback** — anything else (item-list inputs, multi-output chunked
  segments, a runtime failure) degrades to exact per-node semantics: same
  operators, same order, same answers.

Batch-coupled members over chunked input are the caller's error on every
path (batch statistics a chunk): :meth:`SegmentBinding.run` raises.

Adaptive boundaries close the loop through ``cost/segments.py``: each
compile and each run is recorded under the profile store's
``plan/segment/`` namespace, and a segment whose observed compile cost
swamps its cumulative dispatch savings is demoted back to node dispatch
on the next fit. Node dispatch itself is ``GraphExecutor(graph,
segment_plan={})``: planned, nothing eligible.
"""

from __future__ import annotations

import functools
import logging
import threading
import time
from collections import OrderedDict
from typing import Any, Callable, Dict, List, Optional, Tuple

from ..obs.tracer import current as _trace_current
from ..utils.timing import degraded
from .aot import Signature, signature_of
from .cache import ExecutableCache
from .fingerprint import (
    FingerprintError,
    environment_key,
    segment_entry_key,
    segment_fingerprint,
)

logger = logging.getLogger(__name__)

#: lowered step: (operator, input slots into the segment value vector)
Step = Tuple[Any, Tuple[int, ...]]


def lower_segment(graph: Any, segment: Any) -> Tuple[Callable, List[Step], Tuple[int, ...]]:
    """Compose ``segment``'s member ``trace_batch`` bodies into one
    function ``fn(*inputs) -> outputs tuple``.

    The index space is positional over ``segment.inputs`` followed by
    ``segment.nodes`` — the same space :func:`segment_fingerprint` hashes,
    so two processes that agree on the digest agree on the signature.
    Returns ``(fn, steps, out_slots)``; ``steps``/``out_slots`` also
    drive the exact-semantics fallback path.
    """
    inputs = list(segment.inputs)
    members = list(segment.nodes)
    pos: Dict[Any, int] = {d: i for i, d in enumerate(inputs)}
    for j, n in enumerate(members):
        pos[n] = len(inputs) + j
    steps: List[Step] = [
        (
            graph.get_operator(n),
            tuple(pos[d] for d in graph.get_dependencies(n)),
        )
        for n in members
    ]
    out_slots = tuple(pos[o] for o in segment.outputs)
    n_inputs = len(inputs)

    def fn(*xs):
        # a segment with a ``row_keyed`` member is handed, after its
        # inputs, the data-set index of its first row (SegmentBinding._run)
        row0 = xs[n_inputs] if len(xs) > n_inputs else None
        values = _trace_steps(steps, list(xs[:n_inputs]), row0=row0)
        return tuple(values[s] for s in out_slots)

    return fn, steps, out_slots


def _row_keyed(steps: List[Step]) -> bool:
    """Whether a member's ``trace_batch`` takes the rows' data-set indices
    (``row_keyed``, e.g. a sampler whose draw is keyed on the row)."""
    return any(getattr(op, "row_keyed", False) for op, _ in steps)


def _trace_steps(
    steps: List[Step], values: List[Any],
    made: Optional[Callable[[Any, List[Any], Any], None]] = None,
    row0: Any = None,
) -> List[Any]:
    """``values`` (the segment's inputs) with every step's traced value
    appended — the one composition of ``trace_batch`` bodies. A gather
    join is a tuple of its branches; ``made(op, args, out)`` sees every
    other member's output. A ``row_keyed`` member is told its rows'
    indices: ``row0`` on, or from 0 where the rows are a whole data set."""
    from ..workflow.operators import GatherTransformerOperator

    for op, slots in steps:
        args = [values[s] for s in slots]
        if isinstance(op, GatherTransformerOperator):
            values.append(tuple(args))
        else:
            if row0 is not None and getattr(op, "row_keyed", False):
                import jax.numpy as jnp

                rows = row0 + jnp.arange(args[0].shape[0], dtype=jnp.int32)
                values.append(op.trace_batch(*args, rows=rows))
            else:
                values.append(op.trace_batch(*args))
            if made is not None:
                made(op, args, values[-1])
    return values


class SegmentDispatcher:
    """One executable per input-signature tuple, cache-first — the
    segment-graph sibling of :class:`~keystone_tpu.compile.aot.AotDispatcher`.

    With no cache configured every signature resolves to a structural
    ``jax.jit`` (still one program per segment, just not exported). Inputs
    that have no array signature (tuple payloads out of a gather join)
    also ride the structural jit: jit handles pytrees natively, only the
    AOT export plane needs flat array signatures.
    """

    def __init__(
        self,
        fn: Callable,
        digest: str,
        cache: Optional[ExecutableCache],
        *,
        label: str = "",
        n_nodes: int = 1,
    ):
        self._fn = fn
        self._digest = digest
        self._cache = cache
        self._label = label
        self._n_nodes = n_nodes
        self._env = environment_key() if cache is not None else None
        self._by_sig: Dict[Tuple[Signature, ...], Callable] = {}
        #: input signatures -> bytes of every member's output for ONE row
        #: (:func:`_item_bytes`); kept here, so a refit of the same
        #: pipeline pays the abstract evaluation once a process
        self.item_bytes: Dict[Tuple[Signature, ...], int] = {}
        self._structural: Optional[Callable] = None
        self._lock = threading.Lock()
        self._loaded = 0
        self._traced = 0
        self._ledger = None
        if cache is not None:
            from ..obs.ledger import CompileLedger

            self._ledger = CompileLedger.for_cache_root(cache.root)

    @property
    def digest(self) -> str:
        return self._digest

    @property
    def loaded_count(self) -> int:
        """Signature tuples resolved from the cache (zero traces paid)."""
        return self._loaded

    @property
    def traced_count(self) -> int:
        """Signature tuples that paid a live trace."""
        return self._traced

    def __call__(self, *xs):
        try:
            sigs = tuple(signature_of(x) for x in xs)
        except (AttributeError, TypeError):
            # non-array input (e.g. a gather join's tuple payload): jit
            # dispatches pytrees fine, only AOT export needs flat arrays
            return self._structural_jit()(*xs)
        call = self._by_sig.get(sigs)
        if call is None:
            call = self._resolve(sigs)
        return call(*xs)

    def _structural_jit(self) -> Callable:
        call = self._structural
        if call is None:
            import jax

            with self._lock:
                if self._structural is None:
                    self._structural = jax.jit(self._fn)
                call = self._structural
        return call

    def _resolve(self, sigs: Tuple[Signature, ...]) -> Callable:
        with self._lock:
            call = self._by_sig.get(sigs)
            if call is not None:
                return call
            if self._cache is None:
                import jax

                if self._structural is None:
                    self._structural = jax.jit(self._fn)
                call = self._structural
            else:
                call = self._load(sigs)
                if call is None:
                    call = self._trace_and_export(sigs)
            self._by_sig[sigs] = call
            return call

    def _load(self, sigs: Tuple[Signature, ...]) -> Optional[Callable]:
        import jax
        from jax import export as jax_export

        key = segment_entry_key(self._digest, sigs, self._env)
        t0 = time.perf_counter()
        entry = self._cache.load(key, expect_env=self._env)
        if entry is None:
            return None
        try:
            exported = jax_export.deserialize(bytearray(entry.payload))
            call = jax.jit(exported.call)
        except Exception:
            logger.warning(
                "segment: undeserializable entry for %s — falling back to "
                "live compile", self._label or key, exc_info=True,
            )
            degraded("aot_load")
            self._cache._discard(entry.path, "undeserializable")
            return None
        self._loaded += 1
        load_seconds = time.perf_counter() - t0
        if self._ledger is not None:
            self._ledger.record(
                "load",
                key=key,
                label=self._label,
                kind="segment",
                inputs=len(sigs),
                nbytes=entry.nbytes,
                seconds=load_seconds,
                saved_s=entry.header.get("trace_seconds"),
            )
        tracer = _trace_current()
        if tracer is not None:
            tracer.instant(
                "aot.load",
                op_type="SegmentDispatcher",
                key=key,
                label=self._label,
                inputs=len(sigs),
                bytes=entry.nbytes,
                load_seconds=round(load_seconds, 4),
                seconds_saved=entry.header.get("trace_seconds"),
            )
        logger.info(
            "segment: loaded %s from cache (%d bytes, saved ~%ss of "
            "tracing)", self._label or key, entry.nbytes,
            entry.header.get("trace_seconds", "?"),
        )
        return call

    def _trace_and_export(self, sigs: Tuple[Signature, ...]) -> Callable:
        import jax
        import numpy as np
        from jax import export as jax_export

        from ..cost import segments as seg_cost

        tracer = _trace_current()
        key = segment_entry_key(self._digest, sigs, self._env)
        if tracer is not None:
            tracer.instant(
                "aot.miss", op_type="SegmentDispatcher", key=key,
                label=self._label, inputs=len(sigs),
            )
        specs = [jax.ShapeDtypeStruct(s, np.dtype(d)) for s, d in sigs]
        t0 = time.perf_counter()
        try:
            exported = jax_export.export(jax.jit(self._fn))(*specs)
            call = jax.jit(exported.call)
        except Exception:
            logger.warning(
                "segment: export failed for %s — dispatching via plain jit "
                "(no cross-process caching for this signature)",
                self._label or key, exc_info=True,
            )
            degraded("aot_export")
            self._traced += 1
            seg_cost.record_compile(
                self._digest, time.perf_counter() - t0,
                exported=False, n_nodes=self._n_nodes,
            )
            return jax.jit(self._fn)
        trace_seconds = time.perf_counter() - t0
        self._traced += 1
        if self._ledger is not None:
            self._ledger.record(
                "trace",
                key=key,
                label=self._label,
                kind="segment",
                inputs=len(sigs),
                seconds=trace_seconds,
            )
        try:
            payload = bytes(exported.serialize())
            self._cache.store(
                key,
                payload,
                {
                    "env": self._env,
                    "segment": self._digest,
                    "inputs": [[list(s), d] for s, d in sigs],
                    "label": self._label,
                    "trace_seconds": round(trace_seconds, 4),
                    "created_unix": time.time(),
                },
            )
            from . import manifest as _manifest

            _manifest.record_segment(self._cache, self._digest, sigs)
        except Exception:
            logger.warning(
                "segment: could not persist %s — executable still serves "
                "live", self._label or key, exc_info=True,
            )
            degraded("aot_persist")
            payload = b""
        if payload and self._ledger is not None:
            self._ledger.record(
                "export",
                key=key,
                label=self._label,
                kind="segment",
                inputs=len(sigs),
                nbytes=len(payload),
                seconds=trace_seconds,
            )
        if tracer is not None:
            tracer.instant(
                "aot.export",
                op_type="SegmentDispatcher",
                key=key,
                label=self._label,
                inputs=len(sigs),
                bytes=len(payload),
                trace_seconds=round(trace_seconds, 4),
            )
        seg_cost.record_compile(
            self._digest, trace_seconds, exported=bool(payload),
            n_nodes=self._n_nodes,
        )
        return call


# ---------------------------------------------------------------------------
# Process-wide dispatcher registry: one SegmentDispatcher per (digest,
# cache root), so two executors pulling the same fitted graph share
# resolved executables instead of re-tracing. Bounded LRU — digests churn
# across unrelated pipelines in a long-lived process.
# ---------------------------------------------------------------------------

_DISPATCHERS: "OrderedDict[Tuple[str, Optional[str]], SegmentDispatcher]" = OrderedDict()
_MAX_DISPATCHERS = 128
_dispatchers_lock = threading.Lock()


def dispatcher_for(
    digest: str, fn_factory: Callable[[], Callable], *, label: str = "",
    n_nodes: int = 1,
) -> SegmentDispatcher:
    """The shared dispatcher for ``digest`` against the currently
    configured cache. The cache is re-fetched per call (it may be
    configured after a binding was built), so bindings must not memoize
    the dispatcher they get back."""
    from . import get_cache

    cache = get_cache()
    key = (digest, cache.root if cache is not None else None)
    with _dispatchers_lock:
        disp = _DISPATCHERS.get(key)
        if disp is not None:
            _DISPATCHERS.move_to_end(key)
            return disp
        disp = SegmentDispatcher(
            fn_factory(), digest, cache, label=label, n_nodes=n_nodes
        )
        _DISPATCHERS[key] = disp
        while len(_DISPATCHERS) > _MAX_DISPATCHERS:
            _DISPATCHERS.popitem(last=False)
        return disp


def reset_dispatchers() -> None:
    """Drop every registered dispatcher (test hygiene)."""
    with _dispatchers_lock:
        _DISPATCHERS.clear()


# ---------------------------------------------------------------------------
# What a compiled dispatch can observe of its size: the bytes its members'
# outputs take a row, and the device's memory.
# ---------------------------------------------------------------------------


def _nbytes(tree: Any) -> int:
    """Bytes of the arrays (or shape structs) of ``tree``."""
    import jax
    import numpy as np

    return sum(
        int(np.prod(v.shape)) * np.dtype(v.dtype).itemsize
        for v in jax.tree_util.tree_leaves(tree)
    )


def _item_bytes(
    sigs: Tuple[Signature, ...], steps: List[Step],
    out_slots: Tuple[int, ...],
) -> int:
    """Bytes ONE row generates across the segment's member outputs (and
    what a member declares it holds a row besides, ``row_scratch_bytes``) at
    these input shapes — what ``check/segments.py`` prices from specs,
    taken here from the shapes the dispatch really has (``jax.eval_shape``:
    no operation runs). 0 where an output does not keep the row axis (the
    segment cannot be cut by rows) or the evaluation fails."""
    import jax
    import numpy as np

    produced: List[Any] = []
    scratch: List[int] = []

    def made(op, args, out):
        produced.append(out)
        # what a member holds a row besides its output, where it says so
        # (a kernel's operands laid out in HBM ahead of it)
        row_scratch = getattr(op, "row_scratch_bytes", None)
        if row_scratch is not None and args:
            scratch.append(int(row_scratch(args[0].shape)))

    def members(*xs):
        values = _trace_steps(steps, list(xs), made)
        return produced, [values[s] for s in out_slots]

    rows = sigs[0][0][0]
    try:
        specs = [jax.ShapeDtypeStruct(s, np.dtype(d)) for s, d in sigs]
        made, outs = jax.eval_shape(members, *specs)
        row_wise = all(
            o.shape and o.shape[0] == rows
            for o in jax.tree_util.tree_leaves(outs)
        )
        return -(-_nbytes(made) // rows) + sum(scratch) if row_wise else 0
    except Exception:
        logger.debug("segment: no per-row size estimate", exc_info=True)
        return 0


def _member_facts(
    steps: List[Step], arrays: List[Any], slice_rows: int, rows: int
) -> Dict[str, Any]:
    """What the members that speak of their rows under names of their own
    say of ``rows`` rows — ``segment_facts(shape, rows)``: the fused
    convolution's ``conv_fused_rows`` where its kernel engages, the sampled
    SIFT body's ``sift_sampled_rows`` and ``sift_sampled_path``, the
    guarded cosine's ``cosine_bounded_rows``, the fused cosine scores'
    ``cosine_scores_rows`` — each asked at the shape its
    first input has where ``arrays`` go through ``slice_rows`` at a time.
    The members a speaking member's first input is made from are
    evaluated abstractly (``jax.eval_shape``: no operation runs), and no
    other: a trace costs the host milliseconds, at every dispatch."""
    import jax

    from ..workflow.operators import GatherTransformerOperator

    speaking = {
        i for i, (op, _) in enumerate(steps) if hasattr(op, "segment_facts")
    }
    # a member's value sits behind the inputs', in step order
    first = len(arrays)
    needed, todo = set(), [steps[i][1][0] for i in speaking]
    while todo:
        slot = todo.pop()
        if slot >= first and slot not in needed:
            needed.add(slot)
            todo.extend(steps[slot - first][1])
    facts: Dict[str, Any] = {}
    values: Dict[int, Any] = {
        slot: jax.ShapeDtypeStruct((slice_rows,) + a.shape[1:], a.dtype)
        for slot, a in enumerate(arrays)
    }
    for i, (op, slots) in enumerate(steps):
        if i in speaking:
            facts.update(op.segment_facts(values[slots[0]].shape, rows))
        if first + i not in needed:
            continue
        args = [values[s] for s in slots]
        if isinstance(op, GatherTransformerOperator):
            values[first + i] = tuple(args)
        else:
            values[first + i] = jax.eval_shape(op.trace_batch, *args)
    return facts


def _device_memory() -> Optional[Tuple[int, int]]:
    """``(free, limit)`` bytes of the fullest device, as jax's memory
    stats give them (what ``benchmark/harness.peak_bytes`` reads), or None
    where the backend reports none (the CPU)."""
    import jax

    worst = None
    for dev in jax.local_devices():
        stats = dev.memory_stats() or {}
        limit = stats.get("bytes_limit")
        if not limit:
            return None
        free = int(limit) - int(stats.get("bytes_in_use", 0))
        if worst is None or free < worst[0]:
            worst = (free, int(limit))
    return worst


def unheld_caches(
    graph: Any, verdicts: Dict[Any, str], held: Dict[Any, Any]
) -> Dict[Any, int]:
    """The ``Cacher`` nodes of ``graph`` whose value the device cannot
    hold, each with the bytes it was asked to keep. Upstream a ``Cacher`` is
    ``RDD.cache()``, which drops what does not fit and computes it again;
    here such a request is declined when the pull is planned, from what the
    plan can observe: the value's shape (``jax.eval_shape`` through the
    traceable members above it, from the arrays the executor holds — no
    operation runs) against what the device has free. A cache is kept where
    it takes at most half of that — the other half is for the programs that
    read it, as a row slice's is (:meth:`SegmentBinding.row_plan`). Empty
    where the backend reports no memory (the CPU), where the graph has no
    Cacher, and for every cache whose size cannot be told."""
    from ..check.segments import BARRIER_CACHER, barrier_reason
    from ..workflow.graph import NodeId
    from ..workflow.operators import DatasetOperator

    def reason(n: Any):
        return barrier_reason(
            graph.get_operator(n), verdicts.get(n, "opaque")
        )

    cachers = [
        n for n in graph.nodes
        if n not in held and reason(n) == BARRIER_CACHER
    ]
    memory = _device_memory() if cachers else None
    if memory is None:
        return {}
    import jax

    def struct_of(dataset: Any):
        payload = getattr(dataset, "payload", None)
        if not getattr(dataset, "is_batched", False) or not hasattr(
            payload, "shape"
        ):
            return None
        return jax.ShapeDtypeStruct(payload.shape, payload.dtype)

    memo: Dict[Any, Any] = {}

    def abstract(n: Any):
        if n not in memo:
            memo[n] = None  # a cycle-free graph; unknown until told
            memo[n] = _abstract(n)
        return memo[n]

    def _abstract(n: Any):
        if not isinstance(n, NodeId) or n not in graph.operators:
            return None
        if n in held:
            return struct_of(held[n].get())
        op = graph.get_operator(n)
        if isinstance(op, DatasetOperator):
            return struct_of(op.dataset)
        deps = [abstract(d) for d in graph.get_dependencies(n)]
        if not deps or any(d is None for d in deps):
            return None
        why = reason(n)
        if why == BARRIER_CACHER:
            return deps[0]
        if why is not None:
            return None
        try:
            return jax.eval_shape(op.trace_batch, *deps)
        except Exception:
            logger.debug("segment: no size for %s", op.label, exc_info=True)
            return None

    free, _limit = memory
    declined: Dict[Any, int] = {}
    for n in cachers:
        value = abstract(n)
        if value is not None and _nbytes(value) > free // 2:
            declined[n] = _nbytes(value)
    return declined


@functools.lru_cache(maxsize=None)
def _row_slice_jit() -> Callable:
    import jax

    return jax.jit(
        lambda a, start, rows: jax.lax.dynamic_slice_in_dim(
            a, start, rows, axis=0
        ),
        static_argnums=(2,),
    )


@functools.lru_cache(maxsize=None)
def _row_put_jit() -> Callable:
    """``buf[start : start + len(part)] = part`` as one program whatever
    the start — in place on an accelerator; the CPU backend's donation is
    not to be trusted (``linalg/bcd.py`` ``_block_update``)."""
    import jax

    return jax.jit(
        lambda buf, part, start: jax.lax.dynamic_update_slice_in_dim(
            buf, part, start, axis=0
        ),
        donate_argnums=() if jax.default_backend() == "cpu" else (0,),
    )


def _row0(start: int):
    """The data-set index of a dispatch's first row, as the program of a
    segment with a ``row_keyed`` member takes it: an int32 scalar argument,
    so that every slice runs the one program."""
    import numpy as np

    return np.asarray(start, np.int32)


def _row_slice(a: Any, start: int, rows: int):
    """``a[start : start + rows]`` by ONE compiled program whatever the
    start (a Python slice compiles a program a start)."""
    return _row_slice_jit()(a, start, rows)


# ---------------------------------------------------------------------------
# SegmentBinding: the executor-facing handle
# ---------------------------------------------------------------------------


class SegmentBinding:
    """One plannable segment, lowered and ready to dispatch.

    ``run(datasets)`` takes the materialized input Datasets (positional
    over the segment's pinned ``inputs`` order) and returns
    ``(outputs, path)`` — one Dataset per segment output plus which
    runtime path served it (``compiled`` / ``chunked`` / ``fallback``).
    Any runtime failure demotes the binding permanently (this process)
    and re-runs through exact node semantics — segment dispatch must
    never change answers or surface new errors.

    ``digest`` is None where a member's state has no content-stable form
    (a closure, a lambda): the segment is still one structural ``jax.jit``
    program, owned by this binding — only sharing across executors, the
    cost records and the export need a digest.
    """

    def __init__(
        self,
        *,
        index: int,
        inputs: List[Any],
        outputs: List[Any],
        fn: Callable,
        steps: List[Step],
        out_slots: Tuple[int, ...],
        digest: Optional[str],
        label: str,
        node_ids: List[str],
        coupled_labels: List[str],
    ):
        self.index = index
        self.inputs = list(inputs)
        self.outputs = list(outputs)
        self.fn = fn
        self.steps = steps
        self.out_slots = out_slots
        self.digest = digest
        self.label = label
        self.node_ids = list(node_ids)
        #: labels of the members whose ``trace_batch`` couples rows
        self.coupled_labels = list(coupled_labels)
        self.batch_coupled = bool(coupled_labels)
        self._own_dispatcher = None if digest is not None else (
            SegmentDispatcher(fn, "", None, label=label, n_nodes=len(steps))
        )
        self._demoted = False
        #: bytes of the caches among the members that the plan declined
        #: (:func:`unheld_caches`); :func:`bind_segment` says
        self.cache_declined_bytes = 0

    def __len__(self) -> int:
        return len(self.steps)

    def _dispatcher(self) -> SegmentDispatcher:
        if self._own_dispatcher is not None:
            return self._own_dispatcher
        return dispatcher_for(
            self.digest, lambda: self.fn, label=self.label,
            n_nodes=len(self.steps),
        )

    def run(
        self, datasets: List[Any], facts: Optional[dict] = None
    ) -> Tuple[Tuple[Any, ...], str]:
        """``facts`` (the ``exec.segment`` span's attrs) is given what the
        compiled path dispatched: ``rows``, ``row_slices``, ``slice_rows``
        (one slice of all the rows is the whole-batch dispatch)."""
        from ..data.chunked import ChunkedDataset

        # ChunkedDataset reports is_batched=True — check it FIRST
        chunked = any(isinstance(ds, ChunkedDataset) for ds in datasets)
        if chunked and self.batch_coupled:
            # the caller's error on every path, not a failed dispatch
            raise ValueError(
                f"batch-coupled node(s) {self.coupled_labels} cannot stream "
                "per-chunk: batch statistics would be computed per "
                "chunk — materialize the dataset first"
            )
        if self._demoted:
            return self._fallback(datasets), "fallback"
        try:
            return self._run(
                datasets, {} if facts is None else facts, chunked
            )
        except Exception as e:
            self._demote(f"runtime failure: {e!r}")
            return self._fallback(datasets), "fallback"

    def _run(
        self, datasets: List[Any], facts: dict, chunked: bool
    ) -> Tuple[Tuple[Any, ...], str]:
        from ..data.chunked import align_and_zip
        from ..data.dataset import Dataset
        from ..data.pipeline_scan import ChunkPadder

        if chunked:
            if len(self.out_slots) != 1 or _row_keyed(self.steps):
                # a multi-output chunked segment would rescan the source
                # once per output, and a row-keyed member counts its rows
                # along its own scan — node semantics handle both exactly
                return self._fallback(datasets), "fallback"
            disp = self._dispatcher()
            if len(datasets) == 1:
                out = datasets[0].map_batch(
                    ChunkPadder(lambda c: disp(c)[0], shard=True)
                )
            else:
                out = align_and_zip(list(datasets)).map_batch(
                    ChunkPadder(lambda t: disp(*t)[0], shard=True)
                )
            return (out,), "chunked"
        if datasets and all(ds.is_batched for ds in datasets):
            from ..cost import segments as seg_cost

            disp = self._dispatcher()
            t0 = time.perf_counter()
            arrays = [ds.to_array() for ds in datasets]
            rows, slice_rows = self.row_plan(disp, arrays)
            keyed = _row_keyed(self.steps)
            if slice_rows < rows:
                raw = self._dispatch_row_slices(
                    disp, arrays, rows, slice_rows, keyed
                )
            elif keyed:
                raw = disp(*arrays, _row0(0))
            else:
                raw = disp(*arrays)
            if rows:
                facts.update(
                    rows=rows, row_slices=-(-rows // slice_rows),
                    slice_rows=slice_rows,
                )
                if self.cache_declined_bytes:
                    facts["cache_declined_bytes"] = self.cache_declined_bytes
                facts.update(
                    _member_facts(self.steps, arrays, slice_rows, rows)
                )
            if self.digest is not None:
                seg_cost.record_run(
                    self.digest, time.perf_counter() - t0,
                    n_nodes=len(self.steps),
                )
            return (
                tuple(Dataset(o, batched=True) for o in raw),
                "compiled",
            )
        # item-list inputs: per-node dispatch is the honest semantics
        return self._fallback(datasets), "fallback"

    def row_plan(
        self, disp: "SegmentDispatcher", arrays: List[Any]
    ) -> Tuple[int, int]:
        """``(rows, slice_rows)`` for one compiled dispatch over ``arrays``.
        ``slice_rows == rows`` is the whole batch in one program — every
        segment whose members' outputs fit what the device has free, and
        every segment this cannot judge: rows that couple, inputs that do
        not share a leading axis, a device that reports no memory (the
        CPU), an estimate that failed. ``rows`` is 0 where the inputs are
        not arrays over one row axis.

        Otherwise the rows go through in slices: the largest power of two
        of rows whose members' outputs take at most a quarter of the
        device's memory (a constant, so that every job of a process cuts
        alike and reuses one program), halved while that outgrows half of
        what is free now."""
        try:
            sigs = tuple(signature_of(a) for a in arrays)
        except (AttributeError, TypeError):
            return 0, 0
        rows = sigs[0][0][0] if sigs[0][0] else 0
        if not rows or any(not s or s[0] != rows for s, _ in sigs):
            return 0, 0
        if self.batch_coupled:
            return rows, rows
        memory = _device_memory()
        if memory is None:
            return rows, rows
        free, limit = memory
        item_bytes = disp.item_bytes.get(sigs)
        if item_bytes is None:
            item_bytes = disp.item_bytes[sigs] = _item_bytes(
                sigs, self.steps, self.out_slots
            )
        if not item_bytes:
            return rows, rows
        if rows * item_bytes <= free:
            return rows, rows
        slice_rows = 1
        while 2 * slice_rows * item_bytes <= limit // 4:
            slice_rows *= 2
        while slice_rows > 1 and slice_rows * item_bytes > free // 2:
            slice_rows //= 2
        return rows, min(slice_rows, rows)

    @staticmethod
    def _dispatch_row_slices(
        disp: "SegmentDispatcher", arrays: List[Any], rows: int,
        slice_rows: int, keyed: bool = False,
    ) -> Tuple[Any, ...]:
        """The segment over ``rows`` rows, ``slice_rows`` at a time through
        the one program of that shape, each slice's outputs written into
        their place in one buffer an output; the last slice is padded with
        its first row (as ``ChunkPadder`` pads) and the padding cut from
        its outputs. Every row goes through once. ``keyed``: the program
        takes the index of the slice's first row after its inputs."""
        import jax.numpy as jnp

        from ..data.pipeline_scan import _pad_rows

        put = _row_put_jit()
        outs: Optional[List[Any]] = None
        for start in range(0, rows, slice_rows):
            take = min(slice_rows, rows - start)
            xs = [_row_slice(a, start, take) for a in arrays]
            if take < slice_rows:
                xs = [_pad_rows(x, take, slice_rows) for x in xs]
            part = disp(*xs, _row0(start)) if keyed else disp(*xs)
            if outs is None:
                # one buffer an output, written slice by slice: joining the
                # slices at the end holds them twice, and XLA's many-operand
                # concatenate a third time (16.06 of 16.9 GB read on the
                # chip at 16,384 × 80,000 float32; PERF.md §6, PR 29)
                outs = [
                    jnp.zeros((rows,) + o.shape[1:], o.dtype) for o in part
                ]
            if take < slice_rows:
                part = tuple(o[:take] for o in part)
            outs = [put(buf, o, start) for buf, o in zip(outs, part)]
        return tuple(outs)

    def _fallback(self, datasets: List[Any]) -> Tuple[Any, ...]:
        """Exact node semantics: same operators, same topo order, same
        execute() paths the node executor would have run."""
        from ..workflow.expressions import DatasetExpression

        values: List[Any] = list(datasets)
        for op, slots in self.steps:
            deps = [DatasetExpression.now(values[s]) for s in slots]
            values.append(op.execute(deps).get())
        return tuple(values[s] for s in self.out_slots)

    def _demote(self, why: str) -> None:
        if self._demoted:
            return
        self._demoted = True
        degraded("segment_demoted")
        logger.warning(
            "segment %s (%s): %s — demoted to node dispatch",
            self.index, self.label, why, exc_info=True,
        )
        if self.digest is None:
            return
        try:
            from ..cost import segments as seg_cost

            seg_cost.record_failure(self.digest, why="runtime")
        except Exception:
            logger.debug("segment: could not record demotion", exc_info=True)


def bind_segment(
    graph: Any, segment: Any, declined: Optional[Dict[Any, int]] = None
) -> Optional[SegmentBinding]:
    """Lower ``segment`` into a dispatchable binding (``declined``: the
    caches the plan could not hold, :func:`unheld_caches` — the binding
    reports the bytes of those among its members), or None when it is
    not worth (or not safe to) segment-dispatch:

    * empty, or a singleton — a single node gains nothing over its node
      thunk — unless the node stands for a chain and says so
      (``binds_alone``: its body wants one program and row slices);
    * any member without a traceable ``trace_batch`` (defense in depth —
      the planner's lattice should have barriered these already);
    * the cost model demoted this digest (compile cost exceeded observed
      dispatch savings — the adaptive-boundary split).

    A segment whose fingerprint is uncomputable (unhashable operator
    state) still binds, with no digest: see :class:`SegmentBinding`.
    """
    from ..workflow.graph import NodeId
    from ..workflow.operators import (
        GatherTransformerOperator,
        TransformerOperator,
    )

    members = list(segment.nodes)
    alone = len(members) == 1 and getattr(
        graph.get_operator(members[0]), "binds_alone", False
    )
    if len(members) < 2 and not alone:
        return None
    ops = []
    for n in members:
        op = graph.get_operator(n)
        if not isinstance(op, TransformerOperator):
            return None
        if not isinstance(op, GatherTransformerOperator) and not callable(
            getattr(op, "trace_batch", None)
        ):
            return None
        ops.append(op)
    for d in segment.inputs:
        if not isinstance(d, NodeId):
            return None
    # convexity: a member → barrier → member path makes an INPUT of the
    # lowered function transitively depend on one of its OUTPUTS (e.g. a
    # shared prefix feeding both a host node and a traceable chain the
    # host node rejoins). Such a group is not one compilation unit.
    mset = set(members)
    stack: List[Any] = []
    for d in segment.inputs:
        stack.extend(graph.get_dependencies(d))
    seen_anc = set()
    while stack:
        a = stack.pop()
        if a in seen_anc:
            continue
        seen_anc.add(a)
        if a in mset:
            return None
        if isinstance(a, NodeId) and a in graph.operators:
            stack.extend(graph.get_dependencies(a))
    try:
        digest: Optional[str] = segment_fingerprint(graph, segment)
    except FingerprintError:
        logger.debug(
            "segment %s: unfingerprintable — a program of its binding's own",
            segment.index, exc_info=True,
        )
        digest = None
    from ..cost import segments as seg_cost

    # (the cost model weighs a compile against the node dispatches it
    # saves: a node that binds alone saves none and is not bound for them)
    if digest is not None and not alone and not seg_cost.should_compile(
        digest, len(members)
    ):
        logger.info(
            "segment %s: demoted by cost model — node dispatch",
            segment.index,
        )
        return None
    fn, steps, out_slots = lower_segment(graph, segment)
    label = "+".join(op.label for op in ops)
    if len(label) > 96:
        label = label[:93] + "..."
    binding = SegmentBinding(
        index=segment.index,
        inputs=list(segment.inputs),
        outputs=list(segment.outputs),
        fn=fn,
        steps=steps,
        out_slots=out_slots,
        digest=digest,
        label=label,
        node_ids=[str(n.id) for n in members],
        coupled_labels=[
            op.label for op in ops if getattr(op, "batch_coupled", False)
        ],
    )
    binding.cache_declined_bytes = sum(
        (declined or {}).get(n, 0) for n in members
    )
    return binding


# ---------------------------------------------------------------------------
# Warm boot: pre-warm every manifest-indexed segment executable
# ---------------------------------------------------------------------------


def prewarm_segment_artifacts(
    cache: ExecutableCache, *, limit: int = 64, max_elements: int = 1 << 22
) -> int:
    """Deserialize + compile + execute-once every segment executable the
    manifest indexes — the fit-side analogue of the serving fleet's bucket
    pre-warm, called from ``ServingFleet.start()`` so a warm fit after a
    warm serve boot loads and never traces. Returns the number warmed.
    Best-effort throughout: a missing/evicted/foreign entry is skipped,
    never a boot failure. ``max_elements`` bounds the dummy-input bytes a
    boot will allocate per signature tuple."""
    import jax
    import numpy as np
    from jax import export as jax_export

    from . import manifest as _manifest

    env = environment_key()
    warmed = 0
    for digest in _manifest.segment_digests(cache):
        if warmed >= limit:
            break
        for sigs in _manifest.segment_signatures(cache, digest):
            if warmed >= limit:
                break
            try:
                elements = sum(
                    int(np.prod(shape)) if shape else 1 for shape, _ in sigs
                )
                if elements > max_elements:
                    logger.info(
                        "segment prewarm: skipping %s (%d elements over "
                        "budget)", digest[:16], elements,
                    )
                    continue
                key = segment_entry_key(digest, sigs, env)
                entry = cache.load(key, expect_env=env)
                if entry is None:
                    continue
                exported = jax_export.deserialize(bytearray(entry.payload))
                call = jax.jit(exported.call)
                args = [
                    jax.numpy.zeros(shape, np.dtype(dtype))
                    for shape, dtype in sigs
                ]
                jax.block_until_ready(call(*args))
                warmed += 1
            except Exception:
                logger.warning(
                    "segment prewarm: could not warm %s — skipped",
                    digest[:16], exc_info=True,
                )
                degraded("warmup")
    if warmed:
        logger.info("segment prewarm: %d executable(s) warmed", warmed)
    return warmed
