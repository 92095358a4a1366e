"""The reusable serving worker: one replica = one device-pinned executable
behind one batch loop.

:class:`ServingEngine` (one replica, gather-then-dispatch batching) and
:class:`~keystone_tpu.serving.fleet.ServingFleet` (N replicas behind a
shared continuous-batching scheduler) both run THIS worker; what differs
between them is only the :class:`BatchSource` that decides which requests
form the next micro-batch. The replica owns the parts every serving
topology shares:

* the **executable reference** — read once per batch at dispatch time, so
  a hot swap is one atomic store and every micro-batch runs whole on
  exactly one executable, never a mix;
* **device pinning** — a replica constructed with a device stages each
  padded batch onto it before dispatch, so N replicas spread over the
  mesh keep every chip busy (placement comes from
  :func:`keystone_tpu.parallel.placement.replica_devices`);
* the **batch execution discipline** — deadline expiry, per-request
  validation isolation, one D2H fetch per batch, per-request result
  distribution, queue-age/latency/occupancy metrics, and the
  ``serve.replica``/``serve.microbatch`` span;
* the **shadow hook** — when a canary swap is in flight, the fleet
  installs a shadow that mirrors completed batches through the candidate
  executable AFTER results are distributed, so comparison never adds
  latency to live requests.

The compile path (:func:`compile_pipeline`) is shared too: strict trace
accounting plus the AOT executable cache ride identically under an
engine, a fleet replica, or a swap candidate.
"""

from __future__ import annotations

import logging
import threading
import time
from concurrent.futures import Future
from dataclasses import dataclass, field
from typing import Any, Callable, List, Optional, Sequence

from ..faults import (
    REPLICA_BATCH,
    ReplicaDown,
    fault_point,
    is_transient,
)
from ..obs import flight as _flight
from ..obs import resource as _resource
from ..obs.span import Span
from ..obs.tracer import NULL_SPAN
from ..obs.tracer import current as _trace_current
from ..utils import timing
from ..workflow.pipeline import FittedPipeline, NotTraceableError
from .batching import BucketPolicy
from .errors import DeadlineExceeded, EngineStopped, InvalidRequest
from .metrics import MetricsRegistry

logger = logging.getLogger(__name__)

#: sentinel a BatchSource returns to stop the replica's loop
STOP = object()


class ReplicaQuarantined(ReplicaDown):
    """The circuit breaker tripped: this replica failed
    ``quarantine_after`` consecutive batches, so its loop exits and the
    fleet supervisor takes over (requeue its work, restart it within the
    restart budget). A ``BaseException`` like its base — it must pass the
    worker loop's ``except Exception`` backstop."""


class _TransientBatchFault(Exception):
    """Internal signal: a batch failed for a TRANSIENT reason (injected
    chaos fault, flaky device I/O) — its unanswered requests should be
    requeued to peers rather than failed, because a retry elsewhere is
    expected to succeed. ``pending`` is those requests, ``cause`` the
    original error."""

    def __init__(self, cause: BaseException, pending: list):
        super().__init__(str(cause))
        self.cause = cause
        self.pending = pending


def settle_future(fut: Future, exc: BaseException) -> bool:
    """Answer a request future with ``exc`` regardless of whether it is
    still pending or already marked running (popped into a batch that
    never finished). Returns True when this call delivered the answer."""
    if fut.done():
        return False
    try:
        try:
            live = fut.set_running_or_notify_cancel()
        except Exception:  # lint: allow-silent -- already RUNNING: settle
            live = True
        if not live:
            return False  # cancelled by the caller
        fut.set_exception(exc)
        return True
    except Exception:  # lint: allow-silent -- lost the set-once race: fine
        return False


@dataclass
class _Request:
    datum: Any
    deadline: Optional[float]  # time.monotonic() timestamp, or None
    enqueued: float
    future: Future = field(default_factory=Future)
    #: times this request has been requeued off a failed/dead replica —
    #: bounds the reroute loop for deadline-less requests, which the
    #: shed check can never retire
    hops: int = 0
    #: cross-process trace context (obs/context.py) for a sampled
    #: request: the replica records its queue-wait and batch spans under
    #: this identity so one request's hops stitch across the tier
    trace: Any = None
    #: QoS identity (autoscale/qos.py): ``priority`` is the shedding
    #: axis (low sheds before high), ``tenant`` the fairness axis (the
    #: weighted-fair queues serve tenants proportionally to weight).
    #: Carried ON the request so requeue clones, steals, and wire hops
    #: preserve both with no side-channel bookkeeping.
    priority: str = "normal"
    tenant: str = "default"


# ---------------------------------------------------------------------------
# shared compile path
# ---------------------------------------------------------------------------


def compile_pipeline(
    fitted: FittedPipeline,
    *,
    metrics: MetricsRegistry,
    signatures: list,
    label: str = "serving",
) -> Callable:
    """Strictly compile ``fitted`` against private trace accounting: every
    XLA trace paid appends its ``(shape, dtype)`` to ``signatures`` and
    bumps the ``compiles`` counter; with an AOT executable cache
    configured, each signature first tries to LOAD a previously exported
    executable (``aot_loads`` counts them) so a warm boot pays zero
    traces. Raises :class:`NotTraceableError` for an unjittable chain —
    at construction, never per-request under traffic."""
    import jax

    # ONE static check drives blockers + the trace build (trace_fn +
    # untraceable_nodes would each re-run the whole-graph pass)
    report = fitted.check(span=False)
    blockers = report.untraceable_labels()
    if blockers:
        raise NotTraceableError(blockers)
    fn = fitted._build_trace_fn()

    def _note_trace(sig):
        signatures.append(sig)
        metrics.inc("compiles")

    aot = _build_aot_dispatcher(fitted, fn, _note_trace, metrics, label)
    if aot is not None:
        return aot

    def _traced(x):
        _note_trace((tuple(x.shape), str(x.dtype)))
        return fn(x)

    return jax.jit(_traced)


def _build_aot_dispatcher(fitted, fn, note_trace, metrics, label):
    """The cache-aware compile path (same isolation contract as the
    private jit). None when no cache is configured or the pipeline cannot
    be content-keyed — then the legacy jit serves."""
    from .. import compile as compile_mod

    cache = compile_mod.get_cache()
    if cache is None:
        return None
    try:
        digest = fitted.fingerprint()
    except compile_mod.FingerprintError as e:
        logger.info(
            "serving: AOT cache skipped (pipeline not fingerprintable): %s", e
        )
        timing.degraded("aot_fingerprint")
        return None
    except Exception:
        # e.g. RecursionError on self-referential operator state: a
        # pipeline that serves fine without the cache must not crash
        # at construction because caching was enabled
        logger.warning(
            "serving: AOT cache skipped (fingerprinting failed)",
            exc_info=True,
        )
        timing.degraded("aot_fingerprint")
        return None

    def _note_load(sig):
        # NOT a compiled signature: no trace was paid for this bucket
        metrics.inc("aot_loads")

    return compile_mod.AotDispatcher(
        fn, digest, cache,
        on_trace=note_trace, on_load=_note_load, label=label,
    )


def serving_report(fitted: FittedPipeline):
    """The static check report of a pipeline about to serve: the datum
    contract (fit-time hint) plus the traceability verdicts every
    serving-path validation reads (keystone_tpu/check/). One call, zero
    executions."""
    return fitted.check(span=False)


def serving_contract(
    fitted: FittedPipeline,
    datum_shape: Optional[Sequence[int]],
    dtype: Any,
    *,
    verb: str = "serve",
    report=None,
):
    """Resolve the per-item (shape, dtype) contract and reject chains the
    bucket policy would silently corrupt — via the static checker's
    :class:`~keystone_tpu.check.CheckReport`, so the refusal carries the
    offending NODE. Explicit args win; otherwise the contract recorded on
    the fitted pipeline at fit time is used."""
    if report is None:
        report = serving_report(fitted)
    # same hazard apply_chunked guards: bucket padding repeats rows, so a
    # node computing whole-batch statistics would silently fold the
    # padding into every real request's answer. require_contract with an
    # open (None) shape/dtype checks ONLY the coupling verdict here.
    report.require_contract(None, None, verb=verb)
    # shape and dtype fall back independently — an explicit shape must not
    # discard the recorded dtype (warming float32 buckets for float64
    # traffic would re-trace every bucket under load)
    if datum_shape is None:
        datum_shape = report.datum_shape
    if dtype is None:
        dtype = report.datum_dtype or "float32"
    return datum_shape, dtype


def check_swap_contract(fitted: FittedPipeline, policy: BucketPolicy) -> None:
    """A replacement model must satisfy the live datum contract (shape +
    dtype) and must not be batch-coupled — re-bucketing or re-shaping a
    live engine/fleet is a restart, not a swap. Validation is the static
    CheckReport compared against the live policy: mismatches raise the
    typed, node-attributed
    :class:`~keystone_tpu.check.ContractMismatchError`."""
    serving_report(fitted).require_contract(
        policy.datum_shape, policy.dtype, verb="swap"
    )


# ---------------------------------------------------------------------------
# the replica worker
# ---------------------------------------------------------------------------


class Replica:
    """One serving worker: a compiled-executable reference, an optional
    pinned device, and the batch loop. Batching POLICY lives in the
    ``source`` handed to :meth:`serve_forever` — the replica only
    executes what the source forms."""

    def __init__(
        self,
        compiled: Callable,
        policy: BucketPolicy,
        metrics: MetricsRegistry,
        *,
        index: Optional[int] = None,
        device: Any = None,
        span_name: str = "serve.replica",
        log_interval_s: float = 10.0,
        quarantine_after: int = 0,
    ):
        #: fleet position, or None for a single-worker topology (the
        #: engine) — None keeps per-replica metrics rows and span attrs
        #: out of snapshots that never had them
        self.index = index
        self.device = device
        self._compiled = compiled
        self._policy = policy
        self._metrics = metrics
        self._span_name = span_name
        self._log_interval = log_interval_s
        self._shadow: Optional[Callable] = None
        #: wall seconds of the last executed batch (compute + D2H), read
        #: by the fleet scheduler to learn its service-time estimate
        self.last_exec_seconds: Optional[float] = None
        #: circuit breaker: this many CONSECUTIVE failed batches raise
        #: :class:`ReplicaQuarantined` out of the loop (0 = disabled —
        #: the single-worker engine, which has no supervisor to catch it)
        self.quarantine_after = int(quarantine_after)
        self.consecutive_failures = 0
        #: the batch currently executing, for the fleet's shutdown path
        #: to requeue/fail if this worker wedges (None between batches)
        self.current_batch: Optional[list] = None
        #: monotonically-increasing model version this replica serves,
        #: stamped by the fleet at construction and on every flip — the
        #: skew-detection surface for long rollouts (a restarted replica
        #: is re-pinned to the PUBLISHED version until promotion)
        self.version: int = 0

    @property
    def compiled(self) -> Callable:
        return self._compiled

    def flip(self, compiled: Callable) -> None:
        """THE swap: one reference store, read once per batch at dispatch
        time — each batch runs whole on exactly one executable."""
        self._compiled = compiled

    def set_shadow(self, shadow: Optional[Callable]) -> None:
        """Install (or clear) the canary mirror: ``shadow(replica, padded,
        primary_out, n_valid, bucket)`` runs after a batch's results are
        distributed, so mirroring never delays live responses."""
        self._shadow = shadow

    # -- the loop -------------------------------------------------------

    def serve_forever(self, source) -> None:
        """Run batches from ``source`` until it returns :data:`STOP`.
        ``source.next_batch(replica)`` returns a request list, None (poll
        again), or STOP; ``source.batch_done(batch, replica)`` runs after
        every batch, exception or not (queue accounting).

        Failure discipline: a TRANSIENT batch failure (injected chaos
        fault, flaky I/O) requeues its unanswered requests through
        ``source.requeue_batch`` when the source offers it (the fleet
        scheduler does; the single-worker engine fails them — it has no
        peers to retry on). Any other ``Exception`` hits the backstop as
        before. A ``BaseException`` — an injected :class:`ReplicaKilled`,
        the quarantine circuit breaker, interpreter teardown — ESCAPES
        with the unanswered requests attached as ``pending``, exactly so
        the fleet supervisor can requeue them and restart the worker."""
        while True:
            batch = source.next_batch(self)
            if batch is STOP:
                return
            if batch:
                self.current_batch = batch
                try:
                    self.run_batch(batch)
                except _TransientBatchFault as e:
                    self._requeue_or_fail(e, source)
                except Exception:  # run_batch isolates; the backstop
                    logger.exception(
                        "serving replica %s: unexpected batch failure",
                        self.index,
                    )
                    self.consecutive_failures += 1
                    for r in batch:
                        if not r.future.done():
                            settle_future(
                                r.future,
                                EngineStopped("internal batch failure"),
                            )
                except BaseException as e:
                    if getattr(e, "pending", None) is None:
                        try:
                            e.pending = [
                                r for r in batch if not r.future.done()
                            ]
                        except Exception:
                            # best-effort annotation for the supervisor;
                            # slots-only exceptions legitimately refuse it
                            logger.debug(
                                "could not attach pending batch to %r",
                                type(e).__name__, exc_info=True,
                            )
                    raise
                finally:
                    self.current_batch = None
                    source.batch_done(batch, self)
                self._maybe_quarantine()
            try:
                # user-registered gauges run inside snapshot(); an
                # exception there must not kill a worker thread
                self._metrics.maybe_log(self._log_interval)
            except Exception:
                logger.exception("serving replica: metrics logging failed")

    def _requeue_or_fail(self, fault: _TransientBatchFault, source) -> None:
        """Route a transient batch failure's unanswered requests back to
        the fleet (deadlines intact) — or fail them when the source has
        no requeue surface (the engine)."""
        pending = [r for r in fault.pending if not r.future.done()]
        requeue = getattr(source, "requeue_batch", None)
        if requeue is not None and pending:
            n = requeue(pending, self, fault.cause)
            logger.warning(
                "serving replica %s: transient batch failure (%s) — "
                "requeued %d of %d request(s) to peers",
                self.index, fault.cause, n, len(pending),
            )
            return
        self._metrics.inc("batch_errors")
        for r in pending:
            settle_future(r.future, fault.cause)

    def _maybe_quarantine(self) -> None:
        if (
            self.quarantine_after
            and self.consecutive_failures >= self.quarantine_after
        ):
            raise ReplicaQuarantined(
                f"replica {self.index} circuit-broken after "
                f"{self.consecutive_failures} consecutive batch failures"
            )

    # -- batch execution ------------------------------------------------

    def run_batch(self, batch: Sequence[_Request]) -> int:
        """Execute one micro-batch through the current executable on this
        replica's device. Returns the number of requests answered with a
        result."""
        import contextlib

        import jax
        import numpy as np

        # cleared up front: a batch that never executes (all expired, all
        # invalid, execution error) must not leave the PREVIOUS batch's
        # duration for the scheduler to re-fold into its service EWMA
        self.last_exec_seconds = None
        try:
            # the chaos seam: kill-kind faults escape as ReplicaDown
            # (thread death), transient-kind become a requeueable batch
            # fault — BEFORE any future is marked running
            fault_point(REPLICA_BATCH, replica=self.index)
        except ReplicaDown:
            raise
        except Exception as e:
            if is_transient(e):
                self._metrics.inc("batch_transient")
                self.consecutive_failures += 1
                raise _TransientBatchFault(e, list(batch)) from e
            raise
        now = time.monotonic()
        tracer = _trace_current()
        live = []
        for r in batch:
            if not r.future.set_running_or_notify_cancel():
                self._metrics.inc("cancelled")
                continue
            if r.deadline is not None and now > r.deadline:
                self._metrics.inc("expired")
                r.future.set_exception(
                    DeadlineExceeded(
                        f"deadline passed {now - r.deadline:.4f}s before batching"
                    )
                )
                continue
            queue_age = now - r.enqueued
            self._metrics.observe_queue_age(queue_age)
            if r.trace is not None and tracer is not None:
                # the queue-wait hop of a traced request: a completed
                # span backdated over the enqueued->dispatched window so
                # the stitched cross-process trace shows WHERE the time
                # went (queued here vs executing below)
                end_pc = time.perf_counter()
                tracer.record_complete(Span(
                    name="serve.queue",
                    start=end_pc - queue_age,
                    end=end_pc,
                    op_type="FleetScheduler",
                    attrs={
                        "trace_id": r.trace.trace_id,
                        "replica": self.index,
                        "queue_age_s": round(queue_age, 6),
                    },
                ))
            live.append(r)

        valid, rows = [], []
        for r in live:
            try:
                rows.append(self._policy.validate(r.datum))
                valid.append(r)
            except InvalidRequest as e:
                self._metrics.inc("invalid")
                r.future.set_exception(e)
        if not valid:
            return 0

        bucket = self._policy.bucket_for(len(valid))
        padded = self._policy.pad(np.stack(rows), bucket)
        if self.device is not None:
            # pin the batch (and so the executable) to this replica's
            # device — N replicas keep N chips busy instead of letting
            # XLA park every dispatch on the default device
            padded = jax.device_put(padded, self.device)
        compiled = self._compiled  # one read: the whole batch runs on it
        t0 = time.perf_counter()
        try:
            # span name differs from the "serve.batch" counter so a merged
            # {name: {seconds, calls, ...}} export of counters + spans never
            # collides on keys
            span_attrs = {"items": len(valid), "bucket": bucket}
            if self.index is not None:
                span_attrs["replica"] = self.index
            traced_ids = [
                r.trace.trace_id for r in valid if r.trace is not None
            ]
            if traced_ids:
                # the batch span carries the first sampled member's
                # identity; members 2..N get their OWN execution spans
                # below (consumers group by args.trace_id, so every
                # coalesced member must own a span over the interval)
                span_attrs["trace_id"] = traced_ids[0]
            # an installed tracer's span, not obs.tracer.span: a batch is
            # the serving path's per-request loop, which stays unannotated
            with (
                tracer.span(self._span_name, op_type="Replica", **span_attrs)
                if tracer is not None
                else contextlib.nullcontext(NULL_SPAN)
            ) as sp:
                out = compiled(padded)
                sp.sync_on(out)
            timing.record("serve.batch", time.perf_counter() - t0)
            out = jax.device_get(out)  # one D2H fetch for the whole batch
        except Exception as e:  # batch-level failure → every member errors
            self.consecutive_failures += 1
            if is_transient(e):
                # transient (injected / flaky I/O): a retry on a peer is
                # expected to succeed — hand the batch back instead of
                # failing every member
                self._metrics.inc("batch_transient")
                raise _TransientBatchFault(e, valid) from e
            self._metrics.inc("batch_errors")
            for r in valid:
                r.future.set_exception(e)
            return 0
        self.last_exec_seconds = time.perf_counter() - t0
        self.consecutive_failures = 0
        # the always-on flight ring gets every batch's summary — with
        # tracing OFF this (one dict + deque append) is the whole
        # observability cost of a batch, and it is what a post-mortem
        # dump shows the replica doing in the seconds before a trigger
        _flight.record_span(
            self._span_name, self.last_exec_seconds,
            items=len(valid), bucket=bucket, replica=self.index,
        )
        if len(traced_ids) > 1 and tracer is not None:
            # coalesced traced members beyond the first: each owns an
            # execution span over the shared batch interval, so per-
            # trace-id grouping never loses a member's compute hop
            # (capped — a full 64-bucket of sampled traffic must not
            # 64x the span volume)
            for extra_tid in traced_ids[1:16]:
                tracer.record_complete(Span(
                    name=self._span_name,
                    start=t0,
                    end=t0 + self.last_exec_seconds,
                    op_type="Replica",
                    attrs={
                        "trace_id": extra_tid,
                        "replica": self.index,
                        "bucket": bucket,
                        "coalesced": True,
                    },
                ))

        done = time.monotonic()
        for i, r in enumerate(valid):
            try:
                r.future.set_result(
                    jax.tree_util.tree_map(lambda a: a[i], out)
                )
            except Exception:  # lint: allow-silent -- set-once race:
                # already settled — a bounded shutdown failed this wedged
                # batch typed while it was still executing; the late real
                # result loses the set-once race, and the REST of the
                # batch must still distribute
                continue
            self._metrics.observe_latency(
                done - r.enqueued, priority=r.priority
            )
        self._metrics.inc("completed", len(valid))
        self._metrics.observe_batch(len(valid), bucket, replica=self.index)
        if _resource.accounting_enabled():
            # charge the batch to its members: measured device-seconds
            # split across the coalesced requests, queue-seconds against
            # the dispatch timestamp, payload bytes from the validated
            # rows — keyed by each request's (tenant, priority) identity
            for (tenant, priority), cost in _resource.split_batch_cost(
                valid, self.last_exec_seconds, now, payloads=rows
            ).items():
                self._metrics.observe_cost(tenant, priority, **cost)
            # batch seam of the device-memory watermark (throttled)
            _resource.sample_memory()

        shadow = self._shadow
        if shadow is not None:
            # canary mirroring rides AFTER result distribution: the
            # candidate's cost lands on the worker, never on live latency
            try:
                shadow(self, padded, out, len(valid), bucket)
            except Exception:
                logger.exception(
                    "serving replica %d: canary shadow failed", self.index
                )
        return len(valid)
