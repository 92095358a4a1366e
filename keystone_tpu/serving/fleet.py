"""The replicated serving fleet: N replicas, one admission surface.

``ServingEngine`` amortizes one compiled pipeline across concurrent
callers — but through ONE worker on ONE device. :class:`ServingFleet`
is the multi-device subsystem on top of the same parts: N
:class:`~.replica.Replica` workers (default one per mesh device,
device-pinned batches) drain a single
:class:`~.scheduler.FleetScheduler` that does continuous batching,
deadline-aware admission shedding (typed :class:`Shed`), and
work-stealing rebalance — see the scheduler module for those
disciplines. All replicas share ONE compiled executable per model
version (and one AOT cache directory under it), so the fleet pays each
bucket signature's trace exactly once no matter how many replicas serve
it; XLA specializes per device underneath without re-tracing.

``swap(fitted)`` is fleet-wide and zero-downtime: the replacement
compiles and pre-warms every bucket OFF the serving path, then replicas
flip one at a time — admission never pauses, every micro-batch runs
whole on exactly one executable, and no request is ever dropped. With
``canary_fraction > 0`` the swap first runs a **shadow/canary phase**:
a fraction of live batches is mirrored through the candidate (after the
live results are distributed, so mirroring never adds request latency),
outputs and latency are compared, and a mismatch auto-rolls-back by
raising :class:`CanaryMismatch` with the evidence — the old model keeps
serving, nothing was promoted.

``start()`` pre-warms every configured bucket AND every signature the
pipeline has ever exported per the AOT cache's bucket-signature manifest
(:mod:`keystone_tpu.compile.manifest`), so a fresh fleet against a warm
shared cache directory boots with zero traces and zero cold
first-requests.

**Replica supervision** (default on): every replica thread runs under a
supervisor. A worker that dies — an injected
:class:`~keystone_tpu.faults.ReplicaKilled`, a real crash — or that
trips the consecutive-batch-failure circuit breaker
(:class:`~.replica.ReplicaQuarantined`) has its queued AND in-flight
requests requeued to live peers with their original deadlines (a
request the learned service estimate says can no longer make it is
answered with the typed ``Shed``, never silently expired), and is
restarted up to a per-replica restart budget. ``restarts``,
``requeues`` and ``quarantined`` land in the metrics;
``fault.replica_down`` / ``fault.replica_restart`` instants land in the
trace. Shutdown is bounded: a wedged replica is joined with a timeout,
logged at WARNING, and abandoned — its work is failed typed and the
final sweep still answers every admitted request.
"""

from __future__ import annotations

import logging
import threading
import time
from concurrent.futures import Future
from typing import Any, Dict, List, Optional, Sequence

from ..autoscale.qos import DEFAULT_TENANT, normalize_priority
from ..faults import ReplicaKilled
from ..obs import flight as _flight
from ..obs import resource as _resource
from ..obs.tracer import current as _trace_current
from ..utils.timing import degraded
from ..workflow.pipeline import FittedPipeline
from .batching import BucketPolicy
from .errors import CanaryMismatch, EngineStopped
from .metrics import MetricsRegistry
from .replica import (
    Replica,
    ReplicaQuarantined,
    _Request,
    check_swap_contract,
    compile_pipeline,
    serving_contract,
    settle_future,
)
from .scheduler import FleetScheduler

logger = logging.getLogger(__name__)

#: manifest entries above this many elements are not pre-warmed (a
#: foreign process may have exported a full-dataset apply shape; warming
#: it would allocate that much zeros on every boot)
_MAX_WARM_ELEMENTS = 1 << 24

#: shutdown never blocks forever on a wedged replica: seconds to wait
#: for the drain to go idle, and per-thread join budget after stop —
#: a thread that misses either is logged at WARNING and abandoned
#: (daemon), and its remaining work is failed typed
_DRAIN_TIMEOUT_S = 60.0
_JOIN_TIMEOUT_S = 10.0


class ServingFleet:
    """Serves a :class:`FittedPipeline` from N replica workers behind one
    deadline-aware admission queue.

    Parameters mirror :class:`~.engine.ServingEngine` where they overlap;
    the new ones:

    replicas:
        Worker count. None (default) = one per data-axis device of the
        active mesh. More replicas than devices is allowed (co-resident
        workers overlap host-side work on shared devices).
    devices:
        Explicit replica→device placement; default
        :func:`keystone_tpu.parallel.placement.replica_devices`.
    steal:
        Work-stealing rebalance between per-replica queues (on by
        default; off pins every request to its admitted queue).
    supervise:
        Replica supervision (on by default): a replica whose thread dies
        — or trips the ``quarantine_after`` consecutive-batch-failure
        circuit breaker — has its queued and in-flight requests requeued
        to peers WITH DEADLINES INTACT (unmeetable ones get the typed
        ``Shed``) and is restarted up to ``max_restarts`` times, counted
        in the ``restarts``/``requeues``/``quarantined`` metrics and
        ``fault.*`` trace instants. ``supervise=False`` still requeues a
        dead replica's work (nothing is ever silently stranded) but
        never restarts it.
    """

    def __init__(
        self,
        fitted: FittedPipeline,
        *,
        replicas: Optional[int] = None,
        buckets: Sequence[int] = (1, 8, 32, 64),
        datum_shape: Optional[Sequence[int]] = None,
        dtype: Any = None,
        max_queue: int = 1024,
        max_wait_ms: float = 2.0,
        metrics: Optional[MetricsRegistry] = None,
        log_interval_s: float = 10.0,
        devices: Optional[Sequence[Any]] = None,
        steal: bool = True,
        supervise: bool = True,
        max_restarts: int = 2,
        quarantine_after: int = 3,
        join_timeout_s: float = _JOIN_TIMEOUT_S,
        drain_timeout_s: float = _DRAIN_TIMEOUT_S,
        tenant_weights: Optional[Dict[str, float]] = None,
    ):
        from ..parallel.placement import replica_devices

        self._fitted = fitted
        datum_shape, dtype = serving_contract(fitted, datum_shape, dtype)
        self._policy = BucketPolicy(buckets, datum_shape, dtype)
        self._metrics = metrics or MetricsRegistry(name="serving-fleet")
        if devices is None:
            devices = replica_devices(replicas)
        elif replicas is not None and len(devices) != replicas:
            raise ValueError(
                f"devices list ({len(devices)}) does not match replicas="
                f"{replicas}"
            )
        self._devices = list(devices)
        n = len(self._devices)
        self._compiled_signatures: list = []
        # ONE executable per model version, shared by every replica: the
        # fleet pays each bucket trace once; device pinning happens per
        # batch via device_put, XLA specializes per device underneath
        compiled = compile_pipeline(
            fitted,
            metrics=self._metrics,
            signatures=self._compiled_signatures,
            label="serving",
        )
        self._replicas = [
            Replica(
                compiled,
                self._policy,
                self._metrics,
                index=i,
                device=self._devices[i],
                span_name="serve.replica",
                log_interval_s=log_interval_s,
                # the breaker only makes sense with a supervisor to
                # catch it and restart the worker
                quarantine_after=quarantine_after if supervise else 0,
            )
            for i in range(n)
        ]
        # the PUBLISHED model: version/digest/executable every replica
        # must serve. A restarted replica is re-pinned to this — so a
        # canary window that outlives a replica restart can never leak
        # the candidate (or anything else) onto the fresh thread, and a
        # long rollout ends with zero version skew. Guarded by
        # _supervise_lock: the supervisor re-pins from the dying
        # replica's thread, which must not take the lifecycle lock.
        self._model_version = 1
        self._model_digest = getattr(compiled, "digest", None)
        self._published_exec = compiled
        for rep in self._replicas:
            rep.version = self._model_version
        self._scheduler = FleetScheduler(
            n,
            self._policy,
            self._metrics,
            max_queue=max_queue,
            max_wait_ms=max_wait_ms,
            steal=steal,
            tenant_weights=tenant_weights,
        )
        self._lifecycle_lock = threading.RLock()
        # serializes whole swaps (incl. the canary window, which runs
        # WITHOUT the lifecycle lock so shutdown is never blocked on a
        # quiet fleet's canary timeout)
        self._swap_lock = threading.Lock()
        # supervision state has its OWN lock: the supervisor runs in the
        # DYING replica's thread, which shutdown (holding the lifecycle
        # lock) may be joining — taking the lifecycle lock there would
        # deadlock the whole stop path
        self._supervise_lock = threading.Lock()
        self._supervise = bool(supervise)
        self._max_restarts = max_restarts if supervise else 0
        self._restart_counts = [0] * n
        self._join_timeout_s = float(join_timeout_s)
        self._drain_timeout_s = float(drain_timeout_s)
        self._threads: List[threading.Thread] = []
        self._closed = False
        self._ran = False
        self._metrics.set_gauge("queue_depth", lambda: self._scheduler.depth)
        # device-memory watermark gauges (live=sum, peak=max,
        # fraction=mean across merged worker snapshots); no-op when
        # KEYSTONE_ACCOUNTING is off
        _resource.install_memory_gauges(self._metrics)

    # -- introspection ---------------------------------------------------

    @property
    def metrics(self) -> MetricsRegistry:
        return self._metrics

    @property
    def policy(self) -> BucketPolicy:
        return self._policy

    def qos_snapshot(self) -> Dict[str, object]:
        """Per-tenant queued depth/weight + queued-by-priority (see
        :meth:`FleetScheduler.qos_snapshot`)."""
        return self._scheduler.qos_snapshot()

    @property
    def scheduler(self) -> FleetScheduler:
        return self._scheduler

    @property
    def replicas(self) -> tuple:
        return tuple(self._replicas)

    @property
    def n_replicas(self) -> int:
        return len(self._replicas)

    @property
    def compiled_signatures(self) -> list:
        """``(shape, dtype)`` of every trace the fleet paid, in compile
        order — len() equals the ``compiles`` counter."""
        return list(self._compiled_signatures)

    @property
    def fitted(self) -> FittedPipeline:
        """The currently-published model (the trainer daemon's absorb
        base — it moves only on a promoted swap)."""
        return self._fitted

    @property
    def model_version(self) -> int:
        """The published model version: 1 at boot, +1 per promoted swap."""
        with self._supervise_lock:
            return self._model_version

    def version_report(self) -> dict:
        """Per-replica version pinning state for long rollouts: the
        published ``version``/``digest`` plus what each replica is
        actually serving. ``skew`` is True when any replica disagrees
        with the published version — transiently possible only inside a
        promotion flip; a steady-state True means a pinning bug."""
        with self._supervise_lock:
            replicas = {
                rep.index: {
                    "version": rep.version,
                    "restarts": self._restart_counts[rep.index],
                }
                for rep in self._replicas
            }
            return {
                "version": self._model_version,
                "digest": self._model_digest,
                "replicas": replicas,
                "skew": any(
                    row["version"] != self._model_version
                    for row in replicas.values()
                ),
            }

    # -- lifecycle -------------------------------------------------------

    def warm_up(self, required: bool = True) -> int:
        """Pre-pay (or AOT-load) every bucket's executable on every
        replica device, plus every signature in the pipeline's AOT
        manifest — a fresh replica against a warm shared cache boots
        with zero traces AND zero cold first-requests. Returns distinct
        signatures warmed. ``required`` follows the engine's contract:
        True raises when no datum shape is known, False downgrades to a
        warning."""
        import numpy as np

        inputs = []
        if self._policy.datum_shape is None:
            if required:
                raise ValueError(
                    "warm-up requested but impossible: no datum shape is "
                    "known — pass datum_shape= to the fleet, or fit the "
                    "pipeline through and_then(estimator, data) so the "
                    "contract is recorded on the FittedPipeline"
                )
            logger.warning(
                "fleet warm-up skipped: no datum_shape configured — the "
                "first live batch of each bucket will pay its compile"
            )
        else:
            inputs = list(self._policy.warmup_inputs())
        seen = {(tuple(x.shape), str(x.dtype)) for x in inputs}
        for shape, dtype in self._manifest_signatures():
            if (shape, dtype) in seen:
                continue
            n_elem = 1
            for d in shape:
                n_elem *= max(int(d), 1)
            if n_elem > _MAX_WARM_ELEMENTS:
                logger.info(
                    "fleet warm-up: skipping oversized manifest signature "
                    "%s (%s elements)", shape, n_elem,
                )
                continue
            seen.add((shape, dtype))
            inputs.append(np.zeros(shape, dtype=dtype))
        self._warm_inputs(self._replicas[0].compiled, inputs)
        self._prewarm_segments()
        logger.info(
            "fleet warm-up: %d signature(s) ready across %d device(s) "
            "(%d traced, %d loaded from the AOT cache)",
            len(inputs), len(self._distinct_devices()),
            self._metrics.count("compiles"),
            self._metrics.count("aot_loads"),
        )
        return len(inputs)

    def _prewarm_segments(self) -> None:
        """Pre-warm every segment executable the AOT cache's segment
        manifest indexes (:mod:`keystone_tpu.compile.segment`) — so a
        warm FIT issued after this boot (a refit on the serving host, a
        cluster worker's local fit) loads whole-segment programs instead
        of tracing them. Best-effort: segment warm-up must never fail a
        fleet that serves fine without it."""
        from .. import compile as compile_mod

        cache = compile_mod.get_cache()
        if cache is None:
            return
        try:
            warmed = compile_mod.prewarm_segment_artifacts(cache)
            if warmed:
                logger.info(
                    "fleet warm-up: %d segment executable(s) pre-warmed",
                    warmed,
                )
        except Exception:
            logger.warning(
                "fleet warm-up: segment pre-warm failed — warm fits will "
                "load lazily", exc_info=True,
            )
            degraded("warmup")

    def _distinct_devices(self) -> list:
        seen, out = set(), []
        for d in self._devices:
            if id(d) not in seen:
                seen.add(id(d))
                out.append(d)
        return out

    def _warm_inputs(self, compiled, inputs) -> None:
        """Run each input through ``compiled`` once per DISTINCT replica
        device (co-resident replicas share executables, so warming per
        replica would re-pay per-device work for nothing)."""
        import jax

        for device in self._distinct_devices():
            for x in inputs:
                jax.block_until_ready(compiled(jax.device_put(x, device)))

    def _manifest_signatures(self) -> list:
        """Signatures the pipeline has ever exported (AOT manifest), or
        [] when no cache / no content-keyed dispatcher is active."""
        from .. import compile as compile_mod

        digest = getattr(self._replicas[0].compiled, "digest", None)
        cache = compile_mod.get_cache()
        if digest is None or cache is None:
            return []
        # the manifest records batch shapes; only warm entries matching
        # this fleet's per-item contract and dtype (a foreign config's
        # exports would trace programs this fleet can never serve). With
        # NO shape contract there is nothing to match against — warm
        # nothing rather than pay startup compiles for signatures the
        # first live request may immediately contradict.
        want = self._policy.datum_shape
        if want is None:
            return []
        sigs = compile_mod.exported_signatures(cache, digest)
        out = []
        for shape, dtype in sigs:
            if tuple(shape[1:]) != tuple(want):
                continue
            if str(dtype) != str(self._policy.dtype):
                continue
            out.append((shape, dtype))
        return out

    def start(self, warmup: Optional[bool] = None) -> "ServingFleet":
        """Warm per :meth:`warm_up` (same ``warmup`` semantics as the
        engine), then start every replica worker and begin admitting."""
        with self._lifecycle_lock:
            if self._threads:
                raise RuntimeError("fleet already started")
            if self._closed:
                raise EngineStopped("fleet was shut down")
            if warmup or warmup is None:
                self.warm_up(required=warmup is True)
            for rep in self._replicas:
                self._spawn_replica_thread(rep)
            self._ran = True
        return self

    def _spawn_replica_thread(self, rep: Replica) -> threading.Thread:
        attempt = self._restart_counts[rep.index]
        t = threading.Thread(
            target=self._run_replica,
            args=(rep,),
            name=(
                f"keystone-serving-replica-{rep.index}"
                + (f"-r{attempt}" if attempt else "")
            ),
            daemon=True,
        )
        with self._supervise_lock:
            self._threads.append(t)
        t.start()
        return t

    # -- replica supervision ---------------------------------------------

    def _run_replica(self, rep: Replica) -> None:
        """Every replica thread's real target: the loop plus the
        supervisor. A loop that exits with ANY ``BaseException`` — an
        injected :class:`ReplicaKilled`, the quarantine breaker, a truly
        unexpected death — is treated as a down worker: its queued and
        in-flight requests are requeued to peers (deadlines intact) and
        it restarts within the restart budget."""
        try:
            rep.serve_forever(self._scheduler)
        except BaseException as e:  # noqa: BLE001 — the supervision seam
            try:
                self._on_replica_down(rep, e)
            except Exception:
                logger.exception(
                    "fleet supervisor failed for replica %s", rep.index
                )

    def _on_replica_down(self, rep: Replica, exc: BaseException) -> None:
        pending = getattr(exc, "pending", None) or []
        quarantined = isinstance(exc, ReplicaQuarantined)
        killed = isinstance(exc, ReplicaKilled)
        kind = (
            "quarantined" if quarantined
            else "killed" if killed
            else "died"
        )
        with self._supervise_lock:
            used = self._restart_counts[rep.index]
            will_restart = (
                not self._closed and used < self._max_restarts
            )
            if quarantined:
                self._metrics.inc("quarantined")
            # a permanently-down replica stops receiving admissions; a
            # restarting one keeps its slot live (requeue then retries
            # locally when there is no peer — the 1-replica fleet)
            self._scheduler.set_active(rep.index, will_restart)
            moved = 0
            if pending:
                moved += self._scheduler.requeue_batch(
                    pending, rep,
                    cause=exc if isinstance(exc, Exception) else None,
                )
            moved += self._scheduler.requeue_replica(
                rep.index, keep_if_no_peer=will_restart
            )
            logger.warning(
                "fleet: replica %s %s (%s) — requeued %d request(s); "
                "restart %s (budget %d/%d used)",
                rep.index, kind, exc, moved,
                "scheduled" if will_restart else "refused",
                used, self._max_restarts,
            )
            tracer = _trace_current()
            if tracer is not None:
                tracer.instant(
                    "fault.replica_down", op_type="ServingFleet",
                    replica=rep.index, kind=kind, requeued=moved,
                    restarting=will_restart,
                )
            _flight.record_instant(
                "fault.replica_down", replica=rep.index, kind=kind,
                requeued=moved, restarting=will_restart,
            )
            if will_restart:
                self._restart_counts[rep.index] = used + 1
                self._metrics.inc("restarts")
                rep.consecutive_failures = 0
                # re-pin to the PUBLISHED model: a restart during a
                # canary window (or any long rollout) must come back on
                # the version the fleet is actually serving — promotion,
                # which flips every replica under this same lock, is the
                # only thing that moves it forward
                rep.flip(self._published_exec)
                rep.version = self._model_version
            elif not self._scheduler.any_active():
                failed = self._scheduler.fail_remaining(
                    "every replica is down and the restart budget is "
                    "exhausted"
                )
                if failed:
                    logger.warning(
                        "fleet: no live replicas remain — failed %d "
                        "queued request(s)", failed,
                    )
        # post-mortem artifacts, OUTSIDE the supervise lock (dumping is
        # file IO): quarantine always leaves one; so does a replica that
        # exhausted its restart budget (the fleet just lost capacity)
        if quarantined:
            _flight.dump("replica_quarantine")
        elif not will_restart:
            _flight.dump("replica_down")
        if will_restart:
            # spawn OUTSIDE the supervise lock (it re-takes it to
            # register the thread)
            self._spawn_replica_thread(rep)
            _flight.record_instant(
                "fault.replica_restart", replica=rep.index,
                attempt=used + 1,
            )
            tracer = _trace_current()
            if tracer is not None:
                tracer.instant(
                    "fault.replica_restart", op_type="ServingFleet",
                    replica=rep.index, attempt=used + 1,
                )

    def drain(self) -> None:
        """Stop admitting, answer every queued request, stop all workers."""
        self.shutdown(drain=True)

    def shutdown(self, drain: bool = True) -> None:
        """Stop the fleet. ``drain=True`` answers queued requests first;
        ``drain=False`` fails them with :class:`EngineStopped`.
        Idempotent and safe from multiple threads.

        Never blocks forever: the drain and every thread join are
        bounded (``drain_timeout_s`` / ``join_timeout_s``). A replica
        that wedges — a hung host callback, a stuck device — is logged
        at WARNING and abandoned (its thread is a daemon), its in-flight
        requests are failed typed, and the final ``fail_remaining``
        sweep still answers everything queued, so no admitted request is
        ever left without an answer."""
        with self._lifecycle_lock:
            self._closed = True
            self._scheduler.close()
            with self._supervise_lock:
                started = bool(self._threads)
            if not started:
                self._scheduler.fail_remaining(
                    "fleet is shut down" if self._ran else "fleet never started"
                )
                return
            if drain:
                if not self._scheduler.wait_idle(
                    timeout=self._drain_timeout_s
                ):
                    logger.warning(
                        "fleet shutdown: drain did not go idle within "
                        "%.1fs (wedged replica?) — failing the remaining "
                        "work instead of blocking forever",
                        self._drain_timeout_s,
                    )
            self._scheduler.stop()
            with self._supervise_lock:
                threads, self._threads = self._threads, []
            for t in threads:
                t.join(timeout=self._join_timeout_s)
                if t.is_alive():
                    logger.warning(
                        "fleet shutdown: thread %s did not exit within "
                        "%.1fs — abandoning it (daemon) and failing its "
                        "remaining work", t.name, self._join_timeout_s,
                    )
            # a wedged replica's in-flight batch would otherwise hang
            # its callers: answer those futures typed (a late real
            # result loses the set-once race harmlessly)
            for rep in self._replicas:
                batch = rep.current_batch
                if batch:
                    for r in batch:
                        settle_future(
                            r.future,
                            EngineStopped(
                                "fleet shut down while this request's "
                                "replica was wedged"
                            ),
                        )
            # admission-vs-close is atomic in the scheduler, so nothing
            # can land after this point; the sweep is the belt-and-braces
            # guarantee no admitted request is ever left unanswered
            self._scheduler.fail_remaining()

    def __enter__(self) -> "ServingFleet":
        return self.start()

    def __exit__(self, *exc) -> None:
        self.shutdown(drain=True)

    # -- admission -------------------------------------------------------

    def submit(
        self,
        datum: Any,
        timeout: Optional[float] = None,
        trace: Any = None,
        priority: Optional[str] = None,
        tenant: Optional[str] = None,
    ) -> Future:
        """Enqueue one datum; returns a Future of its prediction row.

        ``timeout`` (seconds) is the request's deadline. Raises typed:
        :class:`QueueFull` at capacity, :class:`Shed` when the deadline
        cannot be met given the learned service time and queue depth,
        :class:`EngineStopped` after shutdown. ``trace`` is an optional
        :class:`~keystone_tpu.obs.context.TraceContext` — a sampled
        request's cross-process identity, carried so the replica's
        queue-wait and batch spans record under it (the cluster worker
        passes the context it received off the wire). ``priority``
        (``high``/``normal``/``low``, default normal) sets the shedding
        class; ``tenant`` names the weighted-fair share the request is
        served from (see :mod:`keystone_tpu.autoscale.qos`)."""
        now = time.monotonic()
        req = _Request(
            datum=datum,
            deadline=(now + timeout) if timeout is not None else None,
            enqueued=now,
            trace=trace,
            priority=normalize_priority(priority),
            tenant=str(tenant) if tenant else DEFAULT_TENANT,
        )
        self._scheduler.admit(req)  # counts "submitted" atomically
        return req.future

    def predict(self, datum: Any, timeout: Optional[float] = None) -> Any:
        """Synchronous convenience: submit + wait (see the engine's
        :meth:`~.engine.ServingEngine.predict` contract)."""
        if not self._threads:
            raise RuntimeError(
                "predict() needs a started fleet (call start() or use "
                "the context manager)"
            )
        return self.submit(datum, timeout=timeout).result()

    # -- fleet-wide zero-downtime swap -----------------------------------

    def swap(
        self,
        fitted: FittedPipeline,
        *,
        warmup: Optional[bool] = None,
        canary_fraction: float = 0.0,
        canary_batches: int = 4,
        canary_timeout_s: float = 30.0,
        atol: float = 1e-5,
        rtol: float = 1e-5,
        max_latency_ratio: Optional[float] = None,
    ) -> dict:
        """Replace the served model fleet-wide with zero downtime.

        The replacement compiles strictly and pre-warms every bucket on
        every replica device OFF the serving path; replicas then flip one
        at a time (each micro-batch runs whole on exactly one executable;
        admission never pauses; no request is dropped).

        With ``canary_fraction > 0``, a shadow phase first mirrors that
        fraction of live micro-batches through the candidate — AFTER each
        batch's live results are distributed, so mirroring adds zero
        request latency — and compares outputs (``atol``/``rtol``) and
        execution latency. Any output mismatch (or a latency ratio above
        ``max_latency_ratio``, when given) AUTO-ROLLS-BACK: the candidate
        is discarded, the old model keeps serving, and
        :class:`CanaryMismatch` carries the evidence. The phase ends
        after ``canary_batches`` mirrored batches or ``canary_timeout_s``
        seconds (a quiet fleet promotes on whatever evidence arrived —
        zero mirrored batches included; set a longer timeout to insist).

        Returns a report dict: replicas flipped, signatures warmed,
        compiles/aot_loads paid, and the canary verdict."""
        check_swap_contract(fitted, self._policy)
        with self._swap_lock:
            with self._lifecycle_lock:
                if self._closed:
                    raise EngineStopped("fleet is draining / shut down")
            # compile + warm-up + canary all run WITHOUT the lifecycle
            # lock: a swap that traces fresh buckets (tens of seconds on
            # a real chip) or waits out a quiet canary must never block a
            # concurrent shutdown. _swap_lock serializes competing swaps;
            # _promote re-checks closed, so a shutdown that slips in here
            # merely wastes the candidate's compile.
            compiles_before = self._metrics.count("compiles")
            loads_before = self._metrics.count("aot_loads")
            candidate = compile_pipeline(
                fitted,
                metrics=self._metrics,
                signatures=self._compiled_signatures,
                label="serving",
            )
            warmed = 0
            if (
                (warmup or warmup is None)
                and self._policy.datum_shape is not None
            ):
                inputs = list(self._policy.warmup_inputs())
                self._warm_inputs(candidate, inputs)
                warmed = len(inputs)
            elif warmup is True:
                raise ValueError(
                    "swap(warmup=True) but no datum shape is known — "
                    "the fleet cannot pre-pay the replacement's compiles"
                )

            # the canary window runs WITHOUT the lifecycle lock: waiting
            # (up to canary_timeout_s) for mirrored traffic must never
            # block a concurrent shutdown; _swap_lock still serializes
            # competing swaps end to end
            canary_report = None
            if canary_fraction > 0:
                canary_report = self._run_canary(
                    candidate,
                    fraction=canary_fraction,
                    target_batches=canary_batches,
                    timeout_s=canary_timeout_s,
                    atol=atol,
                    rtol=rtol,
                    max_latency_ratio=max_latency_ratio,
                )

            return self._promote(
                fitted, candidate, warmed, canary_report,
                compiles_before, loads_before,
            )

    def _promote(
        self, fitted, candidate, warmed, canary_report,
        compiles_before, loads_before,
    ) -> dict:
        with self._lifecycle_lock:
            if self._closed:
                raise EngineStopped(
                    "fleet shut down during the swap — nothing promoted"
                )
            # promotion: a rolling flip, one replica at a time. There is
            # no quiesce step and none is needed — run_batch reads the
            # executable reference ONCE per batch, so each in-flight
            # batch finishes whole on whichever executable it dispatched
            # with; the flip is one atomic store per replica. The
            # published version advances FIRST under the supervise lock,
            # so a replica restart racing the flip loop re-pins to the
            # candidate and the loop's own flip is then a no-op — either
            # order ends with every replica on the new version.
            with self._supervise_lock:
                self._model_version += 1
                self._model_digest = getattr(candidate, "digest", None)
                self._published_exec = candidate
                version = self._model_version
                for rep in self._replicas:
                    rep.flip(candidate)
                    rep.version = version
            self._fitted = fitted
            self._metrics.inc("swaps")
            report = {
                "replicas_flipped": len(self._replicas),
                "buckets_warmed": warmed,
                "compiles": self._metrics.count("compiles") - compiles_before,
                "aot_loads": self._metrics.count("aot_loads") - loads_before,
                "canary": canary_report,
                "version": version,
            }
            _flight.record_instant(
                "serve.swap", version=version,
                replicas=len(self._replicas), buckets_warmed=warmed,
            )
            tracer = _trace_current()
            if tracer is not None:
                with tracer.span(
                    "serve.swap",
                    op_type="ServingFleet",
                    replicas=len(self._replicas),
                    version=version,
                    buckets_warmed=warmed,
                    compiles=report["compiles"],
                    aot_loads=report["aot_loads"],
                    canary="pass" if canary_report else None,
                    queue_depth=self._scheduler.depth,
                    live=bool(self._threads),
                ):
                    pass
            logger.info(
                "fleet swap: model replaced on %d replica(s) (%d "
                "signature(s) warmed, %d traced, %d AOT-loaded%s)",
                len(self._replicas), warmed,
                report["compiles"], report["aot_loads"],
                (
                    f"; canary pass on {canary_report['batches_compared']} "
                    "mirrored batch(es)"
                    if canary_report else ""
                ),
            )
            return report

    def _run_canary(
        self,
        candidate,
        *,
        fraction: float,
        target_batches: int,
        timeout_s: float,
        atol: float,
        rtol: float,
        max_latency_ratio: Optional[float],
    ) -> dict:
        """Mirror live traffic through ``candidate``; raise
        :class:`CanaryMismatch` (auto-rollback) on any output mismatch or
        latency blow-up; return the pass report otherwise."""
        shadow = _Shadow(
            candidate,
            fraction=fraction,
            target_batches=target_batches,
            atol=atol,
            rtol=rtol,
        )
        for rep in self._replicas:
            rep.set_shadow(shadow.observe)
        try:
            # poll-wait so a fleet shutdown mid-canary ends the window
            # immediately instead of sitting out the full timeout
            deadline = time.monotonic() + timeout_s
            while not shadow.wait(0.2):
                if self._closed or time.monotonic() >= deadline:
                    break
        finally:
            for rep in self._replicas:
                rep.set_shadow(None)
        report = shadow.report()
        ratio = report.get("latency_ratio")
        too_slow = (
            max_latency_ratio is not None
            and ratio is not None
            and ratio > max_latency_ratio
        )
        if report["mismatches"] or too_slow:
            self._metrics.inc("canary_fail")
            why = (
                f"{report['mismatches']} mismatched batch(es) of "
                f"{report['batches_compared']} mirrored"
                if report["mismatches"]
                else f"candidate latency ratio {ratio:.2f} exceeds "
                     f"{max_latency_ratio}"
            )
            logger.warning("fleet canary FAILED — rolling back: %s", why)
            _flight.record_instant(
                "serve.canary_rollback",
                mismatches=report["mismatches"],
                batches_compared=report["batches_compared"],
                latency_ratio=ratio,
            )
            _flight.dump("canary_rollback")
            raise CanaryMismatch(
                f"canary auto-rollback: {why}; the fleet is still serving "
                "the previous model",
                report,
            )
        self._metrics.inc("canary_pass")
        return report


class _Shadow:
    """Mirrors sampled live batches through a candidate executable and
    accumulates the comparison evidence. Installed as every replica's
    shadow hook during a canaried swap; thread-safe (N replicas call
    ``observe`` concurrently)."""

    def __init__(
        self,
        candidate,
        *,
        fraction: float,
        target_batches: int,
        atol: float,
        rtol: float,
    ):
        self._candidate = candidate
        # deterministic sampling: every k-th completed batch mirrors
        self._every = max(1, int(round(1.0 / max(fraction, 1e-9))))
        self._target = max(1, int(target_batches))
        self._atol = atol
        self._rtol = rtol
        self._lock = threading.Lock()
        self._seen = 0
        self._compared = 0
        self._n_mismatch = 0  # full count; the detail list below is capped
        self._mismatches: list = []
        self._ratios: list = []
        self._done = threading.Event()

    def observe(self, replica, padded, primary_out, n_valid, bucket) -> None:
        import jax
        import numpy as np

        with self._lock:
            self._seen += 1
            if self._compared >= self._target:
                self._done.set()
                return
            if (self._seen - 1) % self._every:
                return
        t0 = time.perf_counter()
        try:
            cand = jax.device_get(self._candidate(padded))
        except Exception as e:
            # a candidate that cannot even run its bucket is the clearest
            # possible mismatch — count it, never break the live batch
            with self._lock:
                self._compared += 1
                self._n_mismatch += 1
                if len(self._mismatches) < 8:
                    self._mismatches.append(
                        {"replica": replica.index, "bucket": bucket,
                         "error": repr(e)[:200]}
                    )
                self._done.set()  # any mismatch decides the verdict
            return
        cand_s = time.perf_counter() - t0
        primary_leaves = jax.tree_util.tree_leaves(primary_out)
        cand_leaves = jax.tree_util.tree_leaves(cand)
        detail = None
        if len(primary_leaves) != len(cand_leaves):
            detail = {"structure": "output tree shape differs"}
        else:
            for a, b in zip(primary_leaves, cand_leaves):
                a, b = np.asarray(a)[:n_valid], np.asarray(b)[:n_valid]
                if a.shape != b.shape:
                    detail = {"shapes": [list(a.shape), list(b.shape)]}
                    break
                if not np.allclose(a, b, atol=self._atol, rtol=self._rtol):
                    diff = np.max(np.abs(
                        a.astype(np.float64) - b.astype(np.float64)
                    ))
                    detail = {"max_abs_diff": float(diff)}
                    break
        with self._lock:
            self._compared += 1
            if replica.last_exec_seconds:
                self._ratios.append(cand_s / replica.last_exec_seconds)
            if detail is not None:
                self._n_mismatch += 1
                if len(self._mismatches) < 8:
                    detail.update(
                        {"replica": replica.index, "bucket": bucket}
                    )
                    self._mismatches.append(detail)
            if detail is not None or self._compared >= self._target:
                # any mismatch decides the verdict — no need to keep
                # mirroring; the swap thread wakes and rolls back
                self._done.set()

    def wait(self, timeout_s: float) -> bool:
        return self._done.wait(timeout=timeout_s)

    def report(self) -> dict:
        import statistics

        with self._lock:
            return {
                "batches_compared": self._compared,
                "mismatches": self._n_mismatch,
                "mismatch_details": list(self._mismatches),
                "latency_ratio": (
                    round(statistics.median(self._ratios), 3)
                    if self._ratios else None
                ),
            }
