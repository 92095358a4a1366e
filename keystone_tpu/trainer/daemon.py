"""The supervised trainer daemon: the closed continual-learning loop.

One background thread turns "fit then serve" into a hands-free online
system by connecting machinery that already exists separately:

* **tail** — read the append-only :class:`~.source.ChunkLog` strictly
  forward (``trainer.ingest`` fault point; transient faults retry
  bounded, then escalate to the supervisor);
* **monitor** — featurize each appended chunk through the serving
  model's FROZEN prefix (``FittedPipeline.prefix_features``), fold it
  into the :class:`~.drift.DriftMonitor` against the fitted solver
  state's own moment snapshot, and score streaming residual error on
  labeled appends;
* **decide** — refit on a wall-clock cadence OR when a drift trigger
  trips (both observable as the ``drift_score`` gauge);
* **absorb** — fold the pending chunk batch into the model with
  ``FittedPipeline.absorb`` in O(new chunks), CHECKPOINTED through
  :class:`~keystone_tpu.faults.FitCheckpoint` (``trainer.absorb`` fires
  per folded chunk, so a kill mid-fold leaves the last completed block
  on disk and the retried attempt resumes bit-identically — served data
  is never rescanned);
* **canary + swap** — publish through
  :meth:`~keystone_tpu.serving.fleet.ServingFleet.swap` with a canary
  fraction: live traffic mirrors through the candidate, the evidence
  report promotes or auto-rolls-back (``trainer.canary`` fires before
  the swap; an injected transient there counts as canary failure);
* **survive** — every failure mode leaves the OLD model serving: an
  absorb crash or canary mismatch retries its chunk batch a bounded
  number of times and then PARKS it (quarantine + WARNING — never a
  poison-pill loop); the loop thread itself restarts within an explicit
  restart budget when something punches through (an injected kill, a
  real crash), with all cursor/batch state preserved on the object.

Metrics land in the fleet's registry (``refits``, ``rollbacks``,
``parked_batches``, ``absorb_failures``, ``absorbed_chunks``,
``absorbed_rows``, plus the ``drift_score`` / ``staleness_s`` /
``trainer_backlog`` gauges); promote/rollback/park/restart are trace
instants and each refit attempt is a ``trainer.refit`` span.
"""

from __future__ import annotations

import logging
import threading
import time
from typing import Any, List, Optional

import numpy as np

from ..faults import (
    TRAINER_ABSORB,
    TRAINER_CANARY,
    TRAINER_INGEST,
    fault_point,
    is_transient,
)
from ..obs.tracer import current as _trace_current
from ..obs.tracer import span as _span
from ..serving.errors import CanaryMismatch, EngineStopped
from .drift import DriftMonitor
from .source import ChunkLog

logger = logging.getLogger(__name__)


class TrainerStopped(RuntimeError):
    """The daemon is not running (never started, stopped, or its restart
    budget is exhausted)."""


class _Attempt:
    """One frozen chunk batch being refit: ``[start, stop)`` log indices
    plus its bounded retry count. Frozen at first attempt so retries are
    deterministic and the absorb checkpoint key stays stable; chunks
    appended later join the NEXT batch."""

    def __init__(self, start: int, stop: int):
        self.start = start
        self.stop = stop
        self.retries = 0

    @property
    def key(self) -> str:
        return f"trainer-batch-{self.start}-{self.stop}"


class TrainerDaemon:
    """Supervised continual-learning loop over a fleet and a chunk log.

    Parameters (every knob is an explicit budget or threshold):

    fleet:
        The live :class:`~keystone_tpu.serving.fleet.ServingFleet`; its
        published model is the absorb base and swap target.
    source:
        The :class:`~.source.ChunkLog` to tail.
    poll_interval_s:
        Idle sleep between loop ticks.
    refit_interval_s:
        Cadence trigger: refit when this much wall clock passed since
        the last promoted refresh (None = drift-only).
    min_refit_chunks:
        Never refit on fewer pending chunks than this.
    drift:
        A pre-built :class:`~.drift.DriftMonitor`, or None to build one
        from the fitted solver state's moment snapshot with the monitor
        defaults (``drift_kwargs`` passes overrides).
    canary_fraction / canary_batches / canary_timeout_s / canary_atol /
    canary_rtol / max_latency_ratio:
        Forwarded to ``fleet.swap`` — the promote-or-rollback evidence.
        The tolerances are the "how different may a refreshed model be"
        knob: a healthy absorb moves outputs a little, a poisoned batch
        moves them wildly.
    max_batch_retries:
        Absorb crashes / canary rollbacks a chunk batch survives before
        it is parked (quarantined) and the loop moves on.
    max_restarts:
        Loop-thread restart budget (the daemon's own supervisor).
    max_ingest_failures:
        Consecutive transient ingest failures tolerated before the tick
        escalates to the supervisor.
    checkpoint_dir:
        Directory for absorb checkpoints (None = absorb is all-or-
        nothing per attempt; retries refold from the first chunk).
    """

    def __init__(
        self,
        fleet,
        source: ChunkLog,
        *,
        poll_interval_s: float = 0.05,
        refit_interval_s: Optional[float] = None,
        min_refit_chunks: int = 1,
        drift: Optional[DriftMonitor] = None,
        drift_kwargs: Optional[dict] = None,
        canary_fraction: float = 0.25,
        canary_batches: int = 2,
        canary_timeout_s: float = 5.0,
        canary_atol: float = 0.25,
        canary_rtol: float = 0.25,
        max_latency_ratio: Optional[float] = None,
        max_batch_retries: int = 1,
        max_restarts: int = 2,
        max_ingest_failures: int = 8,
        checkpoint_dir: Optional[str] = None,
        join_timeout_s: float = 10.0,
    ):
        self._fleet = fleet
        self._source = source
        self._fitted = fleet.fitted
        self.poll_interval_s = float(poll_interval_s)
        self.refit_interval_s = (
            None if refit_interval_s is None else float(refit_interval_s)
        )
        self.min_refit_chunks = int(min_refit_chunks)
        self.canary_fraction = float(canary_fraction)
        self.canary_batches = int(canary_batches)
        self.canary_timeout_s = float(canary_timeout_s)
        self.canary_atol = float(canary_atol)
        self.canary_rtol = float(canary_rtol)
        self.max_latency_ratio = max_latency_ratio
        self.max_batch_retries = int(max_batch_retries)
        self.max_restarts = int(max_restarts)
        self.max_ingest_failures = int(max_ingest_failures)
        self.checkpoint_dir = checkpoint_dir

        self._metrics = fleet.metrics
        self._monitor = drift or DriftMonitor(
            self._state_of(self._fitted).moments(), **(drift_kwargs or {})
        )
        #: log index up to which chunks are RESOLVED (promoted or parked)
        self._resolved = 0
        #: log index up to which chunks were ingested into the monitor
        self._ingested = 0
        self._attempt: Optional[_Attempt] = None
        self._parked: List[tuple] = []
        self._consecutive_ingest_failures = 0
        self._last_promote = time.monotonic()
        self._restarts_used = 0
        self._lock = threading.Lock()
        self._stop = threading.Event()
        self._thread: Optional[threading.Thread] = None
        self._join_timeout_s = float(join_timeout_s)
        # drift/staleness are watermark-shaped (the WORST process is the
        # fleet's truth — summing two drift scores across a merge is
        # fiction); backlog is additive
        self._metrics.set_gauge(
            "drift_score",
            lambda: self._monitor.score()["drift_score"],
            merge="max",
        )
        self._metrics.set_gauge("staleness_s", self.staleness_s, merge="max")
        self._metrics.set_gauge(
            "trainer_backlog", lambda: len(self._source) - self._resolved
        )

    # -- introspection ---------------------------------------------------

    @property
    def fitted(self):
        """The daemon's view of the published model (moves only on a
        promoted refresh)."""
        return self._fitted

    @property
    def monitor(self) -> DriftMonitor:
        return self._monitor

    @property
    def parked_batches(self) -> List[tuple]:
        """Quarantined ``(start, stop)`` chunk-index ranges — appended
        data the loop gave up on after the bounded retries. Their chunks
        stay in the log untouched for offline forensics."""
        with self._lock:
            return list(self._parked)

    def staleness_s(self) -> float:
        """Seconds since the last promoted refresh (or daemon start)."""
        return time.monotonic() - self._last_promote

    @staticmethod
    def _state_of(fitted):
        node, mapper = fitted._absorb_node()
        return mapper.solver_state

    @staticmethod
    def _mapper_of(fitted):
        node, mapper = fitted._absorb_node()
        return mapper

    # -- lifecycle -------------------------------------------------------

    def start(self) -> "TrainerDaemon":
        with self._lock:
            if self._thread is not None:
                raise RuntimeError("trainer already started")
            if self._stop.is_set():
                raise TrainerStopped("trainer was stopped")
            self._spawn_thread()
        return self

    def _spawn_thread(self) -> None:
        attempt = self._restarts_used
        self._thread = threading.Thread(
            target=self._run,
            name=(
                "keystone-trainer" + (f"-r{attempt}" if attempt else "")
            ),
            daemon=True,
        )
        self._thread.start()

    def stop(self) -> None:
        """Idempotent bounded shutdown: the loop exits at the next tick
        boundary; a loop wedged inside a canary window is joined with a
        timeout, WARNed, and abandoned (daemon thread)."""
        self._stop.set()
        with self._lock:
            t = self._thread
        if t is not None and t is not threading.current_thread():
            t.join(timeout=self._join_timeout_s)
            if t.is_alive():
                logger.warning(
                    "trainer shutdown: thread %s did not exit within "
                    "%.1fs — abandoning it (daemon)",
                    t.name, self._join_timeout_s,
                )

    def __enter__(self) -> "TrainerDaemon":
        return self.start()

    def __exit__(self, *exc) -> None:
        self.stop()

    # -- supervision -----------------------------------------------------

    def _run(self) -> None:
        """The thread target: the loop under its own supervisor. ANY
        escape (an injected kill at a trainer fault site, a real crash)
        restarts the loop within the restart budget — with all batch /
        cursor state preserved on the object, so a killed absorb's next
        attempt resumes from its checkpoint."""
        try:
            self._loop()
        except BaseException as e:  # noqa: BLE001 — the supervision seam
            if self._stop.is_set():
                return
            with self._lock:
                will_restart = self._restarts_used < self.max_restarts
                if will_restart:
                    self._restarts_used += 1
                self._metrics.inc("trainer_crashes")
            logger.warning(
                "trainer: loop died (%s: %s) — restart %s (budget %d/%d "
                "used)", type(e).__name__, e,
                "scheduled" if will_restart else "REFUSED",
                self._restarts_used, self.max_restarts,
            )
            self._instant(
                "trainer.restart" if will_restart else "trainer.dead",
                kind=type(e).__name__,
            )
            if will_restart:
                # a fresh loop gets a fresh ingest-fault budget — the
                # escalation that triggered this restart must not leave
                # the counter saturated (one more flake would otherwise
                # burn the next restart immediately)
                self._consecutive_ingest_failures = 0
                with self._lock:
                    self._spawn_thread()
                self._metrics.inc("trainer_restarts")
            else:
                self._stop.set()

    def _loop(self) -> None:
        while not self._stop.is_set():
            did_work = self._tick()
            if not did_work:
                self._stop.wait(self.poll_interval_s)

    # -- one tick --------------------------------------------------------

    def _tick(self) -> bool:
        """Ingest, decide, maybe refit. Returns True when it did real
        work (skip the idle sleep)."""
        new = self._ingest()
        for chunk in new:
            self._observe(chunk)
        if self._attempt is None and self._should_refit():
            self._attempt = _Attempt(self._resolved, self._ingested)
        if self._attempt is not None:
            self._refit(self._attempt)
            return True
        return bool(new)

    def _ingest(self) -> list:
        """Tail the source; transient faults (``trainer.ingest``) are
        tolerated up to ``max_ingest_failures`` consecutive times, then
        escalate to the supervisor."""
        try:
            fault_point(TRAINER_INGEST)
            new = self._source.tail(self._ingested)
        except Exception as e:
            if not is_transient(e):
                raise
            self._consecutive_ingest_failures += 1
            self._metrics.inc("ingest_failures")
            logger.warning(
                "trainer: transient ingest failure %d/%d (%s)",
                self._consecutive_ingest_failures,
                self.max_ingest_failures, e,
            )
            self._instant(
                "trainer.ingest_fault",
                consecutive=self._consecutive_ingest_failures,
                budget=self.max_ingest_failures,
            )
            if self._consecutive_ingest_failures >= self.max_ingest_failures:
                raise
            return []
        self._consecutive_ingest_failures = 0
        self._ingested += len(new)
        return new

    def _observe(self, chunk) -> None:
        """Monitor one appended chunk: featurize through the frozen
        prefix, score moment drift against the fitted snapshot, and (on
        labeled appends) the model's residual error. A chunk that fails
        monitoring is WARNed and still joins its batch — if it is
        genuinely poisoned, the absorb/canary path catches it and the
        bounded-retry-then-park discipline quarantines the batch."""
        from ..data.dataset import Dataset

        try:
            feats = np.asarray(
                Dataset.of(
                    self._fitted.prefix_features(Dataset.of(chunk.data))
                ).to_array()
            )
            residual = None
            if chunk.labels is not None:
                import jax.numpy as jnp

                preds = np.asarray(
                    self._mapper_of(self._fitted).trace_batch(
                        jnp.asarray(feats, dtype=jnp.float32)
                    )
                )
                residual = float(
                    np.mean(
                        (preds - np.asarray(chunk.labels, np.float64)) ** 2
                    )
                )
            self._monitor.observe(feats, residual)
        except Exception:
            self._metrics.inc("monitor_failures")
            logger.warning(
                "trainer: chunk %d failed featurize-for-monitoring "
                "(drift evidence skipped; the absorb path will judge it)",
                chunk.index, exc_info=True,
            )

    def _should_refit(self) -> bool:
        pending = self._ingested - self._resolved
        if pending < self.min_refit_chunks:
            return False
        reason = self._monitor.should_refit()
        if reason is not None:
            logger.info(
                "trainer: drift trigger (%s) — refitting %d pending "
                "chunk(s)", reason, pending,
            )
            return True
        if (
            self.refit_interval_s is not None
            and self.staleness_s() >= self.refit_interval_s
        ):
            return True
        return False

    # -- the refit attempt ----------------------------------------------

    def _refit(self, attempt: _Attempt) -> None:
        """One absorb → canary → swap attempt for the frozen batch.
        Every failure path leaves the old model serving; success
        publishes and re-baselines."""
        with _span(
            "trainer.refit",
            op_type=type(self).__name__,
            batch_start=attempt.start,
            batch_stop=attempt.stop,
            retry=attempt.retries,
        ):
            try:
                candidate = self._absorb(attempt)
            except Exception as e:
                self._metrics.inc("absorb_failures")
                self._batch_failed(attempt, e, phase="absorb")
                return
            try:
                fault_point(TRAINER_CANARY)
                report = self._fleet.swap(
                    candidate,
                    canary_fraction=self.canary_fraction,
                    canary_batches=self.canary_batches,
                    canary_timeout_s=self.canary_timeout_s,
                    atol=self.canary_atol,
                    rtol=self.canary_rtol,
                    max_latency_ratio=self.max_latency_ratio,
                )
            except EngineStopped:
                # the fleet is going away; nothing was promoted and the
                # loop has nothing left to publish to
                logger.info("trainer: fleet stopped — trainer stopping")
                self._stop.set()
                return
            except CanaryMismatch as e:
                self._metrics.inc("rollbacks")
                self._instant(
                    "trainer.rollback",
                    batch_start=attempt.start, batch_stop=attempt.stop,
                    evidence=str(e)[:200],
                )
                self._batch_failed(attempt, e, phase="canary")
                return
            except Exception as e:
                if is_transient(e):
                    # an injected/flaky canary failure: same verdict as a
                    # mismatch — no promotion happened, old model serves
                    self._metrics.inc("rollbacks")
                    self._instant(
                        "trainer.rollback",
                        batch_start=attempt.start,
                        batch_stop=attempt.stop,
                        evidence=f"canary fault: {e}",
                    )
                    self._batch_failed(attempt, e, phase="canary")
                    return
                raise
            self._promoted(attempt, candidate, report)

    def _absorb(self, attempt: _Attempt):
        """The checkpointed fold: ``trainer.absorb`` fires per folded
        chunk INSIDE the checkpoint discipline, so a kill here resumes
        from the last completed block on the next attempt."""
        ds, labels = self._source.as_chunked(attempt.start, attempt.stop)

        def on_chunk(i, _chunk):
            fault_point(TRAINER_ABSORB)

        candidate = self._fitted.absorb(
            ds, labels,
            checkpoint=self.checkpoint_dir,
            checkpoint_key=attempt.key,
            on_chunk=on_chunk,
        )
        self._metrics.inc("absorbed_chunks", attempt.stop - attempt.start)
        self._metrics.inc("absorbed_rows", int(labels.shape[0]))
        # fit seam of the device-memory watermark: absorb holds the
        # candidate's full accumulator state — a footprint peak
        from ..obs import resource as _resource

        _resource.sample_memory()
        return candidate

    def _batch_failed(self, attempt: _Attempt, exc, *, phase: str) -> None:
        attempt.retries += 1
        if attempt.retries > self.max_batch_retries:
            self._park(
                attempt.start, attempt.stop,
                f"{phase} failed {attempt.retries}x: {exc}",
            )
            self._resolved = attempt.stop
            self._attempt = None
            self._discard_checkpoint(attempt)
        else:
            self._metrics.inc("batch_retries")
            logger.warning(
                "trainer: %s failed for batch [%d, %d) (%s) — retry "
                "%d/%d%s",
                phase, attempt.start, attempt.stop, exc,
                attempt.retries, self.max_batch_retries,
                " (will resume from checkpoint)"
                if phase == "absorb" and self.checkpoint_dir
                else "",
            )

    def _park(self, start: int, stop: int, why: str) -> None:
        with self._lock:
            self._parked.append((start, stop))
        self._metrics.inc("parked_batches")
        logger.warning(
            "trainer: PARKING chunk batch [%d, %d) — %s. The old model "
            "keeps serving; the chunks stay in the log for forensics.",
            start, stop, why,
        )
        self._instant("trainer.park", batch_start=start, batch_stop=stop)
        # a parked batch is quarantined data: leave the post-mortem
        # artifact holding what the loop did on the way here
        from ..obs import flight as _flight

        _flight.dump("trainer_park")

    def _discard_checkpoint(self, attempt: _Attempt) -> None:
        """A parked batch's half-folded checkpoint must not survive: it
        would be garbage to any future key collision."""
        if self.checkpoint_dir is None:
            return
        from ..faults import FitCheckpoint

        FitCheckpoint(self.checkpoint_dir, attempt.key).complete()

    def _promoted(self, attempt: _Attempt, candidate, report) -> None:
        self._fitted = candidate
        self._resolved = attempt.stop
        self._attempt = None
        self._last_promote = time.monotonic()
        self._metrics.inc("refits")
        self._monitor.rebaseline(self._state_of(candidate).moments())
        canary = report.get("canary") or {}
        logger.info(
            "trainer: PROMOTED refresh v%s (batch [%d, %d), %d mirrored "
            "canary batch(es))",
            report.get("version"), attempt.start, attempt.stop,
            canary.get("batches_compared", 0),
        )
        self._instant(
            "trainer.promote",
            version=report.get("version"),
            batch_start=attempt.start, batch_stop=attempt.stop,
        )

    def _instant(self, name: str, **attrs) -> None:
        # every trainer verdict lands in the always-on flight ring too:
        # a promote/rollback/park/restart must be visible in a post-
        # mortem dump even when tracing was never configured
        from ..obs import flight as _flight

        _flight.record_instant(name, **attrs)
        tracer = _trace_current()
        if tracer is not None:
            tracer.instant(name, op_type=type(self).__name__, **attrs)
