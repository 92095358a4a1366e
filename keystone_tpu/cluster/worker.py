"""The cluster worker process: one device subset, one local ServingFleet,
one socket back to the router.

``worker_main`` is the spawn target (module-level, picklable args). A
worker's life:

1. **Connect + hello.** Dial the router's listener, present the spawn
   token and worker id (the router refuses strangers — a stray process
   dialing the port cannot join the fleet).
2. **Warm boot.** Configure the SHARED AOT cache directory, build the
   model from the spec (a ``"module:callable"`` factory re-run
   deterministically, or an explicit pickle), carve this worker's device
   subset off the mesh data axis
   (:func:`~keystone_tpu.parallel.placement.worker_device_indices`), and
   start a local :class:`~keystone_tpu.serving.ServingFleet` over it.
   ``start()`` pre-warms every bucket AND every manifest signature from
   the shared cache (``compile/manifest.py`` reads are multi-process
   safe), so a worker booting against a warm cache pays ZERO traces —
   the warm-boot contract the ``ready`` message reports (``compiles`` /
   ``aot_loads``) and the smoke/bench gates assert.
3. **Serve.** One request message → one ``fleet.submit`` with the
   deadline re-anchored from its wire budget; the response rides back on
   the future's completion (replica threads answer out of order — the
   router matches by request id). Typed serving errors cross the wire by
   name (:mod:`.wire`), so a worker-side ``Shed`` is a router-side
   ``Shed``.
4. **Die loudly or drain cleanly.** ``stop`` drains the local fleet
   (bounded — the fleet's own shutdown discipline) and answers ``bye``;
   a dead router (EOF on the socket) shuts the fleet down and exits
   nonzero. SIGTERM gets the same bounded drain, so an operator's kill
   never strands in-flight requests silently.
"""

from __future__ import annotations

import logging
import os
import signal
import socket
import sys
import threading
import time
from typing import Any, Optional

logger = logging.getLogger(__name__)


def resolve_model(model_spec: Any):
    """Build the FittedPipeline a worker serves.

    ``("factory", "module:callable", kwargs)`` imports and calls —
    the deterministic-rebuild path (same fit ⇒ same AOT fingerprint ⇒
    warm boot from the shared cache). ``("pickle", bytes)`` unpickles an
    explicitly shipped model."""
    kind = model_spec[0]
    if kind == "factory":
        import importlib

        path, kwargs = model_spec[1], model_spec[2] or {}
        mod_name, _, fn_name = path.partition(":")
        if not fn_name:
            raise ValueError(
                f"model factory {path!r} must be 'module:callable'"
            )
        fn = getattr(importlib.import_module(mod_name), fn_name)
        return fn(**kwargs)
    if kind == "pickle":
        import pickle

        return pickle.loads(model_spec[1])  # lint: allow-pickle -- explicit model artifact from the router's boot spec
    raise ValueError(f"unknown model spec kind {kind!r}")


def _worker_devices(worker_id: int, n_workers: int, replicas: Optional[int]):
    """This worker's replica→device list: its contiguous slice of the
    mesh data axis, round-robined up to ``replicas`` when more workers
    than devices (or an explicit replica count) ask for co-residents."""
    from ..parallel.placement import data_axis_devices, worker_device_indices

    devs = data_axis_devices()
    idxs = worker_device_indices(worker_id, n_workers)
    n = replicas if replicas is not None else len(idxs)
    return [devs[idxs[i % len(idxs)]] for i in range(max(1, n))]


def worker_main(host: str, port: int, token: str, worker_id: int,
                spec: dict) -> int:
    """Spawn-target entry point; returns the process exit code."""
    logging.basicConfig(
        level=getattr(
            logging, str(spec.get("log_level", "warning")).upper(),
            logging.WARNING,
        ),
        format=(
            f"[worker-{worker_id}] %(levelname)s %(name)s: %(message)s"
        ),
    )
    if spec.get("platform"):
        # the platform the router was asked for, explicit in THIS
        # process too: if it cannot be had, jax raises at backend
        # start-up instead of quietly serving from another
        import jax

        jax.config.update("jax_platforms", spec["platform"])
    if spec.get("virtual_devices"):
        from ..parallel.virtual import provision_virtual_devices

        provision_virtual_devices(int(spec["virtual_devices"]))
    if spec.get("aot_cache"):
        from .. import compile as compile_mod

        compile_mod.configure(spec["aot_cache"])

    from ..obs import flight as _flight
    from ..obs import tracer as _obs_tracer
    from ..obs.context import TraceContext
    from ..obs.export import wire_spans
    from ..obs.span import Span
    from ..serving import ServingFleet
    from ..utils import env_int
    from .wire import (
        ConnectionClosed,
        costs_to_wire,
        deadline_from_wire,
        decode_payload,
        encode_error,
        encode_msg,
        qos_from_wire,
        recv_payload,
        send_payload,
    )

    from .wire import SEND_TIMEOUT_S

    # the flight recorder is always on; SIGQUIT gives operators an
    # on-demand post-mortem dump of a live worker
    _flight.install_sigquit_dump()
    # the router propagates its tracing decision: a traced router means
    # traced workers, whose spans ship back on stats replies and stitch
    # into ONE cross-process trace (obs/export.py)
    tracer = _obs_tracer.start() if spec.get("trace") else None
    process_name = f"keystone:worker-{worker_id}/{os.getpid()}"
    span_cursor = [0]  # spans_since bookmark: each span ships once
    # (tenant, priority) -> last-shipped cumulative cost row: pongs ship
    # deltas so the router can fold them additively without re-counting
    cost_cursor: dict = {}

    def _cost_deltas(cursor: dict, table: dict) -> dict:
        out: dict = {}
        for tenant, prios in table.items():
            for priority, row in prios.items():
                prev = cursor.get((tenant, priority)) or {}
                delta = {
                    k: row.get(k, 0) - prev.get(k, 0)
                    for k in ("device_s", "queue_s", "payload_bytes", "items")
                }
                cursor[(tenant, priority)] = dict(row)
                if any(v > 1e-9 if isinstance(v, float) else v
                       for v in delta.values()):
                    out.setdefault(tenant, {})[priority] = delta
        return out

    # the hot-wire negotiation: the router's spec names the codec it
    # will SEND (and expects back) and, when same-host zero-copy is on,
    # the shared-memory ring pair this worker should attach. An attach
    # failure is a negotiation answer, not an error: the ready report
    # says shm=false and everything stays inline.
    reply_codec = (
        "binary"
        if (spec.get("wire") or {}).get("codec") == "binary"
        else "pickle"
    )
    shm_min_bytes = env_int("KEYSTONE_SHM_MIN_BYTES", 1 << 16, minimum=1)
    shm_rx = shm_tx = None
    shm_cfg = spec.get("shm")
    if shm_cfg and reply_codec == "binary":
        from .shm import ShmRing

        try:
            shm_rx = ShmRing(
                shm_cfg["c2w"], shm_cfg["slots"], shm_cfg["slot_bytes"]
            )
            shm_tx = ShmRing(
                shm_cfg["w2c"], shm_cfg["slots"], shm_cfg["slot_bytes"]
            )
        except Exception:
            logger.warning(
                "worker %d: shared-memory attach failed — wire payloads "
                "stay inline", worker_id, exc_info=True,
            )
            if shm_rx is not None:
                shm_rx.close()
            shm_rx = shm_tx = None

    sock = socket.create_connection((host, port), timeout=30.0)
    # bounded sends, timeout-tolerant receives (see wire.SEND_TIMEOUT_S)
    sock.settimeout(SEND_TIMEOUT_S)
    send_lock = threading.Lock()
    # control replies go out before the fleet (and its registry) exists;
    # the wire counters attach once it does
    metrics_ref: list = [None]

    def reply(msg: dict) -> None:
        # control frames: always pickle, any dict shape
        payload = encode_msg(msg)
        with send_lock:
            send_payload(sock, payload)
        m = metrics_ref[0]
        if m is not None:
            kind = msg.get("type")
            m.inc(f"wire.frames.{kind}")
            m.inc(f"wire.bytes_sent.{kind}", len(payload))

    reply({
        "type": "hello", "token": token, "worker": worker_id,
        "pid": os.getpid(),
        # codec capability advertisement: the router sends binary hot
        # frames only to peers that claim at least this version
        "codec": 1,
    })

    try:
        # placement first: it is the first touch of the backend (a chip
        # that cannot be had fails here, in seconds) and its refusal —
        # more worker processes than accelerator chips — is typed
        devices = _worker_devices(
            worker_id, int(spec.get("n_workers", 1)), spec.get("replicas")
        )
        fitted = resolve_model(spec["model"])
        # upfront contract validation: the router's spec'd datum
        # shape/dtype against the model's STATIC check report — a
        # mis-deployed model (wrong artifact for this topology) fails the
        # boot with a typed, node-attributed error instead of serving
        # garbage or tracing a doomed bucket set (the fleet constructor
        # re-validates coupling)
        fitted.check(span=False).require_contract(
            spec.get("datum_shape"), spec.get("dtype"), verb="boot"
        )
        fleet = ServingFleet(
            fitted,
            devices=devices,
            buckets=tuple(spec.get("buckets") or (1, 8, 32, 64)),
            datum_shape=spec.get("datum_shape"),
            dtype=spec.get("dtype"),
            max_queue=int(spec.get("max_queue", 1024)),
            max_wait_ms=float(spec.get("max_wait_ms", 2.0)),
            tenant_weights=spec.get("tenant_weights"),
        )
        fleet.start(warmup=spec.get("warmup"))
    except Exception as e:
        # the router's start() raises this, typed, in place of a bare
        # "failed to boot — check worker stderr"
        reply({
            "type": "boot_error", "worker": worker_id,
            "error": encode_error(e),
        })
        raise
    metrics_ref[0] = fleet.metrics
    snap = fleet.metrics.snapshot()
    reply({
        "type": "ready",
        "worker": worker_id,
        "compiles": snap["counters"].get("compiles", 0),
        "aot_loads": snap["counters"].get("aot_loads", 0),
        "capacity": fleet.n_replicas * fleet.policy.max_size,
        "replicas": fleet.n_replicas,
        "devices": [str(d) for d in devices],
        "platform": devices[0].platform,
        # the shm negotiation's closing answer: true means both rings
        # attached and zero-copy payloads are live on this connection
        "shm": shm_rx is not None,
    })
    logger.info(
        "worker %d ready: %d replica(s) on %s (compiles=%d aot_loads=%d)",
        worker_id, fleet.n_replicas, [str(d) for d in devices],
        snap["counters"].get("compiles", 0),
        snap["counters"].get("aot_loads", 0),
    )

    stopping = threading.Event()

    def _drain_and_exit(signum, frame):
        # bounded by the fleet's own drain/join timeouts — and run on a
        # SPAWNED thread, never in the handler frame: the signal may
        # interrupt the main thread INSIDE fleet.submit holding the
        # scheduler's non-reentrant lock, and shutdown() takes that same
        # lock (the router's handler avoids the identical deadlock)
        if stopping.is_set():
            return
        stopping.set()

        def _stop():
            try:
                fleet.shutdown(drain=True)
            finally:
                os._exit(0)

        threading.Thread(
            target=_stop, name="ks-worker-sigterm", daemon=False
        ).start()

    try:
        signal.signal(signal.SIGTERM, _drain_and_exit)
    except ValueError:
        pass  # non-main thread (embedded use): router stop still works

    class _ReplyGroup:
        """One coalesced request frame's answer aggregator: members
        settle out of order on replica threads, ONE reply frame goes
        back when the last lands, and only then are the request frame's
        shm slots freed — reply receipt is the ring's reclamation
        signal, so a slot is never reused while its datum may still be
        read."""

        def __init__(self, n: int, legacy: bool, req_shm_slots):
            self._lock = threading.Lock()
            self._remaining = n
            self.members: list = [None] * n
            self.legacy = legacy
            self.req_shm_slots = tuple(req_shm_slots or ())
            #: first traced member's id — the reply-side wire.encode
            #: span hangs off it
            self.traced_id: Optional[str] = None

        def settle(self, pos: int, member: dict) -> None:
            with self._lock:
                self.members[pos] = member
                self._remaining -= 1
                done = self._remaining == 0
            if done:
                _send_res(self)

    def _send_res(group: "_ReplyGroup") -> None:
        # t_unix lets the router price the REPLY hop's transport (unix
        # clocks are host-shared; monotonic ones are not)
        t_unix = time.time()
        t0 = t1 = 0.0
        try:
            if group.legacy:
                # a legacy single-request frame gets the legacy reply
                # shape — old routers never see member lists
                msg = dict(group.members[0])
                msg["type"] = "res"
                msg["t_unix"] = t_unix
                payload = encode_msg(msg)
            else:
                t0 = time.perf_counter()
                payload = encode_msg(
                    {
                        "type": "res",
                        "members": group.members,
                        "t_unix": t_unix,
                    },
                    codec=reply_codec,
                    shm=shm_tx,
                    min_shm_bytes=shm_min_bytes,
                    metrics=fleet.metrics,
                )
                t1 = time.perf_counter()
            with send_lock:
                send_payload(sock, payload)
            fleet.metrics.inc("wire.frames.res")
            fleet.metrics.inc("wire.bytes_sent.res", len(payload))
            if (
                group.traced_id is not None and tracer is not None
                and not group.legacy
            ):
                tracer.record_complete(Span(
                    name="wire.encode", start=t0, end=t1,
                    op_type="ClusterWorker",
                    attrs={
                        "trace_id": group.traced_id,
                        "codec": reply_codec,
                        "bytes": len(payload),
                        "members": len(group.members),
                    },
                ))
        except Exception:
            # router gone; its death handling requeues
            logger.debug(
                "reply frame undeliverable (router gone?)", exc_info=True
            )
        finally:
            if shm_rx is not None:
                for s in group.req_shm_slots:
                    shm_rx.free(s)

    def _member_done(pos: int, req_id: int, fut, group, ctx=None,
                     t_recv_pc=None, transport_s=None) -> None:
        try:
            member = {"id": req_id, "ok": True, "value": fut.result()}
        except BaseException as e:  # noqa: BLE001 — typed over the wire
            member = {"id": req_id, "ok": False, "error": encode_error(e)}
        group.settle(pos, member)
        if ctx is not None and tracer is not None:
            # the worker-residency hop: wire arrival -> reply settled,
            # stitched under the request's cross-process identity with
            # the inbound transport it measured off the wire stamp
            tracer.record_complete(Span(
                name="cluster.handle",
                start=t_recv_pc,
                end=time.perf_counter(),
                op_type="ClusterWorker",
                attrs={
                    "trace_id": ctx.trace_id,
                    # the sender's hop: which edge this residency span
                    # hangs under in the stitched tree
                    "parent_hop": ctx.hop,
                    "worker": worker_id,
                    "transport_s": round(transport_s or 0.0, 6),
                },
            ))

    rc = 0
    try:
        while True:
            payload = recv_payload(sock)
            t_dec0 = time.perf_counter()
            # copy=False: member data may view shm ring slots directly —
            # the fleet consumes each datum before its reply frees the
            # slot, so the zero-copy view is safe for exactly that long
            msg = decode_payload(payload, shm=shm_rx, copy=False)
            t_recv_pc = time.perf_counter()
            kind = msg.get("type")
            if kind == "req":
                members = msg.get("members")
                legacy = members is None
                if legacy:
                    members = [msg]  # pre-coalescing router frame
                group = _ReplyGroup(
                    len(members), legacy, msg.get("_shm_slots")
                )
                for pos, m in enumerate(members):
                    req_id = m["id"]
                    deadline = deadline_from_wire(m.get("deadline_rem"))
                    ctx = TraceContext.from_wire(m.get("trace"))
                    transport_s = (
                        ctx.transport_seconds() if ctx is not None
                        else None
                    )
                    if ctx is not None and group.traced_id is None:
                        group.traced_id = ctx.trace_id
                    try:
                        timeout = (
                            None if deadline is None
                            else max(0.0, deadline - time.monotonic())
                        )
                        priority, tenant = qos_from_wire(m)
                        # every member keeps its own QoS/deadline/trace
                        # identity inside the fleet — coalescing shares
                        # the FRAME, never the scheduling class
                        fut = fleet.submit(
                            m["datum"], timeout=timeout, trace=ctx,
                            priority=priority, tenant=tenant,
                        )
                    except BaseException as e:  # Shed/QueueFull typed back
                        group.settle(pos, {
                            "id": req_id, "ok": False,
                            "error": encode_error(e),
                        })
                        continue
                    fut.add_done_callback(
                        lambda f, p=pos, rid=req_id, g=group, c=ctx,
                        t=t_recv_pc, tr=transport_s: _member_done(
                            p, rid, f, g, ctx=c, t_recv_pc=t,
                            transport_s=tr,
                        )
                    )
                if group.traced_id is not None and tracer is not None:
                    tracer.record_complete(Span(
                        name="wire.decode", start=t_dec0, end=t_recv_pc,
                        op_type="ClusterWorker",
                        attrs={
                            "trace_id": group.traced_id,
                            "codec": (
                                "pickle" if payload[:1] == b"\x80"
                                else "binary"
                            ),
                            "bytes": len(payload),
                            "members": len(members),
                        },
                    ))
            elif kind == "ping":
                # the router's health cadence doubles as the worker's
                # metrics-timeline sampler: one row per ping
                fleet.metrics.sample_timeline()
                pong = {
                    "type": "pong",
                    "t": msg.get("t"),
                    "service_estimate": fleet.scheduler.service_estimate,
                }
                # per-tenant cost DELTAS since the last pong ride the
                # health cadence, so the router's own timeline (and its
                # SloWatchdog's tenant-spend budget) tracks fleet-wide
                # spend continuously, not just on stats round-trips
                table = fleet.metrics.cost_table()
                deltas = _cost_deltas(cost_cursor, table)
                wired = costs_to_wire(deltas)
                if wired:
                    pong["costs"] = wired
                reply(pong)
            elif kind == "stats":
                # a stats round-trip always carries a fresh timeline row
                # (pings drive the steady cadence; an early status() call
                # must not render an empty worker timeline)
                fleet.metrics.sample_timeline()
                shipped = []
                spans_dropped = 0
                if tracer is not None:
                    fresh, span_cursor[0] = tracer.spans_since(
                        span_cursor[0]
                    )
                    # bounded shipping: a stats reply must stay a small
                    # frame even after a long untapped tracing window —
                    # overflow is DROPPED, but counted, never silent
                    spans_dropped = max(0, len(fresh) - 4096)
                    if spans_dropped:
                        _flight.record_instant(
                            "trace.spans_dropped", n=spans_dropped,
                            worker=worker_id,
                        )
                    shipped = wire_spans(
                        fresh[-4096:], tracer.epoch, tracer.epoch_unix,
                        process_name=process_name,
                    )
                    # the router now owns these spans — discarding them
                    # keeps an always-on traced worker's registry
                    # bounded by the stats cadence, not the uptime
                    tracer.discard_through(span_cursor[0])
                reply({
                    "type": "stats",
                    "worker": worker_id,
                    "seq": msg.get("seq"),
                    "snapshot": fleet.metrics.snapshot(sketches=True),
                    "qos": fleet.qos_snapshot(),
                    "spans": shipped,
                    "spans_dropped": spans_dropped,
                })
            elif kind == "stop":
                fleet.shutdown(drain=bool(msg.get("drain", True)))
                reply({"type": "bye", "worker": worker_id})
                break
            else:
                logger.warning("worker %d: unknown message %r", worker_id, kind)
    except ConnectionClosed:
        if not stopping.is_set():
            logger.warning(
                "worker %d: router connection lost — shutting down", worker_id
            )
            rc = 1
    finally:
        try:
            fleet.shutdown(drain=False)
        except Exception:
            logger.exception("worker %d: fleet shutdown failed", worker_id)
        try:
            sock.close()
        except OSError:
            pass
        # drop the shm mappings (the ROUTER owns unlink; a worker only
        # ever attaches)
        for ring in (shm_rx, shm_tx):
            if ring is not None:
                ring.close()
    return rc


def main(argv=None) -> int:  # pragma: no cover - exercised via spawn
    """Debug entry: ``python -m keystone_tpu.cluster.worker host port
    token worker_id`` with the spec pickled on stdin."""
    import pickle

    host, port, token, worker_id = argv or sys.argv[1:5]
    spec = pickle.load(sys.stdin.buffer)  # lint: allow-pickle -- boot spec from the parent router's stdin pipe
    return worker_main(host, int(port), token, int(worker_id), spec)


if __name__ == "__main__":  # pragma: no cover
    raise SystemExit(main())
