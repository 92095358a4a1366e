"""The cluster wire protocol: length-framed messages over a local
socket, with deadlines and typed errors that survive the process
boundary.

Deliberately minimal — the router and its workers share one machine (a
host driving one accelerator slice), so the protocol optimizes for
correctness of the THREE things that must not be lost crossing a
process boundary:

* **Framing.** Every message is a ``>I`` length prefix + payload. The
  payload self-describes its encoding by first byte: hot ``req``/``res``
  frames ride the binary codec (:mod:`.codec` — fixed header + ndarray
  descriptors + raw bytes, :data:`~keystone_tpu.cluster.codec.MAGIC`
  leading), while CONTROL frames (hello/ready/ping/stats/stop/errors)
  stay pickle (protocol >= 2 payloads always lead with ``0x80``, so the
  receiver dispatches per frame and old peers interop).
  ``send_payload``/``send_msg`` hold the caller's per-connection lock
  (sockets interleave concurrent sends otherwise); ``recv_payload``/
  ``recv_msg`` read exactly one frame or raise :class:`ConnectionClosed`
  on EOF — a half-read frame (peer died mid-send) is indistinguishable
  from death and is treated as it. A malformed BINARY frame degrades
  typed too (:class:`~keystone_tpu.cluster.codec.CodecError`): hot-path
  bytes are never handed to ``pickle.loads`` on a parse failure.
* **Deadlines.** ``time.monotonic()`` is process-local, so absolute
  deadlines are meaningless on the wire. A request's deadline travels
  as its REMAINING budget (seconds), stamped at send time and
  re-anchored to the receiver's clock on arrival — the satellite
  contract: crossing the boundary never extends a deadline (transit
  time comes out of the budget, as it should: it is real latency).
* **Typed errors.** The serving layer's whole error discipline is that
  callers branch on types (:class:`~keystone_tpu.serving.errors.Shed`
  vs :class:`DeadlineExceeded` vs :class:`QueueFull`). Worker-side
  errors are encoded by REGISTERED name + message and re-raised as the
  same type router-side; an unregistered type degrades to
  :class:`WorkerError` carrying the original class name — never a
  pickle of an arbitrary exception object (which may not unpickle, or
  may execute reduction code we don't control).

Message payloads are plain dicts with a ``"type"`` key; both codecs
round-trip the same dicts, so ``KEYSTONE_WIRE_CODEC=pickle`` is a
frame-for-frame kill switch, not a different protocol.

**Trace propagation.** A sampled request's ``req`` frame additionally
carries ``"trace"`` — the :class:`~keystone_tpu.obs.context.TraceContext`
wire form (trace id, emitting hop, a ``time.time()`` send stamp) — and
every ``res`` frame carries ``"t_unix"``.

**QoS identity.** A ``req`` frame also carries ``"priority"`` and
``"tenant"`` (see :mod:`keystone_tpu.autoscale.qos`): the worker's
in-process fleet re-applies the same shedding class and weighted-fair
share the router admitted under, so crossing the process boundary never
launders a request into a better class. :func:`qos_to_wire` /
:func:`qos_from_wire` are the two ends; absent keys degrade to the
defaults (normal priority, the default tenant) so old frames decode. Monotonic clocks are
process-local, so cross-process latency attribution rides the HOST-shared
unix clock: the receiver prices each direction's transport as
``time.time() - stamp`` and records it on its hop span, which is how the
stitched trace (``obs/export.py``) shows per-hop serialize/transport/
queue time instead of one opaque round-trip.
"""

from __future__ import annotations

import logging
import pickle
import socket
import struct
import time
from typing import Any, Optional

_LEN = struct.Struct(">I")

#: one frame must fit comfortably in memory; a corrupt length prefix
#: (desynced stream) must not trigger a multi-GB allocation
MAX_FRAME_BYTES = 1 << 30


class ConnectionClosed(ConnectionError):
    """The peer's socket reached EOF (or died mid-frame). A
    ``ConnectionError`` so :func:`keystone_tpu.faults.is_transient`
    classifies it transient — a dead worker's requests are retried on
    peers, exactly like a dead replica thread's."""


class WorkerError(RuntimeError):
    """A worker-side failure whose type is not part of the serving
    error vocabulary. Carries the original class name."""

    def __init__(self, kind: str, message: str):
        super().__init__(f"{kind}: {message}")
        self.kind = kind


def _registry():
    from ..serving.errors import (
        CanaryMismatch,
        DeadlineExceeded,
        EngineClosed,
        EngineStopped,
        InvalidRequest,
        QueueFull,
        ServingError,
        Shed,
    )
    from ..check import ContractMismatchError, PipelineCheckError
    from ..parallel.placement import PlacementError
    from ..workflow.pipeline import NotTraceableError

    types = (
        Shed,
        DeadlineExceeded,
        QueueFull,
        InvalidRequest,
        EngineStopped,
        EngineClosed,
        CanaryMismatch,
        ServingError,
        NotTraceableError,
        ContractMismatchError,
        PipelineCheckError,
        PlacementError,
        WorkerError,
    )
    return {t.__name__: t for t in types}


def _resolve_send_timeout() -> float:
    """The steady-state send timeout: ``KEYSTONE_WIRE_SEND_TIMEOUT``
    seconds (shared env accessor, warned once when unparsable), default
    15s, floored at 0.1s — a zero timeout would turn every full kernel
    buffer into an instant false death."""
    from ..utils import env_float

    return env_float("KEYSTONE_WIRE_SEND_TIMEOUT", 15.0, minimum=0.1)


#: steady-state socket timeout both sides run with: a SEND that cannot
#: make progress for this long means the peer stopped reading (wedged /
#: SIGSTOPped / dead) and is treated as down — a blocking sendall with
#: no timeout would otherwise hold the per-connection send lock forever
#: once the kernel buffer fills, unbounding the health loop and the
#: documented bounded shutdown. RECEIVES simply keep waiting across
#: timeouts (an idle connection is legitimate); only EOF/errors end them.
#: Configurable via ``KEYSTONE_WIRE_SEND_TIMEOUT`` (read once at import,
#: like every wire constant — both endpoint processes read their own
#: environment, which the router's spawn path propagates).
SEND_TIMEOUT_S = _resolve_send_timeout()


def send_payload(sock: socket.socket, payload: bytes) -> None:
    """Write one length-framed, already-encoded payload. Callers
    serialize access per socket (the router's per-worker send lock / the
    worker's reply lock). A ``socket.timeout`` from a full, unread
    buffer surfaces as :class:`ConnectionClosed` — the peer has
    effectively left, and a partially-sent frame has desynced the
    stream anyway."""
    try:
        sock.sendall(_LEN.pack(len(payload)) + payload)
    except socket.timeout as e:
        raise ConnectionClosed(
            f"peer stopped reading (send stalled {SEND_TIMEOUT_S:.0f}s)"
        ) from e


def send_msg(sock: socket.socket, msg: Any) -> None:
    """Write one framed CONTROL message (pickle). Hot-path senders
    encode explicitly (:func:`encode_msg`) and use :func:`send_payload`
    so encode time is attributable per frame."""
    send_payload(
        sock, pickle.dumps(msg, protocol=pickle.HIGHEST_PROTOCOL)
    )


def encode_msg(
    msg: Any,
    codec: str = "pickle",
    shm=None,
    min_shm_bytes: int = 1 << 16,
    metrics=None,
) -> bytes:
    """One message as frame payload bytes. ``codec="binary"`` attempts
    the hot codec for member-list ``req``/``res`` dicts (with ``shm`` as
    this direction's TX ring) and falls back to pickle whenever the
    frame is not binary-describable — the receiver dispatches on the
    first payload byte, so the fallback needs no signalling."""
    if codec == "binary":
        from . import codec as codec_mod

        try:
            payload = codec_mod.encode(
                msg, shm=shm, min_shm_bytes=min_shm_bytes, metrics=metrics
            )
        except Exception:
            logging.getLogger(__name__).debug(
                "binary encode failed; falling back to pickle",
                exc_info=True,
            )
            payload = None
        if payload is not None:
            return payload
    return pickle.dumps(msg, protocol=pickle.HIGHEST_PROTOCOL)


def decode_payload(payload: bytes, shm=None, copy: bool = True) -> Any:
    """One frame payload back into its message, dispatching per frame on
    the leading byte: the binary magic routes to :mod:`.codec` (which
    raises its typed :class:`~keystone_tpu.cluster.codec.CodecError` on
    any malformed frame — binary bytes are NEVER unpickled), anything
    else is a pickle control frame."""
    if payload[:1] and payload[0] != 0x80:
        from . import codec as codec_mod

        if payload[0] == codec_mod.MAGIC:
            return codec_mod.decode(payload, shm=shm, copy=copy)
    return pickle.loads(payload)


def recv_payload(
    sock: socket.socket, deadline: Optional[float] = None
) -> bytes:
    """Read exactly one frame's payload bytes; :class:`ConnectionClosed`
    on EOF or a torn frame. Socket timeouts while WAITING for a frame
    are not errors (idle peer) — the wait continues, unless ``deadline``
    (a ``time.monotonic()`` stamp; the handshake path) passes first."""
    header = _recv_exact(sock, _LEN.size, deadline)
    (n,) = _LEN.unpack(header)
    if n > MAX_FRAME_BYTES:
        raise ConnectionClosed(
            f"frame length {n} exceeds {MAX_FRAME_BYTES} — desynced stream"
        )
    return _recv_exact(sock, n, deadline)


def recv_msg(
    sock: socket.socket,
    deadline: Optional[float] = None,
    shm=None,
    copy: bool = True,
) -> Any:
    """Read + decode exactly one framed message (see
    :func:`recv_payload` / :func:`decode_payload`)."""
    return decode_payload(
        recv_payload(sock, deadline), shm=shm, copy=copy
    )


def _recv_exact(
    sock: socket.socket, n: int, deadline: Optional[float] = None
) -> bytes:
    buf = bytearray()
    while len(buf) < n:
        try:
            part = sock.recv(n - len(buf))
        except socket.timeout:
            # idle is fine; only EOF/errors/an explicit deadline end it
            if deadline is not None and time.monotonic() >= deadline:
                raise ConnectionClosed(
                    "peer sent nothing before the deadline"
                ) from None
            continue
        except OSError as e:
            raise ConnectionClosed(f"socket error mid-frame: {e}") from e
        if not part:
            raise ConnectionClosed(
                "peer closed the connection"
                + (" mid-frame" if buf else "")
            )
        buf.extend(part)
    return bytes(buf)


# -- deadlines across the boundary -------------------------------------------


def deadline_to_wire(deadline: Optional[float]) -> Optional[float]:
    """Absolute ``time.monotonic()`` deadline → remaining-seconds budget
    (clamped at 0: an already-expired deadline stays expired, it does
    not wrap into a huge budget)."""
    if deadline is None:
        return None
    return max(0.0, deadline - time.monotonic())


def deadline_from_wire(remaining: Optional[float]) -> Optional[float]:
    """Remaining budget → absolute deadline on THIS process's clock."""
    if remaining is None:
        return None
    return time.monotonic() + float(remaining)


# -- QoS identity across the boundary ----------------------------------------


def qos_to_wire(priority: Optional[str], tenant: Optional[str]) -> dict:
    """The ``req``-frame keys carrying a request's QoS identity; only
    non-default values are shipped (most traffic is default-class, and
    the frame stays minimal)."""
    out = {}
    if priority and priority != "normal":
        out["priority"] = str(priority)
    if tenant and tenant != "default":
        out["tenant"] = str(tenant)
    return out


def qos_from_wire(msg: dict) -> "tuple[str, str]":
    """``(priority, tenant)`` off a ``req`` frame, defaulting absent
    keys — frames from a pre-QoS peer decode as normal/default."""
    return (
        str(msg.get("priority") or "normal"),
        str(msg.get("tenant") or "default"),
    )


# -- cost accounting across the boundary -------------------------------------


def costs_to_wire(table: Optional[dict]) -> Optional[dict]:
    """A cost-table slice (``{tenant: {priority: {device_s, queue_s,
    payload_bytes, items}}}``) as the compact wire form ``{tenant:
    {priority: [device_s, queue_s, payload_bytes, items]}}`` — rows that
    charge nothing are dropped, and an empty table ships as None so the
    pong frame stays minimal on idle workers."""
    out: dict = {}
    for tenant, prios in (table or {}).items():
        for priority, row in (prios or {}).items():
            vals = [
                round(float(row.get("device_s") or 0.0), 6),
                round(float(row.get("queue_s") or 0.0), 6),
                int(row.get("payload_bytes") or 0),
                int(row.get("items") or 0),
            ]
            if any(vals):
                out.setdefault(str(tenant), {})[str(priority)] = vals
    return out or None


def costs_from_wire(payload: Optional[dict]) -> list:
    """Wire cost rows → ``[(tenant, priority, {field: value})]``;
    malformed rows (a pre-accounting peer, a truncated frame) decode as
    an empty list rather than poisoning the pong handler."""
    rows = []
    for tenant, prios in (payload or {}).items():
        if not isinstance(prios, dict):
            continue
        for priority, vals in prios.items():
            if not isinstance(vals, (list, tuple)) or len(vals) < 4:
                continue
            try:
                rows.append((
                    str(tenant),
                    str(priority),
                    {
                        "device_s": float(vals[0]),
                        "queue_s": float(vals[1]),
                        "payload_bytes": int(vals[2]),
                        "items": int(vals[3]),
                    },
                ))
            except (TypeError, ValueError):
                continue
    return rows


# -- typed errors across the boundary ----------------------------------------


def encode_error(exc: BaseException) -> dict:
    """One registered serving error (or anything else, degraded) as a
    wire-safe dict."""
    kind = type(exc).__name__
    if kind not in _registry():
        return {
            "kind": "WorkerError",
            "message": str(exc),
            "original": kind,
        }
    return {"kind": kind, "message": str(exc)}


def decode_error(enc: dict) -> BaseException:
    """Reconstruct the typed error; unknown kinds come back as
    :class:`WorkerError`."""
    kind = str(enc.get("kind", "WorkerError"))
    message = str(enc.get("message", ""))
    cls = _registry().get(kind)
    if cls is None or cls is WorkerError:
        return WorkerError(enc.get("original", kind), message)
    if cls.__name__ == "NotTraceableError":
        # its __init__ takes the label list, not a message
        return cls([message])
    try:
        return cls(message)
    except Exception:
        logging.getLogger(__name__).debug(
            "decoding %s with a message-only constructor failed; "
            "degrading to WorkerError", kind, exc_info=True,
        )
        return WorkerError(kind, message)
