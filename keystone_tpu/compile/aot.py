"""AOT compilation of fitted pipelines: load an executable, or trace once
and export it for every future process.

:class:`AotDispatcher` is the per-shape compile engine both
``FittedPipeline.compile`` and the serving engine's private jit ride when
an executable cache is configured. For each distinct input signature
``(shape, dtype)`` it resolves a callable exactly once:

* **hit** — the cache holds a ``jax.export`` artifact for (pipeline
  fingerprint, signature, environment): deserialize the StableHLO and
  wrap it in ``jax.jit``. ZERO traces of the pipeline function — the
  whole featurize→predict chain never runs under a jax tracer in this
  process. The wrapper's XLA compile is keyed by the serialized module,
  identical to the one the exporting process paid, so with jax's
  persistent compilation cache layered underneath (see
  ``compile.configure``) even that compile is a disk lookup.
* **miss** — trace ONCE via ``jax.export.export`` (the trace-count hook
  fires here, exactly as a legacy ``jax.jit`` first call would), persist
  the serialized artifact, and execute through the very same exported
  module. Cold and warm boots therefore run byte-identical StableHLO —
  the acceptance bit-equality invariant is structural, not incidental.
* **export unavailable** (an unexportable primitive, a serialization
  failure) — fall back to a plain per-signature ``jax.jit``; the failure
  is logged once and the process behaves exactly as before this layer
  existed.

Obs spans (when a tracer is installed): ``aot.load`` (bytes,
seconds_saved = the producer's measured trace+export cost), ``aot.miss``
and ``aot.export`` (bytes, trace_seconds) — a trace of a warm boot shows
loads and no exports; a cold boot shows the misses it paid.
"""

from __future__ import annotations

import logging
import threading
import time
from typing import Any, Callable, Dict, Optional, Tuple

from ..obs.tracer import current as _trace_current
from ..utils.timing import degraded
from .cache import ExecutableCache
from .fingerprint import entry_key, environment_key

logger = logging.getLogger(__name__)

#: input signature: (shape tuple, canonical dtype string)
Signature = Tuple[Tuple[int, ...], str]


def signature_of(x: Any) -> Signature:
    return (tuple(int(d) for d in x.shape), str(x.dtype))


class AotDispatcher:
    """Resolves one callable per input signature, cache-first.

    ``fn`` is the pure stacked-array pipeline function
    (``FittedPipeline.trace_fn()``). ``on_trace(sig)`` fires once per
    pipeline trace actually paid (the compile-accounting hook);
    ``on_load(sig)`` fires once per executable loaded instead of traced.
    Thread-safe: the serving engine's caller thread warms buckets while
    the worker thread may resolve a late signature.
    """

    def __init__(
        self,
        fn: Callable,
        fingerprint_digest: str,
        cache: ExecutableCache,
        *,
        on_trace: Optional[Callable[[Signature], None]] = None,
        on_load: Optional[Callable[[Signature], None]] = None,
        label: str = "",
        expected_exportable: Optional[bool] = None,
    ):
        self._fn = fn
        self._digest = fingerprint_digest
        self._cache = cache
        self._on_trace = on_trace
        self._on_load = on_load
        self._label = label
        #: the static checker's export verdict (keystone_tpu/check/),
        #: when the caller ran one — the dynamic path asserts against it
        self._expected_exportable = expected_exportable
        self._env = environment_key()
        self._by_sig: Dict[Signature, Callable] = {}
        self._lock = threading.Lock()
        self._loaded = 0
        self._traced = 0
        # the persistent compile ledger lives next to the cache entries:
        # every trace/export/load lands with duration + bytes (the
        # residency-budget evidence; appends never raise)
        from ..obs.ledger import CompileLedger

        self._ledger = CompileLedger.for_cache_root(cache.root)

    # -- introspection --------------------------------------------------

    @property
    def digest(self) -> str:
        """The pipeline fingerprint this dispatcher compiles for — the
        manifest key a booting fleet uses to pre-warm every previously
        exported signature."""
        return self._digest

    @property
    def loaded_count(self) -> int:
        """Signatures resolved from the cache (zero traces paid)."""
        return self._loaded

    @property
    def traced_count(self) -> int:
        """Signatures that paid a live pipeline trace."""
        return self._traced

    # -- the hot path ---------------------------------------------------

    def __call__(self, x):
        sig = signature_of(x)
        call = self._by_sig.get(sig)
        if call is None:
            call = self._resolve(sig)
        return call(x)

    # -- resolution -----------------------------------------------------

    def _resolve(self, sig: Signature) -> Callable:
        with self._lock:
            call = self._by_sig.get(sig)
            if call is not None:
                return call
            call = self._load(sig)
            if call is None:
                call = self._trace_and_export(sig)
            self._by_sig[sig] = call
            return call

    def _load(self, sig: Signature) -> Optional[Callable]:
        import jax
        from jax import export as jax_export

        key = entry_key(self._digest, sig[0], sig[1], self._env)
        t0 = time.perf_counter()
        entry = self._cache.load(key, expect_env=self._env)
        if entry is None:
            return None
        try:
            exported = jax_export.deserialize(bytearray(entry.payload))
            call = jax.jit(exported.call)
        except Exception:
            logger.warning(
                "aot: undeserializable entry for %s %s — falling back to live "
                "compile", self._label or key, sig, exc_info=True,
            )
            degraded("aot_load")
            self._cache._discard(entry.path, "undeserializable")
            return None
        self._loaded += 1
        load_seconds = time.perf_counter() - t0
        self._ledger.record(
            "load",
            key=key,
            label=self._label,
            shape=list(sig[0]),
            dtype=sig[1],
            nbytes=entry.nbytes,
            seconds=load_seconds,
            saved_s=entry.header.get("trace_seconds"),
        )
        tracer = _trace_current()
        if tracer is not None:
            tracer.instant(
                "aot.load",
                op_type="AotDispatcher",
                key=key,
                label=self._label,
                shape=list(sig[0]),
                dtype=sig[1],
                bytes=entry.nbytes,
                load_seconds=round(load_seconds, 4),
                seconds_saved=entry.header.get("trace_seconds"),
            )
        logger.info(
            "aot: loaded %s %s from cache (%d bytes, saved ~%ss of tracing)",
            self._label or key, sig, entry.nbytes,
            entry.header.get("trace_seconds", "?"),
        )
        if self._on_load is not None:
            self._on_load(sig)
        return call

    def _trace_and_export(self, sig: Signature) -> Callable:
        import jax
        import numpy as np
        from jax import export as jax_export

        tracer = _trace_current()
        key = entry_key(self._digest, sig[0], sig[1], self._env)
        if tracer is not None:
            tracer.instant(
                "aot.miss", op_type="AotDispatcher", key=key,
                label=self._label, shape=list(sig[0]), dtype=sig[1],
            )
        fired = []

        def traced(x):
            # runs only under a jax trace — exactly once per compile paid
            fired.append(sig)
            if self._on_trace is not None and len(fired) == 1:
                self._on_trace(sig)
            return self._fn(x)

        spec = jax.ShapeDtypeStruct(sig[0], np.dtype(sig[1]))
        t0 = time.perf_counter()
        try:
            exported = jax_export.export(jax.jit(traced))(spec)
            call = jax.jit(exported.call)
        except Exception:
            logger.warning(
                "aot: export failed for %s %s — serving via plain jit "
                "(no cross-process caching for this signature)",
                self._label or key, sig, exc_info=True,
            )
            degraded("aot_export")
            if self._expected_exportable:
                # static-vs-dynamic disagreement: the checker's lattice
                # said this chain exports. A verdict bug — make it loud
                # so the classifier gets fixed, not papered over.
                logger.error(
                    "aot: STATIC CHECK DISAGREEMENT — the traceability "
                    "lattice classified %s as exportable but jax.export "
                    "refused it; report this pipeline's node set",
                    self._label or key,
                )
            self._traced += 1
            if fired:
                return jax.jit(self._fn)  # already counted by the export try
            return jax.jit(traced)
        trace_seconds = time.perf_counter() - t0
        self._traced += 1
        self._ledger.record(
            "trace",
            key=key,
            label=self._label,
            shape=list(sig[0]),
            dtype=sig[1],
            seconds=trace_seconds,
        )
        try:
            payload = bytes(exported.serialize())
            self._cache.store(
                key,
                payload,
                {
                    "env": self._env,
                    "pipeline": self._digest,
                    "shape": list(sig[0]),
                    "dtype": sig[1],
                    "label": self._label,
                    "trace_seconds": round(trace_seconds, 4),
                    "created_unix": time.time(),
                },
            )
            # index the export in the bucket-signature manifest so a
            # fresh replica can pre-warm every signature at deploy time
            from . import manifest as _manifest

            _manifest.record_export(self._cache, self._digest, sig[0], sig[1])
        except Exception:
            logger.warning(
                "aot: could not persist %s %s — executable still serves "
                "live", self._label or key, sig, exc_info=True,
            )
            degraded("aot_persist")
            payload = b""
        if payload:
            self._ledger.record(
                "export",
                key=key,
                label=self._label,
                shape=list(sig[0]),
                dtype=sig[1],
                nbytes=len(payload),
                seconds=trace_seconds,
            )
        if tracer is not None:
            tracer.instant(
                "aot.export",
                op_type="AotDispatcher",
                key=key,
                label=self._label,
                shape=list(sig[0]),
                dtype=sig[1],
                bytes=len(payload),
                trace_seconds=round(trace_seconds, 4),
            )
        return call
