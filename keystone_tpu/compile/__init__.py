"""AOT pipeline compilation with a persistent executable cache.

The Julia→TPU paper (PAPERS.md #4) compiles whole programs to one
offline XLA artifact; a fitted KeystoneML pipeline is exactly that shape.
This package makes a :class:`~keystone_tpu.workflow.pipeline.FittedPipeline`
boot like one: the first process to compile a (pipeline, input-signature)
pair exports the traced program via ``jax.export`` into an on-disk cache,
and every later process — a restarted service, a new serving replica —
loads the executable instead of re-paying the trace. Warm boots are
milliseconds of deserialization instead of tens of seconds of tracing
and XLA compilation.

Layout of a cache directory (exported StableHLO and manifests only —
XLA's own compilation cache stays wherever ``JAX_COMPILATION_CACHE_DIR``
or the package default placed it, see ``keystone_tpu/__init__.py``)::

    <dir>/entries/<pipeline-digest>-<signature-digest>.aot   # exported StableHLO

Knobs: ``KEYSTONE_AOT_CACHE=<dir>`` (or ``--aot-cache`` on the CLI, or
``utils.obs.configure(aot_cache=...)``), ``KEYSTONE_AOT_CACHE_BYTES``
for the LRU size bound. See the README's "AOT executable cache" section
for the invalidation rules.
"""

from __future__ import annotations

import logging
import threading
from typing import Optional

from ..utils.timing import degraded
from .aot import AotDispatcher, signature_of
from .cache import CacheEntry, ExecutableCache
from .fingerprint import (
    FingerprintError,
    entry_key,
    environment_key,
    pipeline_fingerprint,
    segment_entry_key,
    segment_fingerprint,
)
from .manifest import (
    exported_signatures,
    record_export,
    record_segment,
    segment_digests,
    segment_signatures,
)
from .segment import (
    SegmentBinding,
    SegmentDispatcher,
    bind_segment,
    lower_segment,
    prewarm_segment_artifacts,
)

__all__ = [
    "AotDispatcher",
    "CacheEntry",
    "ExecutableCache",
    "FingerprintError",
    "SegmentBinding",
    "SegmentDispatcher",
    "bind_segment",
    "configure",
    "entry_key",
    "environment_key",
    "exported_signatures",
    "get_cache",
    "lower_segment",
    "pipeline_fingerprint",
    "prewarm_segment_artifacts",
    "record_export",
    "record_segment",
    "reset",
    "segment_digests",
    "segment_entry_key",
    "segment_fingerprint",
    "segment_signatures",
    "signature_of",
]

logger = logging.getLogger(__name__)

_lock = threading.Lock()
_cache: Optional[ExecutableCache] = None
_initialized = False  # False => next get_cache() reads KEYSTONE_AOT_CACHE


def configure(
    path: Optional[str] = None, max_bytes: Optional[int] = None
) -> Optional[ExecutableCache]:
    """Install the process-wide executable cache.

    ``path=None`` follows ``KEYSTONE_AOT_CACHE`` (unset or empty ⇒ AOT
    caching disabled). Installing a cache also zeroes jax's minimum
    compile time for persisting an XLA executable: serve programs compile
    in well under the package default, which would skip exactly the
    entries a warm boot needs. The XLA cache DIRECTORY is never touched
    here — it was placed once, at import.
    """
    global _cache, _initialized
    with _lock:
        _initialized = True
        if path is None:
            from ..utils import env_str

            path = env_str("KEYSTONE_AOT_CACHE")
        if not path:
            _cache = None
            return None
        try:
            _cache = ExecutableCache(path, max_bytes=max_bytes)
        except Exception:
            # an unwritable/invalid dir must degrade to AOT-off, not crash
            # a service that booted fine without the cache
            logger.warning(
                "aot: cache dir %r unusable — AOT caching disabled", path,
                exc_info=True,
            )
            degraded("aot_cache_dir")
            _cache = None
            return None
        import jax

        jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
        return _cache


def get_cache() -> Optional[ExecutableCache]:
    """The installed cache, or None (AOT caching off). Lazily honors
    ``KEYSTONE_AOT_CACHE`` so library callers that never touch
    ``configure`` still get caching when the environment asks for it."""
    if not _initialized:
        return configure()
    return _cache


def reset() -> None:
    """Forget the installed cache AND the env memo, and put the XLA
    persistence threshold :func:`configure` zeroed back at the package
    default (test hygiene)."""
    global _cache, _initialized
    with _lock:
        _cache = None
        _initialized = False
    import jax

    from .. import PERSIST_MIN_COMPILE_SECS

    jax.config.update(
        "jax_persistent_cache_min_compile_time_secs", PERSIST_MIN_COMPILE_SECS
    )
