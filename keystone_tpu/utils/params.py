"""Host-side storage for transformer parameters.

Fitted models and random projections are *parameters of traced programs*:
when a node's ``trace_batch`` closes over them, jit lowering embeds their
values into the XLA module. If they live on device, that embedding does a
blocking device→host fetch per constant in the middle of lowering, and it
defeats the persistent compilation cache's warm path. Storing parameters
as numpy makes lowering pure host work; XLA ships the literals device-ward
once per compiled program.

Parameters are READ-ONLY host arrays (:func:`as_param`). A program that
embedded an array's values, a segment fingerprint that keyed a compiled
program on them and the optimizer's structural key all describe the bytes
as they were; an array that can still change under them is a stale
executable waiting to be served. Read-only is also what lets
:func:`content_digest` — the one content hash of an array, shared by
``workflow/operators.structural_key`` and ``compile/fingerprint`` — hash
each parameter once and remember the answer for as long as the array
lives: what ``jax.device_get`` hands back is read-only already, and an
array that is still writeable is hashed at every call.
"""

from __future__ import annotations

import hashlib
import weakref
from typing import Any, Dict, Optional, Tuple

import numpy as np

from ..obs.tracer import count_digest, span


def as_param(x: Any, dtype: Optional[Any] = None) -> Optional[np.ndarray]:
    """Materialize ``x`` on the host as the canonical parameter form: a
    read-only array that nothing writeable lies beneath (a device array is
    read back under an ``xfer.d2h`` span, read-only as it comes). An array
    made here is frozen in place; a caller's own writeable array is copied
    first, so the node holds the values it was built with and the caller's
    array stays the caller's."""
    if x is None:
        return None
    arr = to_host(x)
    if dtype is not None and arr.dtype != np.dtype(dtype):
        arr = arr.astype(dtype)
    if arr.flags.writeable:
        if arr is x or arr.base is not None:
            arr = arr.copy()
        arr.flags.writeable = False
    return arr


#: id(array) -> (weak reference to it, sha256 of its C-order bytes), for
#: arrays that cannot change (:func:`_frozen`). Weak: an entry dies with
#: its array, keeps nothing alive, and a recycled ``id`` finds no entry
#: (the lookup also checks that the reference still points at the asker).
_DIGESTS: Dict[int, Tuple["weakref.ref[np.ndarray]", bytes]] = {}


def _frozen(arr: np.ndarray) -> bool:
    """Whether ``arr``'s bytes cannot change under a remembered digest: it
    is read-only, and so is every ndarray it is a view of."""
    base: Any = arr
    while isinstance(base, np.ndarray):
        if base.flags.writeable:
            return False
        base = base.base
    return True


def content_digest(arr: np.ndarray) -> bytes:
    """The sha256 digest (32 bytes) of ``arr``'s bytes in C order — what
    ``hashlib.sha256(np.ascontiguousarray(arr).tobytes())`` gives, without
    the copy: the array's own buffer is hashed, and only one that is not
    C-contiguous is made so first. Shape and dtype are the caller's to
    add. Object arrays have no content in their bytes (they hold
    pointers) and raise ``TypeError``.

    The digest of a frozen array (:func:`_frozen`) is remembered with the
    array object and answered from memory the next time; a writeable one
    is hashed at every call. The process counters ``digest_bytes`` /
    ``digest_hits`` (``obs/tracer.py``; every span carries their deltas)
    count the bytes hashed and the answers from memory."""
    if arr.dtype.hasobject:
        raise TypeError("an object array has no content digest")
    key = id(arr)
    frozen = _frozen(arr)
    if frozen:
        entry = _DIGESTS.get(key)
        if entry is not None and entry[0]() is arr:
            count_digest(hits=1)
            return entry[1]
    # ascontiguousarray hands a C-contiguous array back as it is
    flat = np.ascontiguousarray(arr).reshape(-1).view(np.uint8)
    digest = hashlib.sha256(flat).digest()
    count_digest(nbytes=arr.nbytes)
    if frozen:

        def forget(ref, key=key):
            entry = _DIGESTS.get(key)
            if entry is not None and entry[0] is ref:
                del _DIGESTS[key]

        _DIGESTS[key] = (weakref.ref(arr, forget), digest)
    return digest


def to_host(x: Any) -> np.ndarray:
    """``x`` as a host array. Reading a device array back waits for the
    work that produces it and then moves the bytes: an ``xfer.d2h`` span."""
    import jax

    if isinstance(x, jax.Array):
        with span("xfer.d2h", bytes=int(x.nbytes)):
            return jax.device_get(x)
    return np.asarray(x)


def to_device(x: Any) -> Any:
    """A host array (or a batched dataset of one) placed on the device
    under an ``xfer.h2d`` span; anything else is returned as it came."""
    from ..data.dataset import Dataset

    if isinstance(x, Dataset):
        if x.is_batched and isinstance(x.payload, np.ndarray):
            return Dataset(to_device(x.payload), batched=True)
        return x
    if isinstance(x, np.ndarray):
        import jax.numpy as jnp

        with span("xfer.h2d", bytes=int(x.nbytes)):
            return jnp.asarray(x)
    return x
