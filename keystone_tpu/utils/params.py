"""Host-side storage for transformer parameters.

Fitted models and random projections are *parameters of traced programs*:
when a node's ``trace_batch`` closes over them, jit lowering embeds their
values into the XLA module. If they live on device, that embedding does a
blocking device→host fetch per constant in the middle of lowering, and it
defeats the persistent compilation cache's warm path. Storing parameters
as numpy makes lowering pure host work; XLA ships the literals device-ward
once per compiled program.
"""

from __future__ import annotations

from typing import Any, Optional

import numpy as np

from ..obs.tracer import span


def as_param(x: Any, dtype: Optional[Any] = None) -> Optional[np.ndarray]:
    """Materialize ``x`` on the host as the canonical parameter form (a
    device array is read back under an ``xfer.d2h`` span)."""
    if x is None:
        return None
    arr = to_host(x)
    if dtype is not None and arr.dtype != np.dtype(dtype):
        arr = arr.astype(dtype)
    return arr


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
