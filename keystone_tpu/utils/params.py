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


def as_param(x: Any, dtype: Optional[Any] = None) -> Optional[np.ndarray]:
    """Materialize ``x`` on the host as the canonical parameter form."""
    if x is None:
        return None
    try:
        import jax

        if isinstance(x, jax.Array):
            x = jax.device_get(x)
    except ImportError:  # pragma: no cover
        pass
    arr = np.asarray(x)
    if dtype is not None and arr.dtype != np.dtype(dtype):
        arr = arr.astype(dtype)
    return arr
