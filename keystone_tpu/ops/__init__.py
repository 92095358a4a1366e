"""Hand-written bodies for hot ops: Pallas TPU kernels where hand-tiling
beats or stabilizes the XLA lowering, and :mod:`.bounded_cos`, a plain
``lax`` cosine for arguments of bounded size that XLA fuses behind a
product (``CosineRandomFeatures``; cells ``timit_cos4.apply`` / ``.fit``).
Current kernels:

* :mod:`.conv_rectify_pool` — filter-bank convolution, symmetric rectifier
  and sum-pool as one kernel (patch rows × filter tile on the MXU; the
  normalisation, bias, both rectified halves and the pool's sums on the
  tile in VMEM), the RandomPatchCifar featurizer: the convolution's
  (n, 27, 27, K) output never reaches HBM. Cell: ``cifar_patch10k.fit``.
* :mod:`.cosine_scores` — cosine random features and the fitted linear
  map over them as one kernel (both products on the MXU, the bounded
  cosine and the mean on the tile in VMEM), TimitPipeline's fitted chain:
  the (n, 16,384) features never reach HBM. Cell: ``timit_cos4.apply``.
"""

from __future__ import annotations

from typing import Optional


def kernel_mode() -> Optional[str]:
    """How a kernel of this package would run in this process:
    ``"compiled"`` on one TPU chip, None anywhere else — the callers then
    run the XLA bodies, which XLA can partition over a mesh as a custom
    call cannot. (The CPU tests run a kernel as ``"interpret"``, patching
    its module's ``kernel_mode``.)"""
    import jax

    on_one_tpu = jax.default_backend() == "tpu" and jax.device_count() == 1
    return "compiled" if on_one_tpu else None
