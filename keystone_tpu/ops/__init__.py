"""Hand-written bodies for hot ops: Pallas TPU kernels where hand-tiling
beats or stabilizes the XLA lowering, and :mod:`.bounded_cos`, a plain
``lax`` cosine for arguments of bounded size that XLA fuses behind a
product (``CosineRandomFeatures``; cells ``timit_cos4.apply`` / ``.fit``).
Current kernels:

* :mod:`.gaussian_kernel` — fused Gaussian kernel block (GEMM + norms +
  exp in one VMEM-resident tile), the KRR hot loop's block generator.
* :mod:`.conv_rectify_pool` — filter-bank convolution, symmetric rectifier
  and sum-pool as one kernel (patch rows × filter tile on the MXU; the
  normalisation, bias, both rectified halves and the pool's sums on the
  tile in VMEM), the RandomPatchCifar featurizer: the convolution's
  (n, 27, 27, K) output never reaches HBM. Cell: ``cifar_patch10k.fit``.
"""

from .gaussian_kernel import (
    gaussian_kernel_block_pallas,
    pallas_block_supported,
)

__all__ = ["gaussian_kernel_block_pallas", "pallas_block_supported"]
