"""Local Color Statistics descriptors, batched.

Parity: nodes/images/LCSExtractor.scala:25-130 — per-channel box-filter means
and standard deviations of subPatchSize² windows, sampled at a neighborhood
grid around each keypoint; values interleaved (mean, std) per neighbor per
channel. The per-pixel loops become two box sums and static gathers. The box
sums are shifted additions in float32 (zero-padded, the window placed as the
reference's 'same' convolution places it): a convolution at the backend's
default precision is one bf16 pass on a TPU, where neither a tap of 1/6 nor a
squared pixel is exact and ``E[x²] − E[x]²`` cancels what is left.

Output per image: (numLCSValues, numPoolsX·numPoolsY) with descriptor index
x_idx · numPoolsY + y_idx, matching the reference layout.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

from ...workflow.transformer import Transformer


def _box_sum_same(X, width: int):
    """Sums over every ``width`` × ``width`` window of (n, X, Y), zero
    padded to the input's size with ``(width − 1) // 2`` cells ahead of a
    pixel and the rest behind it (ImageUtils.conv2D's placement): the
    shifted maps added up, one axis after the other. Exact in float32 for
    8-bit pixels and their squares up to windows of 256 cells."""
    ahead = (width - 1) // 2
    behind = width - 1 - ahead
    xd, yd = X.shape[1], X.shape[2]
    P = jnp.pad(X, [(0, 0), (ahead, behind), (0, 0)])
    X = sum(P[:, k : k + xd] for k in range(width))
    P = jnp.pad(X, [(0, 0), (0, 0), (ahead, behind)])
    return sum(P[:, :, k : k + yd] for k in range(width))


class LCSExtractor(Transformer):
    #: dispatched as a compiled segment in row slices even where it is the
    #: segment's only member (``compile/segment.py:bind_segment``) — between
    #: the images and a ``Cacher`` the device can hold it stands alone, and
    #: node dispatch would run the body operation by operation over a whole
    #: data set: 5 GB a take at 2,048 images of 256 × 256
    binds_alone = True

    def __init__(self, stride: int, stride_start: int, sub_patch_size: int):
        self.stride = stride
        self.stride_start = stride_start
        self.sub_patch_size = sub_patch_size

    def num_descriptors(self, xd: int, yd: int) -> int:
        """Keypoints of an ``xd`` × ``yd`` image: every ``stride`` pixels
        inside the border."""
        b = self.stride_start
        return len(range(b, xd - b, self.stride)) * len(
            range(b, yd - b, self.stride)
        )

    def row_scratch_bytes(self, shape) -> int:
        """What an image holds besides its (numLCSValues, numDesc) output
        while that is made — segment dispatch prices a row by it
        (``compile/segment.py:_item_bytes``): the float32 image, and a
        channel's squares, two box sums, mean and deviation maps."""
        _, xd, yd, nc = shape
        return 6 * xd * yd * nc * 4

    def trace_batch(self, X):
        """(n, X, Y, C) → (n, numLCSValues, numDesc)."""
        with jax.named_scope("ks.featurize.lcs"):
            return self._descriptors(X)

    def _descriptors(self, X):
        X = jnp.asarray(X).astype(jnp.float32)
        n, xd, yd, nc = X.shape
        sp = self.sub_patch_size
        cells = float(sp * sp)

        kx = np.arange(self.stride_start, xd - self.stride_start, self.stride)
        ky = np.arange(self.stride_start, yd - self.stride_start, self.stride)
        npx, npy = len(kx), len(ky)

        # neighborhood offsets (LCSExtractor.scala:41-47)
        start = -2 * sp + sp // 2 - 1
        end = sp + sp // 2 - 1
        offsets = list(range(start, end + 1, sp))

        # box means/stds per channel, stacked (n, C, 2, X, Y)
        maps = []
        for c in range(nc):
            ch = X[..., c]
            m = _box_sum_same(ch, sp) / cells
            sq = _box_sum_same(ch * ch, sp) / cells
            maps.append(
                jnp.stack([m, jnp.sqrt(jnp.maximum(sq - m * m, 0.0))], axis=1)
            )
        maps = jnp.stack(maps, axis=1)

        # every (offset, keypoint) position along an axis at once: TWO
        # takes for the whole descriptor matrix, then one transpose into
        # lcsIdx order — c slow, (nx, ny), (mean, std). (Stacking the 96
        # gathered (n, numDesc) rows instead lays each out on a TPU with a
        # unit axis padded to a 128-row tile: 784 MB a row at 512 images.)
        xs = np.clip(np.add.outer(offsets, kx), 0, xd - 1).reshape(-1)
        ys = np.clip(np.add.outer(offsets, ky), 0, yd - 1).reshape(-1)
        picked = jnp.take(jnp.take(maps, xs, axis=3), ys, axis=4)
        no = len(offsets)
        picked = picked.reshape(n, nc, 2, no, npx, no, npy)
        # (n, c, nx, ny, mean|std, x_idx, y_idx)
        return picked.transpose(0, 1, 3, 5, 2, 4, 6).reshape(
            n, nc * no * no * 2, npx * npy
        )

    def apply(self, x):
        return self.trace_batch(jnp.asarray(x)[None])[0]
