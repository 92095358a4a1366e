"""RandomPatchCifar — CIFAR-10 with random-patch convolutional features.

Parity: pipelines/images/cifar/RandomPatchCifar.scala:18-120. Stages:
sample patches (Windower → vectorize → Sampler) → normalize + ZCA-whiten →
random filter bank → Convolver (whitened, patch-normalized) →
SymmetricRectifier → sum-Pooler → vectorize → StandardScaler →
BlockLeastSquaresEstimator → MaxClassifier.
"""

from __future__ import annotations

import argparse
import time
from dataclasses import dataclass
from functools import partial
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np

from ..data.dataset import Dataset
from ..evaluation.multiclass import MulticlassClassifierEvaluator
from ..loaders.cifar import NCHAN, NROW, load_cifar, synthetic_cifar
from ..loaders.csv_loader import LabeledData
from ..nodes.images.core import (
    Convolver,
    ImageVectorizer,
    Pooler,
    SymmetricRectifier,
    vectorize_images,
)
from ..nodes.learning.linear import BlockLeastSquaresEstimator
from ..nodes.learning.zca import ZCAWhitenerEstimator
from ..nodes.stats import StandardScaler
from ..nodes.util import ClassLabelIndicators, MaxClassifier
from ..obs.tracer import span
from ..utils.stats import normalize_rows

NUM_CLASSES = 10


@dataclass
class RandomCifarConfig:
    """Parity: RandomCifarConfig (RandomPatchCifar.scala:89-100)."""

    train_location: str = ""
    test_location: str = ""
    num_filters: int = 100
    whitening_epsilon: float = 0.1
    patch_size: int = 6
    patch_steps: int = 1
    pool_size: int = 14
    pool_stride: int = 13
    alpha: float = 0.25
    lam: Optional[float] = None
    sample_frac: Optional[float] = None
    whitener_size: int = 100000
    seed: int = 0


@partial(jax.jit, static_argnums=(4,))
def _patches_at(X, img, x0, y0, size: int):
    """The ``size``×``size`` windows of ``X`` (n, X, Y, C) whose corners are
    ``(img[i], x0[i], y0[i])``, as (len(img), size, size, C)."""
    def one(i, x, y):
        return jax.lax.dynamic_slice(
            X, (i, x, y, 0), (1, size, size, X.shape[-1])
        )[0]

    return jax.vmap(one)(img, x0, y0)


def sample_patches(images, conf: "RandomCifarConfig"):
    """``whitener_size`` vectorized patches of ``images``: what
    ``Windower → ImageVectorizer → Sampler`` gives (every window of every
    image in the reference's emission order — per image, for x, for y —
    then a seeded draw without replacement, sorted), with the draw made
    FIRST and only the drawn windows cut. All windows of 16,384 images are
    5.2 GB twice over; the sample is 43 MB."""
    X = jnp.asarray(images)
    n, xd, yd, _ = X.shape
    w, st = conf.patch_size, conf.patch_steps
    nx = len(range(0, xd - w + 1, st))
    ny = len(range(0, yd - w + 1, st))
    total = n * nx * ny
    idx = np.sort(np.random.default_rng(conf.seed).choice(
        total, size=min(conf.whitener_size, total), replace=False
    ))
    img, window = np.divmod(idx, nx * ny)
    xi, yi = np.divmod(window, ny)
    as_index = lambda a: jnp.asarray(a, jnp.int32)  # noqa: E731
    patches = _patches_at(
        X, as_index(img), as_index(xi * st), as_index(yi * st), w
    )
    return vectorize_images(patches)


def learn_filters(train_images: Dataset, conf: RandomCifarConfig):
    """Sample patches, whiten, pick + scale random filters
    (parity: RandomPatchCifar.scala:41-58). Returns (filters, whitener)."""
    with span("cifar.sample_patches") as sp:
        base = sample_patches(Dataset.of(train_images).to_array(), conf)
        base_mat = normalize_rows(base, 10.0)
        sp.sync_on(base_mat)
    with span("zca.fit") as sp:
        whitener = ZCAWhitenerEstimator(
            conf.whitening_epsilon
        ).fit_single(base_mat)
        sp.sync_on(whitener.whitener)

    with span("cifar.choose_filters") as sp:
        rng = np.random.default_rng(conf.seed)
        idx = rng.choice(
            base_mat.shape[0],
            size=min(conf.num_filters, base_mat.shape[0]),
            replace=False,
        )
        sample = base_mat[jnp.asarray(np.sort(idx))]
        unnorm = whitener.transform(sample)
        norms = jnp.sqrt(jnp.sum(unnorm * unnorm, axis=1))
        # float32 like the whitener's own products (nodes/learning/zca.py)
        filters = jnp.matmul(
            unnorm / (norms + 1e-10)[:, None], whitener.whitener.T,
            precision=jax.lax.Precision.HIGHEST,
        )
        sp.sync_on(filters)
    return filters, whitener


def build_pipeline(train: LabeledData, conf: RandomCifarConfig):
    labels = ClassLabelIndicators(NUM_CLASSES).apply_batch(train.labels)
    filters, whitener = learn_filters(train.data, conf)
    featurizer = (
        Convolver(
            filters, NROW, NROW, NCHAN, whitener=whitener,
            normalize_patches=True,
        )
        .and_then(SymmetricRectifier(alpha=conf.alpha))
        .and_then(Pooler(conf.pool_stride, conf.pool_size, None, "sum"))
        .and_then(ImageVectorizer())
    )
    return featurizer.and_then(
        StandardScaler(), train.data
    ).and_then(
        BlockLeastSquaresEstimator(4096, 1, conf.lam or 0.0),
        train.data,
        labels,
    ).and_then(MaxClassifier())


def run(train: LabeledData, test: LabeledData, conf: RandomCifarConfig):
    start = time.perf_counter()
    with span("job", pipeline="RandomPatchCifar"):
        if conf.sample_frac is not None:
            # parity: RandomPatchCifar.scala:29-32 (sample training data)
            rng = np.random.default_rng(conf.seed)
            n = len(train)
            keep = np.sort(rng.choice(
                n, size=max(1, int(n * conf.sample_frac)), replace=False
            ))
            train = LabeledData(
                np.asarray(train.labels.to_array())[keep],
                np.asarray(train.data.to_array())[keep],
            )
        with span("plan.build"):
            pipeline = build_pipeline(train, conf)
        fitted = pipeline.fit()
        # through the executor, as the fit's own featurization went: a
        # segment whose intermediates outgrow the device is dispatched in
        # row slices (compile/segment.py); one whole-batch program of the
        # chain is 29 MB of convolution output an image at 10,000 filters
        ev = MulticlassClassifierEvaluator(NUM_CLASSES)
        train_eval = ev.evaluate(
            fitted.apply(train.data).to_array(), train.labels
        )
        test_eval = ev.evaluate(
            fitted.apply(test.data).to_array(), test.labels
        )
    return pipeline, train_eval.total_error, test_eval.total_error, \
        time.perf_counter() - start


def main(argv=None) -> int:
    p = argparse.ArgumentParser("RandomPatchCifar")
    p.add_argument("--trainLocation", default=None)
    p.add_argument("--testLocation", default=None)
    p.add_argument("--numFilters", type=int, default=100)
    p.add_argument("--whiteningEpsilon", type=float, default=0.1)
    p.add_argument("--patchSize", type=int, default=6)
    p.add_argument("--patchSteps", type=int, default=1)
    p.add_argument("--poolSize", type=int, default=14)
    p.add_argument("--poolStride", type=int, default=13)
    p.add_argument("--alpha", type=float, default=0.25)
    p.add_argument("--lambda", dest="lam", type=float, default=None)
    p.add_argument("--nTrain", type=int, default=4096)
    p.add_argument("--nTest", type=int, default=1024)
    args = p.parse_args(argv)
    conf = RandomCifarConfig(
        num_filters=args.numFilters,
        whitening_epsilon=args.whiteningEpsilon,
        patch_size=args.patchSize,
        patch_steps=args.patchSteps,
        pool_size=args.poolSize,
        pool_stride=args.poolStride,
        alpha=args.alpha,
        lam=args.lam,
    )
    if args.trainLocation:
        if not args.testLocation:
            p.error("--testLocation is required with --trainLocation")
        train = load_cifar(args.trainLocation)
        test = load_cifar(args.testLocation)
    else:
        train = synthetic_cifar(args.nTrain, seed=1)
        test = synthetic_cifar(args.nTest, seed=2)
    _, train_err, test_err, seconds = run(train, test, conf)
    print(f"Training error is: {train_err}")
    print(f"Test error is: {test_err}")
    print(f"Pipeline took {seconds} s")
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
