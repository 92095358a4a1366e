"""VOCSIFTFisher — multi-label VOC classification with SIFT + Fisher Vectors.

Parity: pipelines/images/voc/VOCSIFTFisher.scala:20-140. Stages:
PixelScaler → GrayScaler → SIFTExtractor → [ColumnSampler → ColumnPCA] →
BatchPCATransformer → [ColumnSampler → GMM] → FisherVector → FloatToDouble →
MatrixVectorizer → NormalizeRows → SignedHellinger → NormalizeRows →
BlockLeastSquaresEstimator(4096, 1, λ) → MeanAveragePrecisionEvaluator.

PCA matrix and GMM are loadable from CSV checkpoints exactly like the
reference (--pcaFile / --gmmMeanFile …).
"""

from __future__ import annotations

import argparse
import time
from dataclasses import dataclass
from typing import List, Optional

import jax.numpy as jnp
import numpy as np

from ..data.dataset import Dataset
from ..evaluation.mean_average_precision import MeanAveragePrecisionEvaluator
from ..loaders.csv_loader import LabeledData
from ..nodes.images import (
    FisherVector,
    GMMFisherVectorEstimator,
    GrayScaler,
    PixelScaler,
    SIFTExtractor,
)
from ..nodes.learning import (
    BatchPCATransformer,
    BlockLeastSquaresEstimator,
    ColumnPCAEstimator,
    GaussianMixtureModel,
)
from ..nodes.stats import ColumnSampler, NormalizeRows, SignedHellingerMapper
from ..nodes.util import Cacher, MatrixVectorizer, MultiClassLabelIndicators
from ..obs.tracer import span

NUM_CLASSES = 20  # parity: VOCLoader.NUM_CLASSES


@dataclass
class SIFTFisherConfig:
    """Parity: SIFTFisherConfig (VOCSIFTFisher.scala:125-140)."""

    num_pca_samples: int = 1_000_000
    num_gmm_samples: int = 1_000_000
    vocab_size: int = 16
    desc_dim: int = 24
    lam: float = 0.5
    scale_step: int = 0
    pca_file: Optional[str] = None
    gmm_mean_file: Optional[str] = None
    gmm_var_file: Optional[str] = None
    gmm_wts_file: Optional[str] = None
    seed: int = 0


def _sample_descriptors(featurizer, train_images, per_img: int, seed: int):
    """One sampling pass: ``per_img`` columns of every training image's
    descriptor matrix, ``featurizer`` and the sampler composed lazily, so
    that the optimizer sees SIFT → column-wise nodes → sampler and puts the
    one node in their place that makes only the sampled descriptors
    (``nodes/images/chain.py:SampledSIFTRule``) — an image's descriptor
    stack, 37.6 MB at 500 × 375, is not built in a sampling pass, and that
    of the whole training set never exists."""
    n = len(Dataset.of(train_images))
    with span(
        "voc.sample_descriptors", images=n, columns=n * per_img
    ) as sp:
        sampler = ColumnSampler(per_img, seed=seed).to_pipeline()
        sample = sampler(featurizer(train_images)).get()
        sp.attrs["bytes"] = int(sample.to_array().nbytes)
        sp.sync_on(sample.to_array())
    return sample


def run(train_images, train_label_sets, test_images, test_label_sets,
        conf: SIFTFisherConfig):
    """train_images: (n, X, Y, C) uint/float batch; *_label_sets: per-image
    int label lists. Returns (the fitted predictor pipeline, per-class AP
    vector, seconds).

    A job featurizes the training images three times — the PCA's sample,
    the codebook's sample, the fit — and the held-out images once, as the
    reference's does: the descriptors of a data set (37.6 MB an image at
    500 × 375) and their projection (23.5 MB) are never held, whatever the
    ``Cacher`` after the projection asks (the executor declines a cache
    the device cannot hold and computes the value again where it is read).
    The PCA and the codebook are fitted as soon as their sample is drawn:
    the codebook's sample is drawn through the fitted projection, ahead of
    that ``Cacher`` — a cache is not column-wise, and a sampling pass
    projects the sampled descriptors alone."""
    start = time.perf_counter()
    with span("job", pipeline="VOCSIFTFisher"):
        with span("plan.build"):
            n_train = len(Dataset.of(train_images))
            labels = MultiClassLabelIndicators(NUM_CLASSES).apply_batch(
                Dataset.from_items(list(train_label_sets))
            )

            sift = (
                PixelScaler()
                .and_then(GrayScaler())
                .and_then(Cacher())
                .and_then(SIFTExtractor(scale_step=conf.scale_step))
            )

            if conf.pca_file:
                pca_mat = np.loadtxt(conf.pca_file, delimiter=",", ndmin=2).T
                pca = BatchPCATransformer(
                    jnp.asarray(pca_mat, dtype=jnp.float32)
                )
            else:
                # parity: `ColumnPCAEstimator withData (sampler(sift(train)))`
                # — the estimator is fit on sampled descriptors, then
                # composed after the extractor (VOCSIFTFisher.scala:49-55)
                per_img = max(1, conf.num_pca_samples // n_train)
                pca = ColumnPCAEstimator(conf.desc_dim).fit(
                    _sample_descriptors(sift, train_images, per_img, conf.seed)
                )
            projected = sift.and_then(pca)
            pca_featurizer = projected.and_then(Cacher())

            if conf.gmm_mean_file:
                gmm = GaussianMixtureModel.load(
                    conf.gmm_mean_file, conf.gmm_var_file, conf.gmm_wts_file
                )
                fv = FisherVector(gmm)
                # a loaded codebook sets the FV width (e.g. the real VOC
                # codebook is 256 centers, not the config default)
                vocab_size = int(gmm.k)
            else:
                per_img = max(1, conf.num_gmm_samples // n_train)
                fv = GMMFisherVectorEstimator(
                    conf.vocab_size, max_iterations=20, min_cluster_size=1
                ).fit(_sample_descriptors(
                    projected, train_images, per_img, conf.seed + 1
                ))
                vocab_size = conf.vocab_size

            fisher_featurizer = (
                pca_featurizer
                .and_then(fv)
                .and_then(MatrixVectorizer())
                .and_then(NormalizeRows())
                .and_then(SignedHellingerMapper())
                .and_then(NormalizeRows())
                .and_then(Cacher())
            )

            predictor = fisher_featurizer.and_then(
                BlockLeastSquaresEstimator(
                    4096, 1, conf.lam,
                    num_features=2 * conf.desc_dim * vocab_size,
                ),
                train_images,
                labels,
            )

        # fit, then apply the estimator-free pipeline: its chain is one
        # segment the executor can cut by rows; pulled unfitted, every
        # fitted stage is applied node by node to a whole data set
        fitted = predictor.fit()
        predictions = fitted.apply(test_images)
        aps = MeanAveragePrecisionEvaluator(NUM_CLASSES).evaluate(
            predictions, list(test_label_sets)
        )
    return fitted, aps, time.perf_counter() - start


def synthetic_voc(n: int, size: int = 64, seed: int = 0):
    """Multi-label textured images: each image overlays 1-3 class-specific
    oriented gratings in random regions (class signal must live in local
    gradient structure for SIFT to see it)."""
    rng = np.random.default_rng(seed)
    xx, yy = np.meshgrid(np.arange(size), np.arange(size), indexing="ij")
    images = np.zeros((n, size, size, 3), dtype=np.float32)
    label_sets: List[np.ndarray] = []
    for i in range(n):
        k = int(rng.integers(1, 4))
        labels = rng.choice(NUM_CLASSES, size=k, replace=False)
        img = 64.0 + 8.0 * rng.standard_normal((size, size))
        for cl in labels:
            freq = 0.12 + 0.035 * (cl % 10)
            theta = np.pi * cl / NUM_CLASSES
            wave = 96.0 * np.sin(
                2 * np.pi * freq * (np.cos(theta) * xx + np.sin(theta) * yy)
                + rng.uniform(0, 2 * np.pi)
            )
            x0, y0 = rng.integers(0, size // 2, 2)
            mask = np.zeros((size, size))
            mask[x0 : x0 + size // 2, y0 : y0 + size // 2] = 1.0
            img = img + wave * mask
        images[i] = np.clip(img, 0, 255)[..., None].repeat(3, axis=-1)
        label_sets.append(np.sort(labels))
    return images, label_sets


def main(argv=None) -> int:
    p = argparse.ArgumentParser("VOCSIFTFisher")
    # tar-of-JPEG ingestion (parity: VOCSIFTFisher.scala's trainLocation/
    # testLocation/labelPath); --imageSize is the explicit ragged-size
    # policy — one canonical square so the featurizer is one program
    p.add_argument("--trainLocation", default=None,
                   help="VOC image tar (or dir of tars)")
    p.add_argument("--testLocation", default=None)
    p.add_argument("--labelPath", default=None, help="VOC labels CSV")
    p.add_argument("--testLabelPath", default=None)
    p.add_argument("--namePrefix", default="VOCdevkit/VOC2007/JPEGImages/")
    p.add_argument("--imageSize", type=int, default=256)
    p.add_argument("--vocabSize", type=int, default=16)
    p.add_argument("--descDim", type=int, default=24)
    p.add_argument("--lambda", dest="lam", type=float, default=0.5)
    p.add_argument("--numPcaSamples", type=int, default=100_000)
    p.add_argument("--numGmmSamples", type=int, default=100_000)
    p.add_argument("--scaleStep", type=int, default=0)
    p.add_argument("--pcaFile", default=None)
    p.add_argument("--gmmMeanFile", default=None)
    p.add_argument("--gmmVarFile", default=None)
    p.add_argument("--gmmWtsFile", default=None)
    p.add_argument("--nTrain", type=int, default=256)
    p.add_argument("--nTest", type=int, default=64)
    args = p.parse_args(argv)
    conf = SIFTFisherConfig(
        num_pca_samples=args.numPcaSamples,
        num_gmm_samples=args.numGmmSamples,
        vocab_size=args.vocabSize,
        desc_dim=args.descDim,
        lam=args.lam,
        scale_step=args.scaleStep,
        pca_file=args.pcaFile,
        gmm_mean_file=args.gmmMeanFile,
        gmm_var_file=args.gmmVarFile,
        gmm_wts_file=args.gmmWtsFile,
    )
    if args.trainLocation:
        from ..loaders.images import load_voc

        size = (args.imageSize, args.imageSize)
        train = load_voc(args.trainLocation, args.labelPath,
                         name_prefix=args.namePrefix, size=size)
        test = load_voc(args.testLocation or args.trainLocation,
                        args.testLabelPath or args.labelPath,
                        name_prefix=args.namePrefix, size=size)
        tr_imgs = np.asarray(train.data.to_array())
        tr_labels = train.labels
        te_imgs = np.asarray(test.data.to_array())
        te_labels = test.labels
    else:
        tr_imgs, tr_labels = synthetic_voc(args.nTrain, seed=1)
        te_imgs, te_labels = synthetic_voc(args.nTest, seed=2)
    _, aps, seconds = run(tr_imgs, tr_labels, te_imgs, te_labels, conf)
    for i, ap in enumerate(aps):
        print(f"Class {i} avg precision: {ap}")
    print(f"TEST APs are: {aps}")
    print(f"Mean Average Precision: {aps.mean()}")
    print(f"Pipeline took {seconds} s")
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
