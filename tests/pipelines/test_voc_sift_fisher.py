"""VOCSIFTFisher end-to-end on synthetic multi-label textured images
(SURVEY §7 step 5 parity slice) + MAP evaluator oracle."""

import numpy as np

from keystone_tpu.evaluation.mean_average_precision import (
    MeanAveragePrecisionEvaluator,
)
from keystone_tpu.pipelines.voc_sift_fisher import (
    SIFTFisherConfig,
    run,
    synthetic_voc,
)


def test_map_evaluator_oracle():
    # 2 classes, 4 items; class 0 perfectly ranked, class 1 inverted
    preds = np.array(
        [[0.9, 0.1], [0.8, 0.9], [0.2, 0.8], [0.1, 0.7]]
    )
    actuals = [[0], [0], [1], [0, 1]]
    aps = MeanAveragePrecisionEvaluator(2).evaluate(preds, actuals)
    assert aps.shape == (2,)
    # class 0: positives are items 0,1,3 with scores .9,.8,.1 → ranked
    # 1,2,4 of 4 → AP high
    assert aps[0] > 0.8
    assert 0 < aps[1] <= 1.0


def test_voc_sift_fisher_end_to_end():
    tr_i, tr_l = synthetic_voc(64, seed=1)
    te_i, te_l = synthetic_voc(32, seed=2)
    conf = SIFTFisherConfig(
        num_pca_samples=20_000,
        num_gmm_samples=20_000,
        vocab_size=4,
        desc_dim=16,
        lam=10.0,
    )
    _, aps, _ = run(tr_i, tr_l, te_i, te_l, conf)
    assert aps.shape == (20,)
    # random scoring gives MAP ≈ mean positive rate ≈ 0.1; textured classes
    # must do meaningfully better
    assert aps.mean() > 0.3, f"MAP {aps.mean()}"


def test_voc_pca_gmm_checkpoint_load(tmp_path):
    """PCA/GMM loadable from CSV (parity: VOCSIFTFisher.scala:49-66)."""
    rng = np.random.default_rng(0)
    d, dims, k = 128, 8, 4
    pca = rng.standard_normal((dims, d)).astype(np.float32)  # file: dims×d
    np.savetxt(tmp_path / "pca.csv", pca, delimiter=",")
    means = rng.standard_normal((dims, k))
    variances = rng.uniform(0.5, 1.5, (dims, k))
    weights = np.full(k, 1.0 / k)
    np.savetxt(tmp_path / "m.csv", means, delimiter=",")
    np.savetxt(tmp_path / "v.csv", variances, delimiter=",")
    np.savetxt(tmp_path / "w.csv", weights, delimiter=",")

    tr_i, tr_l = synthetic_voc(24, seed=3)
    te_i, te_l = synthetic_voc(12, seed=4)
    conf = SIFTFisherConfig(
        vocab_size=k,
        desc_dim=dims,
        lam=10.0,
        pca_file=str(tmp_path / "pca.csv"),
        gmm_mean_file=str(tmp_path / "m.csv"),
        gmm_var_file=str(tmp_path / "v.csv"),
        gmm_wts_file=str(tmp_path / "w.csv"),
    )
    _, aps, _ = run(tr_i, tr_l, te_i, te_l, conf)
    assert aps.shape == (20,)
    assert np.isfinite(aps).all()
