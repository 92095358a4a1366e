"""ImageNetSiftLcsFV end-to-end on synthetic textured images
(parity slice: ImageNetSiftLcsFV.scala:19-204, BASELINE metric #2)."""

import numpy as np

from keystone_tpu.nodes.learning.weighted import BlockWeightedLeastSquaresEstimator
from keystone_tpu.pipelines.imagenet_sift_lcs_fv import (
    ImageNetSiftLcsFVConfig,
    build_predictor,
    run,
    synthetic_imagenet,
    top_k_err_percent,
)
from keystone_tpu.workflow.pipeline import FittedPipeline


def test_top_k_err_percent_oracle():
    topk = np.array([[0, 1, 2], [3, 4, 5], [6, 7, 8]])
    actual = np.array([1, 9, 8])  # hit, miss, hit
    assert abs(top_k_err_percent(topk, actual) - 100.0 / 3.0) < 1e-9


def test_imagenet_sift_lcs_fv_end_to_end():
    num_classes = 16
    tr_i, tr_l = synthetic_imagenet(96, num_classes, size=48, seed=1)
    te_i, te_l = synthetic_imagenet(48, num_classes, size=48, seed=2)
    conf = ImageNetSiftLcsFVConfig(
        desc_dim=16,
        vocab_size=4,
        num_pca_samples=20_000,
        num_gmm_samples=20_000,
        num_classes=num_classes,
        lam=1e-4,
    )
    predictor, err, _ = run(tr_i, tr_l, te_i, te_l, conf)
    # top-5 of 16 classes: random scoring errs ~68.75%; the gratings are
    # separable so the gathered SIFT+LCS FV features must do far better.
    assert err.top5 < 25.0, f"top-5 error {err.top5}%"
    assert err.top5 <= err.top1 <= 100.0
    # predictions are a (n, 5) int index matrix
    out = np.asarray(predictor.apply(te_i).to_array())
    assert out.shape == (48, 5)


def test_calibrated_gradient_signal_gates(monkeypatch):
    """VERDICT r4 #5: a quality signal that (a) has a computable Bayes
    error, (b) REWARDS the featurizer — raw pixels are near chance because
    the class signal is a second-order (gradient) statistic — and (c) has
    teeth: a SIFT whose orientation layer is collapsed must blow the gate."""
    import jax.numpy as jnp

    from keystone_tpu.data.dataset import Dataset
    from keystone_tpu.nodes.images.sift import SIFTExtractor
    from keystone_tpu.nodes.learning.linear import LinearMapEstimator
    from keystone_tpu.nodes.util import ClassLabelIndicators
    from keystone_tpu.pipelines.imagenet_sift_lcs_fv import (
        synthetic_gradient_imagenet,
    )
    from keystone_tpu.workflow.env import PipelineEnv

    num_classes = 16
    gen = dict(num_classes=num_classes, size=48, theta_sigma=0.12,
               logf_sigma=0.10)
    tr_i, tr_l, bayes = synthetic_gradient_imagenet(256, seed=1, **gen)
    te_i, te_l, _ = synthetic_gradient_imagenet(128, seed=2, **gen)
    conf = ImageNetSiftLcsFVConfig(
        desc_dim=16, vocab_size=8, num_pca_samples=40_000,
        num_gmm_samples=40_000, num_classes=num_classes, lam=1e-4,
    )
    gate = 2.5 * bayes  # achievable-for-a-working-featurizer band

    pred = build_predictor(tr_i, tr_l, conf)
    topk = np.asarray(pred(te_i).get().to_array())
    top1 = 100.0 * float((topk[:, 0] != te_l).mean())
    assert bayes * 0.5 <= top1 <= gate, (top1, bayes)

    # raw pixels: the same data through a plain linear solve — near chance
    Xtr = jnp.asarray(tr_i.reshape(len(tr_i), -1), jnp.float32) / 255.0
    Xte = jnp.asarray(te_i.reshape(len(te_i), -1), jnp.float32) / 255.0
    Y = ClassLabelIndicators(num_classes).apply_batch(
        Dataset.of(tr_l)
    ).to_array()
    m = LinearMapEstimator(lam=10.0).fit(
        Dataset.of(Xtr), Dataset.of(jnp.asarray(Y))
    )
    raw_err = 100.0 * float(
        (np.asarray(jnp.argmax(m.trace_batch(Xte), axis=1)) != te_l).mean()
    )
    assert raw_err > 2 * top1 and raw_err > 40.0, (raw_err, top1)

    # broken featurizer: average away the 8 orientation bins (layout
    # t + 8·i + 32·j, sift.py:16) — the gate must catch it
    PipelineEnv.get_or_create().reset()
    orig = SIFTExtractor.trace_batch

    def broken(self, X):
        D = orig(self, X)  # (n, 128, N)
        n, d, m_ = D.shape
        D4 = D.reshape(n, d // 8, 8, m_)
        return jnp.broadcast_to(
            D4.mean(axis=2, keepdims=True), D4.shape
        ).reshape(n, d, m_)

    monkeypatch.setattr(SIFTExtractor, "trace_batch", broken)
    # the segment dispatchers key on op type+params (not code), so a
    # monkeypatched trace_batch would otherwise be served the healthy
    # compiled program
    from keystone_tpu.compile.segment import reset_dispatchers

    reset_dispatchers()
    broken_topk = np.asarray(
        build_predictor(tr_i, tr_l, conf)(te_i).get().to_array()
    )
    broken_err = 100.0 * float((broken_topk[:, 0] != te_l).mean())
    assert broken_err > gate, (broken_err, gate)


def test_imagenet_fit_from_chunked_source(monkeypatch):
    """Out-of-core fit (VERDICT r4 #1): train images arrive as a
    ChunkedDataset; both featurizer branches run chunk-by-chunk (one
    combined sampling scan per branch), the gathered FV features zip
    per-chunk, and the solver consumes them without the full descriptor
    stacks ever materializing. Run twice — once with the featurized set
    under the HBM budget (materialize+solve) and once forced over budget
    (the streaming weighted trainer) — both must produce a working model."""
    from keystone_tpu.data import ChunkedDataset

    num_classes = 8
    tr_i, tr_l = synthetic_imagenet(48, num_classes, size=48, seed=1)
    te_i, te_l = synthetic_imagenet(24, num_classes, size=48, seed=2)
    conf = ImageNetSiftLcsFVConfig(
        desc_dim=8,
        vocab_size=4,
        num_pca_samples=20_000,
        num_gmm_samples=20_000,
        num_classes=num_classes,
        lam=1e-4,
    )
    chunked = ChunkedDataset.from_array(tr_i, 13)  # ragged chunk boundaries
    predictor, err, _ = run(chunked, tr_l, te_i, te_l, conf)
    assert err.top5 < 40.0, f"top-5 error {err.top5}%"

    from keystone_tpu.workflow.env import PipelineEnv

    PipelineEnv.get_or_create().reset()
    monkeypatch.setenv("KEYSTONE_CHUNK_CACHE_BUDGET", "1")
    predictor2, err2, _ = run(chunked, tr_l, te_i, te_l, conf)
    assert err2.top5 < 40.0, f"top-5 error (streaming solver) {err2.top5}%"


def test_fitted_apply_reproduces_fit_time_features(monkeypatch):
    """Regression: FittedPipeline.apply must execute the exact program
    partitioning fit() used. Re-fusing the transformer chain after fit
    compiled the Fisher-Vector posterior math into a new XLA program whose
    reassociated f32 arithmetic flipped near-tied component assignments —
    apply-time features silently diverged from what the solver trained on
    (train top-5 error went 0% → 40%)."""
    cap = {}
    orig = BlockWeightedLeastSquaresEstimator.fit

    def spy(self, data, labels):
        cap["X"] = np.asarray(data.to_array())
        return orig(self, data, labels)

    monkeypatch.setattr(BlockWeightedLeastSquaresEstimator, "fit", spy)
    num_classes = 8
    tr_i, tr_l = synthetic_imagenet(32, num_classes, size=48, seed=1)
    conf = ImageNetSiftLcsFVConfig(
        desc_dim=8,
        vocab_size=4,
        num_pca_samples=20_000,
        num_gmm_samples=20_000,
        num_classes=num_classes,
        lam=1e-4,
    )
    fitted = build_predictor(tr_i, tr_l, conf).fit()

    # cut the fitted graph at the solver's input and re-apply to train data
    g = fitted.graph
    topk = [
        n for n in g.nodes
        if type(g.get_operator(n)).__name__ == "TopKClassifier"
    ][0]
    solver = g.get_dependencies(topk)[0]
    feat = g.get_dependencies(solver)[0]
    g2, sink2 = g.add_sink(feat)
    sub = FittedPipeline(g2, fitted._source, sink2)
    X_apply = np.asarray(sub.apply(tr_i).to_array())
    np.testing.assert_array_equal(X_apply, cap["X"])


def test_imagenet_pca_gmm_checkpoint_load(tmp_path):
    """Both branches loadable from CSV checkpoints
    (parity: ImageNetSiftLcsFV.scala:40-66)."""
    rng = np.random.default_rng(0)
    dims, k = 8, 4
    num_classes = 8
    paths = {}
    # LCS feature rows with the default patch=6: 3 channels × 4×4
    # neighborhood offsets × (mean, std) = 96.
    for branch, d_in in (("sift", 128), ("lcs", 96)):
        pca = rng.standard_normal((dims, d_in)).astype(np.float32)
        means = rng.standard_normal((dims, k))
        variances = rng.uniform(0.5, 1.5, (dims, k))
        weights = np.full(k, 1.0 / k)
        for name, arr in (
            ("pca", pca), ("m", means), ("v", variances), ("w", weights)
        ):
            f = tmp_path / f"{branch}_{name}.csv"
            np.savetxt(f, arr, delimiter=",")
            paths[f"{branch}_{name}"] = str(f)

    tr_i, tr_l = synthetic_imagenet(24, num_classes, size=48, seed=3)
    te_i, te_l = synthetic_imagenet(12, num_classes, size=48, seed=4)
    conf = ImageNetSiftLcsFVConfig(
        desc_dim=dims,
        vocab_size=k,
        num_classes=num_classes,
        lam=1e-2,
        sift_pca_file=paths["sift_pca"],
        sift_gmm_mean_file=paths["sift_m"],
        sift_gmm_var_file=paths["sift_v"],
        sift_gmm_wts_file=paths["sift_w"],
        lcs_pca_file=paths["lcs_pca"],
        lcs_gmm_mean_file=paths["lcs_m"],
        lcs_gmm_var_file=paths["lcs_v"],
        lcs_gmm_wts_file=paths["lcs_w"],
    )
    _, err, _ = run(tr_i, tr_l, te_i, te_l, conf)
    assert np.isfinite(err.top5) and np.isfinite(err.top1)
