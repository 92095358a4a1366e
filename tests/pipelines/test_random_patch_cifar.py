"""RandomPatchCifar end-to-end on synthetic CIFAR-shaped data (SURVEY §7
step 4 parity slice)."""

import jax
import numpy as np

from keystone_tpu.loaders.cifar import load_cifar, synthetic_cifar
from keystone_tpu.pipelines.random_patch_cifar import (
    RandomCifarConfig,
    run,
)


def test_cifar_loader_binary_roundtrip(tmp_path):
    rng = np.random.default_rng(0)
    n = 5
    labels = rng.integers(0, 10, n, dtype=np.uint8)
    imgs = rng.integers(0, 256, (n, 3, 32, 32), dtype=np.uint8)
    rec = np.concatenate([labels[:, None], imgs.reshape(n, -1)], axis=1)
    f = tmp_path / "data_batch_1.bin"
    rec.astype(np.uint8).tofile(f)

    ld = load_cifar(str(f))
    assert len(ld) == n
    np.testing.assert_array_equal(
        np.asarray(ld.labels.to_array()), labels.astype(np.int32)
    )
    X = np.asarray(ld.data.to_array())
    assert X.shape == (n, 32, 32, 3)
    # X[n, row, col, chan] == raw plane value
    np.testing.assert_allclose(X[0, 2, 3, 1], float(imgs[0, 1, 2, 3]))


def test_random_patch_cifar_end_to_end():
    train = synthetic_cifar(512, seed=1)
    test = synthetic_cifar(128, seed=2)
    conf = RandomCifarConfig(
        num_filters=32,
        patch_steps=2,
        whitener_size=2000,
        lam=100.0,
        seed=0,
    )
    _, train_err, test_err, _ = run(train, test, conf)
    # chance is 90% error; synthetic prototypes are easily separable
    assert train_err < 0.1, f"train error {train_err}"
    assert test_err < 0.3, f"test error {test_err}"


def _dot_precisions(jaxpr):
    """The precision of every ``dot_general`` in ``jaxpr``, sub-jaxprs
    (``pjit``, ``while`` bodies of the SVD) included."""
    found = []
    for eqn in jaxpr.eqns:
        if eqn.primitive.name == "dot_general":
            found.append(eqn.params["precision"])
        for sub in jax.core.jaxprs_in_params(eqn.params):
            found.extend(_dot_precisions(sub))
    return found


def test_the_whitener_is_float32_on_any_backend():
    """On a TPU a float32 product is ONE bf16 pass unless the program says
    otherwise; the whitener scales its smallest directions by eps^-1/2 (316
    at the published 1e-5), and at one pass the filter bank came out 10%
    off the float64 one on the chip (PERF.md section 6, PR 29). The CPU
    cannot show that, so the programs' own precision marks are read."""
    import jax.numpy as jnp

    from keystone_tpu.nodes.learning import zca

    exact = jax.lax.Precision.HIGHEST
    X = jnp.ones((40, 12), jnp.float32)
    fit = _dot_precisions(jax.make_jaxpr(zca._fit_zca)(X, 1e-5).jaxpr)
    assert fit and all(p in (exact, (exact, exact)) for p in fit), fit
    whitener = zca.ZCAWhitenerEstimator(1e-5).fit_single(
        np.random.default_rng(0).standard_normal((40, 12)).astype(np.float32)
    )
    apply = _dot_precisions(jax.make_jaxpr(whitener.trace_batch)(X).jaxpr)
    assert apply == [exact] or apply == [(exact, exact)], apply
