"""The quickest proof that the system still starts on the chip.

One process drives the main path — fit a pipeline, then serve it — once,
through the entry points a user would call, at the full width of
MnistRandomFFT (numFFTs=4, blockSize=2048, λ=1000; 60,000 train / 10,000
test rows of the seeded synthetic task, generated in HBM). Five legs:

* ``fit``    — CLI dispatch and backend selection through
  ``python -m keystone_tpu MnistRandomFFT --backend tpu``'s ``main``, then
  the 60k-row fit through ``pipelines.mnist_random_fft.run``; the test
  error must land inside ``TEST_ERROR_BAND``.
* ``serve``  — the fitted pipeline behind ``ServingFleet`` at its default
  of one replica per device; every reply must equal ``fitted.apply`` on
  the same row.
* ``conv_chain`` — the fused conv → rectify → pool Pallas kernel at
  ``cifar_patch10k``'s 10,000 filters, compiled, against the XLA lowering
  of the three bodies it replaces, and that the front door picked it.

* ``fisher`` — dense SIFT → PCA → Fisher vector at ``voc_fv256``'s 80
  dimensions and 256 centres on a few 500 × 375 images, the PCA and the
  codebook fitted from sampled descriptors as ``voc_sift_fisher.run`` fits
  them, against the plain float32 ``highest`` reference of the benchmark
  (``benchmark/configs/voc_fv256_reference.py``): descriptors, basis,
  codebook and features each inside a stated gap; and the sampled SIFT
  body (``SampledSIFTExtractor``: the descriptors at the sampler's columns
  alone, gathered from the pooled maps) against the same columns of the
  full body's descriptors.

* ``weighted`` — the class-weighted block solve
  (``BlockWeightedLeastSquaresEstimator``) at ``imagenet_fv16``'s d = 4,096,
  λ = 6·10⁻⁵ and w = 0.25 on a few classes, the primal path, against the
  float64 class systems of the benchmark's plain reference
  (``benchmark/configs/imagenet_fv16_reference.py``) by what the two
  predict on held-out rows; and ``LCSExtractor`` on a few 256 × 256 images
  against the reference's local colour statistics.

It refuses anything but a TPU, fails if any catch-and-degrade site fired
on its path, and exits 0 only if every leg passed. Stdout is two lines of
JSON: the report (per leg what ran, compile and cache counts, peak HBM;
its times are bring-up facts, not benchmark numbers), then LAST the
verdict the driver parses, exactly
``{"ok": true, "device": {"platform": "tpu", "kind": "...", "count": 1}}``
with the device as jax reports it — nothing else goes on that line. Run
without an accelerator, or in a directory that holds none of the repo, it
prints neither and exits non-zero. The legs are plain functions with size
arguments: tier-1 and the CPU rehearsal call them at tiny sizes;
``__main__`` has no CPU mode.
"""

from __future__ import annotations

import json
import os
import sys
import time
import traceback
from concurrent.futures import ThreadPoolExecutor

#: the full-width configuration (examples/images/mnist_random_fft.sh,
#: BASELINE metric #1)
FULL = dict(
    num_ffts=4, block_size=2048, lam=1000.0, n_train=60000, n_test=10000
)

#: 10,000-row test error of the 60k-row fit, fixed BEFORE the chip run.
#: ``bayes_error_mc(42)`` is 0.0422 and the CPU run of the same seed lands
#: at 0.0480 (0.0462-0.0483 across featurizer seeds); one sampling σ at
#: n=10,000 is 0.0021. Below the band is better than Bayes (a leak); above
#: it is a solve that lost precision or solved the wrong system.
TEST_ERROR_BAND = (0.040, 0.055)

#: cifar_patch10k's filter bank (benchmark/configs/cifar_patch10k.json) over
#: as many images as the three XLA bodies hold at once (29 MB an image)
CONV_CHAIN_SHAPE = dict(filters=10000, images=64)


def require_tpu() -> dict:
    """The device as jax reports it; exits 2 unless it is a TPU."""
    try:
        from keystone_tpu.parallel.mesh import device_summary
    except ImportError as e:
        print(f"chip_smoke: the program is not here: {e}", file=sys.stderr)
        raise SystemExit(2)

    try:
        found = device_summary()
    except RuntimeError as e:
        print(f"chip_smoke: no accelerator: {e}", file=sys.stderr)
        raise SystemExit(2)
    if found["platform"] != "tpu":
        print(
            f"chip_smoke: need platform tpu, found {found['platform']} "
            f"({found['kind']} x{found['count']}) — this script has no "
            "CPU mode",
            file=sys.stderr,
        )
        raise SystemExit(2)
    return found


class CompileCounts:
    """Compile requests (the tracer's count) and persistent-cache traffic,
    from jax.monitoring. A request answered by the persistent cache still
    counts as a request, so real compiles = requests - hits."""

    def __init__(self):
        from jax import monitoring

        from keystone_tpu.obs import tracer as tracer_mod

        tracer_mod.start()  # from here on: ``fallbacks_fired`` reads spans
        self._record = tracer_mod.compile_record()
        self.hits = self.writes = 0
        monitoring.register_event_listener(self._on_event)

    def _on_event(self, event, **kw):
        if event.endswith("compilation_cache/cache_hits"):
            self.hits += 1
        elif event.endswith("compilation_cache/cache_misses"):
            self.writes += 1

    def snapshot(self) -> dict:
        return {
            "backend_compile_requests": self._record.requests["load"],
            "persistent_cache_hits": self.hits,
            "persistent_cache_writes": self.writes,
        }


def fallbacks_fired() -> dict:
    """Every catch-and-degrade site that fired since the process started:
    the ``degrade.*`` counters (a demoted segment, a failed segment plan,
    an AOT export/load/persist failure, a skipped warm-up or fingerprint)
    plus segment spans that ran node by node — those are seen only while
    the tracer is on, so the legs start it. Empty means none did."""
    from keystone_tpu.obs import tracer as tracer_mod
    from keystone_tpu.utils import timing

    fired = {
        name: row["calls"] for name, row in timing.snapshot("degrade.").items()
    }
    fallback_spans = sum(
        1 for sp in tracer_mod.start().spans()
        if sp.name == "exec.segment" and sp.attrs.get("path") == "fallback"
    )
    if fallback_spans:
        fired["exec.segment.fallback"] = fallback_spans
    return fired


def _cache_entries(root: str) -> int:
    return sum(len(files) for _, _, files in os.walk(root))


def _peak_bytes() -> list:
    import jax

    return [
        (d.memory_stats() or {}).get("peak_bytes_in_use")
        for d in jax.devices()
    ]


def _placement(x) -> str:
    """``.sharding`` of a device array; fitted parameters are host numpy
    (utils/params.py: literals of the compiled program)."""
    sharding = getattr(x, "sharding", None)
    return str(sharding) if sharding is not None else f"host {type(x).__name__}"


def _weights(fitted):
    """The fitted BlockLinearMapper's first weight block."""
    from keystone_tpu.nodes.learning.linear import BlockLinearMapper

    graph = fitted.graph
    for node in graph.nodes:
        op = graph.get_operator(node)
        if isinstance(op, BlockLinearMapper):
            return op.xs[0]
    raise LookupError("no BlockLinearMapper in the fitted graph")


def fit_leg(
    *, num_ffts, block_size, lam, n_train, n_test, band, backend="tpu"
):
    """CLI dispatch + backend selection, then the fit through ``run`` —
    twice, so the first call (compiles) and the repeat (full re-execution
    on fresh estimators, programs cached) are told apart. Returns
    ``(report, fitted, test_rows)``."""
    import numpy as np

    from keystone_tpu.__main__ import main as cli_main
    from keystone_tpu.obs import tracer as tracer_mod
    from keystone_tpu.pipelines.mnist_random_fft import (
        MnistRandomFFTConfig,
        build_featurizer,
        run,
        synthetic_mnist_device,
    )

    tracer_mod.start()
    t0 = time.perf_counter()
    rc = cli_main([
        "MnistRandomFFT", "--backend", backend,
        "--numFFTs", str(num_ffts), "--blockSize", str(block_size),
        "--lambda", str(lam),
    ])
    cli_seconds = time.perf_counter() - t0

    conf = MnistRandomFFTConfig(
        num_ffts=num_ffts, block_size=block_size, lam=lam
    )
    train, test = synthetic_mnist_device(n_train=n_train, n_test=n_test)
    pipeline, train_err, test_err, first_seconds = run(train, test, conf)
    _, _, repeat_err, repeat_seconds = run(train, test, conf)
    fitted = pipeline.fit()  # fit-once: the state run() already paid for
    features = build_featurizer(conf).apply(train.data).to_array()
    report = {
        "ok": bool(
            rc == 0
            and band[0] <= test_err <= band[1]
            and band[0] <= repeat_err <= band[1]
        ),
        "cli_rc": rc,
        "n_train": n_train,
        "n_test": n_test,
        "features": int(features.shape[1]),
        "train_error": float(train_err),
        "test_error": float(test_err),
        "repeat_test_error": float(repeat_err),
        "band": list(band),
        "cli_seconds": round(cli_seconds, 3),
        "first_seconds": round(first_seconds, 3),
        "repeat_seconds": round(repeat_seconds, 3),
        "sharding": {
            "train_rows": _placement(train.data.to_array()),
            "features": _placement(features),
            "W": _placement(_weights(fitted)),
        },
    }
    rows = np.asarray(test.data.to_array()[: min(n_test, 512)])
    return report, fitted, rows


def serve_leg(fitted, rows, *, buckets, n_requests):
    """``n_requests`` ``submit(...).result()`` calls against the public
    fleet, arriving three ways so more than one bucket fills: an open
    burst (half of them submitted back to back, then collected), 16
    closed-loop client threads, and a one-at-a-time trickle.

    With the AOT cache on, boot may also pre-warm signatures the fit
    exported (the manifest), so ``compiles + aot_loads`` is at least the
    bucket count at boot; the steady-state gate is that traffic adds
    none."""
    import numpy as np

    from keystone_tpu.obs import tracer as tracer_mod
    from keystone_tpu.serving import ServingFleet

    tracer = tracer_mod.start()
    picks = [i % len(rows) for i in range(n_requests)]
    expected = np.asarray(fitted.apply(rows).to_array()).ravel()
    span_mark = len(tracer.spans())

    def executables(fleet):
        c = fleet.metrics.snapshot()["counters"]
        return c.get("compiles", 0), c.get("aot_loads", 0)

    t0 = time.perf_counter()
    fleet = ServingFleet(fitted, buckets=buckets, datum_shape=rows.shape[1:])
    with fleet:
        boot_seconds = time.perf_counter() - t0
        compiles, aot_loads = executables(fleet)

        def one(i):
            return fleet.submit(rows[i], timeout=120.0).result()

        burst, closed, trickle = np.split(
            np.asarray(picks), [n_requests // 2, n_requests - n_requests // 8]
        )
        t0 = time.perf_counter()
        futures = [fleet.submit(rows[i], timeout=120.0) for i in burst]
        replies = [f.result() for f in futures]
        with ThreadPoolExecutor(max_workers=16) as pool:
            replies += list(pool.map(one, closed))
        replies += [one(i) for i in trickle]
        traffic_seconds = time.perf_counter() - t0
        recompiled = executables(fleet) != (compiles, aot_loads)
        snap = fleet.metrics.snapshot()
        n_replicas = fleet.n_replicas
        n_buckets = len(fleet.policy.batch_sizes)

    agree = int(np.sum(np.asarray(replies).ravel() == expected[picks]))
    per_replica = {
        str(i): row["batches"] for i, row in snap.get("replicas", {}).items()
    }
    buckets_filled = sorted({
        sp.attrs["bucket"]
        for sp in tracer.spans()[span_mark:]
        if sp.name == "serve.replica" and "bucket" in sp.attrs
    })
    completed = snap["counters"].get("completed", 0)
    return {
        "ok": bool(
            agree == n_requests
            and completed == n_requests
            and compiles + aot_loads >= n_buckets
            and not recompiled
            and len(per_replica) == n_replicas
            and all(b >= 1 for b in per_replica.values())
            and len(buckets_filled) > 1
        ),
        "requests": n_requests,
        "agree": agree,
        "completed": completed,
        "buckets": list(buckets),
        "buckets_filled": buckets_filled,
        "compiles": compiles,
        "aot_loads": aot_loads,
        "compiled_under_traffic": recompiled,
        "replicas": n_replicas,
        "per_replica_batches": per_replica,
        "boot_seconds": round(boot_seconds, 3),
        "traffic_seconds": round(traffic_seconds, 3),
    }


def conv_chain_leg(*, filters, images, side=32, interpret=False):
    """The fused conv → rectify → pool kernel (``ops/conv_rectify_pool.py``)
    against the XLA lowering of the three bodies it replaces
    (``Convolver`` → ``SymmetricRectifier`` → ``Pooler``), both at the
    backend's default precision: one bf16 pass on the chip. Compiled, the
    front door (``ConvRectifyPool.kernel_mode``) must pick the kernel for
    this shape; interpreted (the CPU rehearsal) the bodies' product is
    float32, so only the shape and finiteness are held."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from keystone_tpu.nodes.images.chain import ConvRectifyPool
    from keystone_tpu.nodes.images.core import (
        Convolver,
        Pooler,
        SymmetricRectifier,
    )
    from keystone_tpu.nodes.learning.zca import ZCAWhitener
    patch, channels = 6, 3
    m = patch * patch * channels
    kf, km, kx = jax.random.split(jax.random.PRNGKey(0), 3)
    conv = Convolver(
        0.1 * jax.random.normal(kf, (filters, m), jnp.float32),
        side, side, channels,
        whitener=ZCAWhitener(
            np.eye(m, dtype=np.float32),
            np.asarray(jax.random.normal(km, (m,), jnp.float32)),
        ),
        normalize_patches=True,
    )
    rect, pool = SymmetricRectifier(alpha=0.25), Pooler(13, 14, None, "sum")
    node = ConvRectifyPool(conv, rect, pool)
    X = jax.random.uniform(
        kx, (images, side, side, channels), jnp.float32, 0.0, 255.0
    )
    fused = jax.jit(lambda X: node.fused(X, interpret=interpret))
    t0 = time.perf_counter()
    got = jax.block_until_ready(fused(X))
    first_seconds = time.perf_counter() - t0
    t0 = time.perf_counter()
    jax.block_until_ready(fused(X))
    repeat_seconds = time.perf_counter() - t0
    want = jax.jit(lambda X: pool.trace_batch(
        rect.trace_batch(conv.trace_batch(X))
    ))(X)
    got, want = np.asarray(got), np.asarray(want)
    gap = float(np.max(np.abs(got - want)) / np.max(np.abs(want)))
    report = {
        "shape": {"filters": filters, "images": images, "side": side},
        "interpret": interpret,
        "finite": bool(np.isfinite(got).all()),
        "max_rel_gap_xla": gap,
        "first_seconds": round(first_seconds, 3),
        "repeat_seconds": round(repeat_seconds, 4),
    }
    ok = report["finite"] and got.shape == want.shape
    if not interpret:
        chosen = node.kernel_mode(X.shape) == "compiled" and bool(
            np.array_equal(np.asarray(jax.jit(node.trace_batch)(X)), got)
        )
        # as segment dispatch ships a program to another process
        # (compile/segment.py:_trace_and_export): exported, serialized,
        # read back, the same numbers
        from jax import export as jax_export

        shipped = jax_export.deserialize(bytearray(jax_export.export(
            jax.jit(node.trace_batch)
        )(jax.ShapeDtypeStruct(X.shape, X.dtype)).serialize()))
        report["matches_xla"] = gap <= 1e-5
        report["front_door_chose_kernel"] = chosen
        report["survives_export"] = bool(
            np.array_equal(np.asarray(jax.jit(shipped.call)(X)), got)
        )
        ok = ok and chosen and report["matches_xla"]
        ok = ok and report["survives_export"]
    report["ok"] = bool(ok)
    return report


#: voc_fv256's widths (benchmark/configs/voc_fv256.json) on as many images
#: as make 16,000 sampled descriptors for the PCA and for the codebook
FISHER_SHAPE = dict(images=8, x=500, y=375, dims=80, centres=256)

#: what ``fisher_leg`` allows, fixed BEFORE the chip run: descriptors are
#: whole numbers after a floor (an off-by-one where two summation orders
#: straddle one, on a thousandth of them at most); the basis is a float32
#: eigh against a float64 one; the features carry the basis and the
#: codebook through posteriors whose products run at three bf16 passes
#: here and at six in the reference; the sampled body's descriptors are
#: the full body's at the same columns but for the same floor
FISHER_GAPS = dict(
    descriptor_share=1e-3, basis=5e-3, codebook=5e-2, features=5e-2,
    sampled_share=1e-3,
)


def fisher_leg(*, images, x, y, dims, centres, per_image=2000):
    """Dense SIFT → sampled columns → PCA → k-means++ / EM → Fisher vectors
    → normalisations through the program's nodes, each as ``run`` uses it,
    against the benchmark's plain reference on the same seeded images (R8:
    an estimator a leg — ``pca.py``, ``kmeans.py``, ``gmm.py`` and
    ``sift.py`` had only CPU tests, where a float32 product is float32)."""
    import jax
    import numpy as np

    from benchmark import harness
    from keystone_tpu.data.dataset import Dataset
    from keystone_tpu.nodes.images import (
        GMMFisherVectorEstimator,
        GrayScaler,
        PixelScaler,
        SIFTExtractor,
    )
    from keystone_tpu.nodes.images.chain import SampledSIFTExtractor
    from keystone_tpu.nodes.learning import ColumnPCAEstimator
    from keystone_tpu.nodes.stats import (
        ColumnSampler,
        NormalizeRows,
        SignedHellingerMapper,
    )
    from keystone_tpu.nodes.util import MatrixVectorizer

    here = os.path.dirname(os.path.abspath(__file__))
    configs = os.path.join(here, "benchmark", "configs")
    ref = harness.load_module(os.path.join(configs, "voc_fv256_reference.py"))
    cfg = harness.load_json(os.path.join(configs, "voc_fv256.json"))
    cfg.update(
        image_x=x, image_y=y, desc_dim=dims, vocab_size=centres,
        n_train=images, num_pca_samples=images * per_image,
        num_gmm_samples=images * per_image, reference_slice=min(4, images),
        reference_rows=images,
    )
    X, _ = ref.make_rows(cfg, cfg["train_seed"], images)

    def jit(node):
        return jax.jit(node.trace_batch)

    t0 = time.perf_counter()
    gray = jit(GrayScaler())(jit(PixelScaler())(X))
    D = jit(SIFTExtractor())(gray)
    seed = cfg["sample_seed"]
    pca = ColumnPCAEstimator(dims).fit(
        ColumnSampler(per_image, seed=seed).apply_batch(Dataset.of(D))
    )
    P = jit(pca)(D)
    fv = GMMFisherVectorEstimator(
        centres, max_iterations=20, min_cluster_size=1
    ).fit(ColumnSampler(per_image, seed=seed + 1).apply_batch(Dataset.of(P)))
    F = jit(fv)(P)
    for node in (MatrixVectorizer(), NormalizeRows(), SignedHellingerMapper(),
                 NormalizeRows()):
        F = jit(node)(F)
    F = np.asarray(jax.block_until_ready(F))
    seconds = time.perf_counter() - t0

    # the sampling pass's own bodies: the index arithmetic where the numerics
    # are the chip's — a sparse sample is gathered bin by bin, one four times
    # as dense is read through the keypoint grid (``sampled_path``)
    sampled_gaps = []  # (path, widest gap, share of elements off)
    for count in (per_image, 4 * per_image):
        sampler = ColumnSampler(count, seed=seed)
        node = SampledSIFTExtractor(SIFTExtractor(), (), sampler)
        gap = np.abs(
            np.asarray(jit(node)(gray)) - np.asarray(jit(sampler)(D))
        )
        path = node.segment_facts(gray.shape, images)["sift_sampled_path"]
        sampled_gaps.append(
            (path, float(gap.max()), float(np.mean(gap != 0)))
        )

    want_D = ref.sift(cfg, X)
    book = ref.learn_codebook(cfg, X)
    want_F = np.asarray(ref.fisher_vectors(cfg, book, want_D, "highest"))
    got_D = np.asarray(D).transpose(0, 2, 1)
    want_D = np.asarray(want_D)

    def rel(a, b):
        a, b = np.asarray(a, np.float64), np.asarray(b, np.float64)
        return float(np.linalg.norm(a - b) / np.linalg.norm(b))

    report = {
        "shape": {"images": images, "x": x, "y": y, "dims": dims,
                  "centres": centres, "descriptors": int(got_D.shape[1])},
        "finite": bool(np.isfinite(F).all()),
        "descriptor_max_gap": float(np.abs(got_D - want_D).max()),
        "descriptor_share": float(np.mean(got_D != want_D)),
        "basis": rel(pca.pca_mat, book["basis"]),
        "codebook": max(
            rel(fv.gmm.means.T, book["means"]),
            rel(fv.gmm.variances.T, book["variances"]),
            rel(fv.gmm.weights, book["weights"]),
        ),
        "features": rel(F, want_F),
        "sampled_paths": sorted({path for path, _, _ in sampled_gaps}),
        "sampled_max_gap": max(gap for _, gap, _ in sampled_gaps),
        "sampled_share": max(share for _, _, share in sampled_gaps),
        "seconds_program": round(seconds, 3),
    }
    report["ok"] = bool(
        report["finite"] and F.shape == want_F.shape
        and report["descriptor_max_gap"] <= 1.0
        and report["sampled_max_gap"] <= 1.0
        and all(report[k] <= v for k, v in FISHER_GAPS.items())
    )
    return report


#: imagenet_fv16's widths (benchmark/configs/imagenet_fv16.json): one block
#: of 4,096 features, d + 256 rows (the primal path, a population covariance
#: of full rank), a chunk of 8 class systems, LCS on 256 × 256 images
WEIGHTED_SHAPE = dict(rows=4352, dims=4096, classes=8, held_out=512, images=4,
                      size=256)

#: what ``weighted_leg`` allows, fixed BEFORE the chip run: the scores of a
#: float32 pivoted LU at λ = 6e-5 against float64 systems (3.5e-6 was read
#: on the chip, PR 35; the cell's ``scores_gap`` has the featurizer's gap in
#: it and cannot hold the solver alone); LCS from
#: exact box sums on both sides, so what differs is a mean's and a root's
#: last rounding on values of 0..255
WEIGHTED_GAPS = dict(scores=1e-2, lcs=1e-2)


def weighted_leg(*, rows, dims, classes, held_out, images, size):
    """The class-weighted solve and LCS through the program's nodes against
    the benchmark's plain reference (R8: an estimator a leg —
    ``nodes/learning/weighted.py`` and ``nodes/images/lcs.py`` had only CPU
    tests, where a float32 product is float32 and a convolution is not one
    bf16 pass)."""
    import jax
    import numpy as np

    from benchmark import harness
    from keystone_tpu.data.dataset import Dataset
    from keystone_tpu.nodes.images import LCSExtractor
    from keystone_tpu.nodes.learning.weighted import (
        BlockWeightedLeastSquaresEstimator,
    )
    from keystone_tpu.obs import tracer as tracer_mod

    here = os.path.dirname(os.path.abspath(__file__))
    configs = os.path.join(here, "benchmark", "configs")
    ref = harness.load_module(
        os.path.join(configs, "imagenet_fv16_reference.py")
    )
    cfg = harness.load_json(os.path.join(configs, "imagenet_fv16.json"))
    cfg.update(
        num_classes=classes, d=dims, block_size=max(dims, cfg["block_size"]),
        image_x=size, image_y=size,
    )

    # rows of two unit-norm halves about class means, as the two Fisher
    # vectors of an image are
    rng = np.random.default_rng(0)
    centres = rng.standard_normal((classes, dims))

    def draw(n):
        y = rng.integers(0, classes, n)
        F = rng.standard_normal((n, dims)) + 0.5 * centres[y]
        half = dims // 2
        F[:, :half] /= np.linalg.norm(F[:, :half], axis=1, keepdims=True)
        F[:, half:] /= np.linalg.norm(F[:, half:], axis=1, keepdims=True)
        return F.astype(np.float32), y.astype(np.int32)

    (F, y), (F_test, _) = draw(rows), draw(held_out)
    Y = 2.0 * np.eye(classes, dtype=np.float32)[y] - 1.0
    t0 = time.perf_counter()
    # the path that ran is read from the solve's own span
    installed = tracer_mod.current()
    tracer = installed or tracer_mod.start()
    mark = len(tracer.spans())
    try:
        mapper = BlockWeightedLeastSquaresEstimator(
            cfg["block_size"], 1, cfg["lam"], cfg["mixture_weight"],
            num_features=dims,
        ).fit(Dataset.of(F), Dataset.of(Y))
    finally:
        if installed is None:
            tracer_mod.stop()
    W = np.concatenate([np.asarray(x) for x in mapper.xs], axis=0)
    seconds = time.perf_counter() - t0
    paths = [
        sp.attrs.get("path") for sp in tracer.spans()[mark:]
        if sp.name == "wls.block"
    ]
    want = ref.weighted_model(
        F, y, cfg, "highest", solve=ref.solve_direct
    )

    def scores(W, b):
        return np.asarray(F_test, np.float64) @ np.asarray(W, np.float64) + (
            np.asarray(b, np.float64)
        )

    S_got, S_want = scores(W, mapper.b), scores(want["W"], want["b"])

    X, _ = ref.make_rows(cfg, cfg["train_seed"], images)
    lcs = cfg["lcs"]
    got_lcs = np.asarray(jax.jit(
        LCSExtractor(lcs["stride"], lcs["border"], lcs["patch"]).trace_batch
    )(X))
    want_lcs = np.asarray(ref.lcs(cfg, X)).transpose(0, 2, 1)
    report = {
        "shape": {"rows": rows, "dims": dims, "classes": classes,
                  "images": images, "size": size,
                  "lcs_descriptors": int(got_lcs.shape[2])},
        "finite": bool(np.isfinite(W).all() and np.isfinite(got_lcs).all()),
        "paths": paths,
        "scores": float(
            np.linalg.norm(S_got - S_want) / np.linalg.norm(S_want)
        ),
        "labels_agree": float(
            np.mean(S_got.argmax(axis=1) == S_want.argmax(axis=1))
        ),
        "lcs": float(np.abs(got_lcs - want_lcs).max()),
        "lcs_scale": float(np.abs(want_lcs).max()),
        "seconds_program": round(seconds, 3),
    }
    report["ok"] = bool(
        report["finite"] and got_lcs.shape == want_lcs.shape
        and paths == ["primal"]
        and all(report[k] <= v for k, v in WEIGHTED_GAPS.items())
    )
    return report


def _fetch_scalar(x) -> None:
    """Read one element back to the host: the device stream has really
    completed when it arrives."""
    import numpy as np

    while getattr(x, "ndim", 0) > 0:
        x = x[0]
    np.asarray(x)


def sync_check(*, size, steps):
    """Does a timing that ends in ``block_until_ready`` agree with one
    that ends in a scalar read-back, on the same chain of dependent matmul
    dispatches?"""
    import jax
    import jax.numpy as jnp

    w = jax.random.normal(jax.random.PRNGKey(1), (size, size), jnp.float32)
    w = w / jnp.sqrt(size)
    step = jax.jit(lambda x: jnp.tanh(x @ w))

    def chain(finish):
        x = w
        t0 = time.perf_counter()
        for _ in range(steps):
            x = step(x)
        finish(x)
        return time.perf_counter() - t0

    chain(jax.block_until_ready)  # compile + warm
    block = min(chain(jax.block_until_ready) for _ in range(3))
    fetch = min(chain(_fetch_scalar) for _ in range(3))
    return {
        "size": size,
        "steps": steps,
        "block_until_ready_seconds": round(block, 4),
        "scalar_fetch_seconds": round(fetch, 4),
        "block_until_ready_synchronizes": bool(
            abs(block - fetch) <= 0.2 * max(block, fetch)
        ),
    }


def verdict_line(ok: bool, device: dict) -> str:
    """The last line of stdout: the two keys the driver's check reads and
    no others (the report line above it carries everything else)."""
    return json.dumps({
        "ok": bool(ok),
        "device": {
            "platform": str(device["platform"]),
            "kind": str(device["kind"]),
            "count": int(device["count"]),
        },
    })


def main() -> int:
    os.environ.setdefault("JAX_PLATFORMS", "tpu")
    device = require_tpu()

    import jax
    import jaxlib

    from keystone_tpu import compile as compile_mod

    started = time.perf_counter()
    counts = CompileCounts()
    cache_dir = jax.config.jax_compilation_cache_dir
    # exported StableHLO rides next to the XLA entries, so whoever places
    # the compile cache places the whole warm-boot state
    compile_mod.configure(os.path.join(cache_dir, "keystone_aot"))
    entries_before = _cache_entries(cache_dir)

    legs: dict = {}
    fitted = rows = None

    def leg(name, fn):
        """Run one leg; a raise is that leg's failure, not the script's —
        the other legs still report."""
        before = counts.snapshot()
        t0 = time.perf_counter()
        try:
            report = fn()
        except Exception as e:
            traceback.print_exc()
            report = {"ok": False, "error": f"{type(e).__name__}: {e}"}
        after = counts.snapshot()
        report["seconds"] = round(time.perf_counter() - t0, 3)
        report.update({k: after[k] - before[k] for k in after})
        legs[name] = report

    def fit():
        nonlocal fitted, rows
        report, fitted, rows = fit_leg(band=TEST_ERROR_BAND, **FULL)
        return report

    leg("fit", fit)
    leg("serve", lambda: serve_leg(
        fitted, rows, buckets=(8, 32, 128), n_requests=400
    ))
    leg("conv_chain", lambda: conv_chain_leg(**CONV_CHAIN_SHAPE))
    leg("fisher", lambda: fisher_leg(**FISHER_SHAPE))
    leg("weighted", lambda: weighted_leg(**WEIGHTED_SHAPE))
    leg("sync", lambda: {"ok": True, **sync_check(size=8192, steps=24)})

    fallbacks = fallbacks_fired()
    ok = all(report["ok"] for report in legs.values()) and not fallbacks
    report = {
        "ok": bool(ok),
        "device": device,
        "jax": jax.__version__,
        "jaxlib": jaxlib.__version__,
        "compile_cache_dir": cache_dir,
        "cache_entries_before": entries_before,
        "cache_entries_added": _cache_entries(cache_dir) - entries_before,
        **counts.snapshot(),
        "fallbacks_fired": fallbacks,
        "legs": legs,
        "peak_bytes_in_use": _peak_bytes(),
        "wall_seconds": round(time.perf_counter() - started, 3),
        "claim": None,
    }
    sys.stderr.flush()
    print(json.dumps(report))
    print(verdict_line(ok, device), flush=True)
    return 0 if ok else 1


if __name__ == "__main__":
    raise SystemExit(main())
