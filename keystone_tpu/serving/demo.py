"""``--serve-demo``: fit a small pipeline, push synthetic traffic through
the engine — or, with ``--replicas N``, through a continuous-batching
:class:`~keystone_tpu.serving.fleet.ServingFleet`, or, with
``--workers N`` (or ``KEYSTONE_WORKERS``), through the multi-process
:class:`~keystone_tpu.cluster.ClusterRouter` — print the metrics
snapshot. The smoke path behind ``bin/serve-smoke.sh`` and the CLI's
``--serve-demo`` flag.
"""

from __future__ import annotations

import argparse
from concurrent.futures import ThreadPoolExecutor
from typing import List, Optional

import numpy as np

from ..utils import env_int


def build_demo_fitted(
    num_ffts: int = 2,
    block_size: int = 512,
    lam: float = 100.0,
    n_train: int = 2048,
    n_test: int = 64,
):
    """The smoke serving pipeline: deterministic synthetic MNIST + random-FFT
    featurizer + block least squares + argmax. Deterministic end to end, so
    two processes building it get the SAME fitted parameters — and the same
    AOT fingerprint, which is what lets the cold-start bench's second
    process boot from the first one's exported executables. Returns
    ``(fitted, test_data)``."""
    import numpy as np

    from ..nodes.util import ClassLabelIndicators, MaxClassifier
    from ..nodes.learning.linear import BlockLeastSquaresEstimator
    from ..pipelines.mnist_random_fft import (
        NUM_CLASSES,
        MnistRandomFFTConfig,
        build_featurizer,
        synthetic_mnist_device,
    )

    conf = MnistRandomFFTConfig(
        num_ffts=num_ffts, block_size=block_size, lam=lam
    )
    train, test = synthetic_mnist_device(n_train=n_train, n_test=max(n_test, 64))
    labels = ClassLabelIndicators(NUM_CLASSES).apply_batch(train.labels)
    fitted = (
        build_featurizer(conf)
        .and_then(
            BlockLeastSquaresEstimator(conf.block_size, 1, conf.lam or 0.0),
            train.data, labels,
        )
        .and_then(MaxClassifier())
        .fit()
    )
    return fitted, np.asarray(test.data.to_array())


def _serve_through_cluster(args, buckets) -> int:
    """The ``--workers N`` path: a ClusterRouter over N worker processes,
    each rebuilding the SAME deterministic pipeline (same fingerprint ⇒
    warm boot from the shared AOT cache when one is configured) and
    serving it from a local fleet of ``--replicas`` replicas.

    This parent never initializes a jax backend: an accelerator chip
    belongs to one process, and the workers need it. So its request rows
    are seeded numpy, and its check is consistency instead of a local
    ``fitted.apply``: every row is served twice, under different arrival
    orders and batch-mates (with several workers, by whichever process
    the router picks), and the two replies must agree."""
    from jax._src import xla_bridge

    from .. import compile as compile_mod
    from ..cluster import ClusterRouter
    from ..pipelines.mnist_random_fft import MNIST_IMAGE_SIZE

    held_backend = xla_bridge.backends_are_initialized()
    data = np.random.default_rng(0).standard_normal(
        (args.requests, MNIST_IMAGE_SIZE)
    ).astype(np.float32)
    # --tenants "gold:3,bronze:1": weighted-fair shares in the worker
    # fleets, traffic round-robined across the named tenants so the
    # --status QoS section has shares to render
    tenant_weights = None
    if args.tenants:
        tenant_weights = {}
        for part in args.tenants.split(","):
            name, _, w = part.partition(":")
            tenant_weights[name.strip()] = float(w) if w else 1.0
    tenant_names = list(tenant_weights) if tenant_weights else None
    cache = compile_mod.get_cache()
    router = ClusterRouter(
        ("factory", "keystone_tpu.cluster.demo:build_demo_model", {
            "num_ffts": args.numFFTs, "block_size": args.blockSize,
            "lam": args.lam, "n_train": args.nTrain,
        }),
        workers=args.workers,
        replicas_per_worker=max(1, args.replicas),
        buckets=buckets,
        datum_shape=data.shape[1:],
        max_queue=args.maxQueue,
        max_wait_ms=args.maxWaitMs,
        aot_cache=cache.root if cache is not None else None,
        tenant_weights=tenant_weights,
    )
    router.install_signal_handlers()

    def _one(i_row):
        i, row = i_row
        tenant = (
            tenant_names[i % len(tenant_names)] if tenant_names else None
        )
        return router.submit(row, timeout=120.0, tenant=tenant).result()

    with router:
        with ThreadPoolExecutor(max_workers=args.clients) as pool:
            preds = list(pool.map(_one, enumerate(data)))
            again = list(pool.map(_one, reversed(list(enumerate(data)))))
        snap = router.snapshot()
        reports = [r for r in router.worker_reports if r]
        if args.status:
            from ..cluster import format_status

            # the fleet-wide timeline view: per-process metrics
            # timelines, worker liveness/restart budgets, SLO verdicts
            # (reuses the snapshot above — one stats round-trip, not two)
            print(format_status(router.status(snap=snap)))
    agree = int(np.sum(
        np.asarray(preds).ravel() == np.asarray(again[::-1]).ravel()
    ))
    c = snap["counters"]
    lat = snap["latency"]
    compiles = sum(r.get("compiles", 0) for r in reports)
    aot_loads = sum(r.get("aot_loads", 0) for r in reports)
    worker_batches = {}
    for key, row in snap.get("replicas", {}).items():
        w = key.split("/")[0]
        worker_batches[w] = worker_batches.get(w, 0) + row.get("batches", 0)
    print(
        f"SERVE ok={agree}/{len(data)} compiles={compiles} "
        f"aot_loads={aot_loads} batches={c.get('batches', 0)} "
        f"completed={c.get('completed', 0)} "
        f"p50={lat.get('p50', 0):.4f}s p99={lat.get('p99', 0):.4f}s "
        f"workers={args.workers} shed={c.get('shed', 0)} "
        f"restarts={c.get('restarts', 0)} "
        f"per_worker_batches={worker_batches} "
        f"platform={','.join(sorted({str(r.get('platform')) for r in reports}))}"
    )
    ok = agree == len(data) and c.get("completed", 0) == 2 * len(data)
    if xla_bridge.backends_are_initialized() and not held_backend:
        print("SERVE FAIL: the router parent initialized a jax backend")
        ok = False
    if len(reports) < args.workers:
        print(f"SERVE FAIL: only {len(reports)}/{args.workers} workers ready")
        ok = False
    # the router must actually spread load: every worker PROCESS served
    # at least one micro-batch
    if len(worker_batches) < args.workers or any(
        b < 1 for b in worker_batches.values()
    ):
        print(f"SERVE FAIL: idle worker (batches {worker_batches})")
        ok = False
    if args.expect_zero_compiles and compiles != 0:
        print(
            f"SERVE FAIL: warm worker boots paid {compiles} trace(s), "
            "expected 0 (shared AOT cache + manifest)"
        )
        ok = False
    print("SERVE " + ("PASS" if ok else "FAIL"))
    return 0 if ok else 1


def main(argv: Optional[List[str]] = None) -> int:
    p = argparse.ArgumentParser("keystone-tpu serve-demo")
    p.add_argument("--numFFTs", type=int, default=2)
    p.add_argument("--blockSize", type=int, default=512)
    p.add_argument("--lambda", dest="lam", type=float, default=100.0)
    p.add_argument("--nTrain", type=int, default=2048)
    p.add_argument("--requests", type=int, default=64)
    p.add_argument(
        "--replicas", type=int, default=1,
        help="serve from a ServingFleet of N replica workers (continuous "
             "batching + work stealing) instead of the single-worker "
             "engine; default 1 = ServingEngine",
    )
    p.add_argument(
        "--workers", type=int,
        default=env_int("KEYSTONE_WORKERS", 0, minimum=0),
        help="serve from a multi-process ClusterRouter of N worker "
             "processes (each a local fleet of --replicas workers, "
             "sharing the AOT cache dir for warm boots); default 0 = "
             "in-process serving (also: KEYSTONE_WORKERS)",
    )
    p.add_argument("--buckets", default="8,32",
                   help="comma-separated static batch-size buckets")
    p.add_argument("--maxQueue", type=int, default=256)
    p.add_argument("--maxWaitMs", type=float, default=2.0)
    p.add_argument("--clients", type=int, default=8,
                   help="concurrent submitter threads")
    p.add_argument(
        "--status", action="store_true",
        help="with --workers N: print the fleet-wide status/timeline "
             "view (ClusterRouter.status() rendered — per-process "
             "metrics timelines, worker liveness, SLO verdicts) after "
             "the traffic drains",
    )
    p.add_argument(
        "--tenants", default=None,
        help="with --workers N: 'name:weight,...' — weighted-fair tenant "
             "shares in the worker fleets; demo traffic round-robins the "
             "names, and --status renders per-tenant served shares",
    )
    p.add_argument(
        "--expect-zero-compiles", action="store_true",
        dest="expect_zero_compiles",
        help="fail unless warm-up paid ZERO pipeline traces — the warm-"
             "boot assertion for a populated AOT cache (--aot-cache / "
             "KEYSTONE_AOT_CACHE): every bucket must load its executable",
    )
    args = p.parse_args(argv)
    buckets = tuple(int(b) for b in args.buckets.split(","))

    if args.workers > 0:
        return _serve_through_cluster(args, buckets)

    from ..parallel.mesh import report_platform
    from .engine import ServingEngine
    from .fleet import ServingFleet

    report_platform()
    fitted, test_data = build_demo_fitted(
        num_ffts=args.numFFTs, block_size=args.blockSize, lam=args.lam,
        n_train=args.nTrain, n_test=args.requests,
    )
    data = test_data[: args.requests]
    if args.replicas > 1:
        engine = ServingFleet(
            fitted,
            replicas=args.replicas,
            buckets=buckets,
            datum_shape=data.shape[1:],
            max_queue=args.maxQueue,
            max_wait_ms=args.maxWaitMs,
        )
    else:
        engine = ServingEngine(
            fitted,
            buckets=buckets,
            datum_shape=data.shape[1:],
            max_queue=args.maxQueue,
            max_wait_ms=args.maxWaitMs,
        )
    with engine:
        with ThreadPoolExecutor(max_workers=args.clients) as pool:
            preds = list(pool.map(lambda row: engine.predict(row, timeout=60.0), data))

    expected = np.asarray(fitted.apply(data).to_array()) if len(data) else np.array([])
    agree = int(np.sum(np.asarray(preds).ravel() == expected.ravel()))
    snap = engine.metrics.snapshot()
    c = snap["counters"]
    lat = snap["latency"]
    occ = snap["batch_occupancy"]["ratio"]
    compiles = c.get("compiles", 0)
    aot_loads = c.get("aot_loads", 0)
    per_replica = {
        idx: row["batches"] for idx, row in snap.get("replicas", {}).items()
    }
    print(
        f"SERVE ok={agree}/{len(data)} compiles={compiles} "
        f"aot_loads={aot_loads} "
        f"batches={c.get('batches', 0)} completed={c.get('completed', 0)} "
        f"occupancy={'n/a' if occ is None else format(occ, '.3f')} "
        f"p50={lat.get('p50', 0):.4f}s p99={lat.get('p99', 0):.4f}s"
        + (
            f" replicas={args.replicas} shed={c.get('shed', 0)} "
            f"steals={c.get('steals', 0)} per_replica_batches={per_replica}"
            if args.replicas > 1 else ""
        )
    )
    ok = agree == len(data) and c.get("completed", 0) == len(data)
    if args.replicas == 1:
        # every bucket's executable arrived exactly once — traced live or
        # loaded from the AOT cache (policy dedups bucket sizes, so
        # compare against what it kept)
        ok = ok and compiles + aot_loads == len(engine.policy.batch_sizes)
    else:
        # the fleet shares ONE dispatcher across replicas, so the
        # per-bucket identity is replica-count-independent — but manifest
        # pre-warm may ADD signatures beyond the buckets, hence >=
        ok = ok and compiles + aot_loads >= len(engine.policy.batch_sizes)
        # the continuous-batching fleet must actually spread load: every
        # replica worker executed at least one micro-batch (work stealing
        # makes this robust — an idle replica steals from a busy one)
        if len(per_replica) < args.replicas or any(
            b < 1 for b in per_replica.values()
        ):
            print(f"SERVE FAIL: idle replica (batches {per_replica})")
            ok = False
    if args.expect_zero_compiles and compiles != 0:
        print(f"SERVE FAIL: warm boot paid {compiles} trace(s), expected 0")
        ok = False
    print("SERVE " + ("PASS" if ok else "FAIL"))
    return 0 if ok else 1


if __name__ == "__main__":
    raise SystemExit(main())
