"""Cold-start probe: boot a serving engine against an AOT cache dir and
report what warm-up cost, as one JSON line on stdout.

This is the measurement half of the ``serve_cold_start`` bench section
and of ``bin/serve-smoke.sh``'s second boot: the driver runs this module
in a FRESH subprocess twice against the same ``--cache`` dir — the first
boot traces and exports every bucket (cold), the second must load every
bucket and pay zero traces (warm). Everything process-local that could
mask the effect (jax's in-memory jit cache, the backend) is fresh by
construction because the process is.

The probe also verifies correctness, not just speed: a handful of
predictions served through the (possibly cache-loaded) engine must be
bit-equal to ``FittedPipeline.apply`` on the same rows — a cache that
boots fast but serves a different model must fail here, loudly.

Usage::

    python -m keystone_tpu.compile.coldstart --cache /tmp/aot [--buckets 8,32]

Output (one line)::

    {"platform": "cpu", "construct_seconds": ..., "warmup_seconds": ...,
     "compiles": N, "aot_loads": M, "buckets": [...],
     "outputs_match": true, ...}
"""

from __future__ import annotations

import argparse
import json
import time
from typing import List, Optional


def main(argv: Optional[List[str]] = None) -> int:
    p = argparse.ArgumentParser("keystone-tpu coldstart probe")
    p.add_argument("--cache", required=True, help="AOT executable cache dir")
    p.add_argument("--buckets", default="8,32")
    p.add_argument("--numFFTs", type=int, default=2)
    p.add_argument("--blockSize", type=int, default=512)
    p.add_argument("--nTrain", type=int, default=512)
    p.add_argument("--requests", type=int, default=16)
    args = p.parse_args(argv)
    buckets = tuple(int(b) for b in args.buckets.split(","))

    t_proc0 = time.perf_counter()
    from ..utils.obs import configure

    configure(aot_cache=args.cache)

    import numpy as np

    from ..parallel.mesh import device_summary
    from ..serving.demo import build_demo_fitted
    from ..serving.engine import ServingEngine

    # the fit is deterministic but NOT what this probe measures — serving
    # replicas load a fitted model; they don't refit it
    fitted, test_data = build_demo_fitted(
        num_ffts=args.numFFTs, block_size=args.blockSize,
        n_train=args.nTrain, n_test=args.requests,
    )

    t0 = time.perf_counter()
    engine = ServingEngine(fitted, buckets=buckets)
    construct_seconds = time.perf_counter() - t0

    t0 = time.perf_counter()
    warmed = engine.warm_up(required=True)
    warmup_seconds = time.perf_counter() - t0

    data = test_data[: args.requests]
    engine.start(warmup=False)  # already warm; don't re-run (nor re-count)
    try:
        preds = [engine.predict(row, timeout=60.0) for row in data]
    finally:
        engine.shutdown()
    expected = np.asarray(fitted.apply(data).to_array())
    outputs_match = bool(
        np.array_equal(np.asarray(preds).ravel(), expected.ravel())
    )

    counters = engine.metrics.snapshot()["counters"]
    print(
        json.dumps(
            {
                "platform": device_summary()["platform"],
                "construct_seconds": round(construct_seconds, 4),
                "warmup_seconds": round(warmup_seconds, 4),
                "buckets_warmed": warmed,
                "buckets": list(engine.policy.batch_sizes),
                "compiles": counters.get("compiles", 0),
                "aot_loads": counters.get("aot_loads", 0),
                "requests": len(data),
                "outputs_match": outputs_match,
                "process_seconds": round(time.perf_counter() - t_proc0, 4),
            }
        )
    )
    return 0 if outputs_match else 1


if __name__ == "__main__":
    raise SystemExit(main())
