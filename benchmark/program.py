"""What the benchmark takes from the program besides its entry points:
the device it runs on and the fitted linear model, read back as plain
arrays. The adapters under ``configs/`` share these."""

from __future__ import annotations

import dataclasses
import sys
from typing import Any


@dataclasses.dataclass
class FitHandle:
    """What one fit job left: the pipeline ``run`` returned and the test
    error it reported."""

    pipeline: Any
    test_error: float


def fitted(handle: FitHandle):
    """The estimator-free pipeline (fit-once: the state ``run`` paid for)."""
    return handle.pipeline.fit()


def model(handle: FitHandle) -> dict:
    """The fitted linear model of the job, as plain arrays."""
    return linear_model(fitted(handle))


def linear_model(fitted) -> dict:
    """``{"W", "b", "mean"}`` of the fitted pipeline's block linear mapper
    (host numpy: the program keeps fitted parameters on the host)."""
    import numpy as np

    from keystone_tpu.nodes.learning.linear import BlockLinearMapper

    graph = fitted.graph
    for node in graph.nodes:
        op = graph.get_operator(node)
        if isinstance(op, BlockLinearMapper):
            return {
                "W": np.concatenate([np.asarray(x) for x in op.xs], axis=0),
                "b": np.asarray(op.b),
                "mean": np.concatenate(
                    [np.asarray(m) for m in op.feature_means], axis=0
                ),
            }
    raise LookupError("no BlockLinearMapper in the fitted graph")


def require_tpu(chips: int, peaks: dict) -> dict:
    """The device as jax reports it, with its row of the peaks table. Exits
    2 — printing no result — where jax finds no TPU, fewer chips than the
    cell asks for, or a ``device_kind`` that the table does not hold: a
    share of a made-up peak is not a measurement."""
    import jax

    try:
        devices = jax.devices()
    except RuntimeError as e:
        print(f"benchmark: no accelerator: {e}", file=sys.stderr)
        raise SystemExit(2)
    found = {
        "platform": devices[0].platform,
        "kind": devices[0].device_kind,
        "count": len(devices),
    }
    if found["platform"] != "tpu":
        print(
            f"benchmark: need platform tpu, found {found['platform']} "
            f"({found['kind']} x{found['count']}) — a time from another "
            "device is not a benchmark number",
            file=sys.stderr,
        )
        raise SystemExit(2)
    if found["count"] < chips:
        print(
            f"benchmark: the cell asks for {chips} chips, jax found "
            f"{found['count']}", file=sys.stderr,
        )
        raise SystemExit(2)
    if found["kind"] not in peaks:
        print(
            f"benchmark: no published peaks on record for device_kind "
            f"{found['kind']!r} (known: {sorted(peaks)})", file=sys.stderr,
        )
        raise SystemExit(2)
    return found
