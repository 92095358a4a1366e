"""``fit_loop``: whole fit jobs back to back on device-resident data — a
fit is a batch job, so the loop is closed and has one client. Every job
builds fresh estimators and ends synchronised."""

from __future__ import annotations

import time

import numpy as np

from benchmark import compare
from benchmark.drivers import common


def _job(run, state):
    return run.program.fit(
        run.config, state["X_train"], state["y_train"], state["X_test"],
        state["y_test"],
    )


def setup(run):
    cfg = run.config
    with run.phases("datagen"):
        X_train, y_train = common.train_rows(run, cfg["n_train"])
        X_test, y_test = common.seed_rows(run, cfg["n_test"])
        state = {
            "X_train": X_train, "y_train": common.host_labels(y_train),
            "X_test": X_test, "y_test": common.host_labels(y_test),
        }
    with run.phases("fit"):  # the one warm-up call: every shape of a job
        _job(run, state)
    return state


def window(run, state, seconds: float):
    import jax

    fits = 0
    handle = None
    t0 = time.perf_counter()
    while True:
        with jax.profiler.TraceAnnotation("bench:fit.step"):
            del handle  # two jobs' features do not fit side by side
            handle = _job(run, state)
        fits += 1
        elapsed = time.perf_counter() - t0
        if elapsed >= seconds:
            break
    run.facts.update(
        fit_s=elapsed / fits, fits=fits, units=fits, window_s=elapsed,
        attempted=fits, failed=0,
    )
    model = run.program.model(handle)
    return {"model": model, "test_error": handle.test_error}


def release(run, state):
    common.release(state)


def check(run, produced) -> dict:
    cfg = run.config
    numbers = compare.fit_numbers(
        cfg, run.reference, common.train_rows(run, cfg["n_train"]),
        common.seed_rows(run, cfg["n_test"]), produced["model"],
        produced["test_error"],
    )
    run.facts["compared_all"] = numbers
    return compare.with_limits(numbers, run.limits)


def control(run, precision: dict, *, rows=None) -> dict:
    """What the reference, computed at ``precision``, puts in the program's
    place: its model and the test error it reports. ``rows`` limits the fit
    to the first so many training rows (the half-batch fault)."""
    import jax.numpy as jnp

    from benchmark import refmath

    cfg = run.config
    X_train, y_train = common.train_rows(run, cfg["n_train"])
    X_test, y_test = common.seed_rows(run, cfg["n_test"])
    if rows is not None:
        X_train, y_train = X_train[:rows], y_train[:rows]
    model = run.reference.fit(cfg, X_train, y_train, precision=precision)
    S = refmath.scores(
        run.reference.featurizer(cfg, precision["featurizer"]), X_test,
        model, rows_per_block=8192, precision=precision["apply"],
    )
    error = float(jnp.mean(jnp.argmax(S, axis=1) != y_test))
    return {
        "model": {k: np.asarray(v) for k, v in model.items()},
        "test_error": error,
    }
