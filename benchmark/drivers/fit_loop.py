"""``fit_loop``: whole fit jobs back to back on device-resident data — a
fit is a batch job, so the loop is closed and has one client. Every job
builds fresh estimators, starts with the last job's garbage collected and
ends synchronised. ``fit_s`` is the whole window over its jobs."""

from __future__ import annotations

import gc
import statistics
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

    job_s = []
    handle = None
    t0 = start = time.perf_counter()
    while True:
        with jax.profiler.TraceAnnotation("bench:fit.step"):
            del handle  # two jobs' features do not fit side by side
            # A job starts as in a process of its own: with none of an
            # earlier job's garbage. Left to the collector's own schedule,
            # where its young passes fall among the jobs decides which jobs
            # take 586 ms and which 606-620, a pattern that moves as a
            # whole with any change to what the process allocates (PERF.md
            # section 6, PR 28). 0.1 ms, inside the timed window; the
            # collector stays on.
            gc.collect(1)
            handle = _job(run, state)
        now = time.perf_counter()
        job_s.append(now - start)
        start = now
        if now - t0 >= seconds:
            break
    fits, elapsed = len(job_s), now - t0
    # fit_s is all of the window over all of its jobs: a stalled job is a
    # job the user paid. The steadier statistics stand beside it.
    run.facts.update(
        fit_s=elapsed / fits, fit_median_s=statistics.median(job_s),
        fits=fits, units=fits, window_s=elapsed, attempted=fits, failed=0,
        job_ms=[round(1e3 * s, 2) for s in job_s],
    )
    if fits > 10:  # the highest percentile with ten jobs beyond it
        run.facts["fit_p_high_s"] = sorted(job_s)[-11]
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
