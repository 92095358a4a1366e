"""``apply_loop``: batch scoring — ``fitted.apply`` over a device-resident
scoring set in fixed chunks, pass after pass. A pass dispatches every
chunk and then waits for all of them, as a scoring job does."""

from __future__ import annotations

import time

import numpy as np

from benchmark import compare
from benchmark.drivers import common


def _split(X, chunk_rows: int) -> list:
    """The set as a list of chunk arrays: slicing inside the window would
    compile one program a chunk."""
    import jax

    from benchmark import refmath

    if X.shape[0] % chunk_rows:
        raise ValueError(
            f"{X.shape[0]} rows do not split into chunks of {chunk_rows}"
        )
    return jax.block_until_ready(list(refmath.row_blocks(X, chunk_rows)))


def _score(fitted, chunk):
    return fitted.apply(chunk).to_array()


def setup(run):
    import jax

    tr = run.traffic
    with run.phases("datagen"):
        X_fit, y_fit = common.train_rows(run, tr["fit_rows"])
        # the fit's held-out rows are not scored: their labels are not read
        held_out, _ = common.seed_rows(run, tr["fit_test_rows"])
        y_held = np.zeros((tr["fit_test_rows"],), np.int32)
    with run.phases("fit"):
        handle = run.program.fit(
            run.config, X_fit, common.host_labels(y_fit), held_out, y_held
        )
        fitted = run.program.fitted(handle)
    del X_fit, y_fit, held_out, handle
    with run.phases("datagen"):  # after the fit: the two never share HBM
        X_score, _ = common.seed_rows(run, tr["score_rows"])
        chunks = _split(X_score, tr["chunk_rows"])
        del X_score
    with run.phases("warmup"):  # one chunk: the window's one shape
        jax.block_until_ready(_score(fitted, chunks[0]))
    return {"fitted": fitted, "chunks": chunks}


def window(run, state, seconds: float):
    import jax

    fitted, chunks = state["fitted"], state["chunks"]
    passes = []
    t0 = time.perf_counter()
    while True:
        labels = []
        for chunk in chunks:
            with jax.profiler.TraceAnnotation("bench:apply.chunk"):
                labels.append(_score(fitted, chunk))
        with jax.profiler.TraceAnnotation("bench:apply.wait"):
            jax.block_until_ready(labels)
        passes.append(labels)
        elapsed = time.perf_counter() - t0
        if elapsed >= seconds:
            break
    rows = len(passes) * sum(c.shape[0] for c in chunks)
    run.facts.update(
        apply_rows_per_s=rows / elapsed, rows=rows, units=rows,
        passes=len(passes), window_s=elapsed, attempted=rows, failed=0,
    )
    last = [np.asarray(x) for x in passes[-1]]
    differ = sum(
        int(np.sum(np.asarray(x) != ref))
        for labels in passes[:-1] for x, ref in zip(labels, last)
    )
    return {"labels": last, "passes_differ": differ}


def release(run, state):
    common.release(state)


def check(run, produced) -> dict:
    tr, cfg = run.traffic, run.config
    X_fit, y_fit = common.train_rows(run, tr["fit_rows"])
    X_score, _ = common.seed_rows(run, tr["score_rows"])
    ref_model = run.reference.fit(cfg, X_fit, y_fit, precision=compare.HIGHEST)
    feat = run.reference.featurizer(cfg, "highest")
    from benchmark import refmath

    step = tr.get("reference_rows", 16384)
    rows = refmath.row_blocks(X_score, step)
    labels = np.concatenate(produced["labels"])
    numbers = compare.label_numbers(
        feat, ref_model, rows,
        [labels[i : i + step] for i in range(0, labels.shape[0], step)],
    )
    numbers["passes_differ"] = produced["passes_differ"]
    run.facts["compared_all"] = numbers
    return compare.with_limits(numbers, run.limits)


def control(run, precision: dict) -> dict:
    """What the reference, computed at ``precision``, puts in the program's
    place: the labels of one pass over the scoring set."""
    from benchmark import refmath

    tr, cfg = run.traffic, run.config
    X_fit, y_fit = common.train_rows(run, tr["fit_rows"])
    X_score, _ = common.seed_rows(run, tr["score_rows"])
    model = run.reference.fit(cfg, X_fit, y_fit, precision=precision)
    labels = compare.reference_labels(
        run.reference.featurizer(cfg, precision["featurizer"]), model,
        refmath.row_blocks(X_score, tr["chunk_rows"]), precision["apply"],
    )
    return {"labels": labels, "passes_differ": 0}
