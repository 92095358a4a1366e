"""``serve_open_loop``: the fitted pipeline behind ``ServingFleet``, one
item a request, sent on a schedule whether or not earlier ones have
finished. The rate is fixed in the traffic file; every seed sends the same
set of gaps and rows in another order. Each request is timed from when it
was DUE, so a generator or a server that falls behind shows in the tail.
"""

from __future__ import annotations

import math
import threading
import time

import numpy as np

from benchmark import compare
from benchmark.drivers import common


def schedule(traffic: dict, seed: int, seconds: float, n_rows: int):
    """``(due, picks)``: seconds after the window opens at which each
    request is due, and the row each sends. The gaps are the quantiles of
    the exponential distribution at ``rate_per_s`` — the same set whatever
    the seed, so every run offers the same load — in an order drawn from
    the seed."""
    rate = float(traffic["rate_per_s"])
    n = max(int(round(rate * seconds)), 1)
    if traffic.get("arrivals", "poisson") != "poisson":
        raise ValueError(f"arrivals {traffic['arrivals']!r}: only poisson")
    gaps = -np.log1p(-(np.arange(n) + 0.5) / n) / rate
    rng = np.random.default_rng(seed)
    due = np.cumsum(rng.permutation(gaps))
    picks = rng.permutation(max(n, n_rows))[:n] % n_rows
    return due, picks


def setup(run):
    from keystone_tpu.serving import ServingFleet

    cfg, tr = run.config, run.traffic
    with run.phases("datagen"):
        X_train, y_train = common.train_rows(run, cfg["n_train"])
        X_test, y_test = common.seed_rows(run, cfg["n_test"])
        rows = np.asarray(X_test)  # a client sends host rows
    with run.phases("fit"):
        handle = run.program.fit(
            cfg, X_train, common.host_labels(y_train), X_test,
            common.host_labels(y_test),
        )
        fitted = run.program.fitted(handle)
    del X_train, y_train, X_test, y_test, handle
    with run.phases("fleet_boot"):
        fleet = ServingFleet(
            fitted, replicas=tr["replicas"], buckets=tuple(tr["buckets"]),
            datum_shape=rows.shape[1:],
        )
        fleet.start()  # compiles and runs one batch of every bucket
    with run.phases("warmup"):  # a fixed number of requests, one by one
        for i in range(tr["warmup_requests"]):
            fleet.submit(rows[i % len(rows)], timeout=tr["timeout_s"]).result()
    return {"fleet": fleet, "rows": rows}


def offer(run, fleet, rows, due, picks, *, timeout_s: float, traced: bool):
    """Send the schedule and wait for every reply. Returns per request the
    seconds from due to done (``inf`` where it failed), how late it was
    sent, and its reply."""
    import jax

    n = len(due)
    done = np.full(n, np.nan)
    late = np.zeros(n)
    futures: list = [None] * n
    contexts = None
    if traced:
        from keystone_tpu.obs.context import TraceContext, new_trace_id

        contexts = [TraceContext(trace_id=new_trace_id(i)) for i in range(n)]

    def stamp(i):
        def on_done(_):
            done[i] = time.perf_counter()
        return on_done

    t0 = time.perf_counter()
    for i in range(n):
        at = t0 + due[i]
        with jax.profiler.TraceAnnotation("bench:loadgen.wait"):
            while True:
                wait = at - time.perf_counter()
                if wait <= 0:
                    break
                if wait > 0.0005:
                    time.sleep(wait - 0.0003)
        with jax.profiler.TraceAnnotation("bench:loadgen.submit"):
            late[i] = time.perf_counter() - at
            try:
                fut = fleet.submit(
                    rows[picks[i]], timeout=timeout_s,
                    trace=contexts[i] if traced else None,
                )
            except Exception as e:  # QueueFull / Shed: a refusal is a miss
                futures[i] = e
                continue
            fut.add_done_callback(stamp(i))
            futures[i] = fut
    replies = np.full(n, -1, np.int64)
    failed = 0
    with jax.profiler.TraceAnnotation("bench:serve.drain"):
        for i, fut in enumerate(futures):
            if isinstance(fut, Exception):
                failed += 1
                continue
            try:
                replies[i] = int(
                    np.asarray(fut.result(timeout=timeout_s)).reshape(-1)[0]
                )
            except Exception:
                failed += 1
                replies[i] = -1
    elapsed = time.perf_counter() - t0
    # the callback runs on the replica's thread right after the result is
    # set; a reply that was read before its stamp landed is done by now
    done = np.where(np.isnan(done), time.perf_counter(), done)
    latency = np.where(replies >= 0, done - (t0 + due), np.inf)
    return latency, late, replies, failed, elapsed


def percentile(values, q: float) -> float:
    """The q-th percentile over ALL requests, a failed one counting as the
    slowest (nearest rank, so an ``inf`` stays an ``inf``)."""
    ordered = np.sort(np.asarray(values))
    rank = max(int(math.ceil(q / 100.0 * len(ordered))) - 1, 0)
    return float(ordered[rank])


def window(run, state, seconds: float):
    tr = run.traffic
    fleet, rows = state["fleet"], state["rows"]
    due, picks = schedule(tr, run.seed, seconds, len(rows))
    tracer = None
    if run.trace:
        from keystone_tpu.obs import tracer as tracer_mod

        tracer = tracer_mod.start()
        mark = len(tracer.spans())
    before = fleet.metrics.snapshot()
    latency, late, replies, failed, elapsed = offer(
        run, fleet, rows, due, picks, timeout_s=tr["timeout_s"],
        traced=run.trace,
    )
    after = fleet.metrics.snapshot()
    if tracer is not None:
        run.spans = [
            (sp.name, sp.start, sp.end, dict(sp.attrs))
            for sp in tracer.spans()[mark:]
        ]
        tracer_mod.stop()
    run.registry = {"before": before, "after": after}
    finite = np.where(np.isfinite(latency), latency, tr["timeout_s"])
    run.facts.update(
        serve_p50_ms=1e3 * percentile(finite, 50),
        serve_p95_ms=1e3 * percentile(finite, 95),
        serve_p99_ms=1e3 * percentile(finite, 99),
        late_ms_p99=1e3 * percentile(late, 99),
        completed_per_s=(len(due) - failed) / elapsed,
        units=len(due), window_s=elapsed, attempted=len(due), failed=failed,
        backlog_at_close=int(np.sum(latency + due > due[-1])),
    )
    return {"picks": picks, "replies": replies}


def release(run, state):
    fleet = state.pop("fleet", None)
    if fleet is not None:
        fleet.shutdown()
    common.release(state)


def check(run, produced) -> dict:
    import jax.numpy as jnp

    cfg = run.config
    X_train, y_train = common.train_rows(run, cfg["n_train"])
    X_test, _ = common.seed_rows(run, cfg["n_test"])
    ref_model = run.reference.fit(
        cfg, X_train, y_train, precision=compare.HIGHEST
    )
    feat = run.reference.featurizer(cfg, "highest")
    answered = produced["replies"] >= 0
    picks = jnp.asarray(produced["picks"][answered])
    numbers = compare.label_numbers(
        feat, ref_model, [X_test[picks]],
        [produced["replies"][answered]],
    )
    numbers["unanswered"] = int(np.sum(~answered))
    run.facts["compared_all"] = numbers
    return compare.with_limits(numbers, run.limits)


def control(run, precision: dict) -> dict:
    """What the reference, computed at ``precision``, puts in the program's
    place: the replies to one window's requests."""
    cfg = run.config
    X_train, y_train = common.train_rows(run, cfg["n_train"])
    X_test, _ = common.seed_rows(run, cfg["n_test"])
    model = run.reference.fit(cfg, X_train, y_train, precision=precision)
    labels = compare.reference_labels(
        run.reference.featurizer(cfg, precision["featurizer"]), model,
        [X_test], precision["apply"],
    )[0]
    _, picks = schedule(run.traffic, run.seed, run.seconds, X_test.shape[0])
    return {"picks": picks, "replies": labels[picks].astype(np.int64)}
