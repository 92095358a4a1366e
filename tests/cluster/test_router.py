"""Cluster router integration tests (ISSUE 12): real worker processes.

One module-scoped 2-worker router serves every test (worker boots pay a
fresh interpreter + jax import each, so the fixture is shared); tests
run in definition order (tier-1 disables random ordering) and are
sequenced so state they leave behind — a warmed service estimate, a
killed-and-respawned worker — never invalidates a later assertion.
"""

import os
import signal
import time
from concurrent.futures import ThreadPoolExecutor

import numpy as np
import pytest

from keystone_tpu.cluster import ClusterRouter
from keystone_tpu.serving.errors import (
    DeadlineExceeded,
    EngineStopped,
    Shed,
)

D = 32
STALL_S = 0.002


@pytest.fixture(scope="module")
def router():
    r = ClusterRouter(
        ("factory", "keystone_tpu.cluster.demo:build_stall_model",
         {"d": D, "stall_s": STALL_S}),
        workers=2,
        replicas_per_worker=1,
        buckets=(8,),
        datum_shape=(D,),
        max_wait_ms=1.0,
        spawn_timeout_s=180,
        # long health interval: worker pongs must not warm the router's
        # service estimate behind the deterministic tests' backs
        health_interval_s=3600.0,
        # bounded-shutdown test budget: keep the wedged-worker path fast
        drain_timeout_s=3.0,
        join_timeout_s=2.0,
        max_restarts=2,
    )
    r.start()
    yield r
    r.shutdown(drain=False)


@pytest.fixture(scope="module")
def data():
    return np.random.RandomState(0).randn(32, D).astype(np.float32)


@pytest.fixture(scope="module")
def expected(data):
    from keystone_tpu.cluster.demo import build_stall_model

    local = build_stall_model(d=D, stall_s=0.0)
    return np.asarray(local.apply(data).to_array())


def test_a_predict_parity_and_load_spread(router, data, expected):
    n = 64
    with ThreadPoolExecutor(max_workers=16) as pool:
        outs = list(pool.map(
            lambda i: router.predict(data[i % len(data)]), range(n)
        ))
    for i, out in enumerate(outs):
        np.testing.assert_allclose(
            np.asarray(out), expected[i % len(data)], atol=1e-5
        )
    snap = router.snapshot()
    c = snap["counters"]
    assert c["submitted"] == c["completed"] == n
    # concurrent load reached both worker processes
    workers_with_batches = {
        key.split("/")[0] for key, row in snap["replicas"].items()
        if row.get("batches")
    }
    assert len(workers_with_batches) == 2, snap["replicas"]
    # merged quantiles came from worker sketches as well as the router
    assert snap["latency"]["count"] >= n


def test_a2_boot_spec_carries_the_platform(router):
    # the platform this process was asked for (conftest: cpu) rides the
    # boot spec, and every worker reports the platform it came up on
    assert router._spec["platform"] == "cpu"
    reports = router.worker_reports
    assert [r["platform"] for r in reports] == ["cpu", "cpu"]


def test_a3_a_worker_boot_failure_is_raised_typed_by_start():
    # the worker refuses the contract at boot; start() must raise THAT
    # error, inside the spawn timeout — not "check worker stderr"
    from keystone_tpu.check import ContractMismatchError

    bad = ClusterRouter(
        ("factory", "keystone_tpu.cluster.demo:build_demo_model",
         {"num_ffts": 2, "block_size": 256, "n_train": 256}),
        workers=1, buckets=(8,), datum_shape=(783,), spawn_timeout_s=120,
    )
    t0 = time.monotonic()
    with pytest.raises(ContractMismatchError):
        bad.start()
    assert time.monotonic() - t0 < 120


def test_b_deadline_crosses_the_process_boundary(router, data):
    # the router's estimate is COLD (no observe_service, health pongs
    # disabled), so the front door cannot shed — an already-expired
    # deadline must be enforced on the WORKER side and come back typed:
    # its fleet admission sheds it (warm worker estimate) or its replica
    # expires it (DeadlineExceeded); either proves the deadline survived
    # the hop as a remaining budget.
    assert router.service_estimate is None
    with pytest.raises((Shed, DeadlineExceeded)):
        router.predict(data[0], timeout=1e-9)
    # a generous deadline sails through end to end
    out = router.predict(data[0], timeout=30.0)
    assert np.asarray(out).shape == (16,)


def test_c_shed_determinism_with_seeded_estimate(router, data):
    # seed the front door exactly like the fleet-scheduler tests seed
    # theirs: 10s per batch makes every 100ms deadline unmeetable
    router.observe_service(10.0)
    before_shed = router.metrics.count("shed")
    before_submitted = router.metrics.count("submitted")
    for _ in range(10):
        with pytest.raises(Shed):
            router.submit(data[0], timeout=0.1)
    assert router.metrics.count("shed") == before_shed + 10
    # shed at the front door: nothing was admitted, nothing crossed to
    # a worker
    assert router.metrics.count("submitted") == before_submitted
    # deadline-less traffic is never shed, whatever the estimate says
    assert router.predict(data[0]) is not None


def test_d_worker_kill_mid_load_zero_admitted_failures(router, data):
    pids = router.worker_pids
    victim_pid = pids[0]
    stop = [False]
    failures = []
    served = [0]

    def hammer(tid):
        while not stop[0]:
            try:
                router.predict(data[served[0] % len(data)])
                served[0] += 1
            except Exception as e:  # pragma: no cover - the assertion
                failures.append(e)

    threads = ThreadPoolExecutor(max_workers=6)
    futs = [threads.submit(hammer, t) for t in range(6)]
    time.sleep(0.4)
    os.kill(victim_pid, signal.SIGKILL)  # a worker process dies mid-load
    time.sleep(1.0)
    stop[0] = True
    for f in futs:
        f.result(timeout=60)
    threads.shutdown(wait=True)
    assert not failures, f"admitted requests failed: {failures[:3]}"
    assert served[0] > 0
    assert router.metrics.count("restarts") >= 1
    # the respawned worker rejoins within its budget (fresh interpreter
    # + jax import: allow generous wall clock)
    deadline = time.monotonic() + 120
    while router.live_workers < 2 and time.monotonic() < deadline:
        time.sleep(0.25)
    assert router.live_workers == 2, "killed worker was not respawned"
    assert router.worker_pids[0] != victim_pid
    # routing still works through the respawned worker
    with ThreadPoolExecutor(max_workers=8) as pool:
        list(pool.map(lambda i: router.predict(data[i % 8]), range(24)))


def test_e_bounded_shutdown_with_wedged_worker(router, data):
    # SIGSTOP a worker: its socket stays open but it answers nothing —
    # the worst wedge shape. Shutdown must stay bounded (drain timeout,
    # per-process join timeouts, terminate→kill escalation) and answer
    # every stranded request typed.
    victim_pid = router.worker_pids[0]
    os.kill(victim_pid, signal.SIGSTOP)
    try:
        futs = [router.submit(data[i % 8]) for i in range(8)]
        t0 = time.monotonic()
        router.shutdown(drain=True)
        elapsed = time.monotonic() - t0
        # drain 3s + join 2s (+ terminate/kill escalation ~4s) per the
        # fixture budgets, times some slack — never a hang
        assert elapsed < 30.0, f"shutdown took {elapsed:.1f}s"
        from concurrent.futures import TimeoutError as FutureTimeout

        from keystone_tpu.serving.errors import ServingError

        for f in futs:
            # a stranded future must be SETTLED (typed serving error or
            # a result) — a FutureTimeout here means shutdown left it
            # unanswered, which is exactly the bug this test exists for
            try:
                f.result(timeout=5.0)
            except FutureTimeout:
                raise AssertionError(
                    "shutdown left an admitted request unanswered"
                )
            except (ServingError, ConnectionError):
                pass  # typed answer: the contract held
        with pytest.raises(EngineStopped):
            router.submit(data[0])
    finally:
        try:
            os.kill(victim_pid, signal.SIGKILL)
        except ProcessLookupError:
            pass
