#!/usr/bin/env bash
# Smoke-test the serving engine on CPU: fit a small pipeline, push
# synthetic traffic through ServingEngine, assert every response matched
# and every bucket's executable arrived exactly once (the demo exits
# nonzero on any mismatch). Then boot AGAIN against the same AOT
# executable cache dir and assert the warm boot paid ZERO pipeline
# traces — every bucket must load the executable the first boot
# exported (--expect-zero-compiles makes any warm-boot trace fatal).
# Finally boot a 2-replica ServingFleet against the same warm cache:
# still zero steady-state compiles (replicas share one dispatcher +
# cache dir and pre-warm from the bucket-signature manifest), every
# replica served batches, and the trace carries per-replica
# serve.replica spans plus the scheduler's serve.dispatch events.
# Finally a fault-tolerance stage: under an injected mid-demo replica
# thread kill (KEYSTONE_FAULTS), the supervised fleet must answer every
# request (zero failures) and record restarts >= 1.
# Boot 5 lifts serving to the PROCESS tier: a ClusterRouter over 2
# worker processes against the same pre-warmed AOT cache — every worker
# must boot with ZERO compiles (shared cache dir + bucket-signature
# manifest over the filesystem) and serve >= 1 micro-batch, with every
# response matching (--expect-zero-compiles + the demo's per-worker
# batch assertion make either failure fatal).
# Boot 7 closes the autoscaling loop: an elastic 1..2-worker router
# under an 8-thread burst must scale UP on SLO breaches (a new worker
# process spawned and admitted), then — traffic stopped — drain the
# scaled worker back DOWN after the idle cooldown, with both decisions
# rendered in the --status view's autoscale section and zero requests
# failed around either transition.
# Boot 8 closes the accounting/export loop: a live router with the
# Prometheus exposition endpoint enabled (metrics_port=0) is scraped
# mid-demo — the text must parse, carry # TYPE lines, agree with the
# merged snapshot's submitted counter, and render the per-tenant
# cost families the attribution plane charges.
# Boot 6 closes the continual-learning loop: a fleet + trainer daemon
# (keystone_tpu/trainer/) with live traffic while chunk batches append —
# every good batch must canary-pass and PROMOTE a refreshed model, the
# poisoned batch must canary-FAIL, roll back, and be parked, and not one
# request may fail (the demo exits nonzero on any of it).
# Extra flags pass through to the demo, e.g.:
#   bin/serve-smoke.sh --requests 128 --buckets 8,32,64
set -euo pipefail
cd "$(dirname "$0")/.."
cachedir="$(mktemp -d /tmp/keystone-aot-smoke-XXXXXX)"
trap 'rm -rf "$cachedir"' EXIT
# both cache layers root in the throwaway dir so boot 1 is genuinely cold
run=(env JAX_PLATFORMS=cpu JAX_COMPILATION_CACHE_DIR="$cachedir/xla"
     python -m keystone_tpu --serve-demo --backend cpu
     --aot-cache "$cachedir")
echo "== boot 1 (cold: traces + exports every bucket) =="
"${run[@]}" "$@"
echo "== boot 2 (warm: must load every bucket, zero traces) =="
"${run[@]}" --expect-zero-compiles "$@"
echo "== boot 3 (2-replica fleet, warm: zero traces + per-replica spans) =="
fleettrace="$cachedir/fleet-trace.json"
"${run[@]}" --trace "$fleettrace" --replicas 2 --expect-zero-compiles "$@"
python - "$fleettrace" <<'PY'
import json, sys

with open(sys.argv[1]) as f:
    events = json.load(f)["traceEvents"]

def args_of(e):
    return e.get("args") or {}

replica_spans = [e for e in events if e.get("name") == "serve.replica"]
dispatches = [e for e in events if e.get("name") == "serve.dispatch"]
swaps_seen = {args_of(e).get("replica") for e in replica_spans}
assert replica_spans, "no serve.replica spans in the fleet trace"
assert dispatches, "no serve.dispatch events in the fleet trace"
assert {0, 1} <= swaps_seen, f"expected spans from both replicas, got {swaps_seen}"
for e in dispatches:
    a = args_of(e)
    assert "bucket" in a and "occupancy" in a, f"dispatch event missing attrs: {a}"
print(
    f"FLEET TRACE OK: {len(replica_spans)} serve.replica span(s) across "
    f"replicas {sorted(swaps_seen)}, {len(dispatches)} dispatch event(s)"
)
PY
echo "== boot 4 (replica kill mid-demo: supervised restart, zero failed requests) =="
env JAX_PLATFORMS=cpu KEYSTONE_FAULTS="replica.batch=kill@5" python - <<'PY'
from concurrent.futures import ThreadPoolExecutor

import numpy as np

from keystone_tpu.serving import ServingFleet
from keystone_tpu.serving.demo import build_demo_fitted

fitted, test = build_demo_fitted(n_train=512)
fleet = ServingFleet(fitted, replicas=2, buckets=(8,), max_wait_ms=2.0)
n = 96
with fleet:
    with ThreadPoolExecutor(max_workers=8) as pool:
        outs = list(pool.map(
            lambda i: fleet.predict(test[i % len(test)], timeout=30.0),
            range(n),
        ))
c = fleet.metrics.snapshot()["counters"]
assert len(outs) == n, f"answered {len(outs)}/{n}"
assert c.get("completed") == c.get("submitted") == n, c
assert c.get("restarts", 0) >= 1, f"expected a supervised restart: {c}"
assert c.get("batch_errors", 0) == 0, f"failed batches under kill: {c}"
print(
    f"KILL STAGE OK: {n}/{n} answered, restarts={c['restarts']}, "
    f"requeues={c.get('requeues', 0)}, quarantined={c.get('quarantined', 0)}"
)
PY
echo "== boot 5 (router + 2 worker processes, warm: zero compiles in every worker) =="
out5="$(mktemp /tmp/keystone-serve-status-XXXXXX.log)"
"${run[@]}" --workers 2 --expect-zero-compiles --status \
  --tenants gold:3,bronze:1 "$@" | tee "$out5"
# --status rendered the fleet-wide timeline view (per-process rows)
grep -q "cluster status: workers 2/2" "$out5" || {
  echo "STATUS FAIL: fleet liveness line missing from --status output"
  rm -f "$out5"; exit 1;
}
grep -q "timeline \[worker-0\]" "$out5" || {
  echo "STATUS FAIL: no per-worker timeline in --status output"
  rm -f "$out5"; exit 1;
}
# the QoS view: weighted-fair tenant shares rendered from the merged
# per-worker tenant.served.* counters
grep -q "qos tenants: .*gold" "$out5" || {
  echo "STATUS FAIL: no per-tenant QoS shares in --status output"
  rm -f "$out5"; exit 1;
}
rm -f "$out5"
echo "== boot 6 (continual learning: trainer daemon promotes refreshes, rolls back the poisoned batch) =="
env JAX_PLATFORMS=cpu python -m keystone_tpu --trainer-demo --backend cpu
echo "== boot 7 (autoscale: burst scales 1->2 on SLO breaches, idle cooldown drains back to 1) =="
env JAX_PLATFORMS=cpu python - <<'PY'
import threading
import time

import numpy as np

from keystone_tpu.autoscale import ScalePolicy
from keystone_tpu.cluster import ClusterRouter, format_status
from keystone_tpu.serving.slo import SloPolicy

d = 256
spec = (
    "factory", "keystone_tpu.cluster.demo:build_stall_model",
    {"d": d, "stall_s": 0.020},
)
data = np.random.RandomState(3).randn(32, d).astype(np.float32)
router = ClusterRouter(
    spec, workers=1, replicas_per_worker=1, buckets=(8,),
    datum_shape=(d,), max_wait_ms=2.0, max_queue=4096,
    spawn_timeout_s=300, health_interval_s=0.25,
    slo=SloPolicy(p99_budget_s=0.05),
    autoscale=ScalePolicy(
        min_workers=1, max_workers=2, up_breaches=2,
        breach_window_s=5.0, up_cooldown_s=2.0, down_cooldown_s=4.0,
        down_after_idle_ticks=4,
    ),
)
with router:
    for _ in range(8):
        router.predict(data[0])
    router.observe_service(8.0 / 300.0)
    stop = [False]
    failures = [0]

    def hammer(k):
        i = 0
        while not stop[0]:
            try:
                router.predict(data[i % len(data)], timeout=2.0)
            except Exception:
                failures[0] += 1
            i += 1

    threads = [
        threading.Thread(target=hammer, args=(k,)) for k in range(8)
    ]
    for t in threads:
        t.start()
    deadline = time.monotonic() + 60
    while router.live_workers < 2 and time.monotonic() < deadline:
        time.sleep(0.25)
    scaled_up = router.live_workers == 2
    stop[0] = True
    for t in threads:
        t.join()
    assert scaled_up, "burst never scaled the fleet to 2 workers"
    # idle now: the cooldown must drain the scaled worker back down
    deadline = time.monotonic() + 60
    while time.monotonic() < deadline:
        view = router.scale_view()
        if view["admitting"] == 1 and view["draining"] == 0:
            break
        time.sleep(0.25)
    snap = router.snapshot()
    status = format_status(router.status(snap=snap))
print(status)
c = snap["counters"]
assert c.get("scale_ups", 0) >= 1, f"no scale-up counted: {c}"
assert c.get("scale_downs", 0) >= 1, f"no scale-down counted: {c}"
assert failures[0] == 0, f"{failures[0]} requests failed around scaling"
assert "autoscale:" in status, "status view missing the autoscale section"
assert "SCALE up" in status and "SCALE down" in status, status
print(
    "AUTOSCALE STAGE OK: scaled 1->2 on breaches, drained 2->1 on idle, "
    f"zero failed requests (scale_ups={c['scale_ups']}, "
    f"scale_downs={c['scale_downs']})"
)
PY
echo "== boot 8 (export plane: live scrape parses and matches the merged snapshot) =="
env JAX_PLATFORMS=cpu python - <<'PY'
import re
import urllib.request

import numpy as np

from keystone_tpu.cluster import ClusterRouter

d = 64
spec = (
    "factory", "keystone_tpu.cluster.demo:build_stall_model",
    {"d": d, "stall_s": 0.001},
)
data = np.random.RandomState(7).randn(16, d).astype(np.float32)
router = ClusterRouter(
    spec, workers=1, replicas_per_worker=1, buckets=(8,),
    datum_shape=(d,), max_wait_ms=2.0, max_queue=1024,
    spawn_timeout_s=300, health_interval_s=0.25,
    tenant_weights={"gold": 3.0, "bronze": 1.0},
    metrics_port=0,
)
n = 48
with router:
    host, port = router.metrics_address
    for i in range(n):
        tenant = "gold" if i % 2 else "bronze"
        router.submit(
            data[i % len(data)], tenant=tenant, timeout=30.0
        ).result()
    with urllib.request.urlopen(
        f"http://{host}:{port}/metrics", timeout=10
    ) as resp:
        assert resp.status == 200, resp.status
        body = resp.read().decode("utf-8")
    snap = router.snapshot()

sample = re.compile(
    r"^[a-zA-Z_:][a-zA-Z0-9_:]*(\{[^}]*\})? -?[0-9.e+-]+$"
)
samples = {}
typed = 0
for line in body.splitlines():
    if not line:
        continue
    if line.startswith("# TYPE "):
        typed += 1
        continue
    if line.startswith("#"):
        continue
    assert sample.match(line), f"malformed exposition line: {line!r}"
    key, value = line.rsplit(" ", 1)
    samples[key] = float(value)
assert typed > 0, "no # TYPE lines in the scrape"
submitted = samples["keystone_submitted_total"]
assert submitted == snap["counters"]["submitted"] == n, (
    submitted, snap["counters"].get("submitted"), n,
)
cost_keys = [
    k for k in samples
    if k.startswith("keystone_tenant_device_seconds_total{")
]
assert any('tenant="gold"' in k for k in cost_keys), sorted(samples)[:40]
assert any('tenant="bronze"' in k for k in cost_keys), cost_keys
print(
    f"SCRAPE STAGE OK: {len(samples)} samples, {typed} families, "
    f"submitted={int(submitted)} matches the merged snapshot, "
    f"{len(cost_keys)} per-tenant device-second series"
)
PY
