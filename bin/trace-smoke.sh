#!/usr/bin/env bash
# Smoke-test pipeline tracing end-to-end: run the MNIST pipeline on CPU
# at a tier-1-fast config with --trace, then validate the output is
# well-formed Chrome-trace JSON — non-empty traceEvents, monotonic ts,
# and at least one cache-annotated DAG-node span. A second stage runs a
# chunked out-of-core scan under tracing and asserts the pipelined scan
# runtime's `scan.pipeline` spans (with the producer/consumer stall
# counters) land in the trace. Exits non-zero on any failure. Extra
# flags pass through to the pipeline, e.g.:
#   bin/trace-smoke.sh /tmp/trace.json --numFFTs 4
# A third stage runs a host-bound gather pipeline under the concurrent
# executor and asserts the scheduled node spans carry queue_wait_seconds /
# worker attribution and still nest under the pull root.
# A fourth stage compiles a fitted pipeline against a fresh AOT executable
# cache twice (fresh process each) and asserts the cache-miss run traces
# `aot.miss` + `aot.export` spans and the hit run traces `aot.load`.
# A fifth stage runs a mesh-sharded streaming fit on a 4-device virtual
# mesh and asserts the sharded scan emits per-lane spans with device
# attribution and a per-scan `collectives` attr on the scan span.
# A sixth stage fits a pipeline twice against a fresh profile store under
# tracing and asserts the cost-model spans: `cost.estimate` (solver choice
# + cache-plan pricing) and `cost.replan` (trace-informed re-plan) on the
# cold run, and an evidence-planned (`source: profiles`) cost.estimate on
# the warm run.
# A seventh stage runs two λ-grid sweeps (a Gram family and an ungrouped BCD
# family), an incremental refit, and a hot swap under continuous load, and
# asserts the `sweep.*` spans (one grid_solve for the shared Gram group),
# prefix memo-hit events for members 2..G, the `pipeline.absorb` span, and a
# `serve.swap` span with zero dropped in-flight requests.
# A tenth stage (segment compilation) fits + applies against a fresh AOT
# cache twice: the cold run must trace `exec.segment` spans with
# `aot.export`, the warm run must trace `aot.load` and ZERO `aot.export`,
# with bit-equal outputs.
# An eleventh stage (hot wire path) serves a concurrent burst through the
# router on the binary codec and asserts the coalescer put multiple
# members on single frames (coalesce.frames < requests answered), the
# stitched trace carries wire.encode/wire.decode spans, and a second run
# under the KEYSTONE_WIRE_CODEC=pickle kill switch returns bit-equal
# outputs.
# A twelfth stage (the span primitive) runs a tiny TIMIT job with NO tracer
# installed under a plain `jax.profiler` session and asserts that any
# profiler session records the spans: `obs.tracer.session_spans()` holds
# `job`, `plan.*`, `exec.segment`, `block_ls.solve`, `xfer.d2h`,
# `eval.metrics`, unsynced, and the xplane's host plane holds the same
# regions as `ks:` annotations.
set -euo pipefail
cd "$(dirname "$0")/.."
out="${1:-$(mktemp /tmp/keystone-trace-XXXXXX.json)}"
[ $# -gt 0 ] && shift
env JAX_PLATFORMS=cpu python -m keystone_tpu mnist --backend cpu \
  --numFFTs 2 --blockSize 512 --lambda 100 --trace "$out" "$@"
python - "$out" <<'PY'
import json
import sys

with open(sys.argv[1]) as f:
    doc = json.load(f)
events = doc.get("traceEvents")
assert isinstance(events, list) and events, "empty or missing traceEvents"
ts = [e["ts"] for e in events]
assert all(b >= a for a, b in zip(ts, ts[1:])), "non-monotonic ts"
assert any(
    e.get("args", {}).get("cache") for e in events
), "no cache-annotated DAG-node spans"
print(f"TRACE OK: {len(events)} events -> {sys.argv[1]}")
PY

# -- the span primitive under a plain profiler session ------------------------
env JAX_PLATFORMS=cpu python - <<'PY'
import glob
import os
import tempfile

import jax

from keystone_tpu.obs import tracer
from keystone_tpu.pipelines.timit import TimitConfig, run, synthetic_timit

conf = TimitConfig(num_cosines=2, cosine_features=64, num_classes=5,
                   num_epochs=2)
train, test = synthetic_timit(256, 5, seed=1), synthetic_timit(64, 5, seed=2)
assert tracer.current() is None, "this stage runs with no tracer installed"
trace_dir = tempfile.mkdtemp(prefix="keystone-session-")
with jax.profiler.trace(trace_dir):
    run(train, test, conf)
spans = tracer.session_spans()
names = {sp.name for sp in spans}
want = {"job", "plan.build", "plan.optimize", "plan.rule", "plan.segments",
        "pipeline.pull", "exec.segment", "block_ls.solve", "xfer.h2d",
        "xfer.d2h", "eval.metrics"}
assert want <= names, sorted(want - names)
assert all(sp.sync_seconds == 0.0 for sp in spans), "a session never syncs"
(path,) = glob.glob(
    os.path.join(trace_dir, "plugins", "profile", "*", "*.xplane.pb")
)
annotated = {
    ev.name
    for plane in jax.profiler.ProfileData.from_file(path).planes
    if plane.name.startswith("/host:")
    for line in plane.lines
    for ev in line.events
    if ev.name.startswith("ks:")
}
assert {"ks:" + n for n in names} == annotated, (names, annotated)
print(f"SESSION SPANS OK: {len(spans)} spans, {len(annotated)} ks: names")
PY

# -- pipelined-scan spans ----------------------------------------------------
scan_out="$(mktemp /tmp/keystone-scan-trace-XXXXXX.json)"
env JAX_PLATFORMS=cpu KEYSTONE_TRACE="$scan_out" python - "$scan_out" <<'PY'
import json
import sys

import numpy as np

from keystone_tpu.utils.obs import configure, export_trace

configure()

from keystone_tpu.data import ChunkedDataset

ds = ChunkedDataset.from_array(
    np.ones((64, 4), np.float32), 9
).map_batch(lambda c: c * 2.0)
assert float(np.asarray(ds.to_array()).sum()) == 64 * 4 * 2.0
path = export_trace()
assert path == sys.argv[1], (path, sys.argv[1])
with open(path) as f:
    doc = json.load(f)
scans = [e for e in doc["traceEvents"] if e["name"] == "scan.pipeline"]
assert scans, "no scan.pipeline spans in the trace"
args = scans[-1]["args"]
for key in (
    "chunks",
    "producer_seconds",
    "producer_stall_seconds",
    "consumer_stall_seconds",
    "staged_bytes",
    "occupancy_max",
):
    assert key in args, (key, args)
assert args["chunks"] == 8  # ceil(64/9)
print(f"SCAN SPANS OK: {len(scans)} scan.pipeline span(s) -> {path}")
PY

# -- concurrent-executor spans -----------------------------------------------
par_out="$(mktemp /tmp/keystone-par-trace-XXXXXX.json)"
env JAX_PLATFORMS=cpu KEYSTONE_TRACE="$par_out" KEYSTONE_EXEC_WORKERS=2 \
  python - "$par_out" <<'PY'
import json
import sys
import time

import numpy as np

from keystone_tpu.utils.obs import configure, export_trace

configure()

from keystone_tpu.workflow.pipeline import Pipeline
from keystone_tpu.workflow.transformer import FunctionNode


def mk(i):
    def feat(x):
        time.sleep(0.005)  # host-stall stand-in; forces real overlap
        return np.asarray(x) * (i + 1.0)

    return FunctionNode(item_fn=feat, label=f"host{i}")


Pipeline.gather([mk(i) for i in range(4)]).apply(
    np.ones((3, 4), np.float32)
).get()
path = export_trace()
assert path == sys.argv[1], (path, sys.argv[1])
with open(path) as f:
    doc = json.load(f)
events = [e for e in doc["traceEvents"] if e.get("ph") == "X"]
sched = [e for e in events if "queue_wait_seconds" in e.get("args", {})]
assert len(sched) >= 2, "no scheduler-attributed executor spans"
for e in sched:
    assert str(e["args"]["worker"]).startswith("keystone-exec"), e["args"]
    assert e["args"]["queue_wait_seconds"] >= 0.0, e["args"]
pull = [e for e in events if e["name"] == "pipeline.pull"]
assert len(pull) == 1, [e["name"] for e in events]
lo, hi = pull[0]["ts"], pull[0]["ts"] + pull[0]["dur"]
for e in sched:
    # the span tree still nests: scheduled node spans (worker threads) sit
    # inside the pull root opened on the caller thread
    assert lo <= e["ts"] and e["ts"] + e["dur"] <= hi + 1000.0, (e, pull[0])
    assert e["tid"] != pull[0]["tid"], e
print(f"PAR SPANS OK: {len(sched)} scheduled node span(s) -> {path}")
PY

# -- AOT executable-cache spans ----------------------------------------------
aot_dir="$(mktemp -d /tmp/keystone-aot-trace-XXXXXX)"
trap 'rm -rf "$aot_dir"' EXIT
for mode in miss hit; do
  aot_out="$(mktemp /tmp/keystone-aot-trace-XXXXXX.json)"
  env JAX_PLATFORMS=cpu KEYSTONE_TRACE="$aot_out" \
    KEYSTONE_AOT_CACHE="$aot_dir" JAX_COMPILATION_CACHE_DIR="$aot_dir/xla" \
    python - "$aot_out" "$mode" <<'PY'
import json
import sys

import numpy as np

from keystone_tpu.utils.obs import configure, export_trace

configure()

from keystone_tpu.serving.demo import build_demo_fitted

fitted, _test = build_demo_fitted(n_train=512, n_test=16)
compiled = fitted.compile()
x = np.zeros((8, 784), np.float32)
np.asarray(compiled(x))
path = export_trace()
assert path == sys.argv[1], (path, sys.argv[1])
with open(path) as f:
    doc = json.load(f)
names = [e["name"] for e in doc["traceEvents"]]
mode = sys.argv[2]
if mode == "miss":
    assert "aot.miss" in names and "aot.export" in names, names
    assert "aot.load" not in names, names
    assert fitted.compile_count == 1, fitted.compiled_signatures
else:
    assert "aot.load" in names, names
    assert "aot.export" not in names, names
    assert fitted.compile_count == 0, fitted.compiled_signatures
# segment dispatchers share the cache and emit aot.* spans during fit;
# pick the whole-pipeline apply span (the one carrying the input shape)
args = [
    e for e in doc["traceEvents"]
    if e["name"].startswith("aot.") and "shape" in e["args"]
][0]["args"]
# the exporter stringifies non-scalar attrs
assert args.get("key") and str(args.get("shape")) == "[8, 784]", args
print(f"AOT SPANS OK ({mode}): "
      + ", ".join(sorted(n for n in set(names) if n.startswith("aot."))))
PY
done

# -- mesh-sharded scan spans --------------------------------------------------
shard_out="$(mktemp /tmp/keystone-shard-trace-XXXXXX.json)"
env JAX_PLATFORMS=cpu KEYSTONE_TRACE="$shard_out" KEYSTONE_VIRTUAL_DEVICES=4 \
  python - "$shard_out" <<'PY'
import json
import sys

from keystone_tpu.parallel.virtual import provision_from_env

provision_from_env()  # 4-device virtual mesh from KEYSTONE_VIRTUAL_DEVICES

import numpy as np

from keystone_tpu.utils.obs import configure, export_trace

configure()

import jax.numpy as jnp

from keystone_tpu.linalg import solve_blockwise_l2_streaming
from keystone_tpu.parallel.lanes import scan_lanes

assert scan_lanes() == 4, scan_lanes()
rng = np.random.default_rng(0)
A = rng.standard_normal((96, 8)).astype(np.float32)
y = rng.standard_normal((96, 2)).astype(np.float32)
solve_blockwise_l2_streaming(
    lambda: iter([A[i : i + 16] for i in range(0, 96, 16)]),
    jnp.asarray(y), reg=0.1, block_size=4,
    means=jnp.asarray(A.mean(axis=0)),
)
path = export_trace()
assert path == sys.argv[1], (path, sys.argv[1])
with open(path) as f:
    doc = json.load(f)
scans = [e for e in doc["traceEvents"] if e["name"] == "scan.pipeline"
         and e.get("args", {}).get("label") == "bcd.stream"]
assert scans, "no sharded scan.pipeline spans"
for e in scans:
    a = e["args"]
    assert str(a["lanes"]) == "4", a
    assert int(a["collectives"]) > 0, a  # per-block reduce+broadcast, O(blocks)
lanes = [e for e in doc["traceEvents"] if e["name"] == "scan.pipeline.lane"]
assert len(lanes) >= 4 * len(scans), (len(lanes), len(scans))
devices = {str(e["args"]["device"]) for e in lanes}
assert len(devices) == 4, devices  # per-lane device attribution
print(f"SHARDED SCAN SPANS OK: {len(scans)} scan span(s), "
      f"{len(lanes)} lane span(s) over {len(devices)} devices -> {path}")
PY

# -- cost-model spans ---------------------------------------------------------
prof_dir="$(mktemp -d /tmp/keystone-prof-trace-XXXXXX)"
trap 'rm -rf "$aot_dir" "$prof_dir"' EXIT
for mode in cold warm; do
  cost_out="$(mktemp /tmp/keystone-cost-trace-XXXXXX.json)"
  env JAX_PLATFORMS=cpu KEYSTONE_TRACE="$cost_out" \
    KEYSTONE_PROFILE_DIR="$prof_dir" python - "$cost_out" "$mode" <<'PY'
import json
import sys

import numpy as np

from keystone_tpu.utils.obs import configure, export_trace

configure()

from keystone_tpu.data.dataset import Dataset
from keystone_tpu.nodes.learning import LeastSquaresEstimator
from keystone_tpu.workflow.env import PipelineEnv
from keystone_tpu.workflow.optimizers import AutoCachingOptimizer

PipelineEnv.get_or_create().set_optimizer(AutoCachingOptimizer())

import keystone_tpu.cost as cost

cost.reset_sampling()
rng = np.random.default_rng(0)
X = rng.standard_normal((1024, 32)).astype(np.float32)
Y = rng.standard_normal((1024, 4)).astype(np.float32)
LeastSquaresEstimator(lam=1e-2).with_data(Dataset.of(X), Dataset.of(Y)).fit()
sampled = cost.sampling_executions()["total"]
path = export_trace()
assert path == sys.argv[1], (path, sys.argv[1])
with open(path) as f:
    doc = json.load(f)
mode = sys.argv[2]
est = [e for e in doc["traceEvents"] if e["name"] == "cost.estimate"]
rep = [e for e in doc["traceEvents"] if e["name"] == "cost.replan"]
assert est, "no cost.estimate spans"
assert rep, "no cost.replan spans"
solver_spans = [e for e in est if e["args"].get("solver")]
assert solver_spans, "no solver-choice cost.estimate span"
cache_spans = [e for e in est if e["args"].get("op_type") == "AutoCacheRule"]
assert cache_spans, "no cache-plan cost.estimate span"
if mode == "cold":
    assert sampled > 0, "cold run should pay sampling"
    assert any(
        str(e["args"].get("source", "")).startswith("sampled")
        for e in cache_spans
    ), cache_spans
else:
    assert sampled == 0, f"warm run sampled {sampled} executions"
    assert any(
        e["args"].get("source") == "profiles" for e in cache_spans
    ), cache_spans
print(f"COST SPANS OK ({mode}): {len(est)} cost.estimate, "
      f"{len(rep)} cost.replan, sampling={sampled}")
PY
done

# -- sweep + incremental-refit + hot-swap spans -------------------------------
sweep_out="$(mktemp /tmp/keystone-sweep-trace-XXXXXX.json)"
env JAX_PLATFORMS=cpu KEYSTONE_TRACE="$sweep_out" python - "$sweep_out" <<'PY'
import json
import sys
import time as _t
from concurrent.futures import ThreadPoolExecutor

import numpy as np

from keystone_tpu.utils.obs import configure, export_trace

configure()

import jax.numpy as jnp

from keystone_tpu.data.dataset import Dataset
from keystone_tpu.nodes.learning import LinearMapEstimator
from keystone_tpu.nodes.learning.linear import BlockLeastSquaresEstimator
from keystone_tpu.serving import ServingEngine
from keystone_tpu.sweep import GridSweep
from keystone_tpu.workflow.transformer import FunctionNode

rng = np.random.default_rng(0)
X = rng.standard_normal((512, 32)).astype(np.float32) + 0.5
Y = (np.tanh(X) @ rng.standard_normal((32, 4))).astype(np.float32)
LAMS = [1e-2, 1e-1, 1.0]
prefix = FunctionNode(
    batch_fn=lambda A: jnp.tanh(A) * 2.0, label="feat"
).to_pipeline()

# Gram-family sweep: one shared accumulation pass, G solves
res = GridSweep(
    prefix, lambda lam: LinearMapEstimator(lam=lam), {"lam": LAMS},
    Dataset.of(X), Dataset.of(Y),
).fit()

# ungrouped (cold BCD) sweep: members 2..G memo-hit the shared prefix
GridSweep(
    prefix, lambda lam: BlockLeastSquaresEstimator(8, num_iter=1, lam=lam),
    {"lam": LAMS}, Dataset.of(X), Dataset.of(Y),
).fit()

# incremental refit, then hot-swap under continuous load
fitted = res.fitted_for(lam=1e-1)
Xn = rng.standard_normal((96, 32)).astype(np.float32) + 0.5
Yn = (np.tanh(Xn) @ rng.standard_normal((32, 4))).astype(np.float32)
updated = fitted.absorb(Dataset.of(Xn), Dataset.of(Yn))

engine = ServingEngine(
    fitted, buckets=(8,), datum_shape=(32,), max_wait_ms=1.0
)
with engine:
    stop = [False]

    def hammer():
        n = 0
        while not stop[0]:
            engine.predict(X[n % 64], timeout=30.0)
            n += 1
        return n

    with ThreadPoolExecutor(max_workers=2) as pool:
        futs = [pool.submit(hammer) for _ in range(2)]
        _t.sleep(0.1)
        engine.swap(updated)
        _t.sleep(0.1)
        stop[0] = True
        served = sum(f.result(timeout=30) for f in futs)
    snap = engine.metrics.snapshot()

# zero dropped in-flight requests across the swap
c = snap["counters"]
assert served > 0 and c["completed"] == c["submitted"], c
assert c.get("failed", 0) == 0 and c.get("rejected", 0) == 0, c
assert c["swaps"] == 1, c

path = export_trace()
assert path == sys.argv[1], (path, sys.argv[1])
with open(path) as f:
    doc = json.load(f)
ev = doc["traceEvents"]

def spans(name):
    return [e for e in ev if e["name"] == name]

assert len(spans("sweep.fit")) == 2, "one sweep.fit root per sweep"
assert len(spans("sweep.plan")) == 2
assert len(spans("sweep.member")) == 2 * len(LAMS)
solves = spans("sweep.grid_solve")
assert len(solves) == 1, "one shared Gram solve group"
assert solves[0]["args"]["family"] == "gram_ne", solves[0]
assert int(solves[0]["args"]["members"]) == len(LAMS), solves[0]
# members 2..G of the ungrouped sweep memo-hit the shared prefix
hits = [
    e for e in ev
    if e.get("ph") == "i" and e["name"] == "node.feat"
    and e.get("args", {}).get("cache") == "hit"
]
assert len(hits) >= len(LAMS) - 1, f"{len(hits)} prefix cache hits"
absorbs = spans("pipeline.absorb")
assert len(absorbs) == 1
assert int(absorbs[0]["args"]["absorbed_rows"]) == 96, absorbs[0]
swaps = spans("serve.swap")
assert len(swaps) == 1
assert int(swaps[0]["args"]["buckets_warmed"]) >= 1, swaps[0]
print(
    f"SWEEP/SWAP SPANS OK: {len(solves)} grid_solve, "
    f"{len(hits)} prefix cache hit(s), absorb+swap spans present, "
    f"{served} request(s) served across the swap with zero failures"
)
PY

# Stage 9 (below, after stage 8): distributed tracing + flight recorder
# (keystone_tpu/obs/context.py, flight.py, cluster/). A router + 2 worker
# processes serve one traced request; the stitched export must contain a
# cross-process span tree: >= 3 hops under one trace id spanning >= 2
# pids, with wire (transport_s) and queue (queue_age_s) attribution and
# per-pid process_name tracks. A worker then gets SIGKILLed and the
# router's always-on flight recorder must leave a JSON dump containing
# the fault.worker_down instant.

# Stage 8: static --check mode (keystone_tpu/check/). Running mnist with
# --check must emit a non-empty `check.report` span whose segment plan
# has >= 2 traceable segments, with ZERO sampled executions recorded on
# the span (the checker proves its facts without running anything), and
# must exit 0 without producing a single chunk.
out8="$(mktemp /tmp/keystone-check-XXXXXX.json)"
env JAX_PLATFORMS=cpu python -m keystone_tpu mnist --backend cpu \
  --numFFTs 2 --blockSize 512 --lambda 100 --check --trace "$out8" \
  | grep -q "CHECK OK" || { echo "check mode did not report CHECK OK"; exit 1; }
python - "$out8" <<'PY'
import json
import sys

with open(sys.argv[1]) as f:
    doc = json.load(f)
ev = doc["traceEvents"]
reports = [e for e in ev if e["name"] == "check.report"]
assert reports, "no check.report span"
args = reports[-1].get("args", {})
assert int(args["segments"]) >= 2, args
assert int(args["nodes"]) > 0, args
assert int(args["sampling_total"]) == 0, (
    f"static check sampled: {args}"
)
# --check executes nothing: no scan, no node pulls, no fit root
for forbidden in ("pipeline.fit", "scan.pipeline", "node.feat"):
    assert not any(e["name"] == forbidden for e in ev), (
        f"{forbidden} span present in a --check run"
    )
print(
    f"CHECK SPAN OK: {args['nodes']} nodes, {args['segments']} segments, "
    f"sampling_total=0, no execution spans"
)
PY

# -- distributed tracing + flight recorder ------------------------------------
flight_dir="$(mktemp -d /tmp/keystone-flight-smoke-XXXXXX)"
trap 'rm -rf "$aot_dir" "$prof_dir" "$flight_dir"' EXIT
out9="$(mktemp /tmp/keystone-stitched-XXXXXX.json)"
env JAX_PLATFORMS=cpu KEYSTONE_FLIGHT_DIR="$flight_dir" \
  python - "$out9" "$flight_dir" <<'PY'
import json
import os
import signal
import sys
import time

import numpy as np

from keystone_tpu.cluster import ClusterRouter
from keystone_tpu.obs import tracer as trace_mod

trace_mod.install(trace_mod.Tracer())
r = ClusterRouter(
    ("factory", "keystone_tpu.cluster.demo:build_stall_model",
     {"d": 32, "stall_s": 0.002}),
    workers=2, replicas_per_worker=1, buckets=(8,), datum_shape=(32,),
    max_wait_ms=1.0, spawn_timeout_s=300,
)
data = np.random.RandomState(0).randn(8, 32).astype(np.float32)
with r:
    r.predict(data[0], timeout=30.0)  # THE traced request
    # worker spans ship on stats round-trips: cluster.handle ends when
    # the reply is SENT, so it rides a LATER reply than the request's.
    # collect_trace accumulates — poll until the hop tree is complete.
    deadline = time.monotonic() + 30
    while True:
        path = r.export_trace(sys.argv[1])
        with open(path) as f:
            doc = json.load(f)
        shipped = {e["name"] for e in doc["traceEvents"]}
        if {"cluster.handle", "serve.replica"} <= shipped:
            break
        assert time.monotonic() < deadline, sorted(shipped)
        time.sleep(0.2)
    ev = doc["traceEvents"]
    procs = {e["pid"]: e["args"]["name"] for e in ev
             if e["name"] == "process_name"}
    assert len(procs) >= 3, procs  # router + 2 workers, distinct pids
    assert any("router" in n for n in procs.values()), procs
    assert sum("worker" in n for n in procs.values()) >= 2, procs
    ts = [e["ts"] for e in ev]
    assert all(b >= a for a, b in zip(ts, ts[1:])), "non-monotonic ts"
    from collections import defaultdict

    by_trace = defaultdict(list)
    for e in ev:
        tid = e.get("args", {}).get("trace_id")
        if tid:
            by_trace[tid].append(e)
    # the stitched span tree: one trace id, >= 3 hops, >= 2 processes,
    # wire + queue attribution on the hops that own them
    best = max(by_trace.values(), key=lambda s: len({e["name"] for e in s}))
    names = {e["name"] for e in best}
    assert len(names) >= 3, names
    assert {"rpc.request", "cluster.handle", "serve.replica"} <= names, names
    assert len({e["pid"] for e in best}) >= 2, best
    handle = next(e for e in best if e["name"] == "cluster.handle")
    assert float(handle["args"]["transport_s"]) >= 0.0, handle
    queue = next(e for e in best if e["name"] == "serve.queue")
    assert float(queue["args"]["queue_age_s"]) >= 0.0, queue
    print(
        f"STITCHED TRACE OK: {len(names)} hop span(s) over "
        f"{len({e['pid'] for e in best})} process(es), "
        f"{len(procs)} process tracks -> {path}"
    )

    # the chaos half: SIGKILL one worker; the router's always-on flight
    # recorder must leave a post-mortem dump with the kill instant
    os.kill(r.worker_pids[0], signal.SIGKILL)
    deadline = time.monotonic() + 60
    dumps = []
    while time.monotonic() < deadline:
        try:
            r.predict(data[1], timeout=30.0)  # keeps the tier moving
        except Exception:
            pass
        dumps = [f for f in os.listdir(sys.argv[2]) if "worker_down" in f]
        if dumps:
            break
        time.sleep(0.1)
    assert dumps, "no flight-recorder dump after the worker kill"
    with open(os.path.join(sys.argv[2], sorted(dumps)[-1])) as f:
        dump = json.load(f)
    kills = [e for e in dump["entries"]
             if e["kind"] == "instant" and e["name"] == "fault.worker_down"]
    assert kills, [e["name"] for e in dump["entries"]][-20:]
    spans = [e for e in dump["entries"] if e["kind"] == "span"]
    print(
        f"FLIGHT DUMP OK: trigger={dump['trigger']} "
        f"kill_instants={len(kills)} span_summaries={len(spans)} "
        f"-> {sorted(dumps)[-1]}"
    )
PY

# -- segment-compiled execution ----------------------------------------------
seg_dir="$(mktemp -d /tmp/keystone-seg-smoke-XXXXXX)"
trap 'rm -rf "$aot_dir" "$prof_dir" "$flight_dir" "$seg_dir"' EXIT
for mode in cold warm; do
  seg_out="$(mktemp /tmp/keystone-seg-trace-XXXXXX.json)"
  env JAX_PLATFORMS=cpu KEYSTONE_TRACE="$seg_out" \
    KEYSTONE_AOT_CACHE="$seg_dir" \
    python - "$seg_out" "$mode" "$seg_dir" <<'PY'
import json
import os
import sys

import numpy as np

from keystone_tpu.utils.obs import configure, export_trace

configure()

from keystone_tpu.nodes.learning.linear import BlockLeastSquaresEstimator
from keystone_tpu.nodes.util import ClassLabelIndicators, MaxClassifier
from keystone_tpu.pipelines.mnist_random_fft import (
    NUM_CLASSES,
    MnistRandomFFTConfig,
    build_featurizer,
    synthetic_mnist,
)

train, test = synthetic_mnist(n_train=256, n_test=64, seed=7)
conf = MnistRandomFFTConfig(num_ffts=2, block_size=512, lam=10.0)
labels = ClassLabelIndicators(NUM_CLASSES).apply_batch(train.labels)
pipeline = build_featurizer(conf).and_then(
    BlockLeastSquaresEstimator(conf.block_size, 1, conf.lam or 0.0),
    train.data, labels,
).and_then(MaxClassifier())
fitted = pipeline.fit()
out = np.asarray(fitted.apply(test.data).to_array())
np.save(os.path.join(sys.argv[3], f"out_{sys.argv[2]}.npy"), out)

path = export_trace()
assert path == sys.argv[1], (path, sys.argv[1])
with open(path) as f:
    doc = json.load(f)
ev = doc["traceEvents"]
names = [e["name"] for e in ev]
segs = [e for e in ev if e["name"] == "exec.segment"]
node_dispatches = sum(
    1 for e in ev if e.get("ph") == "X" and e["name"].startswith("node.")
)
mode = sys.argv[2]
assert segs, f"no exec.segment spans in the {mode} segment run"
if mode == "cold":
    assert any(int(e["args"]["nodes"]) >= 2 for e in segs), segs
    assert "aot.export" in names, "cold segment run exported nothing"
else:
    assert "aot.load" in names, "warm segment run loaded nothing"
    assert "aot.export" not in names, "warm segment run re-exported"
print(f"SEGMENT SPANS OK ({mode}): {len(segs)} exec.segment span(s), "
      f"{node_dispatches} node dispatch span(s)")
PY
done
python - "$seg_dir" <<'PY'
import sys

import numpy as np

d = sys.argv[1]
outs = {m: np.load(f"{d}/out_{m}.npy") for m in ("cold", "warm")}
assert np.array_equal(outs["cold"], outs["warm"]), "cold vs warm outputs differ"
print("SEGMENT DISPATCH OK: cold exports, warm loads, outputs bit-equal")
PY

# -- hot wire path: coalescing + binary codec + pickle kill switch ------------
hw_dir="$(mktemp -d /tmp/keystone-hotwire-smoke-XXXXXX)"
trap 'rm -rf "$aot_dir" "$prof_dir" "$flight_dir" "$seg_dir" "$hw_dir"' EXIT
for codec in binary pickle; do
  hw_out="$(mktemp /tmp/keystone-hotwire-trace-XXXXXX.json)"
  env JAX_PLATFORMS=cpu KEYSTONE_WIRE_CODEC="$codec" \
    python - "$hw_out" "$codec" "$hw_dir" <<'PY'
import json
import sys
from concurrent.futures import ThreadPoolExecutor

import numpy as np

from keystone_tpu.cluster import ClusterRouter
from keystone_tpu.obs import tracer as trace_mod

trace_mod.install(trace_mod.Tracer())
N = 48
r = ClusterRouter(
    ("factory", "keystone_tpu.cluster.demo:build_stall_model",
     {"d": 32, "stall_s": 0.004}),
    workers=2, replicas_per_worker=1, buckets=(16,), datum_shape=(32,),
    max_wait_ms=2.0, spawn_timeout_s=300,
)
data = np.random.RandomState(7).randn(N, 32).astype(np.float32)
with r:
    with ThreadPoolExecutor(max_workers=N) as pool:
        outs = list(pool.map(
            lambda i: np.asarray(r.predict(data[i], timeout=60.0)), range(N)
        ))
    snap = r.snapshot()
    path = r.export_trace(sys.argv[1])

codec = sys.argv[2]
np.save(f"{sys.argv[3]}/out_{codec}.npy", np.stack(outs))
c = snap["counters"]
frames = int(c.get("wire.frames.req", 0))
co_frames = int(c.get("coalesce.frames", 0))
co_members = int(c.get("coalesce.members", 0))
assert frames and frames < N, (
    f"coalescer sent {frames} req frames for {N} requests"
)
assert co_frames >= 1 and co_members > co_frames, c
assert int(c.get("wire.bytes_sent.req", 0)) > 0, c
with open(path) as f:
    doc = json.load(f)
names = {e["name"] for e in doc["traceEvents"]}
assert "wire.encode" in names, sorted(names)
print(f"HOT WIRE OK ({codec}): {N} requests on {frames} req frame(s), "
      f"{co_members} member(s) coalesced into {co_frames} frame(s)")
PY
done
python - "$hw_dir" <<'PY'
import sys

import numpy as np

d = sys.argv[1]
a = np.load(f"{d}/out_binary.npy")
b = np.load(f"{d}/out_pickle.npy")
assert np.array_equal(a, b), "binary vs pickle outputs differ"
print(f"HOT WIRE PARITY OK: {a.shape[0]} outputs bit-equal across codecs")
PY
