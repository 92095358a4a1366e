"""One run of one cell: what every traffic kind shares.

The driver of the cell's traffic kind (``drivers/<kind>.py``) supplies four
functions — ``setup``, ``window``, ``release``, ``check`` — and this module
does the rest: it finds the cell's files by name, refuses anything but the
chip the cell asks for, zeroes the persistence threshold of XLA's cache
before the first jit, times the set-up phases, counts compile requests and
cache hits in set-up and in the window, takes the profiler's trace in a
``--trace 1`` run, reads the peak memory before the reference runs, and
prints the result.
"""

from __future__ import annotations

import argparse
import contextlib
import gc
import hashlib
import importlib.util
import json
import os
import shutil
import sys
import time

from benchmark import program

_HERE = os.path.dirname(os.path.abspath(__file__))

#: where a traced run keeps its one trace, inside the checkout; emptied
#: before each traced run so that a check writes little to disk
TRACE_DIR = ".bench_trace"


def load_json(path: str) -> dict:
    with open(path) as f:
        return json.load(f)


def load_module(path: str):
    """The module in the file at ``path``, loaded once. Found by path and
    not by package name, so that a cell, a driver or a reader that a later
    PR adds as a file is found wherever the benchmark's directory lies."""
    path = os.path.abspath(path)
    name = "benchmark_file_" + hashlib.sha1(path.encode()).hexdigest()[:16]
    if name not in sys.modules:
        spec = importlib.util.spec_from_file_location(name, path)
        if spec is None or not os.path.exists(path):
            raise SystemExit(f"benchmark: no such file: {path}")
        module = importlib.util.module_from_spec(spec)
        sys.modules[name] = module
        try:
            spec.loader.exec_module(module)
        except BaseException:
            del sys.modules[name]
            raise
    return sys.modules[name]


class Manifest:
    """``BENCHMARK.json`` and the files its names lead to."""

    def __init__(self, root: str, bench_dir: str = _HERE):
        self.root = root
        self.bench_dir = bench_dir
        self.doc = load_json(os.path.join(root, "BENCHMARK.json"))

    def cell(self, name: str) -> dict:
        for cell in self.doc["workloads"]:
            if cell["name"] == name:
                return cell
        raise SystemExit(
            f"benchmark: no workload {name!r} in BENCHMARK.json (has: "
            + ", ".join(c["name"] for c in self.doc["workloads"]) + ")"
        )

    def _config_file(self, name: str) -> str:
        for entry in self.doc["configs"]:
            if entry["name"] == name:
                return os.path.join(self.root, entry["file"])
        raise SystemExit(f"benchmark: no configuration {name!r}")

    def config(self, name: str) -> dict:
        return load_json(self._config_file(name))

    def adapter(self, config_name: str, part: str):
        """Beside ``<config>.json``: ``<config>_reference.py``, the plain
        reference, and ``<config>_program.py``, which drives the program."""
        stem = self._config_file(config_name)[: -len(".json")]
        return load_module(f"{stem}_{part}.py")

    def _path(self, kind: str, name: str, ext: str) -> str:
        return os.path.join(self.bench_dir, kind, name + ext)

    def driver(self, kind: str):
        return load_module(self._path("drivers", kind, ".py"))

    def reader(self, name: str):
        return load_module(self._path("readers", name, ".py"))

    def ops(self, name: str):
        return load_module(self._path("ops", name, ".py"))

    def limits(self, workload: str) -> dict:
        doc = load_json(self._path("limits", workload, ".json"))
        return {name: row["limit"] for name, row in doc["numbers"].items()}

    def traffic(self, name: str) -> dict:
        return load_json(self._path("traffic", name, ".json"))

    def peaks(self) -> dict:
        table = load_json(os.path.join(self.bench_dir, "peaks.json"))
        return {k: v for k, v in table.items() if not k.startswith("_")}

    def runtime_env(self, traffic: dict) -> dict:
        """The TPU runtime's environment for a cell: ``runtime_env.json``,
        with the traffic file's ``env`` group over it."""
        table = load_json(os.path.join(self.bench_dir, "runtime_env.json"))
        env = {k: v for k, v in table.items() if not k.startswith("_")}
        env.update(traffic.get("env", {}))
        return {k: str(v) for k, v in env.items()}

    def metrics_of(self, cell: str, group: str) -> list:
        """The metrics of ``group`` (``end_to_end`` / ``per_layer``) that
        ``cell`` reports: those that list it, and those that list none."""
        return [
            m for m in self.doc[group]
            if "workloads" not in m or cell in m["workloads"]
        ]

    def metric_file(self, name: str) -> dict:
        return load_json(self._path("metrics", name, ".json"))


def row_seed(seed: int) -> int:
    """``--seed`` as ``jax.random.PRNGKey`` takes it: the driver's seeds
    pass 2**31, and the seeds under 1,024 are the configurations' own
    (``task_seed``, ``train_seed``, ``feature_seed``)."""
    return 1024 + int(seed) % (2**31 - 2048)


class Phases:
    """Seconds of each set-up phase on the host clock, in order."""

    def __init__(self):
        self.seconds: dict = {}

    @contextlib.contextmanager
    def __call__(self, name: str):
        t0 = time.perf_counter()
        try:
            yield
        finally:
            self.seconds[name] = round(
                self.seconds.get(name, 0.0) + time.perf_counter() - t0, 4
            )


class CompileCounts:
    """Compile requests and persistent-cache traffic from ``jax.monitoring``.
    A request that the persistent cache answers still counts as a request,
    so real compiles = requests − hits."""

    def __init__(self):
        from jax import monitoring

        self.requests = self.hits = self.misses = 0
        monitoring.register_event_duration_secs_listener(self._on_duration)
        monitoring.register_event_listener(self._on_event)

    def _on_duration(self, event, duration, **kw):
        if event.endswith("backend_compile_duration"):
            self.requests += 1

    def _on_event(self, event, **kw):
        if event.endswith("compilation_cache/cache_hits"):
            self.hits += 1
        elif event.endswith("compilation_cache/cache_misses"):
            self.misses += 1

    def snapshot(self) -> dict:
        return {
            "compile_requests": self.requests,
            "persistent_cache_hits": self.hits,
            "persistent_cache_misses": self.misses,
        }


def cache_entries(root) -> int:
    """Files under the compile cache's directory (0 where there is none)."""
    if not root:
        return 0
    return sum(len(files) for _, _, files in os.walk(root))


def peak_bytes() -> int:
    """Peak bytes in use on the fullest chip."""
    import jax

    return max(
        int((d.memory_stats() or {}).get("peak_bytes_in_use", 0))
        for d in jax.devices()
    )


class Run:
    """What a driver and the readers are handed: the cell's files, the
    seed, the device and what the run has gathered so far."""

    def __init__(self, *, manifest, cell, config, traffic, seed, seconds,
                 trace, device, peak, phases):
        self.manifest = manifest
        self.cell, self.config, self.traffic = cell, config, traffic
        self.seed, self.seconds, self.trace = seed, seconds, trace
        self.device, self.peak, self.phases = device, peak, phases
        self.row_seed = row_seed(seed)
        self.reference = manifest.adapter(cell["config"], "reference")
        self.program = manifest.adapter(cell["config"], "program")
        self.limits = manifest.limits(cell["name"])
        #: filled by the harness: ``setup.*`` / ``window.*`` compile counts
        self.counters: dict = {}
        #: filled by the driver's ``window``: fits, rows, seconds, latencies
        self.facts: dict = {}
        #: filled in a traced run: ``trace.Reduction``, program spans,
        #: the serving registry's snapshot
        self.reduction = None
        self.spans: list = []
        self.registry: dict = {}


def _read_layer_metrics(manifest: Manifest, run: Run) -> dict:
    out = {}
    for entry in manifest.metrics_of(run.cell["name"], "per_layer"):
        spec = manifest.metric_file(entry["name"])
        reader = manifest.reader(spec["reader"])
        value = reader.read(spec.get("params", {}), run)
        if value is not None:
            out[entry["name"]] = {"value": float(value), "unit": entry["unit"]}
    return out


def _is_shown(fact) -> bool:
    """A fact the report line prints: a number, or up to 256 of them."""
    number = (int, float)
    return isinstance(fact, number) or (
        isinstance(fact, list) and len(fact) <= 256
        and all(isinstance(x, number) for x in fact)
    )


def _end_to_end(manifest: Manifest, run: Run, setup_s: float) -> dict:
    facts = dict(run.facts, setup_s=setup_s)
    out = {}
    for entry in manifest.metrics_of(run.cell["name"], "end_to_end"):
        if entry["name"] not in facts:
            raise SystemExit(
                f"benchmark: the {run.traffic['kind']} driver gave no "
                f"{entry['name']} for {run.cell['name']}"
            )
        out[entry["name"]] = {
            "value": float(facts[entry["name"]]), "unit": entry["unit"]
        }
    return out


def main(argv, *, root: str, started: float) -> int:
    ap = argparse.ArgumentParser("benchmark/run.py")
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument(
        "--setup-only", action="store_true",
        help="stop after set-up and print its phases (no window, no result)",
    )
    args = ap.parse_args(argv)

    manifest = Manifest(root)
    cell = manifest.cell(args.workload)
    config = manifest.config(cell["config"])
    traffic = manifest.traffic(cell["traffic"])
    phases = Phases()

    with phases("import"):
        os.environ.setdefault("JAX_PLATFORMS", "tpu")
        # before jax loads the runtime: its start is most of what varies
        for key, value in manifest.runtime_env(traffic).items():
            os.environ.setdefault(key, value)
        peaks = manifest.peaks()
        try:
            import jax

            import keystone_tpu  # noqa: F401  (places the compile cache)
        except ImportError as e:
            print(f"benchmark: the program is not here: {e}", file=sys.stderr)
            return 2
        driver = manifest.driver(traffic["kind"])
    with phases("backend_init"):
        device = program.require_tpu(cell["chips"], peaks)
        # every warm compile answered from the cache: a program that
        # compiles in under the package's half second is persisted too.
        # The cache stays where JAX_COMPILATION_CACHE_DIR or the package
        # put it; the AOT cache stays off, so that every run traces the
        # same programs whatever an earlier run left.
        jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
        jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)
    return execute(
        manifest, driver, cell=cell, config=config, traffic=traffic,
        args=args, device=device, peak=peaks[device["kind"]], phases=phases,
        started=started,
    )


def execute(manifest, driver, *, cell, config, traffic, args, device, peak,
            phases, started) -> int:
    """The run from the first jit on. ``main`` has looked for the chip; a
    test calls this with the device it has."""
    import jax

    cache_dir = jax.config.jax_compilation_cache_dir
    counts = CompileCounts()
    entries_before = cache_entries(cache_dir)

    run = Run(
        manifest=manifest, cell=cell, config=config, traffic=traffic,
        seed=args.seed, seconds=args.seconds, trace=bool(args.trace),
        device=device, peak=peak, phases=phases,
    )
    state = driver.setup(run)
    setup_s = time.perf_counter() - started
    run.counters.update(
        {f"setup.{k}": v for k, v in counts.snapshot().items()}
    )
    entries_setup = cache_entries(cache_dir)

    report = {
        "workload": cell["name"], "seed": args.seed, "trace": args.trace,
        "setup_s": round(setup_s, 4), "phases_s": phases.seconds,
        "runtime_env": {
            k: os.environ.get(k) for k in manifest.runtime_env(traffic)
        },
        "setup": {
            **counts.snapshot(),
            "cache_entries_before": entries_before,
            "cache_entries_added": entries_setup - entries_before,
        },
    }
    if args.setup_only:
        driver.release(run, state)
        print(json.dumps(report), flush=True)
        return 0

    before = counts.snapshot()
    trace_dir = os.path.join(manifest.root, TRACE_DIR)
    if run.trace:
        from benchmark import trace as trace_mod

        shutil.rmtree(trace_dir, ignore_errors=True)
        seconds = min(args.seconds, float(traffic.get("trace_seconds", 5.0)))
        with trace_mod.traced(trace_dir):
            produced = driver.window(run, state, seconds)
    else:
        produced = driver.window(run, state, args.seconds)
    after = counts.snapshot()
    run.counters.update(
        {f"window.{k}": after[k] - before[k] for k in after}
    )
    entries_window = cache_entries(cache_dir)
    memory_peak = peak_bytes()

    driver.release(run, state)
    del state
    gc.collect()
    t0 = time.perf_counter()
    compared = driver.check(run, produced)
    reference_s = time.perf_counter() - t0
    correct = all(row["value"] <= row["limit"] for row in compared.values())

    device_out = dict(device, memory_peak_bytes=memory_peak)
    result = {
        "correct": bool(correct),
        "attempted": int(run.facts["attempted"]),
        "failed": int(run.facts["failed"]),
    }
    if run.trace:
        run.reduction = trace_mod.reduce(
            trace_mod.load(trace_dir), chips=cell["chips"]
        )
        shutil.rmtree(trace_dir, ignore_errors=True)
        result["metrics"] = _read_layer_metrics(manifest, run)
        device_out["busy_s"] = run.reduction.busy_s
        device_out["window_s"] = run.reduction.window_s
        result["device"] = device_out
        result["breakdown"] = run.reduction.breakdown()
    else:
        result["metrics"] = _end_to_end(manifest, run, setup_s)
        result["device"] = device_out
    result["compared"] = compared

    report["window"] = {k[7:]: v for k, v in run.counters.items()
                        if k.startswith("window.")}
    report["window"]["cache_entries_added"] = entries_window - entries_setup
    report["window"]["seconds"] = run.facts.get("window_s")
    # the driver's own numbers: the metric's statistic beside the others,
    # and a short list such as each job's duration
    report["window"]["facts"] = {
        k: v for k, v in run.facts.items() if _is_shown(v)
    }
    report["reference_s"] = round(reference_s, 3)
    # every number the comparison read, those that have no limit too
    report["compared_all"] = run.facts.get("compared_all")
    sys.stderr.flush()
    print(json.dumps(report), flush=True)
    for name, row in compared.items():
        print(
            f"compared {name}: {row['value']!r} (limit {row['limit']!r})",
            file=sys.stderr,
        )
    sys.stderr.flush()
    print(json.dumps(result), flush=True)
    return 0
