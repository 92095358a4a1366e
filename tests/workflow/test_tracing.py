"""Pipeline tracing subsystem (keystone_tpu/obs): span tree, executor
cache hit/miss attribution, Chrome-trace export, the autocache
estimate-vs-observed audit, serving micro-batch spans, and the CLI
``--trace`` wiring."""

import json
import re
import threading

import numpy as np
import pytest

from keystone_tpu.data.dataset import Dataset
from keystone_tpu.obs import tracer as trace_mod
from keystone_tpu.obs.audit import cache_audit, log_cache_audit
from keystone_tpu.obs.export import (
    format_top_spans,
    to_chrome_trace,
    write_chrome_trace,
)
from keystone_tpu.workflow.executor import GraphExecutor
from keystone_tpu.workflow.graph import Graph
from keystone_tpu.workflow.operators import DatasetOperator
from keystone_tpu.workflow.transformer import FunctionNode, Transformer


@pytest.fixture(autouse=True)
def clean_tracer(past_first_job):
    """Tracing must never leak across tests — a leaked tracer would add a
    device sync to every executor pull in the rest of the suite. A test
    starts past the process's first job; the boot recorder's own tests
    arm it anew with ``reset()``."""


def _installed():
    return trace_mod.install(trace_mod.Tracer())


# ---------------------------------------------------------------------------
# tracer core
# ---------------------------------------------------------------------------


def test_span_tree_nesting_and_ids():
    t = trace_mod.Tracer()
    with t.span("outer") as outer:
        with t.span("inner") as inner:
            pass
    spans = {sp.name: sp for sp in t.spans()}
    assert spans["inner"].parent_id == spans["outer"].span_id
    assert spans["inner"].depth == 1
    assert spans["outer"].parent_id is None
    assert spans["outer"].end >= spans["inner"].end
    assert outer.span_id != inner.span_id


def test_span_stacks_are_per_thread():
    t = trace_mod.Tracer()
    started = threading.Barrier(2)

    def work(name):
        with t.span(name):
            started.wait(timeout=5)

    threads = [threading.Thread(target=work, args=(f"t{i}",)) for i in (0, 1)]
    for th in threads:
        th.start()
    for th in threads:
        th.join()
    # both spans overlapped in time yet neither parents the other
    assert all(sp.parent_id is None for sp in t.spans())
    assert {sp.name for sp in t.spans()} == {"t0", "t1"}


def test_disabled_tracing_records_nothing():
    t = trace_mod.Tracer()
    trace_mod.install(t)
    trace_mod.stop()
    fitted = (
        FunctionNode(batch_fn=lambda X: X * 2.0, label="double")
        .to_pipeline()
        .fit()
    )
    fitted.apply(np.ones((3, 2), np.float32))
    assert trace_mod.current() is None
    assert t.spans() == []


def test_suspended_reinstalls_tracer():
    t = _installed()
    with trace_mod.suspended():
        assert trace_mod.current() is None
    assert trace_mod.current() is t


# ---------------------------------------------------------------------------
# executor instrumentation
# ---------------------------------------------------------------------------


class _Scale(Transformer):
    def __init__(self, factor):
        self.factor = factor

    def apply(self, x):
        return x * self.factor


def _chain_graph():
    g = Graph()
    g, leaf = g.add_node(
        DatasetOperator(Dataset(np.ones((4, 2), np.float32), batched=True)), []
    )
    g, n1 = g.add_node(_Scale(2.0), [leaf])
    g, n2 = g.add_node(_Scale(3.0), [n1])
    g, sink = g.add_sink(n2)
    return g, (leaf, n1, n2), sink


def test_executor_records_miss_then_hit_spans():
    g, (leaf, n1, n2), sink = _chain_graph()
    t = _installed()
    ex = GraphExecutor(g, optimize=False)
    ex.execute(sink).get()
    misses = [sp for sp in t.spans() if sp.cache == "miss"]
    assert {sp.node_id for sp in misses} == {
        str(leaf.id), str(n1.id), str(n2.id)
    }
    for sp in misses:
        assert sp.op_type in ("DatasetOperator", "_Scale")
        assert sp.sync_seconds >= 0.0
    # a second pull returns the memoized sink expression: hit, no recompute
    before = len(t.spans())
    ex.execute(sink).get()
    new = t.spans()[before:]
    assert [sp.cache for sp in new] == ["hit"]
    assert new[0].node_id == str(n2.id)
    assert new[0].instant


def test_executor_span_reports_output_bytes():
    g, (leaf, n1, n2), sink = _chain_graph()
    t = _installed()
    GraphExecutor(g, optimize=False).execute(sink).get()
    sp = next(s for s in t.spans() if s.node_id == str(n2.id))
    assert sp.output_bytes == 4 * 2 * 4  # (4,2) float32


# ---------------------------------------------------------------------------
# exporters
# ---------------------------------------------------------------------------


def test_chrome_trace_well_formed(tmp_path):
    g, _, sink = _chain_graph()
    t = _installed()
    GraphExecutor(g, optimize=False).execute(sink).get()
    GraphExecutor(g, optimize=False).execute(sink).get()  # fresh miss spans
    doc = to_chrome_trace(t)
    events = doc["traceEvents"]
    assert events
    ts = [e["ts"] for e in events]
    assert all(b >= a for a, b in zip(ts, ts[1:])), "ts must be monotonic"
    complete = [e for e in events if e["ph"] == "X"]
    assert all("dur" in e and e["dur"] >= 0 for e in complete)
    assert any(e["args"].get("cache") == "miss" for e in complete)
    path = tmp_path / "trace.json"
    write_chrome_trace(t, str(path))
    assert json.loads(path.read_text())["traceEvents"]


def test_top_summary_and_schema():
    g, _, sink = _chain_graph()
    t = _installed()
    GraphExecutor(g, optimize=False).execute(sink).get()
    summary = t.span_summary()
    assert summary
    for row in summary.values():
        # the one shape shared with timing.snapshot / metrics "phases"
        assert {"seconds", "calls"} <= set(row)
    text = format_top_spans(t, n=3)
    assert "node." in text and "seconds" in text


# ---------------------------------------------------------------------------
# autocache audit
# ---------------------------------------------------------------------------


def _reused_dag():
    """leaf → a → b → (c, d): b is consumed twice, so greedy caches it."""
    g = Graph()
    g, leaf = g.add_node(
        DatasetOperator(Dataset(np.ones((4, 2), np.float32), batched=True)), []
    )
    g, a = g.add_node(_Scale(2.0), [leaf])
    g, b = g.add_node(_Scale(3.0), [a])
    g, c = g.add_node(_Scale(4.0), [b])
    g, d = g.add_node(_Scale(5.0), [b])
    g, s1 = g.add_sink(c)
    g, s2 = g.add_sink(d)
    return g, (a, b, c, d), (s1, s2)


def test_cache_audit_covers_every_cacher_annotated_node(caplog):
    from keystone_tpu.workflow.autocache import AutoCacheRule, Profile

    g, (a, b, c, d), (s1, s2) = _reused_dag()
    profiles = {
        a: Profile(ns=1e6, mem_bytes=100),
        b: Profile(ns=5e6, mem_bytes=200),  # expensive + reused → cached
        c: Profile(ns=1e3, mem_bytes=50),
        d: Profile(ns=1e3, mem_bytes=50),
    }
    t = _installed()
    g2, ann = AutoCacheRule("greedy", 10_000, profiles).apply(g, {})
    ex = GraphExecutor(g2, optimize=False)
    ex._annotations = ann
    ex.execute(s1).get()
    ex.execute(s2).get()

    rows = cache_audit(t)
    by_node = {r["node"]: r for r in rows}
    cachers = {
        str(g2.get_dependencies(n)[0].id)
        for n in g2.nodes
        if type(g2.get_operator(n)).__name__ == "Cacher"
    }
    assert cachers, "greedy must have inserted at least one Cacher"
    # the audit covers every Cacher-annotated node, with estimate AND
    # observation joined (the feedback loop the reference never closed)
    for node in cachers:
        row = by_node[node]
        assert row["cacher"] is True
        assert row["observed"] is True
        assert row["est_seconds"] > 0 and row["obs_seconds"] is not None
        assert row["est_bytes"] > 0 and row["obs_bytes"] is not None
    # every profiled node is audited, cached or not
    assert {str(n.id) for n in profiles} <= set(by_node)

    import logging

    with caplog.at_level(logging.INFO, logger="keystone_tpu.obs.audit"):
        assert log_cache_audit(t) == rows
    assert "autocache audit" in caplog.text


def test_observed_seconds_are_exclusive_of_children():
    """Lazy evaluation nests upstream spans inside downstream ones; the
    audit's observations must subtract child time or every downstream
    node reads as mis-estimated (inclusive-vs-exclusive mismatch)."""
    import time

    from keystone_tpu.obs.audit import observed_by_node

    t = trace_mod.Tracer()
    with t.span("node.parent", node_id="1", cache="miss"):
        with t.span("node.child", node_id="2", cache="miss"):
            time.sleep(0.05)
    obs = observed_by_node(t)
    assert obs["2"]["seconds"] >= 0.045
    assert obs["1"]["seconds"] < 0.04, "child time must not count twice"


def test_profiling_runs_do_not_pollute_the_trace():
    from keystone_tpu.workflow.autocache import profile_nodes

    g, _, _ = _reused_dag()
    t = _installed()
    profile_nodes(g, sample_sizes=(2,), full_size=4)
    assert t.spans() == [], "sampled-scale profiling pulls must be suspended"


# ---------------------------------------------------------------------------
# serving spans
# ---------------------------------------------------------------------------


def test_serving_microbatch_span_and_metrics_alignment():
    from keystone_tpu.serving.engine import ServingEngine

    fitted = (
        FunctionNode(batch_fn=lambda X: X * 2.0, label="double")
        >> FunctionNode(batch_fn=lambda X: X.sum(axis=1), label="rowsum")
    ).fit()
    t = _installed()
    engine = ServingEngine(fitted, buckets=(4,), datum_shape=(2,))
    with engine:
        engine.predict(np.ones(2, np.float32), timeout=30.0)
    spans = [sp for sp in t.spans() if sp.name == "serve.microbatch"]
    assert spans and spans[0].attrs["bucket"] == 4
    snap = engine.metrics.snapshot()
    assert "serve.microbatch" in snap["spans"]
    # phases and spans share one {name: {seconds, calls, ...}} schema and
    # disjoint names, so they concatenate without collisions
    merged = {**snap["phases"], **snap["spans"]}
    assert len(merged) == len(snap["phases"]) + len(snap["spans"])
    for row in merged.values():
        assert {"seconds", "calls"} <= set(row)


def test_metrics_spans_empty_without_tracer():
    from keystone_tpu.serving.metrics import MetricsRegistry

    assert MetricsRegistry("t").snapshot()["spans"] == {}


# ---------------------------------------------------------------------------
# CLI wiring
# ---------------------------------------------------------------------------


def test_cli_trace_flag_writes_chrome_trace(tmp_path, capsys):
    from keystone_tpu.__main__ import main

    path = tmp_path / "t.json"
    rc = main([
        "mnist", "--numFFTs", "2", "--blockSize", "512", "--lambda", "100",
        "--trace", str(path),
    ])
    assert rc == 0
    assert "TEST Error" in capsys.readouterr().out
    doc = json.loads(path.read_text())
    events = doc["traceEvents"]
    assert events
    ts = [e["ts"] for e in events]
    assert all(b >= a for a, b in zip(ts, ts[1:]))
    node_events = [e for e in events if e.get("args", {}).get("node")]
    assert node_events, "expected per-DAG-node spans"
    assert any(e["args"].get("cache") for e in node_events)
    assert any(e["name"] == "pipeline.fit" for e in events)


def test_cli_alias_rejects_unknown_name():
    from keystone_tpu.__main__ import main

    with pytest.raises(SystemExit):
        main(["mnits"])


# ---------------------------------------------------------------------------
# the span primitive (obs.tracer.span): annotation always, a Span in memory
# while an installed tracer or a profiler session records
# ---------------------------------------------------------------------------


@pytest.fixture
def session(tmp_path):
    """A profiler session around the body: ``with session() as done:`` and,
    after it, ``done.annotations()`` — the host plane's ``ks:`` events as
    ``(name, start_ns, end_ns, line)`` read from the xplane."""
    import contextlib
    import glob

    import jax

    class _Done:
        def annotations(self):
            from jax.profiler import ProfileData

            (path,) = glob.glob(
                str(tmp_path / "plugins" / "profile" / "*" / "*.xplane.pb")
            )
            return [
                (ev.name, ev.start_ns, ev.start_ns + ev.duration_ns, line.name)
                for plane in ProfileData.from_file(path).planes
                if plane.name.startswith("/host:")
                for line in plane.lines
                for ev in line.events
                if ev.name.startswith("ks:")
            ]

    @contextlib.contextmanager
    def run():
        options = jax.profiler.ProfileOptions()
        options.python_tracer_level = 0
        jax.profiler.start_trace(str(tmp_path), profiler_options=options)
        try:
            yield _Done()
        finally:
            jax.profiler.stop_trace()

    return run


def test_span_off_runs_the_body_and_allocates_no_span(monkeypatch):
    made = []
    real = trace_mod.Span

    def counting(*a, **kw):
        made.append(kw.get("name"))
        return real(*a, **kw)

    monkeypatch.setattr(trace_mod, "Span", counting)
    ran = []
    with trace_mod.span("off.region", rows=3) as sp:
        ran.append(True)
        sp.sync_on(object())
        sp.attrs["path"] = "compiled"
        sp.attrs.update(more=1)
    assert ran == [True] and made == []
    assert sp is trace_mod.NULL_SPAN and dict(sp.attrs) == {}
    assert trace_mod.session_spans() == []
    # the counter does count once someone records
    with trace_mod.install(trace_mod.Tracer()).span("on.region"):
        pass
    assert made == ["on.region"]


def test_span_propagates_the_bodys_exception_and_still_records():
    t = _installed()
    with pytest.raises(KeyError):
        with trace_mod.span("raises"):
            raise KeyError("boom")
    (sp,) = t.spans()
    assert sp.name == "raises" and sp.end >= sp.start
    assert t.current_span() is None  # the stack unwound


def test_session_records_unsynced_spans_and_the_same_annotations(
    session, monkeypatch
):
    import jax
    import jax.numpy as jnp

    from keystone_tpu.obs import span as span_mod

    blocked = []
    monkeypatch.setattr(
        jax, "block_until_ready", lambda x: blocked.append(x) or x
    )
    sized = []
    monkeypatch.setattr(
        trace_mod, "cheap_nbytes", lambda x: sized.append(x) or 0
    )
    with session() as done:
        assert trace_mod.current() is None
        with trace_mod.span("outer", rows=4) as outer:
            with trace_mod.span("inner") as inner:
                inner.sync_on(jnp.ones((4,)))
            with trace_mod.span("inner"):
                pass
    with trace_mod.span("after.the.session") as sp:
        assert sp is trace_mod.NULL_SPAN
    # never synced, never sized: the device trace knows when the chip ran
    assert blocked == [] and sized == []
    assert span_mod.sync_value is trace_mod.sync_value  # what was not called
    spans = trace_mod.session_spans()  # readable after stop_trace
    assert [sp.name for sp in spans] == ["inner", "inner", "outer"]
    assert all(sp.parent_id == outer.span_id for sp in spans[:2])
    assert spans[2].attrs == {"rows": 4} and spans[0].sync_seconds == 0.0
    assert all(sp.output_bytes is None for sp in spans)

    # the xplane's host plane holds the same spans with the same nesting
    notes = sorted(done.annotations(), key=lambda a: a[1])
    assert [a[0] for a in notes] == ["ks:outer", "ks:inner", "ks:inner"]
    (_, o_start, o_end, o_line), first, second = notes
    for _, start, end, line in (first, second):
        assert o_start <= start <= end <= o_end and line == o_line
    assert first[2] <= second[1]
    # ... and on one clock once shifted: durations agree to a millisecond
    for sp, note in zip(sorted(spans, key=lambda s: s.start), notes):
        assert abs((note[2] - note[1]) * 1e-9 - sp.seconds) < 1e-3


def test_a_new_session_starts_a_new_list_and_the_list_is_bounded(
    session, monkeypatch
):
    monkeypatch.setattr(trace_mod, "SESSION_MAX_SPANS", 3)
    with session():
        for i in range(5):
            with trace_mod.span(f"s{i}"):
                pass
    assert [sp.name for sp in trace_mod.session_spans()] == ["s0", "s1", "s2"]
    assert trace_mod._session.dropped == 2
    with trace_mod.span("between"):  # no session: seen, not recorded
        pass
    with session():
        with trace_mod.span("again"):
            pass
    assert [sp.name for sp in trace_mod.session_spans()] == ["again"]


def test_installed_tracer_wins_over_a_session_and_syncs(session):
    import jax.numpy as jnp

    t = _installed()
    with session():
        with trace_mod.span("synced") as sp:
            sp.sync_on(jnp.ones((8,), jnp.float32))
    assert trace_mod.session_spans() == []  # not "the tracer" of anyone
    (recorded,) = t.spans()
    assert recorded.output_bytes == 32 and recorded.sync_target is None
    assert t.span_summary()["synced"]["calls"] == 1


def test_session_recorder_is_not_the_current_tracer(session):
    """``fit_instrumentation``, ``AutoCacheRule`` and ``cost.finalize`` ask
    ``current()``: a profiler session must switch none of them on."""
    with session():
        with trace_mod.span("seen"):
            assert trace_mod.current() is None
        with trace_mod.suspended():
            with trace_mod.span("profiling.run") as sp:
                assert sp is trace_mod.NULL_SPAN
    assert [sp.name for sp in trace_mod.session_spans()] == ["seen"]


def _gather_pull():
    """A pull with width: two host-bound branches the concurrent executor
    forces on ``keystone-exec`` workers, under one ``pipeline.pull``."""
    import time as _time

    from keystone_tpu.workflow.pipeline import Pipeline

    def slow(tag):
        # per-item and untraceable: fusion and segments leave it a node
        def fn(x):
            _time.sleep(0.005)
            return np.asarray(x) + tag

        return FunctionNode(item_fn=fn, label=f"slow{tag}")

    pipe = Pipeline.gather([slow(1), slow(2)])
    return pipe.apply(np.ones((4, 2), np.float32))


@pytest.mark.parametrize("recorder", ["installed", "session"])
def test_adopt_parents_worker_spans_under_the_pull(recorder, session):
    if recorder == "installed":
        t = _installed()
        _gather_pull().get()
        spans = t.spans()
    else:
        with session():
            _gather_pull().get()
        spans = trace_mod.session_spans()
    pull = next(sp for sp in spans if sp.name == "pipeline.pull")
    by_id = {sp.span_id: sp for sp in spans}
    slow = [sp for sp in spans if sp.name.startswith("node.slow")]
    assert len(slow) == 2
    for sp in slow:
        assert sp.thread_name.startswith("keystone-exec")
        up = sp
        while up.parent_id is not None:
            up = by_id[up.parent_id]
        assert up is pull, f"{sp.name} is a root on its worker thread"


def test_suspension_reaches_the_workers_a_suspended_thread_starts(session):
    with session():
        with trace_mod.suspended():
            _gather_pull().get()
    assert trace_mod.session_spans() == []


# ---------------------------------------------------------------------------
# one tiny TIMIT job: the spans of every layer, the scopes on the kernels
# ---------------------------------------------------------------------------


@pytest.fixture(scope="module")
def timit_job(tmp_path_factory):
    """One tiny TIMIT job on the CPU under a profiler session: its spans,
    and the lowered text (with debug info) of the block solver's scan and
    of the fitted apply."""
    import jax
    import jax.numpy as jnp

    from keystone_tpu.linalg.bcd import _bcd_scan
    from keystone_tpu.pipelines.timit import TimitConfig, run, synthetic_timit
    from keystone_tpu.workflow.env import PipelineEnv

    conf = TimitConfig(
        num_cosines=2, cosine_features=64, num_classes=5, num_epochs=2
    )
    train = synthetic_timit(256, 5, seed=1)
    test = synthetic_timit(64, 5, seed=2)
    PipelineEnv.get_or_create().reset()
    trace_mod.reset()
    options = jax.profiler.ProfileOptions()
    options.python_tracer_level = 0
    jax.profiler.start_trace(
        str(tmp_path_factory.mktemp("timit_job")), profiler_options=options
    )
    try:
        predictor, _, _ = run(train, test, conf)
    finally:
        jax.profiler.stop_trace()
    spans = trace_mod.session_spans()
    solver = _bcd_scan.lower(
        jnp.ones((256, 128)), jnp.ones((256, 5)), jnp.float32(0.0),
        jnp.zeros((128,)), block_size=64, num_iter=2,
    ).as_text(debug_info=True)
    apply = jax.jit(predictor.fit().trace_fn()).lower(
        jnp.ones((64, 440), jnp.float32)
    ).as_text(debug_info=True)
    PipelineEnv.get_or_create().reset()
    return {"spans": spans, "solver": solver, "apply": apply}


@pytest.mark.parametrize("name", [
    "plan.build", "plan.optimize", "plan.segments", "exec.segment",
    "block_ls.solve", "xfer.d2h", "eval.metrics",
])
def test_a_timit_job_emits_the_span_inside_job(timit_job, name):
    spans = timit_job["spans"]
    (job,) = [sp for sp in spans if sp.name == "job"]
    assert job.attrs == {"pipeline": "Timit"} and job.parent_id is None
    by_id = {sp.span_id: sp for sp in spans}
    named = [sp for sp in spans if sp.name == name]
    assert named, sorted({sp.name for sp in spans})
    for sp in named:
        up = sp
        while up.parent_id is not None:
            up = by_id[up.parent_id]
        assert up is job, f"{name} is not inside job"
        assert job.start <= sp.start <= sp.end <= job.end


@pytest.mark.parametrize("text,scope", [
    ("solver", "ks.solver.gram"), ("solver", "ks.solver.cross"),
    ("solver", "ks.solver.factor_solve"), ("solver", "ks.solver.residual"),
    ("apply", "ks.featurize.cosine"), ("apply", "ks.apply.scores"),
    ("apply", "ks.apply.argmax"),
])
def test_the_kernels_lower_under_their_named_scopes(timit_job, text, scope):
    # loc("jit(fn)/<scope>") on a call, loc("<scope>/<op>") inside a scan
    assert re.search(rf'["/]{re.escape(scope)}["/]', timit_job[text])
    # metadata only: the jitted functions keep the names the benchmark's
    # older patterns match on
    assert "_bcd_scan_impl" in timit_job["solver"]


def test_a_timit_job_hashes_each_parameter_array_once():
    """The counter that says the shared digest engaged: ``digest_bytes``
    over a job's ``plan.build`` / ``plan.rule`` / ``plan.segments`` spans
    is the job's distinct parameter bytes — each ``W`` and ``b`` hashed
    once, where the three sites hashed each four times — and the other
    three looks at each array are ``digest_hits``. The next job's arrays
    are new objects: fresh content until hashed, so hashed once again."""
    from keystone_tpu.pipelines.timit import TimitConfig, run, synthetic_timit
    from keystone_tpu.workflow.env import PipelineEnv

    branches, features, dim = 4, 64, 440
    conf = TimitConfig(
        num_cosines=branches, cosine_features=features, num_classes=5,
        num_epochs=2,
    )
    train = synthetic_timit(256, 5, seed=1)
    test = synthetic_timit(64, 5, seed=2)
    w_bytes, b_bytes = features * dim * 4, features * 4
    tracer = _installed()
    for job in range(2):
        PipelineEnv.get_or_create().reset()
        seen = len(tracer.spans())
        run(train, test, conf)
        spans = tracer.spans()[seen:]
        by_site = {
            site: (
                sum(sp.digest_bytes for sp in spans if sp.name == site),
                sum(sp.digest_hits for sp in spans if sp.name == site),
            )
            for site in ("plan.build", "plan.rule", "plan.segments")
        }
        # built and first keyed in plan.build; the optimizer's rule and
        # the two segments' fingerprints then meet the same array objects
        assert by_site == {
            "plan.build": (branches * (w_bytes + b_bytes), 0),
            "plan.rule": (0, branches * 2),
            "plan.segments": (0, 2 * branches * 2),
        }, (job, by_site)
        (whole,) = [sp for sp in spans if sp.name == "job"]
        assert whole.digest_bytes == branches * (w_bytes + b_bytes)
        # 12 of the hits are on the four W: 4 in the rule, 8 in the segments
        assert whole.digest_hits == 3 * branches * 2
    PipelineEnv.get_or_create().reset()


@pytest.mark.parametrize("num_iter,keeps", [(3, True), (1, False)])
def test_block_ls_solve_span_says_what_the_solver_program_keeps(
    num_iter, keeps
):
    """The counter that says factor-once engaged: ``gram_products`` is the
    Gram products (and factorisations) the dispatched program computes,
    ``factor_bytes`` the stack it holds across the epochs."""
    import jax.numpy as jnp

    from keystone_tpu.nodes.learning import BlockLeastSquaresEstimator

    n, d, k, bs = 64, 32, 3, 8
    rng = np.random.default_rng(0)
    tracer = _installed()
    BlockLeastSquaresEstimator(block_size=bs, num_iter=num_iter, lam=0.1).fit(
        Dataset.of(jnp.asarray(rng.standard_normal((n, d)), jnp.float32)),
        Dataset.of(jnp.asarray(rng.standard_normal((n, k)), jnp.float32)),
    )
    (solve,) = [sp for sp in tracer.spans() if sp.name == "block_ls.solve"]
    assert solve.attrs["gram_products"] == d // bs
    if keeps:
        assert solve.attrs["factor_bytes"] == (d // bs) * bs * bs * 4
    else:
        assert solve.attrs["factor_bytes"] == 0
