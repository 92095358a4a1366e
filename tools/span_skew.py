"""How well the program's spans, joined to the profiler's clock as the
benchmark's ``span_idle`` reader joins them, agree with the ``ks:``
annotations that the same ``span`` calls left in the same trace.

    chiprun -- python3 tools/span_skew.py --workload timit_cos4.fit

It drives one traced window of a cell exactly as ``benchmark/run.py
--trace 1`` does, but keeps the trace and loads the xplane itself (the
harness keeps ``bench:`` annotations only). Printed, as one JSON line: the
offset and its per-step scatter, the widest gap between an aligned span's
edges and its annotation's, the idle split, what the content digest hashed
and answered from memory a unit by span name, what segment dispatch did
with the window's first segments (``rows``, the slices, ``conv_fused_rows``,
``sift_sampled_rows``, ``sift_sampled_path``, ``cosine_bounded_rows``,
``cosine_scores_rows``), what
the kernel solver did a unit (``krr.fit``'s ``blocks``, ``epochs``,
``block_size``, ``cached_bytes``; the ``entries`` of the ``krr.kernel``
generations and of the ``krr.apply`` spans), and
— for the readers that match device operations by name — whether the operations' names or stats
carry the ``ks.*`` named scopes. ``--cpu`` rehearses the host side on the
CPU with the tests' tiny benchmark (no device plane: no idle split).

It also prints the process's FIRST job — set-up's warm-up fit, which the
program's boot recorder keeps (``obs.tracer.first_job_spans()``): by span
name its seconds and what jax traced, lowered, compiled or loaded inside
it, and the table by program. ``--first-job`` takes the profiler session
around set-up instead of the window, so that the chip's idle gaps of a
one-job process are split by span too, joined at the ends of the ``ks:job``
annotation the ``job`` span itself leaves (no window is driven then).
"""

from __future__ import annotations

import argparse
import glob
import json
import os
import re
import shutil
import sys
import tempfile
import time

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)

SCOPE = re.compile(r"ks\.[a-z]+\.[a-z_]+")


def _xplane(trace_dir: str):
    from jax.profiler import ProfileData

    paths = sorted(glob.glob(
        os.path.join(trace_dir, "plugins", "profile", "*", "*.xplane.pb")
    ))
    return ProfileData.from_file(paths[-1])


def host_annotations(data, prefix: str) -> list:
    """``(name, start_s, end_s, line)`` of the host's annotations that
    start with ``prefix``."""
    out = []
    for plane in data.planes:
        if not plane.name.startswith("/host:"):
            continue
        for i, line in enumerate(plane.lines):  # a line a thread
            out.extend(
                (ev.name, ev.start_ns * 1e-9,
                 (ev.start_ns + ev.duration_ns) * 1e-9, (plane.name, i))
                for ev in line.events if ev.name.startswith(prefix)
            )
    return out


def scopes_on_device(path: str) -> dict:
    """Where the xplane keeps a device operation's ``ks.*`` scope, read
    from the raw protobuf (jax's ``ProfileData`` shows an event's own
    stats, not those of its metadata): which field or stat of the "XLA
    Ops" events' metadata holds it, with one sample."""
    try:
        from tensorflow.tsl.profiler.protobuf import xplane_pb2
    except ImportError as e:
        return {"unread": str(e)}
    space = xplane_pb2.XSpace()
    with open(path, "rb") as f:
        space.ParseFromString(f.read())
    found = {"op_metadata": 0, "held_by": {}, "sample": None}
    for plane in space.planes:
        if not plane.name.startswith("/device:"):
            continue
        ops = {
            ev.metadata_id for line in plane.lines if line.name == "XLA Ops"
            for ev in line.events
        }
        for mid in ops:
            meta = plane.event_metadata[mid]
            found["op_metadata"] += 1
            fields = {"name": meta.name, "display_name": meta.display_name}
            for stat in meta.stats:
                key = "stat:" + plane.stat_metadata[stat.metadata_id].name
                if stat.HasField("str_value"):
                    fields[key] = stat.str_value
                elif stat.HasField("ref_value"):
                    fields[key] = plane.stat_metadata[stat.ref_value].name
            for key, value in fields.items():
                if SCOPE.search(value):
                    found["held_by"][key] = found["held_by"].get(key, 0) + 1
                    if found["sample"] is None:
                        found["sample"] = {
                            "op": meta.name[:120], "held_by": key,
                            "value": value[:300],
                        }
    found["hlo_proto"] = _scopes_in_hlo_protos(space)
    return found


def scopes_in_profile_data(data) -> dict:
    """The same question put to ``jax.profiler.ProfileData``, which is all
    ``benchmark/trace.py`` reads with: the stats of the "XLA Ops" events
    that hold a scope, by stat name."""
    held: dict = {}
    events = 0
    for plane in data.planes:
        if not plane.name.startswith("/device:"):
            continue
        for line in plane.lines:
            if line.name != "XLA Ops":
                continue
            for ev in line.events:
                events += 1
                for key, value in ev.stats:
                    if isinstance(value, str) and SCOPE.search(value):
                        held[key] = held.get(key, 0) + 1
    return {"events": events, "held_by": held}


def _scopes_in_hlo_protos(space) -> dict:
    """The ``/host:metadata`` plane keeps each program's ``HloProto`` as a
    bytes stat: whether its instructions' ``metadata.op_name`` carry the
    scopes, with the instructions (by the name the "XLA Ops" line uses)
    that a scope names."""
    try:
        from tensorflow.compiler.xla.service import hlo_pb2
    except ImportError as e:
        return {"unread": str(e)}
    out = {"protos": 0, "stat": None, "by_scope": {}}
    for plane in space.planes:
        if "metadata" not in plane.name:
            continue
        for meta in plane.event_metadata.values():
            for stat in meta.stats:
                if not stat.HasField("bytes_value"):
                    continue
                proto = hlo_pb2.HloProto()
                try:
                    proto.ParseFromString(stat.bytes_value)
                except Exception:  # noqa: BLE001 — not an HloProto
                    continue
                out["protos"] += 1
                out["stat"] = (
                    f"{plane.name}: event metadata stat "
                    f"{plane.stat_metadata[stat.metadata_id].name!r}"
                )
                for comp in proto.hlo_module.computations:
                    for inst in comp.instructions:
                        found = SCOPE.search(inst.metadata.op_name)
                        if found:
                            row = out["by_scope"].setdefault(
                                found.group(0), {"instructions": 0, "some": []}
                            )
                            row["instructions"] += 1
                            if len(row["some"]) < 4 and "fusion" in inst.name:
                                row["some"].append(
                                    f"{proto.hlo_module.name}/{inst.name}"
                                )
    return out


def _by_thread(rows, thread_of, start_of):
    groups: dict = {}
    for row in sorted(rows, key=start_of):
        groups.setdefault(thread_of(row), []).append(row)
    return list(groups.values())


def skew(spans, annotations, offset: float) -> dict:
    """The gaps between a span's aligned edges and those of the ``ks:``
    annotation the same call left. A thread's spans and a line's
    annotations are paired where they name the same regions in the same
    order (a pool's workers all do: then the nearest in time), and the
    k-th span of the pair is held against the k-th annotation."""
    import statistics

    lines = _by_thread(annotations, lambda a: a[3], lambda a: a[1])
    starts, ends, main = [], [], []
    for group in _by_thread(spans, lambda sp: sp.tid, lambda sp: sp.start):
        names = ["ks:" + sp.name for sp in group]
        alike = [ln for ln in lines if [a[0] for a in ln] == names]
        if not alike:
            continue
        line = min(
            alike, key=lambda ln: abs(ln[0][1] - group[0].start - offset)
        )
        lines.remove(line)
        for sp, (_, a_start, a_end, _) in zip(group, line):
            starts.append(abs(a_start - sp.start - offset))
            ends.append(abs(a_end - sp.end - offset))
            if sp.thread_name == "MainThread":
                main.append(max(starts[-1], ends[-1]))
    if not starts:
        return {"matched": 0, "spans": len(spans)}
    return {
        "matched": len(starts), "spans": len(spans),
        "start_s_max": max(starts), "end_s_max": max(ends),
        "start_s_median": statistics.median(starts),
        "end_s_median": statistics.median(ends),
        "main_thread_s_max": max(main) if main else None,
    }


def _key(sp) -> str:
    """A span's name, with what tells its kind apart: the rule of a
    ``plan.rule``, the members of an ``exec.segment``."""
    for attr in ("rule", "label"):
        if attr in sp.attrs:
            return f"{sp.name}:{str(sp.attrs[attr])[:48]}"
    return sp.name


def first_job_table(spans, programs: dict, top: int = 12) -> dict:
    """A job's spans by name — seconds and what jax.monitoring reported
    inside (a nested span counts its children's too; ``own_compile_s`` is
    its counts less theirs) — and the programs that took most of it."""
    from keystone_tpu.obs.export import compile_seconds_by_span

    fields = ("seconds", "trace_s", "lower_s", "load_s", "compiles",
              "cache_hits")
    by_span: dict = {}
    for sp in spans:
        row = by_span.setdefault(
            _key(sp), dict.fromkeys(fields, 0) | {"calls": 0}
        )
        row["calls"] += 1
        for field in fields:
            row[field] += getattr(sp, field)
    for name, own in compile_seconds_by_span(spans, key=_key).items():
        by_span[name]["own_compile_s"] = own
    rows = sorted(
        programs.items(),
        key=lambda kv: -sum(seconds for _, seconds in kv[1].values()),
    )[:top]
    return {
        "by_span": dict(sorted(
            by_span.items(), key=lambda kv: -kv[1]["seconds"]
        )),
        "programs": {
            fun: {kind: {"requests": n, "seconds": s}
                  for kind, (n, s) in row.items()}
            for fun, row in rows
        },
        "programs_in_all": len(programs),
    }


def main(argv=None) -> int:
    ap = argparse.ArgumentParser("tools/span_skew.py")
    ap.add_argument("--workload", default="timit_cos4.fit")
    ap.add_argument("--seed", type=int, default=2147484001)
    ap.add_argument("--seconds", type=float, default=5.0)
    ap.add_argument("--cpu", action="store_true")
    ap.add_argument(
        "--first-job", action="store_true",
        help="trace set-up (the process's first job), not the window",
    )
    args = ap.parse_args(argv)
    started = time.perf_counter()

    from benchmark import harness, program
    from benchmark import trace as trace_mod

    root = ROOT
    if args.cpu:
        os.environ.setdefault("JAX_PLATFORMS", "cpu")
        from tests.benchmark import tiny

        root = tiny.build(tempfile.mkdtemp(prefix="span_skew_"))
    manifest = harness.Manifest(root, os.path.join(root, "benchmark"))
    cell = manifest.cell(args.workload)
    traffic = manifest.traffic(cell["traffic"])
    if not args.cpu:
        os.environ.setdefault("JAX_PLATFORMS", "tpu")
        for key, value in manifest.runtime_env(traffic).items():
            os.environ.setdefault(key, value)
    import jax

    import keystone_tpu  # noqa: F401  (places the compile cache)
    from keystone_tpu.obs import tracer

    if args.cpu:
        from tests.benchmark import tiny

        device, peak = dict(tiny.DEVICE), tiny.PEAK
    else:
        peaks = manifest.peaks()
        device = program.require_tpu(cell["chips"], peaks)
        peak = peaks[device["kind"]]
        jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
        jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)
    driver = manifest.driver(traffic["kind"])
    run = harness.Run(
        manifest=manifest, cell=cell, config=manifest.config(cell["config"]),
        traffic=traffic, seed=args.seed, seconds=args.seconds, trace=True,
        device=device, peak=peak, phases=harness.Phases(),
    )
    trace_dir = tempfile.mkdtemp(prefix="span_skew_trace_")
    programs_before = tracer.compile_record().programs()
    t0 = time.perf_counter()
    if args.first_job:
        # the session takes the first job from the boot recorder
        with trace_mod.traced(trace_dir):
            driver.setup(run)
        anchor = "ks:job"
    else:
        state = driver.setup(run)
        t0 = time.perf_counter()
        with trace_mod.traced(trace_dir):
            driver.window(run, state, args.seconds)
        anchor = "bench:fit.step"
    traced_s = time.perf_counter() - t0
    spans = [sp for sp in tracer.session_spans() if not sp.instant]
    data = _xplane(trace_dir)
    ks = host_annotations(data, "ks:")
    anchors = [
        (start, end) for name, start, end, _ in
        host_annotations(data, anchor) if name == anchor
    ]
    if args.first_job:
        run.facts.setdefault("units", max(len(anchors), 1))
        first, programs = spans, tracer.compile_record().programs(
            since=programs_before  # all of set-up: the data's programs too
        )
    else:
        first = tracer.first_job_spans()
        programs = tracer.first_job_programs()

    from benchmark.readers import span_idle

    out = {
        "device": device, "workload": cell["name"],
        "setup_s": round(t0 - started, 3), "traced_window_s": traced_s,
        "units": run.facts.get("units"), "fit_s": run.facts.get("fit_s"),
        "spans": len(spans), "ks_annotations": len(ks),
        "spans_per_unit": len(spans) / max(run.facts.get("units", 1), 1),
        "names": sorted({sp.name for sp in spans}),
    }
    job = next((sp for sp in first if sp.name == "job"), None)
    if job is not None:
        record = tracer.compile_record()
        out["first_job"] = {
            "traced": bool(args.first_job),
            "seconds": job.seconds, "trace_s": job.trace_s,
            "lower_s": job.lower_s, "load_s": job.load_s,
            "compiles": job.compiles, "cache_hits": job.cache_hits,
            "spans": len(first),
            # the process so far, set-up's data and the window included
            "process": {
                "requests": dict(record.requests),
                "seconds": dict(record.seconds),
                "cache_hits": record.cache_hits,
                "cache_read_s": record.cache_read_s,
            },
            **first_job_table(first, programs),
        }
    # what a WARM job still pays jax: the median window job beside the first
    jobs = [sp for sp in spans if sp.name == "job"]
    if jobs and not args.first_job:
        import statistics

        out["window_job"] = {
            field: statistics.median(getattr(sp, field) for sp in jobs)
            for field in ("seconds", "trace_s", "lower_s", "load_s")
        }
    # what utils/params.content_digest did inside each kind of span, a unit
    # (a nested span counts its children's too: plan.optimize holds the
    # plan.rule spans, job everything)
    units = max(run.facts.get("units", 1), 1)
    digests: dict = {}
    for sp in spans:
        if sp.digest_bytes or sp.digest_hits:
            row = digests.setdefault(sp.name, [0, 0, 0.0])
            row[0] += sp.digest_bytes
            row[1] += sp.digest_hits
            row[2] += sp.seconds
    out["digests_per_unit_by_span"] = {
        name: {"bytes": b / units, "hits": h / units, "seconds": s / units}
        for name, (b, h, s) in digests.items()
    }
    # what segment dispatch did with the first segments of the window: the
    # slices, and the counts that say a fused body engaged
    facts = ("label", "path", "rows", "row_slices", "slice_rows",
             "conv_fused_rows", "sift_sampled_rows", "sift_sampled_path",
             "cosine_bounded_rows", "cosine_scores_rows",
             "cache_declined_bytes")
    out["segments"] = [
        {k: sp.attrs[k] for k in facts if k in sp.attrs}
        for sp in spans if sp.name == "exec.segment"
    ][:16]
    # what the kernel solver did a unit: its fit and the kernel entries it
    # made, in the fit and in the applies
    krr: dict = {}
    for sp in spans:
        if sp.name == "krr.fit" and "fit" not in krr:
            krr["fit"] = {
                k: sp.attrs.get(k)
                for k in ("blocks", "epochs", "block_size", "cached_bytes")
            }
        elif sp.name in ("krr.kernel", "krr.apply"):
            row = krr.setdefault(
                sp.name, {"spans": 0, "entries": 0.0, "seconds": 0.0},
            )
            row["spans"] += 1 / units
            row["entries"] += sp.attrs.get("entries", 0) / units
            row["seconds"] += sp.seconds / units
    if krr:
        out["krr_per_unit"] = krr
    roots = [(sp.name, sp.start, sp.end) for sp in spans if sp.name == "job"]
    offset = span_idle.offset_of(roots, anchors) if anchors else None
    if offset is not None:
        ends = sorted(a[1] for a in anchors)
        job_ends = sorted(r[2] for r in roots)
        out["offset_s"] = offset
        out["offset_scatter_s"] = max(
            abs((a - j) - offset) for a, j in zip(ends, job_ends)
        )
        out["skew"] = skew(spans, ks, offset)
    if not args.cpu:
        reduction = trace_mod.reduce(trace_mod.load(trace_dir), cell["chips"])
        out["busy_s"], out["window_s"] = reduction.busy_s, reduction.window_s
        if offset is not None:
            split = span_idle.idle_by_span(
                [(sp.name + (":" + sp.attrs["rule"] if "rule" in sp.attrs
                             else ""), sp.start, sp.end) for sp in spans],
                anchors if args.first_job
                else reduction.annotations.get(anchor, []),
                reduction.busy, "job",
            )
            out["idle_s_by_span"] = dict(
                sorted(split.items(), key=lambda kv: -kv[1])
            )
            out["idle_s_under_anchor"] = (
                sum(split.values()) if args.first_job
                else reduction.idle_seconds.get(anchor)
            )
        out["op_names_with_scope"] = sum(
            1 for name in reduction.op_seconds if SCOPE.search(name)
        )
        out["scopes_in_profile_data"] = scopes_in_profile_data(data)
        out["scopes_on_device"] = scopes_on_device(glob.glob(os.path.join(
            trace_dir, "plugins", "profile", "*", "*.xplane.pb"
        ))[-1])
    shutil.rmtree(trace_dir, ignore_errors=True)
    os.makedirs(os.path.join(ROOT, "chiprun_out"), exist_ok=True)
    with open(os.path.join(ROOT, "chiprun_out", "span_skew.json"), "w") as f:
        json.dump(out, f, indent=1)
    print(json.dumps(out), flush=True)
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
