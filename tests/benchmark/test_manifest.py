"""``BENCHMARK.json`` against the contract's limits and against the files
its names lead to."""

import json
import os
import re

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")
SOURCES = {"device_trace", "program_span", "program_counter", "host_clock"}
#: a width may never be reduced
WIDTH = re.compile(r"(_dim|_rank)$|hidden|intermediate|latent|state|proj|head")


@pytest.fixture(scope="module")
def doc():
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        return json.load(f)


def _line(text, limit=200):
    return 1 <= len(text) <= limit and "\n" not in text and "\t" not in text


def test_top_level_keys_and_command(doc):
    assert set(doc) == {
        "command", "paths", "run_seconds", "configs", "workloads",
        "end_to_end", "per_layer",
    }
    assert 1 <= len(doc["paths"]) <= 16
    assert all(os.path.isdir(os.path.join(ROOT, p)) for p in doc["paths"])
    assert len(doc["command"]) <= 32 and all(_line(w) for w in doc["command"])
    script = doc["command"][1]
    assert any(script.startswith(p + "/") for p in doc["paths"])
    assert isinstance(doc["run_seconds"], int) and 1 <= doc["run_seconds"] <= 51
    assert os.path.getsize(os.path.join(ROOT, "BENCHMARK.json")) <= 64 * 1024


def test_configs_have_their_files(doc):
    assert 1 <= len(doc["configs"]) <= 24
    names = [c["name"] for c in doc["configs"]]
    files = [c["file"] for c in doc["configs"]]
    assert len(set(names)) == len(names) and len(set(files)) == len(files)
    used = {w["config"] for w in doc["workloads"]}
    for c in doc["configs"]:
        assert set(c) == {"name", "source", "file", "reduced", "why"}
        assert NAME.match(c["name"]) and c["name"] in used
        assert _line(c["source"]) and _line(c["why"])
        assert any(c["file"].startswith(p + "/") for p in doc["paths"])
        assert len(c["reduced"]) <= 16
        assert not any(WIDTH.search(k) for k in c["reduced"])
        with open(os.path.join(ROOT, c["file"])) as f:
            held = json.load(f)
        assert held["reduced"] == c["reduced"] and held["source"] == c["source"]
        stem = os.path.join(ROOT, c["file"])[: -len(".json")]
        assert os.path.exists(stem + "_reference.py")
        assert os.path.exists(stem + "_program.py")


def test_reference_imports_nothing_of_the_program(doc):
    for c in doc["configs"]:
        stem = os.path.join(ROOT, c["file"])[: -len(".json")]
        with open(stem + "_reference.py") as f:
            assert "keystone_tpu" not in f.read().replace(
                "pipelines/", ""
            ), c["name"]
    with open(os.path.join(ROOT, "benchmark", "refmath.py")) as f:
        assert "keystone_tpu" not in f.read()


def test_cells_have_their_files(doc):
    assert 1 <= len(doc["workloads"]) <= 24
    names = [w["name"] for w in doc["workloads"]]
    pairs = [(w["config"], w["traffic"]) for w in doc["workloads"]]
    assert len(set(names)) == len(names) and len(set(pairs)) == len(pairs)
    configs = {c["name"] for c in doc["configs"]}
    four = 0
    for w in doc["workloads"]:
        assert set(w) == {"name", "config", "traffic", "chips", "why"}
        assert NAME.match(w["name"]) and NAME.match(w["traffic"])
        assert w["config"] in configs and w["chips"] in (1, 4)
        assert _line(w["why"])
        four += w["chips"] == 4
        bench = os.path.join(ROOT, "benchmark")
        with open(os.path.join(bench, "traffic", w["traffic"] + ".json")) as f:
            kind = json.load(f)["kind"]
        assert os.path.exists(os.path.join(bench, "drivers", kind + ".py"))
        with open(os.path.join(bench, "limits", w["name"] + ".json")) as f:
            limits = json.load(f)
        assert limits["workload"] == w["name"] and limits["numbers"]
    assert four <= max(len(names) // 4, 1)


def test_metrics_are_legal_and_reported(doc):
    e2e = {m["name"]: m for m in doc["end_to_end"]}
    assert 1 <= len(e2e) <= 16 and "setup_s" in e2e
    assert 1 <= len(doc["per_layer"]) <= 128
    cells = [w["name"] for w in doc["workloads"]]
    names = [m["name"] for m in doc["end_to_end"] + doc["per_layer"]]
    assert len(set(names)) == len(names)

    def reports(metric, cell):
        return "workloads" not in metric or cell in metric["workloads"]

    for m in doc["end_to_end"]:
        assert set(m) - {"workloads"} == {
            "name", "unit", "better", "bound", "source"
        }
        assert m["source"] in ("host_clock", "device_trace")
        assert 0.01 <= m["bound"] <= 0.1
    for m in doc["per_layer"]:
        assert set(m) - {"workloads"} == {
            "name", "unit", "better", "source", "layer", "moves"
        }
        assert m["source"] in SOURCES and _line(m["layer"])
        assert m["moves"] in e2e
        # every cell of a per-layer metric reports the metric it moves
        for cell in m.get("workloads", cells):
            assert cell in cells and reports(e2e[m["moves"]], cell), m["name"]
        if m["name"].endswith("_roofline") or "mfu" in re.split(r"[._]", m["name"]):
            assert m["unit"] == "%"
        with open(os.path.join(
            ROOT, "benchmark", "metrics", m["name"] + ".json"
        )) as f:
            spec = json.load(f)
        for key in ("unit", "better", "source", "layer", "moves"):
            assert spec[key] == m[key], (m["name"], key)
        # the cells are the manifest's to list: a later PR adds a cell by
        # an entry there and may not edit the metric's file
        assert "workloads" not in spec
        assert os.path.exists(os.path.join(
            ROOT, "benchmark", "readers", spec["reader"] + ".py"
        ))
    for m in doc["end_to_end"] + doc["per_layer"]:
        assert NAME.match(m["name"]) and UNIT.match(m["unit"])
        assert m["better"] in ("lower", "higher")
    for cell in cells:
        mine = [m["name"] for m in doc["end_to_end"] if reports(m, cell)]
        assert "setup_s" in mine and len(mine) >= 2, cell
        assert any(reports(m, cell) for m in doc["per_layer"]), cell


def test_peaks_name_their_source():
    with open(os.path.join(ROOT, "benchmark", "peaks.json")) as f:
        peaks = json.load(f)
    assert "TPU v5e" in peaks["_source"]
    assert peaks["TPU v5 lite"]["flops_per_s"] == 197e12
    assert peaks["TPU v5 lite"]["hbm_bytes_per_s"] == 819e9


def test_runtime_env_is_data_and_a_traffic_file_overrides_it():
    from benchmark import harness

    manifest = harness.Manifest(ROOT)
    base = manifest.runtime_env({})
    assert base == {"TPU_PREMAPPED_BUFFER_SIZE": str(256 << 20)}
    over = manifest.runtime_env({"env": {"TPU_PREMAPPED_BUFFER_SIZE": 1 << 32}})
    assert over == {"TPU_PREMAPPED_BUFFER_SIZE": str(1 << 32)}
    for cell in manifest.doc["workloads"]:
        env = manifest.runtime_env(manifest.traffic(cell["traffic"]))
        assert all(isinstance(v, str) and v for v in env.values()), cell
