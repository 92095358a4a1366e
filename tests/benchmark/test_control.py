"""``correct`` has been shown to fail: the control — the plain reference
one step of precision below what the configuration states, put in the
program's place — comes out as not correct in every tiny cell, and so
does a run whose timed path is broken underneath."""

import json
import os

import numpy as np
import pytest

from benchmark import control, harness
from tests.benchmark import tiny

CELLS = ["tiny_fft.fit", "tiny_cos.fit", "tiny_cos.apply", "tiny_fft.serve"]


def _manifest(root):
    return harness.Manifest(root, os.path.join(root, "benchmark"))


@pytest.mark.parametrize("workload", CELLS)
def test_the_control_is_not_correct(tiny_root, workload):
    out = control.read(
        _manifest(tiny_root), workload, 5, seconds=0.5,
        device=dict(tiny.DEVICE), peak=tiny.PEAK,
    )
    assert out["correct"] is False
    failed = [n for n, r in out["compared"].items() if r["value"] > r["limit"]]
    assert failed, out["compared"]


@pytest.mark.parametrize("workload", ["tiny_fft.fit", "tiny_cos.fit"])
def test_half_of_the_batch_left_out_is_not_correct(tiny_root, workload):
    out = control.read(
        _manifest(tiny_root), workload, 5, seconds=0.5,
        device=dict(tiny.DEVICE), peak=tiny.PEAK, fault="half_rows",
    )
    assert out["compared"]["scores_gap"]["value"] > (
        10 * out["compared"]["scores_gap"]["limit"]
    )


def _broken(monkeypatch, manifest, config, how):
    """Break the timed path underneath: patch the program adapter that the
    drivers call."""
    adapter = manifest.adapter(config, "program")
    real_fit, real_model = adapter.fit, adapter.model

    def half_fit(cfg, X, y, Xt, yt):
        half = X.shape[0] // 2
        return real_fit(cfg, X[:half], y[:half], Xt, yt)

    def altered_model(handle):
        model = real_model(handle)
        model["W"] = np.roll(model["W"], 1, axis=1)  # answers altered
        return model

    if how == "half_rows":
        monkeypatch.setattr(adapter, "fit", half_fit)
    else:
        monkeypatch.setattr(adapter, "model", altered_model)


@pytest.mark.parametrize("how", ["half_rows", "altered"])
def test_a_broken_fit_reads_not_correct(tiny_root, monkeypatch, capsys, how):
    _broken(monkeypatch, _manifest(tiny_root), "tiny_cos", how)
    rc, lines = tiny.run_cell(tiny_root, "tiny_cos.fit", seconds=0.05,
                              capsys=capsys)
    assert rc == 0 and json.loads(lines[-1])["correct"] is False


def test_altered_labels_read_not_correct(tiny_root, monkeypatch, capsys):
    manifest = _manifest(tiny_root)
    driver = manifest.driver("apply_loop")
    real = driver._score

    def altered(fitted, chunk):
        return (real(fitted, chunk) + 1) % 5  # every label altered

    monkeypatch.setattr(driver, "_score", altered)
    rc, lines = tiny.run_cell(tiny_root, "tiny_cos.apply", seconds=0.05,
                              capsys=capsys)
    assert rc == 0 and json.loads(lines[-1])["correct"] is False


def test_altered_replies_read_not_correct(tiny_root, monkeypatch, capsys):
    manifest = _manifest(tiny_root)
    driver = manifest.driver("serve_open_loop")
    real = driver.offer

    def altered(*a, **kw):
        latency, late, replies, failed, elapsed = real(*a, **kw)
        return latency, late, (replies + 1) % 10, failed, elapsed

    monkeypatch.setattr(driver, "offer", altered)
    rc, lines = tiny.run_cell(tiny_root, "tiny_fft.serve", seconds=0.05,
                              capsys=capsys)
    assert rc == 0 and json.loads(lines[-1])["correct"] is False


def test_the_command_refuses_anything_but_a_tpu(capsys):
    """On this CPU the command exits non-zero and prints no result."""
    with pytest.raises(SystemExit) as e:
        from benchmark import program

        program.require_tpu(1, {"TPU v5 lite": {}})
    assert e.value.code == 2
    assert capsys.readouterr().out == ""


def test_an_unknown_device_kind_is_an_error(monkeypatch, capsys):
    import jax

    from benchmark import program

    class Chip:
        platform, device_kind = "tpu", "TPU v9 imaginary"

    monkeypatch.setattr(jax, "devices", lambda *a: [Chip()])
    with pytest.raises(SystemExit) as e:
        program.require_tpu(1, {"TPU v5 lite": {}})
    assert e.value.code == 2 and "no published peaks" in capsys.readouterr().err
