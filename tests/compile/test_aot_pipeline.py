"""AOT compile wiring: FittedPipeline.compile and the ServingEngine load
executables instead of tracing, fall back to live compiles on any cache
problem with bit-identical outputs, and invalidate on environment skew."""

import numpy as np
import pytest

import keystone_tpu.compile as cmod
from keystone_tpu import FunctionNode
from keystone_tpu.compile import AotDispatcher, ExecutableCache
from keystone_tpu.serving import ServingEngine
from keystone_tpu.utils import serialization

from .test_fingerprint import build_toy

DATUM = (8,)


@pytest.fixture(autouse=True)
def _isolate_global_cache():
    """These tests install a process-global cache; the rest of the suite
    must not inherit it (nor a dangling tmp dir)."""
    yield
    cmod.reset()


def _x(n=4):
    return np.linspace(0.0, 1.0, n * DATUM[0], dtype=np.float32).reshape(n, *DATUM)


# ---------------------------------------------------------------------------
# FittedPipeline.compile
# ---------------------------------------------------------------------------


def test_compile_exports_then_loads_with_zero_traces(tmp_path):
    cache = ExecutableCache(str(tmp_path))
    fitted = build_toy()
    cold = fitted.compile(cache=cache)
    y_cold = np.asarray(cold(_x()))
    assert fitted.compile_count == 1  # the export's trace, counted
    assert len(cache.entries()) == 1

    clone = serialization.loads(serialization.dumps(fitted))
    warm = clone.compile(cache=cache)
    y_warm = np.asarray(warm(_x()))
    assert clone.compile_count == 0, "warm boot must pay zero traces"

    legacy = np.asarray(build_toy().compile(cache=None)(_x()))
    assert np.array_equal(y_cold, y_warm)
    assert np.array_equal(y_cold, legacy)


def test_corrupted_entry_falls_back_to_live_compile(tmp_path):
    import os

    cache = ExecutableCache(str(tmp_path))
    fitted = build_toy()
    y_ref = np.asarray(fitted.compile(cache=cache)(_x()))
    (key, size, _mtime), = cache.entries()
    with open(cache.entry_path(key), "r+b") as f:
        f.seek(size // 2)
        f.write(b"ROT!")

    clone = serialization.loads(serialization.dumps(fitted))
    y = np.asarray(clone.compile(cache=cache)(_x()))
    assert clone.compile_count == 1  # live compile paid, not a crash
    assert np.array_equal(y, y_ref), "fallback must not change results"
    assert len(cache.entries()) == 1  # re-exported over the corrupt entry


def test_environment_skew_is_a_miss_then_a_fresh_export(tmp_path):
    """A cache written by a different toolchain (simulated by skewing the
    dispatcher's environment key) never loads — the pipeline re-traces
    and re-exports under its own key."""
    cache = ExecutableCache(str(tmp_path))
    fitted = build_toy()
    fitted.compile(cache=cache)(_x())
    assert len(cache.entries()) == 1

    fn = fitted.trace_fn()
    traces = []
    disp = AotDispatcher(
        fn, fitted.fingerprint(), cache, on_trace=traces.append
    )
    disp._env = dict(disp._env, jax="0.0.0-skewed")
    y = np.asarray(disp(_x()))
    assert traces, "skewed environment must not load the old entry"
    assert np.array_equal(y, np.asarray(fitted.compile(cache=None)(_x())))
    assert len(cache.entries()) == 2  # old entry intact + new env's entry


def test_unfingerprintable_pipeline_compiles_without_cache(tmp_path):
    fitted = (
        FunctionNode(batch_fn=lambda X: X * 2.0, label="dbl").to_pipeline()
    ).fit()
    # a lambda fingerprints by code digest; sabotage with a live object
    next(iter(fitted.graph.operators.values())).opaque = object()
    cache = ExecutableCache(str(tmp_path))
    compiled = fitted.compile(cache=cache)
    y = np.asarray(compiled(_x()))
    assert fitted.compile_count == 1
    assert cache.entries() == []  # silently fell back to the legacy jit
    assert np.allclose(y, _x() * 2.0)


# ---------------------------------------------------------------------------
# ServingEngine warm boots
# ---------------------------------------------------------------------------


def _serve(engine, rows):
    with engine:
        return [engine.predict(r, timeout=60.0) for r in rows]


def test_engine_cold_then_warm_boot_zero_traces(tmp_path):
    cmod.configure(str(tmp_path))
    fitted = build_toy()
    rows = _x(6)

    cold = ServingEngine(fitted, buckets=(4, 8), datum_shape=DATUM)
    preds_cold = _serve(cold, rows)
    c = cold.metrics.snapshot()["counters"]
    assert c.get("compiles") == 2 and c.get("aot_loads", 0) == 0

    warm = ServingEngine(fitted, buckets=(4, 8), datum_shape=DATUM)
    preds_warm = _serve(warm, rows)
    c = warm.metrics.snapshot()["counters"]
    assert c.get("compiles", 0) == 0, "warm boot must pay zero traces"
    assert c.get("aot_loads") == 2
    assert np.array_equal(np.asarray(preds_cold), np.asarray(preds_warm))


def test_configure_never_moves_the_xla_cache(tmp_path):
    """The XLA compilation cache is placed once, at import — by
    JAX_COMPILATION_CACHE_DIR, else at the fixed in-checkout path — and
    compile.configure(dir) leaves it there (the path is part of the key:
    a cache that moves never hits). configure only zeroes the minimum
    compile time for persisting; reset() puts the package default back."""
    import os

    import jax

    import keystone_tpu as pkg

    placed = os.environ.get("JAX_COMPILATION_CACHE_DIR") or pkg.COMPILE_CACHE_DIR
    assert jax.config.jax_compilation_cache_dir == placed
    cmod.configure(str(tmp_path))
    try:
        assert jax.config.jax_compilation_cache_dir == placed
        assert jax.config.jax_persistent_cache_min_compile_time_secs == 0.0
        assert not (tmp_path / "xla").exists()
    finally:
        cmod.reset()
    assert jax.config.jax_compilation_cache_dir == placed
    assert (
        jax.config.jax_persistent_cache_min_compile_time_secs
        == pkg.PERSIST_MIN_COMPILE_SECS
    )


def test_cache_placement_from_the_environment(tmp_path):
    """Set → that directory, before and after configure(); unset → the
    fixed in-checkout path, also after configure(). Checked in fresh
    interpreters: placement happens at package import."""
    import os
    import subprocess
    import sys

    probe = (
        "import jax, keystone_tpu\n"
        "from keystone_tpu import compile as c\n"
        "print(jax.config.jax_compilation_cache_dir)\n"
        f"c.configure({str(tmp_path / 'aot')!r})\n"
        "print(jax.config.jax_compilation_cache_dir)\n"
        "print(keystone_tpu.COMPILE_CACHE_DIR)\n"
    )

    def run(env_dir):
        env = {k: v for k, v in os.environ.items()
               if k != "JAX_COMPILATION_CACHE_DIR"}
        if env_dir:
            env["JAX_COMPILATION_CACHE_DIR"] = env_dir
        root = os.path.dirname(os.path.dirname(os.path.dirname(
            os.path.abspath(__file__)
        )))
        out = subprocess.run(
            [sys.executable, "-c", probe], env=env, cwd=root,
            capture_output=True, text=True, timeout=120, check=True,
        ).stdout.split()
        return out, root

    (before, after, _), _ = run(str(tmp_path / "placed"))
    assert before == after == str(tmp_path / "placed")
    (before, after, fixed), root = run(None)
    assert before == after == fixed == os.path.join(root, ".jax_cache")


def test_engine_without_cache_behaves_exactly_as_before(tmp_path):
    cmod.configure(None)  # explicit: AOT off
    fitted = build_toy()
    engine = ServingEngine(fitted, buckets=(4,), datum_shape=DATUM)
    _serve(engine, _x(3))
    c = engine.metrics.snapshot()["counters"]
    assert c.get("compiles") == 1 and c.get("aot_loads", 0) == 0
