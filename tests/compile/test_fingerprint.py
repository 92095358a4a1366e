"""Pipeline fingerprinting: the cache key must be stable across processes
and across harmless runtime state, and must move when anything that
changes the compiled program moves."""

import os
import subprocess
import sys

import numpy as np
import pytest

from keystone_tpu import FunctionNode, Transformer
from keystone_tpu.compile import (
    FingerprintError,
    entry_key,
    pipeline_fingerprint,
)
from keystone_tpu.utils.params import as_param

_REPO_ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def _double(X):
    return X * 2.0


class _Scale(Transformer):
    """Deterministic fitted-parameter stand-in (numpy state)."""

    def __init__(self, w):
        self.w = as_param(w)

    def trace_batch(self, X):
        return X * self.w


def build_toy(scale: float = 3.0):
    """Deterministic transformer-only chain, buildable identically in any
    process (module-level functions, content-known parameters)."""
    w = np.arange(8, dtype=np.float32) * scale + 1.0
    return (
        FunctionNode(batch_fn=_double, label="double") >> _Scale(w)
    ).fit()


def toy_digest(scale: float = 3.0) -> str:
    return pipeline_fingerprint(build_toy(scale))


def test_rebuild_gives_identical_digest():
    assert toy_digest() == toy_digest()


def test_digest_moves_with_parameters():
    assert toy_digest(3.0) != toy_digest(4.0)


def test_digest_stable_after_use():
    """Executing the pipeline populates memo state (the fused operator's
    ``_jit``); a warm pipeline must fingerprint like a fresh one."""
    fitted = build_toy()
    before = pipeline_fingerprint(fitted)
    fitted.apply(np.ones((4, 8), np.float32))
    fitted.compile(cache=None)(np.ones((4, 8), np.float32))
    assert pipeline_fingerprint(fitted) == before


def test_digest_survives_pickle_round_trip():
    from keystone_tpu.utils import serialization

    fitted = build_toy()
    clone = serialization.loads(serialization.dumps(fitted))
    assert pipeline_fingerprint(clone) == pipeline_fingerprint(fitted)


def test_digest_stable_across_processes():
    """The property the whole cache stands on: a DIFFERENT process
    building the same fitted pipeline derives the same key."""
    out = subprocess.run(
        [
            sys.executable, "-c",
            "from tests.compile.test_fingerprint import toy_digest;"
            "print(toy_digest())",
        ],
        cwd=_REPO_ROOT,
        env=dict(os.environ, JAX_PLATFORMS="cpu"),
        capture_output=True, text=True, timeout=300,
    )
    assert out.returncode == 0, out.stderr[-2000:]
    assert out.stdout.strip().splitlines()[-1] == toy_digest()


def _inner3(X):
    return (lambda: 3.0)() * X


def _inner4(X):
    return (lambda: 4.0)() * X


def _kw2(X, *, s=2.0):
    return X * s


def _kw3(X, *, s=3.0):
    return X * s


def test_digest_sees_nested_code_and_kwdefaults():
    """Functions differing only in an inner lambda's body, or only in a
    keyword-only default, must not collide (a collision would serve one
    model's executable for the other)."""

    def fp(fn):
        return pipeline_fingerprint(
            FunctionNode(batch_fn=fn, label="f").to_pipeline().fit()
        )

    assert fp(_inner3) != fp(_inner4)
    assert fp(_kw2) != fp(_kw3)


def _with_global(scale: float):
    """Same code, different module-global value — only the global differs."""
    ns = {"SCALE": scale}
    exec("def f(X):\n    return X * SCALE", ns)
    return ns["f"]


def test_digest_sees_referenced_module_globals():
    """`def f(X): return X * SCALE` must re-key when SCALE changes, or an
    edited model would load the stale executable."""

    def fp(fn):
        return pipeline_fingerprint(
            FunctionNode(batch_fn=fn, label="f").to_pipeline().fit()
        )

    assert fp(_with_global(2.0)) != fp(_with_global(3.0))
    assert fp(_with_global(2.0)) == fp(_with_global(2.0))


def test_object_dtype_arrays_digest_by_content_not_pointers():
    """tobytes() on an object array would serialize PyObject pointers —
    process-unstable; elements must digest by content instead."""

    def fp(meta):
        fitted = build_toy()
        next(iter(fitted.graph.operators.values())).meta = np.array(
            meta, dtype=object
        )
        return pipeline_fingerprint(fitted)

    assert fp(["a", 1.5]) == fp(["a", 1.5])
    assert fp(["a", 1.5]) != fp(["b", 1.5])


def test_uncanonicalizable_state_raises():
    class Opaque(Transformer):
        def __init__(self):
            self.handle = object()  # no content-stable form

        def trace_batch(self, X):
            return X

    fitted = (FunctionNode(batch_fn=_double, label="double") >> Opaque()).fit()
    with pytest.raises(FingerprintError, match="handle"):
        pipeline_fingerprint(fitted)


def test_entry_key_separates_signature_and_environment():
    env = {"jax": "1", "backend": "cpu"}
    base = entry_key("a" * 64, (8, 4), "float32", env)
    assert entry_key("a" * 64, (16, 4), "float32", env) != base
    assert entry_key("a" * 64, (8, 4), "float64", env) != base
    assert entry_key("a" * 64, (8, 4), "float32", {**env, "jax": "2"}) != base
    assert entry_key("b" * 64, (8, 4), "float32", env) != base
    assert entry_key("a" * 64, (8, 4), "float32", dict(env)) == base


# ---------------------------------------------------------------------------
# the one content digest of an array (utils/params.content_digest)
# ---------------------------------------------------------------------------


def _copying_digest(a) -> bytes:
    """What ``_feed`` gave for an array before the digest was shared."""
    import hashlib

    return hashlib.sha256(np.ascontiguousarray(a).tobytes()).digest()


def _read_back(a):
    import jax

    return jax.device_get(jax.numpy.asarray(a))


def _frozen_copy(a):
    a = np.array(a)
    a.flags.writeable = False
    return a


_ARRAY_KINDS = {
    "c_order": lambda: np.arange(24, dtype=np.float32).reshape(4, 6),
    "fortran_order": lambda: np.asfortranarray(
        np.arange(24, dtype=np.float32).reshape(4, 6)
    ),
    "sliced": lambda: np.arange(48, dtype=np.float64).reshape(6, 8)[1::2, ::3],
    "zero_d": lambda: np.array(2.5, dtype=np.float32),
    "empty": lambda: np.empty((0, 3), dtype=np.int32),
    "read_only": lambda: _frozen_copy(np.arange(7, dtype=np.int64)),
    "read_only_fortran": lambda: _frozen_copy(
        np.asfortranarray(np.arange(6, dtype=np.float32).reshape(2, 3))
    ),
    "bool": lambda: np.array([True, False, True]),
    "float16": lambda: np.linspace(0, 1, 5).astype(np.float16),
    "device_get": lambda: _read_back(np.arange(6.0).reshape(2, 3)),
}


@pytest.mark.parametrize("kind", sorted(_ARRAY_KINDS))
def test_content_digest_is_sha256_of_the_c_order_bytes(kind):
    from keystone_tpu.utils.params import content_digest

    a = _ARRAY_KINDS[kind]()
    want = _copying_digest(a)
    assert content_digest(a) == want
    assert content_digest(a) == want  # remembered or hashed again: the same


def test_content_digest_refuses_object_arrays():
    from keystone_tpu.utils.params import content_digest

    with pytest.raises(TypeError):
        content_digest(np.array(["a", 1.5], dtype=object))


def _counted(fn):
    """``fn()`` under a span: (result, bytes hashed, digests from memory)."""
    from keystone_tpu.obs.tracer import Tracer

    with Tracer(sync=False).span("t") as sp:
        out = fn()
    return out, sp.digest_bytes, sp.digest_hits


def test_writeable_array_is_hashed_at_every_call_and_sees_mutation():
    from keystone_tpu.utils.params import content_digest

    a = np.arange(1024, dtype=np.float32)
    d1, hashed1, hits1 = _counted(lambda: content_digest(a))
    a[3] = -1.0  # in place, the same object
    d2, hashed2, hits2 = _counted(lambda: content_digest(a))
    assert d1 != d2 and d2 == _copying_digest(a)
    assert (hashed1, hits1, hashed2, hits2) == (a.nbytes, 0, a.nbytes, 0)


def test_frozen_array_is_hashed_once_and_cannot_be_mutated():
    from keystone_tpu.utils.params import content_digest

    a = as_param(np.arange(1024, dtype=np.float32))
    with pytest.raises(ValueError, match="read-only"):
        a[3] = -1.0
    d1, hashed1, hits1 = _counted(lambda: content_digest(a))
    d2, hashed2, hits2 = _counted(lambda: content_digest(a))
    assert d1 == d2 == _copying_digest(a)
    assert (hashed1, hits1, hashed2, hits2) == (a.nbytes, 0, 0, 1)
    # an equal array that is another object is fresh content until hashed
    b = as_param(np.arange(1024, dtype=np.float32))
    assert _counted(lambda: content_digest(b)) == (d1, b.nbytes, 0)


def test_read_only_view_of_a_writeable_array_is_not_remembered():
    from keystone_tpu.utils.params import content_digest

    owner = np.arange(64, dtype=np.float32)
    view = owner.view()
    view.flags.writeable = False
    d1 = content_digest(view)
    owner[0] = 9.0  # the view's bytes change under it
    d2, hashed, hits = _counted(lambda: content_digest(view))
    assert d1 != d2 and d2 == _copying_digest(owner)
    assert (hashed, hits) == (view.nbytes, 0)


def test_array_made_writeable_again_is_hashed_again():
    from keystone_tpu.utils.params import content_digest

    a = as_param([1.0, 2.0, 3.0])
    d1 = content_digest(a)
    a.flags.writeable = True  # numpy allows it: the array owns its bytes
    a[0] = 5.0
    d2, hashed, hits = _counted(lambda: content_digest(a))
    assert d1 != d2 and d2 == _copying_digest(a) and (hashed, hits) == (a.nbytes, 0)


def test_digest_memo_holds_no_strong_reference():
    import gc
    import weakref

    from keystone_tpu.utils import params

    a = as_param(np.arange(4096, dtype=np.float32))
    params.content_digest(a)
    key, alive = id(a), weakref.ref(a)
    assert key in params._DIGESTS
    del a
    gc.collect()
    assert alive() is None and key not in params._DIGESTS


def test_as_param_hands_out_read_only_arrays_and_leaves_the_callers_alone():
    import jax.numpy as jnp

    mine = np.arange(6, dtype=np.float32)
    p = as_param(mine)
    assert not p.flags.writeable and p.base is None and p is not mine
    mine[0] = 7.0  # the caller's array stays the caller's
    assert mine.flags.writeable and p[0] == 0.0
    assert not as_param([1, 2, 3]).flags.writeable
    assert not as_param(mine, dtype="float64").flags.writeable
    assert not as_param(mine[::2]).flags.writeable
    assert not as_param(jnp.ones(3)).flags.writeable
    frozen = as_param(mine)
    assert as_param(frozen) is frozen  # nothing to copy
    assert as_param(None) is None


def _cosine_segment_graph():
    """A fixed two-member segment over the arrays ``_feed`` meets: frozen
    parameters (a matrix, two vectors) and one that is still writeable
    and in Fortran order."""
    from keystone_tpu.check import lattice
    from keystone_tpu.check.segments import plan_segments
    from keystone_tpu.nodes.stats import CosineRandomFeatures

    rng = np.random.default_rng(7)
    W = rng.standard_normal((16, 8)).astype(np.float32)
    b = rng.uniform(0, 6.28, 16).astype(np.float32)
    scale = _Scale(rng.standard_normal(16).astype(np.float32))
    scale.meta = np.asfortranarray(rng.standard_normal((3, 5)))
    pipe = CosineRandomFeatures(W, b).and_then(scale)
    graph = pipe.graph
    verdicts = {n: lattice.classify(graph.get_operator(n)) for n in graph.nodes}
    (seg,) = plan_segments(graph, verdicts, {})[0]
    assert len(seg.nodes) == 2
    return pipe, graph, seg


@pytest.mark.parametrize("which", ["segment", "pipeline"])
def test_fingerprints_are_byte_equal_to_the_copying_formula(which, monkeypatch):
    """Cache entries, dispatcher keys and cost records written before the
    digest was shared stay valid: the shared digest feeds the same 32
    bytes the copy-then-sha256 did, the first time and from memory."""
    from keystone_tpu.compile import fingerprint as fp
    from keystone_tpu.workflow.pipeline import FittedPipeline

    pipe, graph, seg = _cosine_segment_graph()
    if which == "segment":
        digest = lambda: fp.segment_fingerprint(graph, seg)  # noqa: E731
    else:
        fitted = FittedPipeline(pipe.graph, pipe.source, pipe.sink)
        digest = lambda: fp.pipeline_fingerprint(fitted)  # noqa: E731
    first, hashed, _ = _counted(digest)
    again, hashed_again, hits = _counted(digest)
    monkeypatch.setattr(fp, "content_digest", _copying_digest)
    assert first == again == digest()
    frozen_bytes, writeable_bytes = (16 * 8 + 16 + 16) * 4, 3 * 5 * 8
    assert hashed == frozen_bytes + writeable_bytes
    assert (hashed_again, hits) == (writeable_bytes, 3)
