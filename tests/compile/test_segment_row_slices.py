"""Row-sliced segment dispatch: a segment whose members' outputs over the
whole batch outgrow what the device has free goes through in fixed-size row
slices of one program and gives the answers of the whole-batch dispatch; a
segment that fits is dispatched exactly as before. The device's memory is
what ``compile.segment._device_memory`` reads — the CPU reports none, so the
tests stand a reading in its place."""

import numpy as np
import pytest

from keystone_tpu.compile import segment as seg_mod
from keystone_tpu.compile.segment import reset_dispatchers
from keystone_tpu.data.dataset import Dataset
from keystone_tpu.obs import tracer as tracer_mod
from keystone_tpu.workflow.pipeline import FittedPipeline
from keystone_tpu.workflow.transformer import Transformer


@pytest.fixture(autouse=True)
def _isolate():
    reset_dispatchers()
    yield
    reset_dispatchers()


class _Widen(Transformer):
    """One row in, ``k`` times its width out: the member whose output is
    what outgrows the device."""

    def __init__(self, k):
        self.k = k

    def trace_batch(self, X):
        import jax.numpy as jnp

        return jnp.maximum(jnp.tile(X, (1, self.k)) * 1.5, 0.01 * jnp.tile(X, (1, self.k)))


class _Fold(Transformer):
    def __init__(self, k):
        self.k = k

    def trace_batch(self, X):
        return X.reshape(X.shape[0], self.k, -1).sum(axis=1)


class _Coupled(_Fold):
    batch_coupled = True

    def trace_batch(self, X):
        return super().trace_batch(X) - X.mean()


def _fitted(last=_Fold):
    pipe = _Widen(64).and_then(last(64))
    return FittedPipeline(pipe.graph, pipe.source, pipe.sink)


def _segments(tracer):
    return [sp for sp in tracer.spans() if sp.name == "exec.segment"]


def _apply_traced(fitted, X):
    tracer = tracer_mod.start()
    try:
        out = np.asarray(fitted.apply(Dataset.of(X)).to_array())
    finally:
        tracer_mod.stop()
    return out, _segments(tracer)


#: a row of 8 float32 makes 64·8·4 + 8·4 = 2,080 bytes across the members
ITEM_BYTES = 64 * 8 * 4 + 8 * 4


def test_item_bytes_prices_every_members_output(monkeypatch):
    X = np.ones((100, 8), np.float32)
    seen = []
    real = seg_mod._item_bytes
    monkeypatch.setattr(
        seg_mod, "_item_bytes", lambda *a: seen.append(real(*a)) or seen[-1]
    )
    _apply_traced(_fitted(), X)
    assert seen == []  # no memory reading (the CPU): nothing is traced twice
    monkeypatch.setattr(seg_mod, "_device_memory", lambda: (1 << 30, 1 << 34))
    _apply_traced(_fitted(), X)
    assert seen == [ITEM_BYTES]


@pytest.mark.parametrize("rows", [256, 250, 37])
def test_a_row_split_segment_equals_the_whole_batch(monkeypatch, rows):
    X = np.random.default_rng(rows).standard_normal((rows, 8)).astype(np.float32)
    whole, spans = _apply_traced(_fitted(), X)
    assert [sp.attrs["row_slices"] for sp in spans] == [1]
    assert spans[0].attrs["rows"] == spans[0].attrs["slice_rows"] == rows

    reset_dispatchers()
    # free: 20 rows' worth; the device: 64 rows' worth a quarter
    monkeypatch.setattr(
        seg_mod, "_device_memory",
        lambda: (20 * ITEM_BYTES, 4 * 64 * ITEM_BYTES),
    )
    split, spans = _apply_traced(_fitted(), X)
    # the quarter allows 64 rows a slice, half of what is free 10: 8
    assert spans[0].attrs["slice_rows"] == 8
    assert spans[0].attrs["row_slices"] == -(-rows // 8)
    # every row went through once: the padding of the last slice is cut
    assert spans[0].attrs["rows"] == rows and split.shape == whole.shape
    np.testing.assert_array_equal(split, whole)


def test_the_slices_share_one_program_and_pad_the_last(monkeypatch):
    X = np.arange(50 * 8, dtype=np.float32).reshape(50, 8)
    monkeypatch.setattr(
        seg_mod, "_device_memory",
        lambda: (40 * ITEM_BYTES, 4 * 16 * ITEM_BYTES),
    )
    shapes = []
    real = seg_mod.SegmentDispatcher.__call__

    def spy(self, *xs):
        shapes.append(tuple(x.shape for x in xs))
        return real(self, *xs)

    monkeypatch.setattr(seg_mod.SegmentDispatcher, "__call__", spy)
    out, spans = _apply_traced(_fitted(), X)
    # 50 rows in slices of 16: three whole and one of 2 padded to 16
    assert shapes == [((16, 8),)] * 4
    assert out.shape == (50, 8) and spans[0].attrs["row_slices"] == 4
    disp = list(seg_mod._DISPATCHERS.values())
    assert len(disp) == 1


def test_a_segment_that_fits_is_dispatched_as_before(monkeypatch):
    X = np.ones((64, 8), np.float32)
    _, spans = _apply_traced(_fitted(), X)
    digests = [d.digest for d in seg_mod._DISPATCHERS.values()]
    reset_dispatchers()
    # plenty free: the whole batch, one program, the same fingerprint
    monkeypatch.setattr(
        seg_mod, "_device_memory", lambda: (1 << 30, 1 << 34)
    )
    calls = []
    real = seg_mod.SegmentDispatcher.__call__

    def spy(self, *xs):
        calls.append(tuple(x.shape for x in xs))
        return real(self, *xs)

    monkeypatch.setattr(seg_mod.SegmentDispatcher, "__call__", spy)
    _, fits = _apply_traced(_fitted(), X)
    assert calls == [((64, 8),)]
    assert fits[0].attrs["row_slices"] == 1
    assert fits[0].attrs["digest"] == spans[0].attrs["digest"]
    assert [d.digest for d in seg_mod._DISPATCHERS.values()] == digests


def test_rows_that_couple_are_never_split(monkeypatch):
    X = np.ones((64, 8), np.float32)
    monkeypatch.setattr(
        seg_mod, "_device_memory", lambda: (ITEM_BYTES, 1 << 20)
    )
    _, spans = _apply_traced(_fitted(_Coupled), X)
    assert spans[0].attrs["row_slices"] == 1


def test_no_memory_reading_means_no_split():
    # the CPU backend reports no memory stats: today's dispatch
    assert seg_mod._device_memory() is None
