"""The trace reduction on a small hand-built event list."""

import re

import pytest

from benchmark import trace

# one chip, a 10 s window. A while loop 1..5 holds a matmul 1..3 and a
# cholesky 3..4; a copy runs 7..8. Busy = [1,5] + [7,8] = 5 s.
OPS = [
    ("while.1", 1.0, 4.0),
    ("convolution_fusion.2", 1.0, 2.0),
    ("cholesky.3", 3.0, 1.0),
    ("copy.4", 7.0, 1.0),
    ("before_the_window", -2.0, 1.0),
]
NOTES = [
    ("bench:window", 0.0, 10.0),
    ("bench:fit.step", 0.0, 6.0),
    ("bench:fit.step", 6.0, 4.0),
]


@pytest.fixture
def reduction():
    raw = trace.Raw({"/device:TPU:0": OPS}, NOTES, (0.0, 10.0))
    return trace.reduce(raw, chips=1)


def test_busy_and_window(reduction):
    assert reduction.busy_s == pytest.approx(5.0)
    assert reduction.window_s == pytest.approx(10.0)


def test_self_time_goes_to_the_nested_operation(reduction):
    ops = reduction.op_seconds
    assert ops["convolution_fusion.2"] == pytest.approx(2.0)
    assert ops["cholesky.3"] == pytest.approx(1.0)
    assert ops["while.1"] == pytest.approx(1.0)  # 4 s less its 3 s of body
    assert "before_the_window" not in ops
    assert reduction.seconds_matching(re.compile("convolution")) == pytest.approx(2.0)


def test_idle_is_attributed_to_the_annotation_over_it(reduction):
    # gaps: 0..1, 5..7, 8..10; fit.step covers the whole window
    assert reduction.idle_seconds["bench:fit.step"] == pytest.approx(5.0)
    assert reduction.idle_seconds[trace.NO_ANNOTATION] == pytest.approx(0.0)
    assert reduction.idle_share_under("bench:fit.step") == pytest.approx(0.5)
    assert reduction.idle_share_under("bench:absent") is None


def test_breakdown_lists_at_most_ten_and_no_zero(reduction):
    b = reduction.breakdown()
    assert b["device_ops"][0] == ["convolution_fusion.2", pytest.approx(2.0)]
    assert len(b["device_ops"]) <= 10 and len(b["idle_gaps"]) <= 10
    assert all(s > 0 for _, s in b["device_ops"] + b["idle_gaps"])


def test_two_chips_average_their_busy_time():
    raw = trace.Raw(
        {"/device:TPU:0": [("a", 0.0, 4.0)], "/device:TPU:1": [("a", 0.0, 2.0)]},
        [("bench:window", 0.0, 10.0)], (0.0, 10.0),
    )
    r = trace.reduce(raw, chips=2)
    assert r.busy_s == pytest.approx(3.0) and r.chips == 2
    assert r.op_seconds["a"] == pytest.approx(6.0)


def test_a_trace_with_no_device_operation_is_an_error():
    with pytest.raises(LookupError):
        trace.reduce(trace.Raw({}, [], (0.0, 1.0)))


def test_operations_are_named_by_module_and_hlo_name():
    hlo = ("%fusion.9 = f32[2048,2048]{1,0:T(8,128)S(1)} fusion(f32[60000,2048]"
           "{1,0:T(8,128)} %A.1), kind=kOutput, calls=%fused_computation.12")
    named = trace.name_ops(
        [(hlo, 1.0, 0.5), ("%copy.1 = f32[8]{0} copy(%x)", 5.0, 0.1)],
        [("jit__bcd_scan_impl(4565542957483804472)", 0.5, 2.0)],
    )
    assert named[0][0] == "jit__bcd_scan_impl/fusion.9|" + hlo
    assert trace.short(named[0][0]) == "jit__bcd_scan_impl/fusion.9"
    assert trace.short(named[1][0]) == "_/copy.1"  # under no module
    # a reader matches on the module, the name or the shapes
    assert re.search(r"^jit__bcd_scan_impl/[^|]*\|.*kind=kOutput", named[0][0])
    raw = trace.Raw({"/device:TPU:0": named}, [("bench:window", 0.0, 10.0)],
                    (0.0, 10.0))
    assert trace.reduce(raw).breakdown()["device_ops"][0] == [
        "jit__bcd_scan_impl/fusion.9", pytest.approx(0.5)
    ]
