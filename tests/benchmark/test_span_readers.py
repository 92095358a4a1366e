"""The readers of the program's spans on hand-built lists: the join of the
spans' clock to the trace's, the split of the chip's idle time by innermost
span, and the totals over spans of one name."""

import pytest

from benchmark.readers import span_idle, span_total

# the trace's clock: two steps of 10 s; the chip is busy 2..6 and 12..17
ANCHORS = [(0.0, 10.0), (10.0, 20.0)]
BUSY = [(2.0, 6.0), (12.0, 17.0)]
#: the spans' clock runs 100 s ahead of the trace's
AHEAD = 100.0


def _spans(rows, shift=AHEAD):
    return [(name, start + shift, end + shift) for name, start, end in rows]


# each job opens 1 s into its step and ends with it; planning 1..2 (idle),
# a pull 2..9 holding the solve 2..7 (busy 2..6, idle 6..7) and 2 s of its
# own idle time 7..9; evaluation 9..10 (idle). Step two is alike, 10 s on,
# but its chip stays busy a second longer.
STEP = [
    ("job", 1.0, 10.0),
    ("plan.build", 1.0, 2.0),
    ("pipeline.pull", 2.0, 9.0),
    ("block_ls.solve", 2.0, 7.0),
    ("eval.metrics", 9.0, 10.0),
]
TWO_STEPS = STEP + [(n, s + 10.0, e + 10.0) for n, s, e in STEP]


def test_a_known_offset_is_recovered():
    roots = [s for s in _spans(TWO_STEPS) if s[0] == "job"]
    assert span_idle.offset_of(roots, ANCHORS) == pytest.approx(-AHEAD)


def test_steps_whose_offsets_disagree_cannot_be_joined():
    rows = list(TWO_STEPS)
    rows[5] = ("job", 11.0, 20.002)  # step two ends 2 ms off step one
    roots = [s for s in _spans(rows) if s[0] == "job"]
    assert span_idle.offset_of(roots, ANCHORS) is None
    assert span_idle.idle_by_span(_spans(rows), ANCHORS, BUSY, "job") is None
    rows[5] = ("job", 11.0, 20.0005)  # half a millisecond is inside
    roots = [s for s in _spans(rows) if s[0] == "job"]
    assert span_idle.offset_of(roots, ANCHORS) == pytest.approx(
        -AHEAD - 0.00025
    )


def test_a_root_that_opens_before_its_anchor_cannot_be_joined():
    rows = list(TWO_STEPS)
    rows[5] = ("job", 9.5, 20.0)
    roots = [s for s in _spans(rows) if s[0] == "job"]
    assert span_idle.offset_of(roots, ANCHORS) is None


def test_roots_and_anchors_pair_one_to_one():
    roots = [s for s in _spans(STEP) if s[0] == "job"]
    assert span_idle.offset_of(roots, ANCHORS) is None
    assert span_idle.offset_of([], ANCHORS) is None


def test_the_innermost_span_takes_the_idle_instant():
    split = span_idle.idle_by_span(_spans(TWO_STEPS), ANCHORS, BUSY, "job")
    # step one: 0..1 none, 1..2 plan, 6..7 solve, 7..9 pull, 9..10 eval;
    # step two: 10..11 none, 11..12 plan, 17..19 pull, 19..20 eval
    assert split == {
        "plan.build": pytest.approx(2.0),
        "block_ls.solve": pytest.approx(1.0),
        "pipeline.pull": pytest.approx(4.0),
        "eval.metrics": pytest.approx(2.0),
        span_idle.NO_SPAN: pytest.approx(2.0),
    }
    assert "job" not in split  # its children cover all of its idle time


def test_the_groups_and_none_sum_to_the_idle_under_the_anchor():
    split = span_idle.idle_by_span(_spans(TWO_STEPS), ANCHORS, BUSY, "job")
    idle_under_anchor = 20.0 - (4.0 + 5.0)
    assert sum(split.values()) == pytest.approx(idle_under_anchor)
    # busy time outside every anchor, and spans outside them, add nothing
    wide = BUSY + [(25.0, 30.0)]
    extra = _spans(TWO_STEPS + [("plan.build", 22.0, 23.0)])
    assert span_idle.idle_by_span(extra, ANCHORS, wide, "job") == split


def test_two_worker_threads_the_one_that_opened_last_takes_it():
    # main: job 0..10 and a pull 1..10; worker A runs a segment 2..8,
    # worker B the solve 4..9. Idle 5..10.
    rows = [
        ("job", 0.0, 10.0), ("pipeline.pull", 1.0, 10.0),
        ("exec.segment", 2.0, 8.0), ("block_ls.solve", 4.0, 9.0),
    ]
    split = span_idle.idle_by_span(
        _spans(rows), [(0.0, 10.0)], [(0.0, 5.0)], "job"
    )
    assert split == {
        "block_ls.solve": pytest.approx(4.0),
        "pipeline.pull": pytest.approx(1.0),
        span_idle.NO_SPAN: pytest.approx(0.0),
    }
    pieces = span_idle.innermost(rows)
    assert pieces == [
        ("job", 0.0, 1.0), ("pipeline.pull", 1.0, 2.0),
        ("exec.segment", 2.0, 4.0), ("block_ls.solve", 4.0, 9.0),
        ("pipeline.pull", 9.0, 10.0),
    ]


class _Reduction:
    def __init__(self, annotations, busy):
        self.annotations, self.busy = annotations, busy


class _Run:
    def __init__(self, reduction):
        self.reduction = reduction


class _Sp:
    instant = False

    def __init__(self, name, start, end, compiles=0):
        self.name, self.start, self.end = name, start, end
        self.compiles = compiles

    @property
    def seconds(self):
        return self.end - self.start


@pytest.fixture
def session(monkeypatch):
    """Stands where the program's session recorder does."""
    from keystone_tpu.obs import tracer

    def put(spans):
        monkeypatch.setattr(tracer, "session_spans", lambda: spans)

    return put


FIT = {"anchor": "bench:fit.step", "root": "job"}


@pytest.mark.parametrize("prefixes,share,want", [
    (["plan."], False, 1000.0),
    (["pipeline.", "exec.", "node."], False, 2000.0),
    (["block_ls.", "bcd."], False, 500.0),
    (["eval."], False, 1000.0),
    (["xfer."], False, 0.0),
    (["_none_", "job"], True, 100.0 * 2.0 / 11.0),
])
def test_span_idle_reads_a_group_per_job(session, prefixes, share, want):
    session([_Sp(*row) for row in _spans(TWO_STEPS)])
    run = _Run(_Reduction({"bench:fit.step": ANCHORS}, BUSY))
    params = dict(FIT, prefixes=prefixes, share=share)
    assert span_idle.read(params, run) == pytest.approx(want)


def test_span_idle_finds_nothing_where_there_is_nothing(session, monkeypatch):
    params = dict(FIT, prefixes=["plan."])
    run = _Run(_Reduction({"bench:fit.step": ANCHORS}, BUSY))
    session([])
    assert span_idle.read(params, run) is None
    session([_Sp(*row) for row in _spans(TWO_STEPS)])
    assert span_idle.read(params, _Run(None)) is None  # no trace was taken
    assert span_idle.read(params, _Run(_Reduction({}, BUSY))) is None
    # a program from before the primitive: no session_spans at all
    from keystone_tpu.obs import tracer

    monkeypatch.delattr(tracer, "session_spans")
    assert span_idle.read(params, run) is None
    assert span_total.read(
        {"span": "job", "field": "compiles", "how": "mean"}, run
    ) is None


def test_span_total_on_compiles_and_on_a_median(session):
    session([
        _Sp("job", 0.0, 1.0, compiles=2), _Sp("job", 1.0, 2.0, compiles=4),
        _Sp("pipeline.apply", 0.0, 0.010), _Sp("pipeline.apply", 1.0, 1.030),
        _Sp("pipeline.apply", 2.0, 2.020),
    ])
    run = _Run(None)
    assert span_total.read(
        {"span": "job", "field": "compiles", "how": "mean"}, run
    ) == pytest.approx(3.0)
    assert span_total.read(
        {"span": "pipeline.apply", "field": "seconds", "how": "median",
         "scale": 1000.0}, run,
    ) == pytest.approx(20.0)
    assert span_total.read(
        {"span": "absent", "field": "seconds", "how": "median"}, run
    ) is None
    with pytest.raises(ValueError):
        span_total.read({"span": "job", "field": "tid", "how": "mean"}, run)
    with pytest.raises(ValueError):
        span_total.total([1.0], "max")
