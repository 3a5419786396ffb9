"""The trace reduction on small hand-made event lists."""
import pytest

from bench import trace
from bench.trace import Event

DEV, HOST = "/device:TPU:0", "/host:CPU"


def op(start, dur, name="fusion", plane=DEV):
    return Event(plane, trace.OPS_LINE, name, start, dur)


def span(name, start, dur, line="python"):
    return Event(HOST, line, name, start, dur)


def test_union_merges_overlaps_and_drops_empty():
    assert trace.union([(5, 7), (0, 2), (1, 3), (7, 8), (9, 9)]) == [
        (0, 3), (5, 8)]


def test_overlap_and_gaps():
    merged = [(0, 3), (5, 8), (10, 12)]
    assert trace.overlap(2, 11, merged) == 1 + 3 + 1
    assert trace.overlap(3, 5, merged) == 0
    assert trace.gaps(merged, -1, 13) == [(-1, 0), (3, 5), (8, 10),
                                          (12, 13)]


def test_busy_clips_to_window_and_ignores_other_lines():
    events = [op(0, 10), op(5, 10), op(30, 10),
              Event(DEV, "XLA Modules", "jit_f", 0, 100)]
    assert trace.busy(events, DEV, 2, 35) == [(2, 15), (30, 35)]


def test_kernels_are_tpu_custom_calls():
    assert trace.is_kernel(op(0, 1, '%k = f32[4] custom-call(), '
                                    'custom_call_target="tpu_custom_call"'))
    assert not trace.is_kernel(op(0, 1, "%fusion = f32[4] fusion()"))


def test_annotation_overlap_gives_host_time():
    events = [span("bench.window", 0, 100), span("bench.launch", 10, 40),
              span("bench.launch", 60, 30), op(20, 10), op(25, 10),
              op(80, 20)]
    merged = trace.busy(events, DEV, 0, 100)
    assert merged == [(20, 35), (80, 100)]
    host = [s.dur_ns - trace.overlap(s.start_ns, s.end_ns, merged)
            for s in trace.spans(events, "bench.launch")]
    assert host == [40 - 15, 30 - 10]


def test_innermost_names_each_segment_after_the_deepest_span():
    segs = trace.innermost([span("outer", 0, 10), span("inner", 2, 3),
                            span("later", 12, 2)])
    assert segs == [(0, 2, "outer"), (2, 5, "inner"), (5, 10, "outer"),
                    (12, 14, "later")]


def test_idle_gaps_are_attributed_to_host_activity():
    events = [span("bench.window", 0, 100), span("bench.launch", 0, 50),
              span("PjitFunction(f)", 10, 10), span("bench.submit", 60, 20),
              op(30, 20)]
    got = dict((k, v * 1e9) for k, v in trace.idle_by_host(events, DEV, 0,
                                                           100))
    assert got == pytest.approx({"bench.launch": 20, "PjitFunction(f)": 10,
                                 "bench.submit": 20, "host: untraced": 30})


def test_top_ops_sums_by_name():
    events = [op(0, 5, "a"), op(10, 5, "a"), op(20, 3, "b")]
    assert trace.top_ops(events, [DEV], 0, 100) == [["a", 10e-9],
                                                    ["b", 3e-9]]
