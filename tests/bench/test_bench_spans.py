"""Host time by layer from the program's spans (``bench/spans.py``) and
the readers of the four metrics built on it, on hand-made event lists."""
import pytest

from bench import run, spans, trace
from bench.trace import Event

DEV0, DEV1, HOST = "/device:TPU:0", "/device:TPU:1", "/host:CPU"


def op(start, dur, plane=DEV0):
    return Event(plane, trace.OPS_LINE, "fusion", start, dur)


def span(name, start, dur, line="python"):
    return Event(HOST, line, name, start, dur)


def ctx_of(events, planes=(DEV0,), window=(0, 1000)):
    return run.Context("cell", {}, {}, len(planes), {}, 0.0, None,
                       events=events, window_ns=window, planes=list(planes))


def one_launch(t=0):
    """One served launch as the program nests its spans, shifted by
    ``t``, with JAX's own events and a cold-path span beneath them."""
    return [
        span("bench.launch", t + 100, 400),
        span("sched.admit", t + 110, 40),
        span("arbiter.split", t + 150, 10),
        span("sched.launch", t + 160, 330),
        span("serve.execute", t + 170, 290),
        span("serve.stack", t + 180, 20),
        span("serve.plan", t + 200, 20),
        span("replan", t + 205, 10),
        span("serve.dispatch", t + 220, 180),
        span("PjitFunction(conv)", t + 230, 20),
        span("DevicePut", t + 260, 10),
        span("serve.results", t + 400, 50),
        span("sched.block", t + 460, 20),
        span("sched.judge", t + 480, 8),
    ]


def test_self_time_under_nesting_counts_children_to_their_span():
    events = [span("bench.window", 0, 1000)] + one_launch()
    got = spans.self_idle_ns(ctx_of(events))
    assert got == pytest.approx({
        "bench.launch": 10 + 10,          # before admit, after launch
        "sched.admit": 40,
        "arbiter.split": 10,
        "sched.launch": 10 + 0 + 2,       # choose, gaps round the block
        "serve.execute": 10 + 10,         # before stack, after results
        "serve.stack": 20,
        "serve.plan": 20,                 # replan beneath it counts here
        "serve.dispatch": 180,            # and JAX's own events here
        "serve.results": 50,
        "sched.block": 20,
        "sched.judge": 8,
    })


def test_busy_time_is_taken_out_over_every_chip():
    events = ([span("bench.window", 0, 1000)] + one_launch()
              + [op(300, 50), op(340, 40, plane=DEV1), op(465, 15)])
    got = spans.self_idle_ns(ctx_of(events, planes=(DEV0, DEV1)))
    assert got["serve.dispatch"] == pytest.approx(180 - 80)
    assert got["sched.block"] == pytest.approx(20 - 15)
    # one chip: the other's op is not in its busy time
    assert spans.self_idle_ns(ctx_of(events))["serve.dispatch"] \
        == pytest.approx(180 - 50)


def test_layers_per_launch_and_the_partition_of_host_time():
    events = ([span("bench.window", 0, 2000)] + one_launch()
              + one_launch(1000) + [op(300, 50), op(1465, 15)])
    ctx = ctx_of(events, window=(0, 2000))
    assert spans.launches(ctx) == 2
    assert spans.layer_ms_per_launch(ctx, "scheduler") == pytest.approx(
        2 * (40 + 12 + 8) / 2 / 1e6)
    assert spans.layer_ms_per_launch(ctx, "planner") == pytest.approx(
        2 * (10 + 20) / 2 / 1e6)
    assert spans.layer_ms_per_launch(ctx, "dispatch") == pytest.approx(
        (2 * (20 + 20 + 180 + 50) - 50) / 2 / 1e6)
    # the layers, the block and what no span covers make host time
    parts = sum(spans.layer_ms_per_launch(ctx, layer)
                for layer in spans.LAYERS)
    parts += spans.idle_ms_per_launch(ctx, ["sched.block", "bench.launch"])
    assert parts == pytest.approx(trace.host_ms_per_launch(ctx))


def test_window_clips_and_other_threads_do_not_count():
    events = ([span("bench.window", 0, 300)] + one_launch()
              + [span("serve.dispatch", 0, 1000, line="worker")])
    got = spans.self_idle_ns(ctx_of(events, window=(0, 300)))
    assert got["serve.dispatch"] == pytest.approx(300 - 220)
    assert "serve.results" not in got
    assert sum(got.values()) == pytest.approx(300 - 100)


def test_without_program_spans_or_devices_the_readers_read_none():
    parent = [span("bench.window", 0, 1000), span("bench.launch", 100, 400),
              span("PjitFunction(conv)", 230, 20), op(300, 50)]
    for name in ("scheduler_ms_per_launch", "planner_ms_per_launch",
                 "dispatch_ms_per_launch", "jit_traces_in_window"):
        read = run.load_reader(name)
        assert read(ctx_of(parent)) is None
        assert read(ctx_of([span("bench.window", 0, 1000)] + one_launch(),
                           planes=())) is None


def test_readers_report_the_layers_and_the_traces_in_the_window():
    events = ([span("bench.window", 0, 1000)] + one_launch()
              + [span("jit.trace", 240, 0), span("jit.trace", 250, 0),
                 span("jit.trace", 1500, 0),
                 Event(HOST, "compile thread", "jit.trace", 600, 0)])
    ctx = ctx_of(events)
    assert run.load_reader("scheduler_ms_per_launch")(ctx) == \
        pytest.approx(60 / 1e6)
    assert run.load_reader("planner_ms_per_launch")(ctx) == \
        pytest.approx(30 / 1e6)
    assert run.load_reader("dispatch_ms_per_launch")(ctx) == \
        pytest.approx(270 / 1e6)
    assert run.load_reader("jit_traces_in_window")(ctx) == 3
    assert spans.jit_traces_in_window(
        ctx_of([span("bench.window", 0, 1000)] + one_launch())) == 0
