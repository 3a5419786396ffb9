"""The flat 1x1 body's roofline reader on a synthetic trace: the ideal
time of the 1x1 unit calls over the device time of the ops named
``res_conv_1x1``, None on a chain or without such ops, and the whole
member's ``res_conv_roofline`` still counting the flat body's ops."""
import json
from pathlib import Path

import pytest

from bench import run as bench_run
from bench.networks import cnn_chain, resnet
from bench.trace import Event
from bench.traffic import Launch, Record

ROOT = Path(__file__).resolve().parents[2]
BENCH = json.loads((ROOT / "BENCHMARK.json").read_text())
PLANE = "/device:TPU:0"
PEAKS = {"bf16_flops": 197e12, "hbm_bytes_per_s": 819e9}


def _op(name, start, dur):
    return Event(PLANE, "XLA Ops",
                 f"%{name} = f32[4,56,56,256] custom-call(f32[] "
                 f"%res_conv_1x1.1), "
                 'custom_call_target="tpu_custom_call"', start, dur)


def _ctx(network, config, events, batches=(4,)):
    record = Record(frames=[], window_s=1.0, sample=[],
                    launches=[Launch(0.0, 0.1, b) for b in batches])
    ctx = bench_run.Context("resnet50.sat", config, {}, 1, PEAKS, 1.0,
                            record, network=network)
    ctx.events, ctx.window_ns, ctx.planes = events, (0.0, 1000.0), [PLANE]
    return ctx


def _ideal(config, n):
    """Ideal seconds of one launch's 1x1 unit calls at batch ``n``."""
    return sum(resnet.unit_work(n, *u[1:]).ideal_s(
        PEAKS["bf16_flops"], PEAKS["hbm_bytes_per_s"])
        for u in resnet.units(config) if u[5] == 1)


def test_res_conv_1x1_roofline_reads_a_synthetic_trace():
    cfg = bench_run.load_config(BENCH, "resnet50")
    events = [_op("res_conv_1x1.7", 0, 300),      # the flat body: 300 ns
              _op("res_conv_1x1.8", 400, 100),    # and 100 ns more
              _op("res_conv.3", 600, 200),        # the row body: not it
              _op("pool_vpu.1", 850, 50),         # reads res_conv_1x1.1
              _op("res_conv_1x1.9", 950, 100)]    # half in the window
    ctx = _ctx(resnet, cfg, events, batches=(4, 2))
    got = bench_run.load_reader("res_conv_1x1_roofline")(ctx)
    assert got == pytest.approx(
        100.0 * (_ideal(cfg, 4) + _ideal(cfg, 2)) / 450e-9)
    # 36 of ResNet-50's 53 units are 1x1
    assert sum(1 for u in resnet.units(cfg) if u[5] == 1) == 36


def test_res_conv_1x1_roofline_reads_none_on_the_chain_and_without_ops():
    chain = _ctx(cnn_chain, bench_run.load_config(BENCH, "vgg16_d"),
                 [_op("res_conv_1x1.7", 0, 300)])
    assert bench_run.load_reader("res_conv_1x1_roofline")(chain) is None
    cfg = bench_run.load_config(BENCH, "resnet50")
    parent = _ctx(resnet, cfg, [_op("res_conv.3", 0, 300)])
    assert bench_run.load_reader("res_conv_1x1_roofline")(parent) is None
    flat = _ctx(resnet, cfg, [_op("res_conv_1x1.7", 0, 300)])
    flat.planes = []
    assert bench_run.load_reader("res_conv_1x1_roofline")(flat) is None


def test_res_conv_roofline_counts_the_flat_body():
    cfg = bench_run.load_config(BENCH, "resnet50")
    events = [_op("res_conv.3", 0, 400), _op("res_conv_1x1.7", 500, 200)]
    got = bench_run.load_reader("res_conv_roofline")(
        _ctx(resnet, cfg, events))
    ideal = sum(w.ideal_s(PEAKS["bf16_flops"], PEAKS["hbm_bytes_per_s"])
                for w in resnet.res_conv_calls(cfg, 4))
    assert got == pytest.approx(100.0 * ideal / 600e-9)


def test_the_metric_is_in_the_benchmark():
    entry = next(m for m in BENCH["per_layer"]
                 if m["name"] == "res_conv_1x1_roofline")
    roof = next(m for m in BENCH["per_layer"]
                if m["name"] == "res_conv_roofline")
    assert (entry["unit"], entry["better"], entry["source"], entry["moves"],
            entry["workloads"]) == ("%", "higher", "device_trace",
                                    "throughput_fps", ["resnet50.sat"])
    assert entry["layer"] == roof["layer"]
    assert "res_conv_1x1_roofline" not in {
        m["name"] for m in bench_run.metrics_for(BENCH, "vgg16.sat", True)}
