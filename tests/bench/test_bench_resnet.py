"""The residual network's benchmark files: its work counts against hand
counts and against the program's plan, its two metric readers on a
synthetic trace, its cell in ``BENCHMARK.json``, and a small ResNet
served through the program against its plain reference (CPU,
interpreted kernels)."""
import hashlib
import json
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from bench import reference
from bench import run as bench_run
from bench.networks import cnn_chain, resnet
from bench.trace import Event
from bench.traffic import Launch, Record

ROOT = Path(__file__).resolve().parents[2]
BENCH = json.loads((ROOT / "BENCHMARK.json").read_text())
# A ResNet-50 cut for the CPU: 32 px, widths / 16, stage blocks (2, 1, 1,
# 1) — so both identity and projection shortcuts occur — and 10 classes.
SMALL = {"image": [32, 32, 3],
         "stem": {"channels": 4, "kernel": 7, "stride": 2, "padding": 3},
         "pool": {"window": 3, "stride": 2, "padding": 1},
         "widths": [4, 8, 16, 32], "blocks": [2, 1, 1, 1], "expansion": 4,
         "strides": [1, 2, 2, 2], "classes": 10, "dtype": "float32"}
# The served logits against the reference: both are f32 at HIGHEST and
# differ only in the order of the sums (about 3e-7 here); the control's
# three bf16 passes read about 3e-5.
SERVED_TOL = 5e-6


def _config(name="resnet50"):
    return bench_run.load_config(BENCH, name)


def test_frame_flops_by_hand():
    # multiply-adds: the 53 convs and FC-1000 (He et al., Table 1: 4.1e9)
    macs = 4_089_184_256
    stem = 2 * 112 * 112 * 64                  # bias, ReLU
    pool = 8 * 56 * 56 * 64                    # 3x3 window: 8 compares
    # per stage: c1 and c2 take bias and ReLU, c3 bias, add and ReLU,
    # the projection its bias
    stage0 = (3 * (2 * 56 * 56 * 64 + 2 * 56 * 56 * 64 + 3 * 56 * 56 * 256)
              + 56 * 56 * 256)
    stage1 = (2 * 56 * 56 * 128 + 2 * 28 * 28 * 128 + 28 * 28 * 512
              + 3 * 28 * 28 * 512
              + 3 * (2 * 28 * 28 * 128 + 2 * 28 * 28 * 128
                     + 3 * 28 * 28 * 512))
    stage2 = (2 * 28 * 28 * 256 + 2 * 14 * 14 * 256 + 14 * 14 * 1024
              + 3 * 14 * 14 * 1024
              + 5 * (2 * 14 * 14 * 256 + 2 * 14 * 14 * 256
                     + 3 * 14 * 14 * 1024))
    stage3 = (2 * 14 * 14 * 512 + 2 * 7 * 7 * 512 + 7 * 7 * 2048
              + 3 * 7 * 7 * 2048
              + 2 * (2 * 7 * 7 * 512 + 2 * 7 * 7 * 512 + 3 * 7 * 7 * 2048))
    head = 7 * 7 * 2048 + 1000                 # average pool adds, FC bias
    assert resnet.frame_flops(_config()) == (
        2 * macs + stem + pool + stage0 + stage1 + stage2 + stage3 + head)


def test_unit_work_by_hand():
    # 6x6x2 input, 3x3 stride-2 pad-1 conv to 4 channels with a shortcut
    # and a ReLU: 3x3x4 = 36 outputs
    w = resnet.unit_work(1, 6, 6, 2, 4, 3, 2, 1, True, True)
    assert w.flops == 2 * 36 * 9 * 2 + 36 * 3
    assert w.bytes == 4 * (72 + 72 + 4 + 36 + 36)


def _site_work(site):
    """The Work of one planned site, from its spec's shapes and knobs."""
    spec = site.spec
    if spec.family == "pool2d":
        return None
    (n, h, w, cin), (k, _, _, cout) = spec.shapes
    return resnet.unit_work(n, h, w, cin, cout, k, spec.knob("stride"),
                            spec.knob("padding"), spec.knob("residual"),
                            spec.knob("relu"))


@pytest.mark.parametrize("n", [1, 4])
def test_calls_match_the_plans_kernel_calls(n):
    from repro.core.plan import plan_network
    cfg = _config()
    params, _ = jax.eval_shape(lambda: resnet.make(cfg, 7, 2))
    plan = plan_network(resnet.description(cfg).site_specs(
        params, (n, *cfg["image"]), "float32"))
    calls = resnet.calls(cfg, n)
    assert len(calls) == len(plan.sites) == plan.total_launches == 54
    assert [s.spec.family for s in plan.sites[:2]] == ["res_conv", "pool2d"]
    assert calls[1] == resnet.pool_work(cfg, n)
    for site, work in zip(plan.sites, calls):
        if site.spec.family == "res_conv":
            assert site.ip.name == "res_conv.res_mxu"
            assert _site_work(site) == work
    assert resnet.res_conv_calls(cfg, n) == calls[:1] + calls[2:]
    # each bottleneck's last conv carries its residual add
    c3 = [s for s in plan.sites if s.spec.name.endswith(".c3.fused")]
    assert len(c3) == 16 and all(s.spec.knob("residual") for s in c3)


def test_the_cell_and_its_metrics_are_in_the_benchmark():
    cfg = next(c for c in BENCH["configs"] if c["name"] == "resnet50")
    assert cfg["reduced"] == [] and _config()["reduced"] == {}
    cell = next(w for w in BENCH["workloads"] if w["name"] == "resnet50.sat")
    assert (cell["config"], cell["traffic"], cell["chips"]) == \
        ("resnet50", "sat", 1)
    reported = {m["name"] for m in bench_run.metrics_for(BENCH,
                                                         "resnet50.sat",
                                                         True)}
    assert {"res_conv_roofline", "glue_ops_share", "cnn_blocks_roofline",
            "mfu", "device_idle_share", "host_ms_per_launch",
            "jit_traces_in_window"} <= reported
    assert {m["name"] for m in bench_run.metrics_for(
        BENCH, "vgg16.sat", True)}.isdisjoint(
        {"res_conv_roofline", "glue_ops_share"})


PLANE = "/device:TPU:0"


def _op(name, start, dur, kernel=True):
    text = (f"%{name} = f32[4,56,56,256] custom-call(f32[] %res_conv.1), "
            'custom_call_target="tpu_custom_call"' if kernel
            else f"%{name} = f32[4,58,58,64] pad(f32[] %res_conv.1)")
    return Event(PLANE, "XLA Ops", text, start, dur)


def _ctx(network, config, events, batches=(4,)):
    record = Record(frames=[], window_s=1.0, sample=[],
                    launches=[Launch(0.0, 0.1, b) for b in batches])
    ctx = bench_run.Context("resnet50.sat", config, {}, 1,
                            {"bf16_flops": 197e12,
                             "hbm_bytes_per_s": 819e9}, 1.0, record,
                            network=network)
    ctx.events, ctx.window_ns, ctx.planes = events, (0.0, 1000.0), [PLANE]
    return ctx


def test_res_conv_roofline_and_glue_share_read_a_synthetic_trace():
    cfg = _config()
    events = [_op("res_conv.3", 0, 400),          # the member: 400 ns
              _op("res_conv.4", 500, 200),        # and 200 ns more
              _op("pool_vpu.1", 700, 50),         # reads res_conv.1 only
              _op("pad.2", 800, 100, kernel=False),
              _op("copy.5", 950, 100, kernel=False)]  # half in the window
    ctx = _ctx(resnet, cfg, events)
    roof = bench_run.load_reader("res_conv_roofline")(ctx)
    peaks = ctx.peaks
    ideal = sum(w.ideal_s(peaks["bf16_flops"], peaks["hbm_bytes_per_s"])
                for w in resnet.res_conv_calls(cfg, 4))
    assert roof == pytest.approx(100.0 * ideal / 600e-9)
    glue = bench_run.load_reader("glue_ops_share")(ctx)
    assert glue == pytest.approx(100.0 * 150 / (400 + 200 + 50 + 150))


def test_res_conv_roofline_reads_none_on_the_chain():
    cfg = _config("vgg16_d")
    events = [_op("fused_cnn_mxu.5", 0, 400),
              _op("pad.2", 500, 100, kernel=False)]
    ctx = _ctx(cnn_chain, cfg, events)
    assert bench_run.load_reader("res_conv_roofline")(ctx) is None
    # the chain has glue too; without a device plane there is nothing
    assert bench_run.load_reader("glue_ops_share")(ctx) == \
        pytest.approx(100.0 * 100 / 500)
    ctx.planes = []
    assert bench_run.load_reader("glue_ops_share")(ctx) is None
    # a network with res_conv calls but no op of that name in the window
    assert bench_run.load_reader("res_conv_roofline")(
        _ctx(resnet, _config(), events)) is None


# sha256 of plan.describe() at batch 4 for the benchmark's chain
# configurations, as planned before the residual network existed.
CHAIN_PLANS = {"vgg16_d": "08d8613e025241de", "lenet5": "1dfa52d8e8c2346c"}


@pytest.mark.parametrize("name", sorted(CHAIN_PLANS))
def test_chain_plans_are_unchanged(name):
    from repro.core.plan import plan_network
    from repro.models.frontends import cnn_frontend_site_specs
    cfg = _config(name)
    params, _ = jax.eval_shape(lambda: cnn_chain.make(cfg, 7, 2))
    specs = cnn_frontend_site_specs(
        params, (4, *cfg["image"]), jnp.float32,
        pool_window=tuple(cfg["pool_window"]), activation=cfg["activation"])
    text = plan_network(specs).describe()
    assert hashlib.sha256(text.encode()).hexdigest()[:16] == CHAIN_PLANS[name]


def test_small_resnet_served_matches_the_reference():
    """network.register -> SLOScheduler.register -> AdaptiveServer with
    the ResidualNet description -> replan -> the compiled step, against
    the network's plain reference on seeded weights."""
    from repro.models.frontends import ResidualNet
    from repro.runtime import AdaptiveServer, SLOScheduler, SLOSpec
    params, frames = resnet.make(SMALL, 2**33 + 5, 2)
    srv = AdaptiveServer(max_batch=2)
    sched = SLOScheduler(srv)
    resnet.register(sched, "r", SMALL, params, SLOSpec(deadline_s=600.0))
    assert srv.tenants["r"].net == ResidualNet(
        (1, 2, 2, 2), stem_stride=2, stem_padding=3, pool_window=3,
        pool_stride=2, pool_padding=1)
    rids = [sched.submit("r", frames[i]) for i in range(2)]
    done = {}
    while sched.pending():
        done.update({c.rid: c for c in
                     sched.run(max_launches=sched.launches + 1)})
    got = np.stack([done[r].result for r in rids])
    assert got.shape == (2, SMALL["classes"])
    ref = resnet.forward(SMALL, params, frames)
    assert reference.rel_errors(got, ref).max() <= SERVED_TOL
    # the control (three bf16 passes) reads past the limit
    control = resnet.forward(SMALL, params, frames, passes=3)
    assert reference.rel_errors(control, ref).min() > SERVED_TOL
