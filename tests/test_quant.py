"""Fixed-point precision subsystem: quantization core, per-family
quantized kernels vs the oracles, calibration, the precision ladder in
the planner, and mixed-precision plan execution."""
import importlib.util
import pathlib

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core.ip import SiteSpec
from repro.core.plan import NetworkPlan, plan_network, plan_single
from repro.core.resources import ResourceBudget
from repro.quant import (Calibrator, MIN_SCALE, dequantize, fake_quant,
                         max_rel_error, quantization_error, quantize_acts,
                         quantize_weights, quantized_activation,
                         quantized_conv2d, quantized_matmul,
                         quantized_pool2d, relative_error)

CONV_X = (2, 16, 16, 8)
CONV_W = (3, 3, 8, 16)


def _randn(rng, shape, scale=1.0):
    return jnp.asarray(rng.normal(0, scale, shape).astype(np.float32))


# --------------------------------------------------------------------------
# Zero-scale regression (satellite): all-zero tensors must round-trip
# exactly instead of producing NaNs.
# --------------------------------------------------------------------------
def test_all_zero_acts_quantize_without_nan():
    z = jnp.zeros((4, 8))
    q = quantize_acts(z)
    assert float(q.scale) >= MIN_SCALE / 127
    deq = dequantize(q)
    assert not bool(jnp.isnan(deq).any())
    np.testing.assert_array_equal(np.asarray(deq), np.zeros((4, 8)))


def test_all_zero_weight_channel_quantizes_without_nan(rng):
    w = _randn(rng, (8, 4))
    w = w.at[:, 2].set(0.0)     # one dead output channel
    wq = quantize_weights(w)
    deq = dequantize(wq)
    assert not bool(jnp.isnan(deq).any())
    np.testing.assert_array_equal(np.asarray(deq[:, 2]), np.zeros(8))
    assert quantization_error(jnp.zeros((8, 4))) == 0.0


def test_quantize_bits_parameter():
    x = jnp.linspace(-3.0, 3.0, 64)
    q8, q16 = quantize_acts(x, bits=8), quantize_acts(x, bits=16)
    assert q8.q.dtype == jnp.int8 and q16.q.dtype == jnp.int16
    e8 = relative_error(dequantize(q8), x)
    e16 = relative_error(dequantize(q16), x)
    assert e16 < e8 < 5e-2
    with pytest.raises(ValueError, match="unsupported quantization width"):
        quantize_acts(x, bits=12)


# --------------------------------------------------------------------------
# Quantized kernels vs the family oracles (per-kernel accuracy bounds)
# --------------------------------------------------------------------------
@pytest.mark.parametrize("bits,bound", [(8, 5e-2), (16, 1e-3)])
def test_quantized_conv2d_close_to_ref(rng, bits, bound):
    from repro.kernels.conv2d.ref import conv2d_ref
    x = _randn(rng, CONV_X)
    w = _randn(rng, CONV_W, scale=0.1)
    ref = conv2d_ref(x, w)
    for ip in ("ip1_vpu", "ip2_mxu"):
        y = quantized_conv2d(x, w, bits=bits, ip=ip)
        assert y.dtype == jnp.float32
        assert relative_error(y, ref) < bound, (ip, bits)


@pytest.mark.parametrize("mode", ["max", "avg"])
def test_quantized_pool2d_close_to_ref(rng, mode):
    from repro.kernels.pool2d.ref import pool2d_ref
    x = _randn(rng, (2, 8, 8, 16))
    ref = pool2d_ref(x, window=(2, 2), mode=mode)
    for ip in ("pool_vpu", "pool_im2col"):
        y = quantized_pool2d(x, window=(2, 2), mode=mode, bits=8, ip=ip)
        assert relative_error(y, ref) < 5e-2, (ip, mode)


@pytest.mark.parametrize("kind", ["relu", "tanh", "sigmoid"])
def test_quantized_activation_close_to_ref(rng, kind):
    from repro.kernels.activation.ref import activation_ref
    x = _randn(rng, (2, 8, 8, 4), scale=2.0)
    ref = activation_ref(x, kind=kind)
    y = quantized_activation(x, kind=kind, bits=8, ip="act_vpu")
    assert relative_error(y, ref) < 5e-2, kind


def test_quantized_matmul_close_to_ref(rng):
    a = _randn(rng, (32, 64))
    b = _randn(rng, (64, 48))
    ref = a @ b
    for ip in ("mm_mxu", "mm_vpu"):
        y = quantized_matmul(a, b, bits=8, ip=ip)
        assert relative_error(y, ref) < 5e-2, ip
    assert relative_error(quantized_matmul(a, b, bits=16, ip="mm_mxu"),
                          ref) < 1e-3


# --------------------------------------------------------------------------
# Calibration
# --------------------------------------------------------------------------
def test_calibrator_running_max_and_scale(rng):
    cal = Calibrator()
    batches = [_randn(rng, (16, 8), scale=s) for s in (0.5, 2.0, 1.0)]
    for b in batches:
        cal.observe("ffn.in", b)
    worst = max(float(jnp.max(jnp.abs(b))) for b in batches)
    assert cal.amax("ffn.in") == pytest.approx(worst)
    assert cal.scale("ffn.in", bits=8) == pytest.approx(worst / 127)
    q = cal.quantize("ffn.in", batches[0])
    assert relative_error(dequantize(q), batches[0]) < 5e-2
    with pytest.raises(KeyError, match="never observed"):
        cal.scale("unknown")


def test_calibrator_ema_and_round_trip():
    cal = Calibrator(momentum=0.5)
    cal.observe("x", jnp.asarray([1.0]))
    cal.observe("x", jnp.asarray([3.0]))
    assert cal.amax("x") == pytest.approx(0.5 * 1.0 + 0.5 * 3.0)
    runmax = Calibrator()
    runmax.observe("x", jnp.asarray([1.0]))
    runmax.observe("x", jnp.asarray([3.0]))
    assert runmax.amax("x") == pytest.approx(3.0)
    restored = Calibrator.from_dict(cal.to_dict())
    assert restored.amax("x") == pytest.approx(cal.amax("x"))
    assert restored.momentum == cal.momentum


# --------------------------------------------------------------------------
# The precision ladder in the planner
# --------------------------------------------------------------------------
def _conv_site(ladder=(), name="c.conv"):
    return SiteSpec.make(name, "conv2d", (CONV_X, CONV_W), "float32",
                         ladder=ladder, dual=False)


def test_ladder_descends_only_on_failure():
    ample = ResourceBudget()
    assert plan_single(_conv_site(ladder=(16, 8)), ample).precision_bits == 32
    tight = ResourceBudget(vmem_bytes=320 * 1024)
    with pytest.raises(ValueError, match="no feasible"):
        plan_single(_conv_site(), tight)
    planned = plan_single(_conv_site(ladder=(16, 8)), tight)
    assert planned.precision_bits == 8 and planned.lowered
    mid = ResourceBudget(vmem_bytes=400 * 1024)
    assert plan_single(_conv_site(ladder=(16, 8)), mid).precision_bits == 16


def test_ladder_unlocks_packed_dual_member():
    """A bf16 dual conv site cannot use ip3_packed (8-bit ceiling); with
    a ladder and no MXU, lowering to int8 is the only way to run."""
    spec = SiteSpec.make("d.conv", "conv2d", (CONV_X, CONV_W), "bfloat16",
                         ladder=(8,), dual=True)
    no_mxu = ResourceBudget(mxu_available=False)
    planned = plan_single(spec, no_mxu)
    assert planned.ip.name == "conv2d.ip3_packed"
    assert planned.precision_bits == 8
    bare = SiteSpec.make("d2.conv", "conv2d", (CONV_X, CONV_W), "bfloat16",
                         dual=True)
    with pytest.raises(ValueError, match="no feasible IP"):
        plan_single(bare, no_mxu)


def test_attention_is_never_lowered():
    spec = SiteSpec.make("a.attn", "attention",
                         ((2, 8, 128, 64), (2, 2, 128, 64)), "bfloat16",
                         ladder=(8,))
    planned = plan_single(spec, ResourceBudget())
    assert planned.precision_bits == spec.native_bits
    assert not planned.lowered


def test_native_int8_site_is_not_lowered():
    spec = SiteSpec.make("i8.conv", "conv2d", (CONV_X, CONV_W), "int8",
                         ladder=(16, 8), dual=False)
    planned = plan_single(spec, ResourceBudget())
    assert planned.precision_bits == 8 and not planned.lowered


def test_mixed_precision_plan_json_round_trip():
    specs = [
        _conv_site(ladder=(16, 8), name="m.conv"),
        SiteSpec.make("m.pool", "pool2d", ((2, 14, 14, 16),), "float32",
                      ladder=(16, 8), window=(2, 2), mode="max"),
        SiteSpec.make("m.act", "activation", ((2, 7, 7, 16),), "float32",
                      kind="relu"),
    ]
    # fuse=False: the squeeze that forces mixed precision targets the
    # per-op footprints (the fused group fits 780 KiB without lowering)
    plan = plan_network(specs, ResourceBudget(vmem_bytes=780 * 1024),
                        fuse=False)
    bits = {s.spec.name: s.precision_bits for s in plan.sites}
    assert any(s.lowered for s in plan.sites)
    assert len(set(bits.values())) > 1      # genuinely mixed precisions
    restored = NetworkPlan.from_json(plan.to_json())
    assert restored == plan
    for name in plan:
        assert restored.precision_of(name) == bits[name]
        assert restored.site(name).spec.ladder == plan.site(name).spec.ladder


def test_sitespec_ladder_round_trip_and_validation():
    spec = _conv_site(ladder=(8, 16))
    assert spec.ladder == (16, 8)           # normalized descending
    back = SiteSpec.from_dict(spec.to_dict())
    assert back == spec
    hash(back)
    with pytest.raises(ValueError, match="unsupported ladder width"):
        SiteSpec.make("bad", "conv2d", (CONV_X, CONV_W), "float32",
                      ladder=(12,), dual=False)


# --------------------------------------------------------------------------
# Mixed-precision execution
# --------------------------------------------------------------------------
def test_ops_wrapper_executes_lowered_plan(rng):
    from repro.kernels.conv2d.ops import conv2d
    from repro.kernels.conv2d.ref import conv2d_ref
    x = _randn(rng, CONV_X)
    w = _randn(rng, CONV_W, scale=0.1)
    ref = conv2d_ref(x, w)
    y = conv2d(x, w, budget=ResourceBudget(vmem_bytes=320 * 1024),
               ladder=(16, 8))
    assert y.dtype == jnp.float32
    assert relative_error(y, ref) < 5e-2


def test_apply_cnn_block_mixed_precision_end_to_end(rng):
    from repro.models.blocks import apply_cnn_block, init_cnn_block
    block = init_cnn_block(jax.random.PRNGKey(0), cin=8, cout=16, k=3)
    x = _randn(rng, CONV_X)
    y_f32 = apply_cnn_block(block, x, activation="relu")
    # fuse=False below: 780 KiB starves the per-op sites (the fused
    # group's smaller working set would still fit at f32)
    tight = ResourceBudget(vmem_bytes=780 * 1024)
    with pytest.raises(ValueError, match="no feasible"):
        apply_cnn_block(block, x, budget=tight, activation="relu",
                        fuse=False)
    report = {}
    y = apply_cnn_block(block, x, budget=tight, ladder=(16, 8),
                        activation="relu", quant_report=report, fuse=False)
    assert y.dtype == y_f32.dtype and y.shape == y_f32.shape
    assert relative_error(y, y_f32) < 5e-2
    # the report covers every site and every quantized site is bounded
    assert set(report) == {"cnn_block.conv", "cnn_block.pool",
                           "cnn_block.act"}
    assert any(r.lowered for r in report.values())
    assert max_rel_error(report) < 5e-2
    for r in report.values():
        assert r.rel_error < 5e-2


def test_apply_cnn_frontend_with_ladder(rng):
    from repro.models.frontends import apply_cnn_frontend, init_cnn_frontend
    p = init_cnn_frontend(jax.random.PRNGKey(1), channels=(3, 8, 16),
                          d_model=32)
    imgs = _randn(rng, (2, 16, 16, 3))
    y_f32 = apply_cnn_frontend(p, imgs)
    report = {}
    y = apply_cnn_frontend(p, imgs, budget=ResourceBudget(vmem_bytes=1000
                                                          * 1024),
                           ladder=(16, 8), quant_report=report, fuse=False)
    assert y.shape == y_f32.shape
    assert relative_error(y, y_f32) < 5e-2
    assert len(report) == 6                 # 2 blocks x 3 sites


def test_fake_quant_precision_ordering(rng):
    w = _randn(rng, (32, 16))
    e8 = relative_error(fake_quant(w, bits=8, axis=-1), w)
    e16 = relative_error(fake_quant(w, bits=16, axis=-1), w)
    assert e16 < e8


# --------------------------------------------------------------------------
# table_precision acceptance (benchmarks/run.py)
# --------------------------------------------------------------------------
def _load_bench():
    path = (pathlib.Path(__file__).resolve().parent.parent
            / "benchmarks" / "run.py")
    spec = importlib.util.spec_from_file_location("bench_run_quant", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def test_table_precision_ladder_wins_and_errors_bounded():
    bench = _load_bench()
    bench.table_precision()
    rows = [d for n, _, d in bench.ROWS if n.startswith("table_precision.")]
    assert rows
    # at least one budget where the f32-only plan is infeasible (or
    # slower) and the ladder plan runs
    assert any("f32=x" in d and "ladder=x" not in d for d in rows), rows
    assert any("ladder_wins=1" in d for d in rows), rows
    # every executed row reports bounded per-site error
    executed = [d for d in rows if "max_rel_err" in d]
    assert executed
    assert all("err_ok=1" in d for d in executed), executed
