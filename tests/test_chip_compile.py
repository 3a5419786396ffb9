"""Compile the served CNN path for a described TPU v5e, at VGG widths.

No chip is needed: the TPU compiler is installed and compiles for a
``v5e:2x2`` topology that is described, not attached.  Each test
compiles what the planner picks for one VGG-16 (configuration D) stage,
or the whole batch-4 frontend step, with the kernels compiled by Mosaic
instead of interpreted, and checks that the program holds them
(``tpu_custom_call``).  A refusal here (VMEM over the scoped limit, an
unaligned block) is what the chip's compiler would raise.

The topology is described inside a fixture, never on import: only the
worker that runs this file loads the TPU library.
"""
import functools

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

import repro.kernels
from repro.core.library import FAMILIES
from repro.core.plan import plan_network
from repro.core.resources import ResourceBudget
from repro.models.blocks import apply_cnn_block
from repro.models.frontends import (apply_cnn_frontend,
                                    cnn_frontend_site_specs,
                                    init_cnn_frontend)

# VGG-16 configuration D stage widths, one conv per stage, batch 4.
IMAGE = (4, 224, 224, 3)
CHANNELS = (3, 64, 128, 256, 512)
D_MODEL = 512
# The standalone members an unfused plan (fuse=False) walks per stage.
STANDALONE = ("conv2d.ip1_vpu", "conv2d.ip2_mxu", "pool2d.pool_vpu",
              "pool2d.pool_im2col", "activation.act_vpu")


def _stage_input(stage):
    """(height, Cin, Cout) of VGG stage ``stage``: each stage is a VALID
    3x3 conv followed by a 2x2 pool."""
    h = IMAGE[1]
    for _ in range(stage):
        h = (h - 2) // 2
    return h, CHANNELS[stage], CHANNELS[stage + 1]


def _member_args(member, n, h, cin, cout, dtype):
    """(callable, abstract operands) of ``member`` for a block whose conv
    sees an (n, h, h, cin) input and writes ``cout`` channels."""
    ip = FAMILIES[member.partition(".")[0]][member]
    x = ((n, h, h, cin), dtype)
    w = ((3, 3, cin, cout), dtype)
    if ip.family == "conv2d":
        if ip.outputs_per_pass == 2:          # two input streams
            return ip.impl, (x, x, w)
        return ip.impl, (x, w)
    if ip.family == "pool2d":
        return (functools.partial(ip.impl, window=(2, 2), mode="max"),
                (((n, h - 2, h - 2, cout), dtype),))
    if ip.family == "activation":
        kind = "tanh" if "lut" in ip.tags else "relu"
        q = (h - 2) // 2
        return (functools.partial(ip.impl, kind=kind),
                (((n, q, q, cout), dtype),))
    if dtype == jnp.int8:          # the fused int8 rung's rescale
        return ip.impl, (x, w, ((1, 1, 1, cout), jnp.float32))
    return ip.impl, (x, w)


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 - any failure means "not here"
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture
def compiled_kernels(monkeypatch):
    """Kernels compiled by Mosaic, as on the chip; traces made in
    interpret mode are dropped before and after."""
    jax.clear_caches()
    monkeypatch.setattr(repro.kernels, "interpret", lambda: False)
    yield
    jax.clear_caches()


@pytest.fixture(scope="module")
def vgg():
    """Abstract frontend weights and the planner's plan at batch 4."""
    params = jax.eval_shape(lambda: init_cnn_frontend(
        jax.random.PRNGKey(0), channels=CHANNELS, d_model=D_MODEL))
    plan = plan_network(cnn_frontend_site_specs(params, IMAGE, jnp.float32),
                        ResourceBudget())
    return params, plan


def _on(sharding, tree):
    return jax.tree.map(
        lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=sharding),
        tree)


def _compile(fn, *args):
    text = jax.jit(fn).lower(*args).compile().as_text()
    assert "tpu_custom_call" in text
    return text


def _scratch_kernel(scratch_bytes):
    """A kernel that keeps ``scratch_bytes`` of VMEM scratch live,
    launched with the limit ``pallas_call`` derives from that footprint."""
    from jax.experimental.pallas import tpu as pltpu

    def body(x_ref, o_ref, s_ref):
        s_ref[0] = x_ref[...]
        o_ref[...] = s_ref[s_ref.shape[0] - 1] + x_ref[...]

    rows = scratch_bytes // (8 * 512 * 4)
    return repro.kernels.pallas_call(
        body, grid=(1,), vmem_bytes=scratch_bytes,
        out_shape=jax.ShapeDtypeStruct((8, 512), jnp.float32),
        scratch_shapes=[pltpu.VMEM((rows, 8, 512), jnp.float32)])


def test_kernel_vmem_budget_is_what_the_compiler_accepts(one_chip,
                                                         compiled_kernels):
    """The planner's per-kernel VMEM budget compiles; the chip's whole
    VMEM plus a little does not."""
    from repro.core.resources import KERNEL_VMEM_BYTES, VMEM_BYTES
    x = jax.ShapeDtypeStruct((8, 512), jnp.float32, sharding=one_chip)
    assert ResourceBudget().vmem_bytes == KERNEL_VMEM_BYTES
    _compile(_scratch_kernel(KERNEL_VMEM_BYTES), x)
    with pytest.raises(Exception, match="vmem"):
        jax.jit(_scratch_kernel(VMEM_BYTES + 2**20)).lower(x).compile()


@pytest.mark.parametrize("stage", range(len(CHANNELS) - 1))
def test_stage_compiles_planned_members(stage, vgg, one_chip,
                                        compiled_kernels):
    params, plan = vgg
    prefix = f"frontend.block{stage}."
    sites = [s for s in plan.sites if s.spec.name.startswith(prefix)]
    assert sites, f"no planned sites for stage {stage}"
    x = jax.ShapeDtypeStruct(sites[0].spec.shapes[0], jnp.float32,
                             sharding=one_chip)
    block = _on(one_chip, params["blocks"][stage])
    _compile(functools.partial(apply_cnn_block, site=prefix[:-1],
                               network=plan), block, x)


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_lut_activation_compiles(dtype, one_chip, compiled_kernels):
    """The fixed-point LUT member, which a tenant with a saturating
    activation on an 8-bit ladder is planned onto, at stage-1 output
    size: its table lookup is a lane gather the TPU supports."""
    from repro.kernels.activation.lut_poly import activation_lut
    x = jax.ShapeDtypeStruct((4, 111, 111, 64), dtype, sharding=one_chip)
    _compile(functools.partial(activation_lut, kind="tanh"), x)


def test_frontend_step_compiles(vgg, one_chip, compiled_kernels):
    params, plan = vgg
    x = jax.ShapeDtypeStruct(IMAGE, jnp.float32, sharding=one_chip)
    text = _compile(functools.partial(apply_cnn_frontend, network=plan),
                    _on(one_chip, params), x)
    assert text.count("tpu_custom_call") >= len(plan.sites)


@pytest.mark.parametrize("member", STANDALONE)
@pytest.mark.parametrize("stage", range(len(CHANNELS) - 1))
def test_stage_compiles_standalone_members(stage, member, vgg, one_chip,
                                           compiled_kernels):
    """Every standalone member an unfused plan can pick at this stage
    fits the planner's budget and compiles."""
    params, _ = vgg
    family = member.partition(".")[0]
    spec, = [s for s in cnn_frontend_site_specs(params, IMAGE, jnp.float32)
             if s.name.startswith(f"frontend.block{stage}.")
             and s.family == family]
    req = FAMILIES[family].plan_site(spec)
    ip = FAMILIES[family][member]
    assert ip in req.candidates
    fp = ip.footprint(*req.fp_args, **dict(req.fp_kwargs))
    assert fp.fits(ResourceBudget()), fp
    h, cin, cout = _stage_input(stage)
    fn, args = _member_args(member, IMAGE[0], h, cin, cout, jnp.float32)
    specs = [jax.ShapeDtypeStruct(s, d, sharding=one_chip) for s, d in args]
    _compile(fn, *specs)


CAPABILITY = [(m.name, d) for f in ("conv2d", "pool2d", "activation",
                                    "cnn_fused")
              for m in FAMILIES[f] for d in m.supports_dtypes]


@pytest.mark.parametrize("member,dtype", CAPABILITY)
def test_compiled_dtypes_match_the_compiler(member, dtype, one_chip,
                                            compiled_kernels):
    """A member's ``compiled_dtypes`` say exactly which operand dtypes
    Mosaic compiles it for: the planner offers what compiles and never
    what the compiler refuses."""
    ip = FAMILIES[member.partition(".")[0]][member]
    fn, args = _member_args(member, 2, 16, 16, 32, jnp.dtype(dtype))
    specs = [jax.ShapeDtypeStruct(s, d, sharding=one_chip) for s, d in args]
    if ip.compiles(dtype):
        _compile(fn, *specs)
    else:
        with pytest.raises(Exception, match="Mosaic|not implemented"):
            jax.jit(fn).lower(*specs).compile()


def test_planner_never_offers_a_refused_rung(monkeypatch):
    """A pool site squeezed to its int8 rung runs in the interpreter,
    but on the TPU, where no pool member compiles int8, it is reported
    infeasible instead of failing at compile time."""
    from repro.core.ip import SiteSpec
    from repro.core.plan import clear_plan_cache
    spec = SiteSpec.make("pool", "pool2d", ((2, 30, 30, 16),), "float32",
                         ladder=(16, 8), window=(2, 2), mode="max")
    budget = ResourceBudget(vmem_bytes=200 * 1024)
    clear_plan_cache()
    assert plan_network((spec,), budget).sites[0].precision_bits == 8
    clear_plan_cache()
    monkeypatch.setattr(repro.kernels, "interpret", lambda: False)
    try:
        with pytest.raises(ValueError, match="no feasible"):
            plan_network((spec,), budget)
    finally:
        clear_plan_cache()


def test_resnet50_step_compiles(one_chip, compiled_kernels):
    """ResNet-50 v1.5 at 224 px and batch 4, served through the planner:
    the stem's 7x7/2 unit, the strided and padded 3x3s, the strided 1x1
    projections and every residual unit compile as ``res_conv`` kernels
    in one step, beside the padded 3x3/2 max pool."""
    from repro.models.frontends import ResidualNet, init_resnet
    from repro.runtime.server import serve_frontend
    params = jax.eval_shape(lambda: init_resnet(jax.random.PRNGKey(0)))
    net = ResidualNet()
    plan = plan_network(net.site_specs(params, IMAGE, "float32"),
                        ResourceBudget())
    x = jax.ShapeDtypeStruct(IMAGE, jnp.float32, sharding=one_chip)
    text = _compile(functools.partial(serve_frontend, net, network=plan),
                    _on(one_chip, params), x)
    assert text.count('custom_call_target="tpu_custom_call"') == 54
    assert text.count("%res_conv") >= 53
