"""The residual network on the planned path, on the CPU (interpreted
kernels): the strided and padded conv members and the fused ``res_conv``
member against ``lax.conv_general_dilated``, the fusion pass's residual
groups, and the network description a tenant registers.  The served
network against the benchmark's reference is in
``tests/bench/test_bench_resnet.py``.
"""
import functools
import json
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax import lax

from repro.core.plan import _fusion_groups, plan_network
from repro.core.resources import MeshSpec
from repro.kernels.conv2d.ops import conv2d
from repro.kernels.fused.res_conv import res_conv
from repro.models.frontends import (ChainNet, ResidualNet, init_resnet,
                                    network_from_dict, resnet_site_specs)
from repro.obs.metrics import planned_site_counts, system_metrics
from repro.runtime import AdaptiveServer


def _ref(x, w, b, r, stride, padding, relu):
    y = lax.conv_general_dilated(
        x, w, (stride, stride), ((padding, padding), (padding, padding)),
        dimension_numbers=("NHWC", "HWIO", "NHWC"),
        precision=lax.Precision.HIGHEST) + b
    if r is not None:
        y = y + r
    return jnp.maximum(y, 0.0) if relu else y


CASES = [  # (n, h, cin, k, cout, stride, padding, residual, relu)
    (1, 9, 4, 3, 8, 1, 1, True, True),
    (1, 10, 4, 3, 8, 2, 1, False, True),
    (1, 11, 3, 7, 8, 2, 3, False, True),
    (1, 8, 16, 1, 8, 2, 0, False, False),
    (1, 7, 8, 1, 16, 1, 0, True, True),
    # the flat 1x1 body: 7x7 blocks, whose 49 pixels are no multiple of 8
    (1, 7, 8, 1, 16, 1, 0, False, True),
    (3, 7, 8, 1, 16, 1, 0, True, False),
    # Cin over one lane tile
    (1, 7, 256, 1, 8, 1, 0, True, True),
    # three Cout tiles, the input resident across them
    (1, 7, 16, 1, 384, 1, 0, True, True),
    # three Cout tiles over four row blocks, the weights resident
    (2, 10, 8, 1, 384, 1, 0, False, False),
    # a strided projection on an odd input (7 -> 4)
    (2, 7, 16, 1, 32, 2, 0, False, False),
    (1, 7, 16, 1, 32, 2, 0, True, True),
]


@pytest.mark.parametrize("case", CASES)
def test_res_conv_matches_conv_bias_add_relu(case):
    n, h, cin, k, cout, stride, padding, residual, relu = case
    keys = jax.random.split(jax.random.PRNGKey(sum(case)), 4)
    x = jax.random.normal(keys[0], (n, h, h, cin))
    w = jax.random.normal(keys[1], (k, k, cin, cout))
    b = jax.random.normal(keys[2], (cout,))
    ho = (h + 2 * padding - k) // stride + 1
    r = jax.random.normal(keys[3], (n, ho, ho, cout)) if residual else None
    got = res_conv(x, w, b, r, stride=stride, padding=padding, relu=relu)
    want = _ref(x, w, b, r, stride, padding, relu)
    assert got.shape == want.shape
    np.testing.assert_allclose(got, want, rtol=1e-5, atol=2e-5)


def test_flat_unit_is_priced_by_its_grid_order():
    """A 1x1 unit's bytes follow the grid order the flat body takes: the
    input resident across the Cout tiles (weights read per row block),
    or the weights resident (input read per Cout tile), whichever moves
    fewer bytes; its MXU cycles are one dot per grid step."""
    from repro.core.resources import cost_cycles, mxu_pass_cycles
    from repro.kernels.conv2d.inner import conv_mxu_cycles
    from repro.kernels.fused.res_conv import _hbm, footprint

    def hbm(n, h, cin, cout):
        return _hbm(n, h, h, cin, 1, 1, cout, 1, 0, itemsize=4,
                    block_cout=128, residual=False)
    out = 16 * 16 * 256 * 4
    # 2 row blocks of 8 rows, 2 Cout tiles: the input resident
    assert hbm(1, 16, 8, 256) == (16 * 16 * 8 + 2 * 8 * 256) * 4 \
        + 256 * 4 + out
    # 4 row blocks of 5 rows, 3 Cout tiles: the weights resident
    out = 2 * 10 * 10 * 384 * 4
    assert hbm(2, 10, 8, 384) == (3 * 2 * 10 * 10 * 8 + 8 * 384) * 4 \
        + 384 * 4 + out
    # one (7 rows x 32, 512) x (512, 128) dot a step, 4 row blocks x 4
    # images, 6 bf16 passes at HIGHEST: under a third of the row body's
    # 112 dots of one (32, 512) row each
    flat = 16 * 6 * mxu_pass_cycles(224, 512, 128)
    assert cost_cycles(flat, hbm(4, 28, 512, 128)) == footprint(
        4, 28, 28, 512, 1, 1, 128).est_cycles
    assert 3 * flat < conv_mxu_cycles(4, 28, 28, 512, 1, 1, 128,
                                      itemsize=4, block_cout=128)


def _pallas_calls(jaxpr):
    """The ``name`` of every ``pallas_call`` in ``jaxpr`` and the jaxprs
    it calls."""
    names = []
    for eqn in jaxpr.eqns:
        if eqn.primitive.name == "pallas_call":
            names.append(str(eqn.params["name"]))
            continue
        for v in eqn.params.values():
            for sub in v if isinstance(v, (tuple, list)) else (v,):
                sub = getattr(sub, "jaxpr", sub)
                if hasattr(sub, "eqns"):
                    names += _pallas_calls(sub)
    return names


def test_served_step_runs_each_1x1_unit_on_the_flat_body(small_params):
    """The small ResNet's served step holds one ``res_conv_1x1`` call
    per 1x1 unit and the row body for the rest; the chain steps hold
    none."""
    from repro.models.frontends import (cnn_frontend_site_specs,
                                        init_cnn_frontend)
    from repro.runtime.server import serve_frontend
    shape = (2, 32, 32, 3)
    net = ResidualNet()
    plan = plan_network(net.site_specs(small_params, shape, "float32"))
    names = _pallas_calls(jax.make_jaxpr(functools.partial(
        serve_frontend, net, network=plan))(
            small_params, jax.ShapeDtypeStruct(shape, jnp.float32)).jaxpr)
    # 5 blocks: c1 and c3 each, and the 4 projections, are 1x1; the stem
    # and the 5 c2 are not
    assert names.count("res_conv_1x1") == 5 * 2 + 4
    assert names.count("res_conv") == 1 + 5
    # the benchmark's VGG-16 (configuration D) and LeNet-5 chains
    configs = Path(__file__).resolve().parents[1] / "bench" / "configs"
    for name in ("vgg16_d", "lenet5"):
        cfg = json.loads((configs / f"{name}.json").read_text())
        params = jax.eval_shape(lambda: init_cnn_frontend(
            jax.random.PRNGKey(0), channels=tuple(cfg["channels"]),
            k=cfg["kernel"], d_model=cfg["d_model"]))
        chain = ChainNet(tuple(cfg["pool_window"]), cfg["activation"])
        batch = (4, *cfg["image"])
        plan = plan_network(cnn_frontend_site_specs(
            params, batch, jnp.float32, pool_window=chain.pool_window,
            activation=chain.activation))
        names = _pallas_calls(jax.make_jaxpr(functools.partial(
            serve_frontend, chain, network=plan))(
                params, jax.ShapeDtypeStruct(batch, jnp.float32)).jaxpr)
        assert names and "res_conv_1x1" not in names, name


@pytest.mark.parametrize("ip", ["ip1_vpu", "ip2_mxu"])
@pytest.mark.parametrize("stride,padding", [(1, 0), (1, 1), (2, 1), (2, 3)])
def test_strided_padded_conv_members_match_lax(ip, stride, padding):
    keys = jax.random.split(jax.random.PRNGKey(stride + 7 * padding), 2)
    x = jax.random.normal(keys[0], (2, 11, 11, 3))
    w = jax.random.normal(keys[1], (3, 3, 3, 8))
    got = conv2d(x, w, ip=ip, stride=stride, padding=padding)
    want = _ref(x, w, 0.0, None, stride, padding, relu=False)
    assert got.shape == want.shape
    np.testing.assert_allclose(got, want, rtol=1e-5, atol=2e-5)


@pytest.fixture(scope="module")
def small_params():
    return jax.eval_shape(lambda: init_resnet(
        jax.random.PRNGKey(0), stem=4, widths=(4, 8, 16, 32),
        blocks=(2, 1, 1, 1), classes=10))


def test_fusion_pass_forms_residual_groups(small_params):
    specs = tuple(resnet_site_specs(small_params, (2, 32, 32, 3),
                                    jnp.float32))
    groups = {specs[start].name: tuple(s.family
                                       for s in specs[start:start + ln])
              for start, ln, _ in _fusion_groups(specs)}
    assert groups["s0b1.c3.conv"] == ("conv2d", "add", "activation")
    assert groups["s0b1.c1.conv"] == ("conv2d", "activation")
    assert groups["stem.conv"] == ("conv2d", "activation")
    assert groups["s1b0.proj.conv"] == ("conv2d",)
    # every conv is in a group; the pool is not
    convs = [s.name for s in specs if s.family == "conv2d"]
    assert sorted(groups) == sorted(convs)


def test_every_conv_plans_onto_res_conv(small_params):
    plan = plan_network(resnet_site_specs(small_params, (2, 32, 32, 3),
                                          jnp.float32))
    members = [s.ip.name for s in plan.sites]
    convs = sum(1 for s in plan.sites if s.spec.family == "res_conv")
    # stem, 5 blocks of 3 convs, 4 projections
    assert convs == 1 + 5 * 3 + 4
    assert members.count("res_conv.res_mxu") == convs
    assert plan.site("s0b1.c3.fused").spec.knob("residual") is True
    assert plan.site("s1b0.proj.fused").spec.knob("relu") is False
    assert plan.total_launches == len(plan.sites) == convs + 1


def test_planned_sites_gauge_counts_each_tenants_plan_per_batch(
        small_params):
    """The gauge holds, per tenant and batch size, the sites of the plan
    that batch size last ran; their sum is the plan's launches."""
    from repro.models.frontends import init_cnn_frontend
    srv = AdaptiveServer(max_batch=2)
    srv.register("r", small_params, (32, 32, 3), net=ResidualNet())
    srv.register("c", jax.eval_shape(lambda: init_cnn_frontend(
        jax.random.PRNGKey(0), channels=(3, 4), d_model=8)), (12, 12, 3))
    srv._rebalance()                    # grant both tenants a share
    plans = {(t, b): srv.plan_for(t, b) for t in ("r", "c") for b in (1, 2)}
    text = system_metrics(srv).render()
    for (tenant, batch), plan in plans.items():
        counts = planned_site_counts(plan)
        assert sum(counts.values()) == plan.total_launches
        for (family, member, fused), n in counts.items():
            assert (f'repro_planned_sites{{batch="{batch}",family="{family}",'
                    f'fused="{str(fused).lower()}",member="{member}",'
                    f'tenant="{tenant}"}} {n}') in text
    assert planned_site_counts(plans["r", 2])[
        ("res_conv", "res_conv.res_mxu", True)] == 1 + 5 * 3 + 4
    assert planned_site_counts(plans["c", 2]) == {
        ("cnn_fused", plans["c", 2].sites[0].ip.name, True): 1}


def test_network_descriptions_round_trip():
    for net in (ChainNet((3, 3), "tanh"), ResidualNet((1, 2, 2, 2)),
                ResidualNet((1, 2), stem_stride=1, stem_padding=1,
                            pool_window=2, pool_stride=2, pool_padding=0)):
        assert network_from_dict(json.loads(json.dumps(net.to_dict()))) == net
    with pytest.raises(ValueError):
        network_from_dict({"kind": "transformer"})


def test_residual_net_geometry_shapes_the_sites(small_params):
    """The stem's stride and padding and its pool's window, stride and
    padding come from the description."""
    def stem_shapes(net):
        specs = {s.name: s for s in net.site_specs(
            small_params, (1, 32, 32, 3), "float32")}
        pool = specs["stem.pool"]
        return (pool.shapes[0], pool.knob("window"), pool.knob("padding"),
                specs["s0b0.c1.conv"].shapes[0])
    assert stem_shapes(ResidualNet()) == (
        (1, 16, 16, 4), (3, 3), 1, (1, 8, 8, 4))
    assert stem_shapes(ResidualNet(stem_stride=1, stem_padding=2,
                                   pool_window=2, pool_padding=0)) == (
        (1, 30, 30, 4), (2, 2), 0, (1, 15, 15, 4))


def test_residual_net_takes_no_ladder_and_no_mesh(small_params):
    with pytest.raises(ValueError, match="native width"):
        ResidualNet().site_specs(small_params, (1, 32, 32, 3), "float32",
                                 (16, 8))
    srv = AdaptiveServer(max_batch=2, mesh=MeshSpec(devices=2))
    with pytest.raises(ValueError, match="one device"):
        srv.register("r", small_params, (32, 32, 3), net=ResidualNet())


def test_unfused_residual_graph_matches_the_fused_one():
    """``fuse=False`` runs every unit on the conv member, the bias in XLA,
    the ``add`` member and the activation member: the same logits as the
    ``res_conv`` plan."""
    from repro.models.frontends import apply_resnet_frontend
    # two stages: an identity and a projection shortcut, one of them
    # strided
    params = init_resnet(jax.random.PRNGKey(3), stem=4, widths=(4, 8),
                         blocks=(2, 1), classes=5)
    x = jax.random.normal(jax.random.PRNGKey(4), (1, 16, 16, 3))
    unfused_specs = resnet_site_specs(params, x.shape, x.dtype)
    plan = plan_network(unfused_specs, fuse=False)
    assert {s.ip.name for s in plan.sites} == {
        "conv2d.ip2_mxu", "activation.act_vpu", "add.add_vpu",
        "pool2d.pool_vpu"}
    fused = plan_network(unfused_specs)
    run = jax.jit(apply_resnet_frontend, static_argnames="network")
    np.testing.assert_allclose(run(params, x, network=plan),
                               run(params, x, network=fused),
                               rtol=1e-5, atol=1e-6)
