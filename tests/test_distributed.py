"""Distributed semantics on 8 placeholder devices — each case runs in a
subprocess so the 8-device XLA flag never leaks into other tests."""
import os
import subprocess
import sys
import textwrap
from pathlib import Path

import pytest

REPO = Path(__file__).resolve().parent.parent


def run_sub(body: str, n_dev: int = 8, timeout: int = 420) -> str:
    code = textwrap.dedent(f"""
        import os
        os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count={n_dev}"
        import jax
        import jax.numpy as jnp
        import numpy as np
        from jax.sharding import Mesh, NamedSharding, PartitionSpec as P
    """) + textwrap.dedent(body)
    env = dict(os.environ,
               PYTHONPATH=str(REPO / "src") + os.pathsep
               + os.environ.get("PYTHONPATH", ""))
    out = subprocess.run([sys.executable, "-c", code], capture_output=True,
                         text=True, timeout=timeout, env=env)
    assert out.returncode == 0, f"STDOUT:\n{out.stdout}\nSTDERR:\n{out.stderr}"
    return out.stdout


def test_ring_all_reduce_equals_psum():
    run_sub("""
        from jax import shard_map
        from repro.distributed.collectives import ring_all_reduce
        mesh = jax.make_mesh((8,), ("data",))
        x = jnp.arange(8 * 6, dtype=jnp.float32).reshape(8, 6)

        def ring(xl):
            return ring_all_reduce(xl, "data")

        def ref(xl):
            return jax.lax.psum(xl, "data")

        got = shard_map(ring, mesh=mesh, in_specs=P("data"),
                        out_specs=P("data"), check_vma=False)(x)
        want = shard_map(ref, mesh=mesh, in_specs=P("data"),
                         out_specs=P("data"), check_vma=False)(x)
        np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                                   rtol=1e-6)
        # odd payload size exercises the padding path
        y = jnp.arange(8 * 7, dtype=jnp.float32).reshape(8, 7)
        got = shard_map(ring, mesh=mesh, in_specs=P("data"),
                        out_specs=P("data"), check_vma=False)(y)
        want = shard_map(ref, mesh=mesh, in_specs=P("data"),
                         out_specs=P("data"), check_vma=False)(y)
        np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                                   rtol=1e-6)
        print("ring OK")
    """)


def test_bucketed_psum_matches_fused():
    run_sub("""
        from jax import shard_map
        from repro.distributed.collectives import bucketed_psum
        mesh = jax.make_mesh((8,), ("data",))
        tree = {"a": jnp.ones((8, 4)), "b": jnp.arange(8.0).reshape(8, 1),
                "c": {"d": jnp.full((8, 3), 2.0)}}

        def f(t):
            return bucketed_psum(t, "data", n_buckets=2)

        got = shard_map(f, mesh=mesh, in_specs=P("data"),
                        out_specs=P("data"), check_vma=False)(tree)
        want = shard_map(lambda t: jax.tree.map(
                             lambda x: jax.lax.psum(x, "data"), t),
                         mesh=mesh, in_specs=P("data"),
                         out_specs=P("data"), check_vma=False)(tree)
        for g, w in zip(jax.tree.leaves(got), jax.tree.leaves(want)):
            np.testing.assert_allclose(np.asarray(g), np.asarray(w))
        print("bucketed OK")
    """)


def test_gpipe_pipeline_forward():
    run_sub("""
        from repro.distributed.pipeline import gpipe_forward
        mesh = jax.make_mesh((4,), ("pipe",))
        # 4 stages, each y = x @ W_s (W_s = (s+1) * I), so pipeline
        # output = x * 1*2*3*4 = 24 x
        eye = jnp.eye(4)
        params = jnp.stack([eye * (s + 1) for s in range(4)])

        def stage(w, x):
            return x @ w

        fn = gpipe_forward(stage, mesh, axis="pipe")
        x_micro = jnp.arange(6 * 2 * 4, dtype=jnp.float32).reshape(6, 2, 4)
        out = jax.jit(fn)(params, x_micro)
        np.testing.assert_allclose(np.asarray(out), np.asarray(x_micro) * 24,
                                   rtol=1e-5)
        print("gpipe OK")
    """)


def test_ring_all_reduce_padding_and_dtypes():
    """Edge cases of the explicit ring: payloads where x.size % n != 0
    (the padding path), a 1-device axis (identity), and integer dtypes —
    int sums are associative, so ring and psum must agree BIT-exactly."""
    run_sub("""
        from jax import shard_map
        from repro.distributed.collectives import ring_all_reduce

        def both(mesh, axis, x):
            ring = shard_map(lambda v: ring_all_reduce(v, axis), mesh=mesh,
                             in_specs=P(axis), out_specs=P(axis),
                             check_vma=False)(x)
            ref = shard_map(lambda v: jax.lax.psum(v, axis), mesh=mesh,
                            in_specs=P(axis), out_specs=P(axis),
                            check_vma=False)(x)
            return np.asarray(ring), np.asarray(ref)

        mesh8 = jax.make_mesh((8,), ("data",))
        # per-device payload 3*5 = 15 elements: 15 % 8 != 0 pads by 1
        xi = jnp.arange(8 * 3 * 5, dtype=jnp.int32).reshape(8, 3, 5)
        g, w = both(mesh8, "data", xi)
        np.testing.assert_array_equal(g, w)          # bit-exact (ints)
        # payload smaller than the axis: 3 % 8 != 0 pads by 5
        xs = jnp.arange(8 * 3, dtype=jnp.int32).reshape(8, 3)
        g, w = both(mesh8, "data", xs)
        np.testing.assert_array_equal(g, w)
        # float with the padding path engaged: same sum up to order
        xf = jnp.linspace(-3, 3, 8 * 7).reshape(8, 7).astype(jnp.float32)
        g, w = both(mesh8, "data", xf)
        np.testing.assert_allclose(g, w, rtol=1e-6, atol=1e-5)
        # n == 1: the ring is the identity and must equal psum bit-exact
        mesh1 = Mesh(np.array(jax.devices()[:1]), ("one",))
        x1 = jnp.linspace(0, 1, 10).reshape(2, 5).astype(jnp.float32)
        g, w = both(mesh1, "one", x1)
        np.testing.assert_array_equal(g, w)
        np.testing.assert_array_equal(g, np.asarray(x1))
        print("ring edges OK")
    """)


def test_gpipe_fill_drain_vs_sequential():
    """Fill+drain schedule against a per-microbatch sequential reference,
    with a stage fn whose f(0) != 0 — stale fill/drain ticks compute on
    zero buffers, and only an explicit validity mask keeps their output
    out of the handoff ring."""
    run_sub("""
        from repro.distributed.pipeline import gpipe_forward
        n_stages = 4
        mesh = jax.make_mesh((n_stages,), ("pipe",))
        rng = np.random.default_rng(0)
        params = jnp.asarray(rng.normal(0, 0.5, (n_stages, 4, 4))
                             .astype(np.float32))

        def stage(w, x):
            # f(0) = 1 != 0: an unmasked drain tick would inject ones
            return x @ w + 1.0

        fn = jax.jit(gpipe_forward(stage, mesh, axis="pipe"))
        for n_micro in (1, 5, 6):
            x_micro = jnp.asarray(
                rng.normal(size=(n_micro, 2, 4)).astype(np.float32))
            ref = x_micro
            for s in range(n_stages):
                ref = jnp.einsum("mbi,ij->mbj", ref, params[s]) + 1.0
            out = fn(params, x_micro)
            np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                                       rtol=1e-5, atol=1e-6)
        print("gpipe fill+drain OK")
    """)


def test_sharded_train_step_matches_single_device():
    """The pjit'd train step on a 4x2 mesh computes the same loss as the
    unsharded step (up to float tolerance) — DP+TP correctness."""
    run_sub("""
        from repro.configs import get_config
        from repro.configs.base import ShapeConfig
        from repro.distributed.sharding import (ShardingPolicy, batch_pspecs,
                                                state_pspecs, to_shardings)
        from repro.models import api
        from repro.models.frontends import make_inputs
        from repro.optim.adamw import AdamWConfig

        cfg = get_config("chatglm3-6b", smoke=True)
        opt = AdamWConfig(warmup_steps=2, total_steps=10)
        shape = ShapeConfig("t", 32, 8, "train")
        batch = make_inputs(cfg, shape, abstract=False)
        state = api.init_train_state(cfg, opt, jax.random.PRNGKey(0))
        _, m_ref = jax.jit(lambda s, b: api.train_step(cfg, opt, s, b))(
            state, batch)

        mesh = jax.make_mesh((4, 2), ("data", "model"))
        policy = ShardingPolicy()
        sspec = state_pspecs(cfg, mesh, state, policy)
        bspec = batch_pspecs(cfg, mesh, batch)
        with mesh:
            st_sh = jax.device_put(state, to_shardings(mesh, sspec))
            b_sh = jax.device_put(batch, to_shardings(mesh, bspec))
            new_state, m = jax.jit(
                lambda s, b: api.train_step(cfg, opt, s, b),
                in_shardings=(to_shardings(mesh, sspec),
                              to_shardings(mesh, bspec)))(st_sh, b_sh)
        assert abs(float(m["loss"]) - float(m_ref["loss"])) < 1e-3, (
            float(m["loss"]), float(m_ref["loss"]))
        print("sharded train OK", float(m["loss"]))
    """)


def test_fsdp_sharded_state_fits_and_runs():
    run_sub("""
        from repro.configs import get_config
        from repro.configs.base import ShapeConfig
        from repro.distributed.sharding import (ShardingPolicy, batch_pspecs,
                                                state_pspecs, to_shardings)
        from repro.models import api
        from repro.models.frontends import make_inputs
        from repro.optim.adamw import AdamWConfig
        import dataclasses

        cfg = get_config("llama3.2-1b", smoke=True)
        cfg = dataclasses.replace(cfg, d_model=128, d_ff=512, head_dim=16,
                                  fsdp=True)
        opt = AdamWConfig(warmup_steps=2, total_steps=10)
        shape = ShapeConfig("t", 32, 8, "train")
        batch = make_inputs(cfg, shape, abstract=False)
        state = api.init_train_state(cfg, opt, jax.random.PRNGKey(0))
        mesh = jax.make_mesh((8, 1), ("data", "model"))
        policy = ShardingPolicy(fsdp=True)
        sspec = state_pspecs(cfg, mesh, state, policy)
        with mesh:
            st_sh = jax.device_put(state, to_shardings(mesh, sspec))
            # big leaves actually sharded over data
            emb = st_sh.params["embed"]
            assert len(emb.sharding.device_set) == 8, emb.sharding
            _, m = jax.jit(lambda s, b: api.train_step(cfg, opt, s, b))(
                st_sh, batch)
        assert np.isfinite(float(m["loss"]))
        print("fsdp OK", float(m["loss"]))
    """)


def test_elastic_remesh_restore():
    """Save under a 4x2 mesh, restore under 3x2 (simulating a lost
    host) — the checkpoint reshards onto the surviving devices."""
    run_sub("""
        import tempfile
        from repro.checkpoint import store
        from repro.configs import get_config
        from repro.distributed.sharding import (ShardingPolicy, state_pspecs,
                                                to_shardings)
        from repro.models import api
        from repro.optim.adamw import AdamWConfig
        from repro.runtime.fault_tolerance import elastic_remesh

        cfg = get_config("olmo-1b", smoke=True)
        opt = AdamWConfig()
        state = api.init_train_state(cfg, opt, jax.random.PRNGKey(0))
        mesh1 = jax.make_mesh((4, 2), ("data", "model"))
        sspec1 = state_pspecs(cfg, mesh1, state, ShardingPolicy())
        st1 = jax.device_put(state, to_shardings(mesh1, sspec1))
        d = tempfile.mkdtemp()
        store.save(d, 3, st1, extra={"next_step": 4})

        # 2 devices died: remesh over 6
        mesh2 = elastic_remesh(6, prefer_model=2)
        assert mesh2.devices.size == 6
        sspec2 = state_pspecs(cfg, mesh2, state, ShardingPolicy())
        restored, extra = store.restore(
            d, state, shardings=to_shardings(mesh2, sspec2))
        assert extra["next_step"] == 4
        for a, b in zip(jax.tree.leaves(st1), jax.tree.leaves(restored)):
            np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
        print("elastic OK")
    """)


def test_dryrun_cells_tiny_mesh():
    """End-to-end dry-run machinery on an 8-device mesh: one train cell
    and one decode cell must lower+compile with coherent shardings."""
    run_sub("""
        import repro.launch.mesh as mesh_mod
        # monkeypatch the production mesh down to 4x2 for this test
        mesh_mod.make_production_mesh = \
            lambda multi_pod=False: jax.make_mesh(
                (2, 2, 2) if multi_pod else (4, 2),
                ("pod", "data", "model") if multi_pod else ("data", "model"))
        import repro.launch.dryrun as dr
        dr.make_production_mesh = mesh_mod.make_production_mesh
        import dataclasses, json, tempfile
        from pathlib import Path
        import repro.configs as C
        # shrink shapes so the tiny mesh compiles fast
        C.SHAPES["train_4k"] = dataclasses.replace(
            C.SHAPES["train_4k"], seq_len=64, global_batch=8)
        C.SHAPES["decode_32k"] = dataclasses.replace(
            C.SHAPES["decode_32k"], seq_len=128, global_batch=8)
        dr.SHAPES = C.SHAPES
        out = Path(tempfile.mkdtemp())
        for shape in ("train_4k", "decode_32k"):
            for multi in (False, True):
                rec = dr.run_cell("olmo-1b", shape, multi, out,
                                  force=True, calibrate=False)
                assert rec["status"] == "ok", rec.get("error")
        print("dryrun tiny OK")
    """, timeout=420)
