#!/usr/bin/env python3
"""Serve a VGG-width CNN tenant on a TPU v5e with compiled kernels.

    python chip_smoke.py              # one chip
    python chip_smoke.py --chips 4    # the batch-sharded mesh path, 4 chips

The tenant is the repo's CNN frontend (``models/frontends.py``) at the
stage widths of VGG-16, configuration D (Simonyan & Zisserman, arXiv
1409.1556, Table 1): a 224x224x3 image, channels (3, 64, 128, 256, 512)
with one 3x3 conv -> 2x2 max pool -> relu block per stage (depth cut to
one conv per stage), projected to d_model 512; f32, random weights made
from ``--seed``.

One chip: 8 requests go through ``AdaptiveServer`` + ``SLOScheduler``
(``register`` / ``submit`` / ``run``) in batches of up to 4, after one
warm-up batch that compiles the step.  Every completion must be ``ok``,
every output must match a plain f32 reference (the ``kernels/*/ref.py``
oracles plus the projection, matmul precision "highest") within
``REL_TOL`` of its norm, and the served plan's frontend step, compiled
again as one program outside the server, must hold Pallas
(``tpu_custom_call``) kernels.  The server dispatches the same members
one jitted call at a time, so that program is a copy of what it runs,
not the executables it ran.

``--chips 4``: only the mesh path, ``AdaptiveServer(mesh=MeshSpec(
devices=4))``.  One batch of 4 is served under a plan that shards every
site over the batch at degree 4, and is compared with the same batch
served on one device.

The last line of stdout is ``{"ok": true, "device": {...}}``.  Without
a TPU the script exits non-zero before printing it.
"""
from __future__ import annotations

import argparse
import json
import sys
import time
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parent / "src"))

# VGG-16 configuration D stage widths, one conv per stage.
VGG = {"hw": 224, "channels": (3, 64, 128, 256, 512), "d_model": 512}
MAX_BATCH = 4
REQUESTS = 8
# Per request: ||served - reference|| / ||reference||.  Served path and
# reference are both f32 and differ only in summation order (~1e-6); a
# single bf16 MXU pass anywhere on the path gives ~1e-3 and fails.
REL_TOL = 1e-4
TENANT = "vgg"


def require(ok, message):
    """A failed check ends the smoke with ``message``."""
    if not ok:
        raise SystemExit(f"chip smoke failed: {message}")


def build(*, hw, channels, d_model, seed):
    """Random frontend weights and ``REQUESTS + MAX_BATCH`` images."""
    import jax
    from repro.models.frontends import init_cnn_frontend
    params = init_cnn_frontend(jax.random.PRNGKey(seed), channels=channels,
                               d_model=d_model)
    images = jax.random.normal(jax.random.PRNGKey(seed + 1),
                               (REQUESTS + MAX_BATCH, hw, hw, channels[0]))
    return params, images


def reference(params, images):
    """The frontend from the family oracles, in plain f32."""
    import jax
    import jax.numpy as jnp
    from repro.kernels.activation.ref import activation_ref
    from repro.kernels.conv2d.ref import conv2d_ref
    from repro.kernels.pool2d.ref import pool2d_ref

    @jax.jit
    def run(p, x):
        for bp in p["blocks"]:
            x = activation_ref(pool2d_ref(conv2d_ref(x, bp["w"])), kind="relu")
        b, h, w, c = x.shape
        return jnp.einsum("bsc,cd->bsd", x.reshape(b, h * w, c), p["proj"])

    with jax.default_matmul_precision("highest"):
        return run(params, images)


def rel_errors(outputs, ref):
    import numpy as np
    errs = []
    for y, r in zip(outputs, ref):
        y, r = np.asarray(y, np.float64), np.asarray(r, np.float64)
        errs.append(float(np.linalg.norm(y - r) / np.linalg.norm(r)))
    return errs


def serve(params, images, *, warmup=None, mesh=None):
    """Register the tenant, serve the ``warmup`` batch if one is given
    (it compiles the step), then ``images`` through the SLO scheduler.
    Returns the server, the completions in request order, the warm-up
    seconds and the per-request wall latencies."""
    import jax
    from repro.runtime import AdaptiveServer, SLOScheduler, SLOSpec
    server = AdaptiveServer(max_batch=MAX_BATCH, mesh=mesh)
    sched = SLOScheduler(server)
    sched.register(TENANT, params, images.shape[1:],
                   slo=SLOSpec(deadline_s=600.0))
    warm_s = 0.0
    if warmup is not None:
        t0 = time.perf_counter()
        sched.submit(TENANT, warmup)
        warm = sched.run()
        jax.block_until_ready([c.result for c in warm])
        warm_s = time.perf_counter() - t0
        require(len(warm) == len(warmup) and all(c.ok for c in warm),
                "warm-up batch failed")
    tel = server.tenants[TENANT].telemetry
    seen = len(tel.wall_latencies)
    rids = sched.submit(TENANT, images)
    comps = {c.rid: c for c in sched.run()}
    missing = [r for r in rids if r not in comps]
    require(not missing, f"requests without a completion: {missing}")
    return (server, [comps[r] for r in rids], warm_s,
            list(tel.wall_latencies)[seen:])


def compiled_step(server, params, x):
    """Compile the tenant's planned frontend step for ``x`` as one
    program, apart from the server's own dispatch.  Returns (compile
    seconds, HLO text)."""
    import functools
    import jax
    from repro.runtime.server import serve_frontend
    tenant = server.tenants[TENANT]
    plan = server.plan_for(TENANT, x.shape[0])
    step = jax.jit(functools.partial(serve_frontend, tenant.net,
                                     network=plan))
    t0 = time.perf_counter()
    compiled = step.lower(params, x).compile()
    return time.perf_counter() - t0, compiled.as_text()


def check_outputs(done, ref, name):
    bad = [c.rid for c in done if not c.ok]
    require(not bad, f"{name}: completions not ok: {bad}")
    errs = rel_errors([c.result for c in done], ref)
    print(f"{name}: {len(done)} completions ok; max relative error "
          f"{max(errs):.3e} (tolerance {REL_TOL:g})", flush=True)
    require(max(errs) <= REL_TOL,
            f"{name}: error {max(errs):.3e} > {REL_TOL}")
    return errs


def run_one_chip(model, seed):
    """The one-chip smoke; returns the max relative error."""
    params, images = build(seed=seed, **model)
    imgs = images[MAX_BATCH:]
    server, done, warm_s, walls = serve(params, imgs,
                                        warmup=images[:MAX_BATCH])
    print("plan (batch 4):", flush=True)
    print(server.plan_for(TENANT, MAX_BATCH).describe(), flush=True)
    print(f"warm-up batch of {MAX_BATCH} (compiles the served kernels): "
          f"{warm_s:.3f} s", flush=True)
    for c, w in zip(done, walls):
        print(f"request {c.rid}: batch {c.batch_size}, wall latency "
              f"{w:.6f} s, ok={c.ok}", flush=True)
    errs = check_outputs(done, reference(params, imgs), "one device")
    compile_s, hlo = compiled_step(server, params, imgs[:MAX_BATCH])
    kernels = hlo.count("tpu_custom_call")
    print(f"served plan's step, compiled again as one program: "
          f"{compile_s:.3f} s; tpu_custom_call sites in its HLO: {kernels}",
          flush=True)
    require(kernels > 0, "the compiled step holds no Pallas kernels")
    return max(errs)


def run_mesh(model, seed, chips):
    """The mesh path: one batch sharded over ``chips`` devices, compared
    with the same batch served on one device."""
    import jax
    from repro.core.resources import MeshSpec
    params, images = build(seed=seed, **model)
    imgs = images[:MAX_BATCH]
    _, single, _, _ = serve(params, imgs)
    server, done, _, walls = serve(params, imgs,
                                   mesh=MeshSpec(devices=chips))
    plan = server.plan_for(TENANT, MAX_BATCH)
    print(f"mesh plan (batch {MAX_BATCH}, {chips} devices):", flush=True)
    print(plan.describe(), flush=True)
    layouts = {(s.shard_axis, s.shard_degree) for s in plan.sites}
    require(layouts == {("batch", chips)},
            f"plan not batch x{chips}: {layouts}")
    devices = set()
    for c, w in zip(done, walls):
        devices |= set(c.result.sharding.device_set)
        print(f"request {c.rid}: wall latency {w:.6f} s (first batch, "
              f"compile included), ok={c.ok}", flush=True)
    print(f"outputs live on {len(devices)} devices", flush=True)
    require(len(devices) == chips, f"outputs span {len(devices)} devices")
    check_outputs(done, reference(params, imgs), f"{chips} devices")
    errs = rel_errors([c.result for c in done], [c.result for c in single])
    print(f"{chips}-device vs one-device max relative difference "
          f"{max(errs):.3e}", flush=True)
    require(max(errs) <= REL_TOL, f"{chips} devices differ from one")
    return max(errs)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--chips", type=int, default=1, choices=(1, 4),
                    help="4 runs only the batch-sharded mesh path")
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args(argv)

    import jax
    if jax.default_backend() != "tpu":
        print(f"no TPU: JAX's default backend is {jax.default_backend()!r}",
              file=sys.stderr)
        return 2
    devices = jax.devices()
    if len(devices) < args.chips:
        print(f"--chips {args.chips} but JAX sees {len(devices)} device(s)",
              file=sys.stderr)
        return 2
    from repro.launch.cache import use_compile_cache
    print(f"compile cache: {use_compile_cache()}", flush=True)
    print(f"device: {devices[0].device_kind} x{len(devices)}", flush=True)
    if args.chips == 1:
        run_one_chip(VGG, args.seed)
    else:
        run_mesh(VGG, args.seed, args.chips)
    print(json.dumps({"ok": True, "device": {
        "platform": devices[0].platform, "kind": devices[0].device_kind,
        "count": len(devices)}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
