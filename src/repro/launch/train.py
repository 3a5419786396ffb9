"""Fault-tolerant training driver.

    PYTHONPATH=src python -m repro.launch.train --arch olmo-1b --smoke \
        --steps 50 --batch 8 --seq 128 --ckpt-dir /tmp/ckpt

Wires together every substrate layer: sharded state (pjit), the
deterministic resumable data pipeline, async checkpointing with atomic
commit, watchdog + straggler monitoring, restore-on-start (elastic:
restores onto whatever mesh the surviving devices support), and
optional cross-pod gradient compression.  ``--simulate-failure N``
raises at step N to exercise the restart path end-to-end (used by the
tests; the *serving* restart path is demoed by
examples/elastic_restart.py).
"""
from __future__ import annotations

import argparse
import dataclasses
import sys
import time

import jax
import numpy as np

from repro.configs import get_config
from repro.data.pipeline import make_pipeline
from repro.distributed.sharding import (ShardingPolicy, batch_pspecs,
                                        state_pspecs, to_shardings)
from repro.launch.mesh import make_host_mesh, mesh_axis_sizes
from repro.models import api
from repro.models.frontends import input_specs
from repro.checkpoint import store
from repro.optim.adamw import AdamWConfig
from repro.runtime.fault_tolerance import StragglerMonitor, Watchdog


class SimulatedFailure(RuntimeError):
    pass


def build(cfg, opt_cfg, mesh, policy):
    state_abs = api.init_train_state_abstract(cfg, opt_cfg)
    sspec = state_pspecs(cfg, mesh, state_abs, policy)
    sshard = to_shardings(mesh, sspec)

    @jax.jit
    def init_fn(key):
        return api.init_train_state(cfg, opt_cfg, key)

    def make_state(key):
        with mesh:
            return jax.jit(init_fn, out_shardings=sshard)(key)

    step_fn = jax.jit(lambda s, b: api.train_step(cfg, opt_cfg, s, b),
                      donate_argnums=(0,))
    return make_state, step_fn, sshard


def train(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="olmo-1b")
    ap.add_argument("--smoke", action="store_true",
                    help="use the reduced same-family config")
    ap.add_argument("--steps", type=int, default=100)
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--seq", type=int, default=128)
    ap.add_argument("--lr", type=float, default=3e-4)
    ap.add_argument("--ckpt-dir", default="/tmp/repro_ckpt")
    ap.add_argument("--ckpt-every", type=int, default=20)
    ap.add_argument("--log-every", type=int, default=10)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--data-shards", type=int, default=1)
    ap.add_argument("--simulate-failure", type=int, default=-1)
    ap.add_argument("--watchdog-timeout", type=float, default=300.0)
    ap.add_argument("--d-model", type=int, default=0,
                    help="override width (e.g. to reach ~100M params)")
    ap.add_argument("--n-layers", type=int, default=0)
    args = ap.parse_args(argv)

    cfg = get_config(args.arch, smoke=args.smoke)
    over = {}
    if args.d_model:
        over.update(d_model=args.d_model,
                    d_ff=4 * args.d_model,
                    head_dim=args.d_model // cfg.n_heads)
    if args.n_layers:
        over.update(n_layers=args.n_layers)
    if over:
        cfg = dataclasses.replace(cfg, **over)
    opt_cfg = AdamWConfig(lr=args.lr, warmup_steps=max(args.steps // 20, 2),
                          total_steps=args.steps,
                          moment_dtype=cfg.moment_dtype)

    mesh = make_host_mesh(data=len(jax.devices()), model=1)
    sizes = mesh_axis_sizes(mesh)
    policy = ShardingPolicy(fsdp=cfg.fsdp)
    print(f"[train] arch={cfg.name} params={cfg.param_count():,} "
          f"mesh={sizes} ckpt={args.ckpt_dir}", flush=True)

    make_state, step_fn, sshard = build(cfg, opt_cfg, mesh, policy)

    # ---- restore or init -------------------------------------------------
    start_step = 0
    state_abs = api.init_train_state_abstract(cfg, opt_cfg)
    latest = store.latest_step(args.ckpt_dir)
    if latest is not None:
        state, extra = store.restore(args.ckpt_dir, state_abs,
                                     shardings=sshard)
        start_step = int(extra.get("next_step", latest))
        print(f"[train] restored step {latest} -> resuming at {start_step}",
              flush=True)
    else:
        state = make_state(jax.random.PRNGKey(args.seed))

    data = make_pipeline(cfg.vocab_size, args.seq, args.batch,
                         seed=args.seed, n_shards=args.data_shards)
    ckpt = store.AsyncCheckpointer(args.ckpt_dir)
    monitor = StragglerMonitor(
        on_straggler=lambda ev: print(
            f"[straggler] step {ev.step}: {ev.step_time:.3f}s "
            f"({ev.ratio:.1f}x ewma) -> rebalance hook", flush=True))
    dog = Watchdog(args.watchdog_timeout,
                   on_timeout=lambda: print("[watchdog] step timeout — "
                                            "restart from last checkpoint",
                                            flush=True)).start()

    losses = []
    try:
        for step in range(start_step, args.steps):
            if step == args.simulate_failure:
                raise SimulatedFailure(f"injected failure at step {step}")
            t0 = time.time()
            batch = data[step]
            with mesh:
                state, metrics = step_fn(state, batch)
            loss = float(metrics["loss"])
            dt = time.time() - t0
            dog.beat()
            monitor.record(step, dt)
            losses.append(loss)
            if step % args.log_every == 0 or step == args.steps - 1:
                print(f"[train] step {step:5d} loss {loss:8.4f} "
                      f"gnorm {float(metrics['grad_norm']):7.3f} "
                      f"lr {float(metrics['lr']):.2e} {dt*1e3:7.1f} ms",
                      flush=True)
            if step and step % args.ckpt_every == 0:
                ckpt.save(step, state, extra={"next_step": step + 1})
        ckpt.save(args.steps - 1, state, extra={"next_step": args.steps})
        ckpt.wait()
        dog.stop()
        print(f"[train] done. first loss {losses[0]:.4f} -> "
              f"last {losses[-1]:.4f} (events: "
              f"{len(monitor.events)} stragglers)", flush=True)
        return losses
    except SimulatedFailure as e:
        ckpt.wait()
        dog.stop()
        print(f"[train] FAILURE: {e} — relaunch me to resume from the last "
              f"committed checkpoint", flush=True)
        sys.exit(17)


if __name__ == "__main__":
    from repro.launch.cache import use_compile_cache
    use_compile_cache()
    train()
