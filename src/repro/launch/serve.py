"""Batched serving driver: prefill + decode with continuous batching.

    PYTHONPATH=src python -m repro.launch.serve --arch olmo-1b --smoke \
        --requests 12 --max-new 16

A minimal but real serving loop: a request queue feeds fixed-slot
batches; prefill fills a slot's KV cache (padded to max_len so decode
appends in place), decode advances all live slots one token per tick,
finished slots are immediately refilled from the queue (continuous
batching).  Greedy sampling; per-slot position bookkeeping.

Note on slot caches: decode_step takes the *batched* cache; a slot's
prefill writes its rows via dynamic_update_slice on the batch dim.
"""
from __future__ import annotations

import argparse
import time
from typing import List, Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs import get_config
from repro.models import api


class Request:
    def __init__(self, rid: int, prompt: np.ndarray, max_new: int):
        self.rid = rid
        self.prompt = prompt
        self.max_new = max_new
        self.generated: List[int] = []
        self.done = False


def serve(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="olmo-1b")
    ap.add_argument("--smoke", action="store_true")
    ap.add_argument("--slots", type=int, default=4)
    ap.add_argument("--requests", type=int, default=12)
    ap.add_argument("--prompt-len", type=int, default=16)
    ap.add_argument("--max-new", type=int, default=16)
    ap.add_argument("--max-len", type=int, default=64)
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args(argv)

    cfg = get_config(args.arch, smoke=args.smoke)
    if cfg.embed_inputs:
        raise SystemExit("serve.py drives token-in archs; use examples for "
                         "stub-frontend archs")
    rng = np.random.default_rng(args.seed)
    params = api.init_params(cfg, jax.random.PRNGKey(args.seed))

    B, L = args.slots, args.max_len

    prefill_one = jax.jit(lambda p, b: api.prefill_step(cfg, p, b,
                                                        pad_to=L))
    decode_fn = jax.jit(lambda p, c, t, i: api.decode_step(cfg, p, c, t, i))

    # Batched slot cache (B slots); per-slot prefill writes its row.
    caches = api.init_decode_caches(cfg, B, L)

    def write_slot(caches, slot_cache, slot: int):
        """Insert a 1-row prefill cache into slot `slot` of the batch."""
        def upd(c, s):
            if c.ndim != s.ndim:
                return c
            pad = [(0, 0)] * s.ndim
            if s.shape[2 if s.ndim >= 3 else 1] != c.shape[2 if c.ndim >= 3 else 1] \
               and s.ndim >= 3:
                pad[2] = (0, c.shape[2] - s.shape[2])
                s = jnp.pad(s, pad)
            return jax.lax.dynamic_update_slice_in_dim(c, s.astype(c.dtype),
                                                       slot, axis=1)
        return jax.tree.map(upd, caches, slot_cache)

    queue = [Request(i, rng.integers(1, cfg.vocab_size,
                                     (args.prompt_len,), dtype=np.int64),
                     args.max_new)
             for i in range(args.requests)]
    slots: List[Optional[Request]] = [None] * B
    pos = np.zeros(B, dtype=np.int64)
    cur_tok = np.zeros(B, dtype=np.int64)
    completed: List[Request] = []
    t0 = time.time()
    n_decode_ticks = 0

    def admit(caches):
        for s in range(B):
            if slots[s] is None and queue:
                req = queue.pop(0)
                batch = {"tokens": jnp.asarray(req.prompt[None, :],
                                               jnp.int32)}
                logits, c1, plen = prefill_one(params, batch)
                caches = write_slot(caches, c1, s)
                slots[s] = req
                pos[s] = plen
                cur_tok[s] = int(jnp.argmax(logits[0]))
                req.generated.append(cur_tok[s])
        return caches

    caches = admit(caches)
    while any(s is not None for s in slots) or queue:
        # one decode tick for all live slots (dead slots decode garbage
        # into their own rows — isolated and overwritten on admit)
        tick_pos = int(max(pos))  # uniform pos: caches padded to max_len
        tokens = jnp.asarray(cur_tok[:, None], jnp.int32)
        logits, caches = decode_fn(params, caches, tokens,
                                   jnp.int32(tick_pos))
        n_decode_ticks += 1
        nxt = np.asarray(jnp.argmax(logits, axis=-1))
        for s in range(B):
            req = slots[s]
            if req is None:
                continue
            pos[s] += 1
            cur_tok[s] = nxt[s]
            req.generated.append(int(nxt[s]))
            if len(req.generated) >= req.max_new or pos[s] >= L - 1:
                req.done = True
                completed.append(req)
                slots[s] = None
        caches = admit(caches)

    dt = time.time() - t0
    toks = sum(len(r.generated) for r in completed)
    print(f"[serve] {len(completed)} requests, {toks} tokens, "
          f"{n_decode_ticks} decode ticks, {dt:.2f}s "
          f"({toks/dt:.1f} tok/s)", flush=True)
    for r in completed[:3]:
        print(f"  req {r.rid}: {r.generated[:8]}...", flush=True)
    return completed


if __name__ == "__main__":
    from repro.launch.cache import use_compile_cache
    use_compile_cache()
    serve()
