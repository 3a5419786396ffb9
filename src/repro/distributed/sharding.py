"""Sharding rules: DP / TP / EP / SP over the production mesh.

Name-path-based rules produce a PartitionSpec pytree for params (and,
structurally identical, the Adam moments), batches, and decode caches.

Policy highlights (see DESIGN.md §5):
  * TP (Megatron): attention heads + FFN hidden over 'model'
    (column-parallel in, row-parallel out).
  * GQA: KV projections replicated when kv_heads % tp != 0.
  * EP: MoE expert axis over 'model' when n_experts % tp == 0, else
    TP over the expert FFN hidden dim.
  * DP: batch over ('pod','data') / ('data',).
  * SP: decode caches shard the sequence axis when batch doesn't divide
    dp (long_500k, batch=1) — flash-decode's partial-softmax merges via
    the psum XLA inserts.
  * FSDP option: additionally shard the largest param axis over 'data'
    (ZeRO-3 via GSPMD all-gathers) — used by small-dense + rwkv archs
    when replicated-under-TP params would not fit.
"""
from __future__ import annotations

import dataclasses
from typing import Any, Optional, Tuple

import jax
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from repro.configs.base import ModelConfig, ShapeConfig
from repro.launch.mesh import auto_axes, dp_axes, mesh_axis_sizes


@dataclasses.dataclass(frozen=True)
class ShardingPolicy:
    tp_axis: str = "model"
    fsdp: bool = False           # shard big param dims over 'data' too
    seq_shard_caches: bool = True


def _div(n: int, k: int) -> bool:
    return k > 0 and n % k == 0


def _path_str(path) -> str:
    parts = []
    for p in path:
        if isinstance(p, jax.tree_util.DictKey):
            parts.append(str(p.key))
        elif isinstance(p, jax.tree_util.SequenceKey):
            parts.append(str(p.idx))
        elif isinstance(p, jax.tree_util.GetAttrKey):
            parts.append(p.name)
        else:
            parts.append(str(p))
    return "/".join(parts)


def param_spec(cfg: ModelConfig, mesh: Mesh, path: str, shape,
               policy: ShardingPolicy = ShardingPolicy()) -> P:
    """PartitionSpec for one parameter leaf, by name path."""
    sizes = mesh_axis_sizes(mesh)
    tp = sizes.get(policy.tp_axis, 1)
    dpx = dp_axes(mesh)
    dp = 1
    for a in dpx:
        dp *= sizes[a]
    tpa = policy.tp_axis
    nd = len(shape)
    name = path.rsplit("/", 1)[-1]
    parent = path

    def _fill_fsdp(spec: P) -> P:
        """Shard the largest still-unsharded dim over the dp axes
        (ZeRO-3 via GSPMD); applied on top of the TP spec when
        policy.fsdp — skips tiny leaves (<1 MiB) where the all-gather
        latency would outweigh the memory win."""
        if not policy.fsdp:
            return spec
        n_elems = 1
        for s in shape:
            n_elems *= s
        if n_elems < (1 << 20):
            return spec
        dims = list(spec) + [None] * (nd - len(spec))
        best, best_dim = 0, -1
        for i, (d, s) in enumerate(zip(dims, shape)):
            if d is None and _div(s, dp) and s > best:
                best, best_dim = s, i
        if best_dim >= 0:
            dims[best_dim] = dpx if len(dpx) > 1 else dpx[0]
        return P(*dims)

    def base() -> P:
        # ---- embeddings -------------------------------------------------
        if name == "embed":                       # (V, D)
            return P(tpa, None) if _div(shape[0], tp) else P(None, None)
        if name == "lm_head":                     # (D, V)
            return P(None, tpa) if _div(shape[1], tp) else P(None, None)

        # ---- attention --------------------------------------------------
        if "attn" in parent:
            lead = (None,) * (nd - 2)             # group/layer stack prefix
            if name == "wq":                      # (..., D, Hq*Dh)
                ok = _div(cfg.n_heads, tp)
                return P(*lead, None, tpa) if ok else P(*lead, None, None)
            if name in ("wk", "wv"):              # (..., D, Hkv*Dh)
                ok = _div(cfg.n_kv_heads, tp)
                return P(*lead, None, tpa) if ok else P(*lead, None, None)
            if name == "wo":                      # (..., Hq*Dh, D)
                ok = _div(cfg.n_heads, tp)
                return P(*lead, tpa, None) if ok else P(*lead, None, None)

        # ---- MoE ----------------------------------------------------------
        if "moe" in parent:
            E = cfg.moe.n_experts
            lead = (None,) * (nd - 3)
            if name == "router":                  # (..., D, E)
                return P(*((None,) * nd))
            ep = _div(E, tp)
            if name in ("w_gate", "w_up", "w_in"):    # (..., E, D, F)
                if ep:
                    return P(*lead, tpa, None, None)
                return (P(*lead, None, None, tpa) if _div(shape[-1], tp)
                        else P(*((None,) * nd)))
            if name == "w_down":                  # (..., E, F, D)
                if ep:
                    return P(*lead, tpa, None, None)
                return (P(*lead, None, tpa, None) if _div(shape[-2], tp)
                        else P(*((None,) * nd)))

        # ---- dense FFN (also rwkv channel-mix w_k/w_v) --------------------
        if name in ("w_gate", "w_up", "w_in") or (
                name == "w_k" and "rwkv_cm" in parent):
            lead = (None,) * (nd - 2)             # (..., D, F)
            return (P(*lead, None, tpa) if _div(shape[-1], tp)
                    else P(*((None,) * nd)))
        if name == "w_down" or (name == "w_v" and "rwkv_cm" in parent):
            lead = (None,) * (nd - 2)             # (..., F, D)
            return (P(*lead, tpa, None) if _div(shape[-2], tp)
                    else P(*((None,) * nd)))

        # ---- mamba ---------------------------------------------------------
        if "mamba" in parent:
            di = cfg.d_inner
            lead = (None,) * (nd - 2)
            if name == "in_proj":                 # (..., D, 2*di)
                return (P(*lead, None, tpa) if _div(di, tp)
                        else P(*((None,) * nd)))
            if name in ("x_proj", "out_proj", "A_log"):   # (..., di, *)
                return (P(*lead, tpa, None) if _div(di, tp)
                        else P(*((None,) * nd)))
            if name == "dt_proj":                 # (..., dtr, di)
                return (P(*lead, None, tpa) if _div(di, tp)
                        else P(*((None,) * nd)))
            if name in ("conv_w",):               # (..., d_conv, di)
                return (P(*lead, None, tpa) if _div(di, tp)
                        else P(*((None,) * nd)))
            if name in ("conv_b", "dt_bias", "D"):        # (..., di)
                lead1 = (None,) * (nd - 1)
                return (P(*lead1, tpa) if _div(di, tp)
                        else P(*((None,) * nd)))

        # ---- rwkv time-mix --------------------------------------------------
        if "rwkv_tm" in parent:
            lead = (None,) * (nd - 2)
            if name in ("w_r", "w_k", "w_v", "w_g"):      # (..., D, D)
                return (P(*lead, None, tpa) if _div(shape[-1], tp)
                        else P(*((None,) * nd)))
            if name == "w_o":                     # (..., D, D)
                return (P(*lead, tpa, None) if _div(shape[-2], tp)
                        else P(*((None,) * nd)))
            if name in ("w_lora_a", "w_lora_b"):
                return P(*((None,) * nd))

        # ---- everything else (norms, mixes, biases, u, ...): replicated --
        return P(*((None,) * nd))

    return _fill_fsdp(base())


def params_pspecs(cfg: ModelConfig, mesh: Mesh, params_tree,
                  policy: ShardingPolicy = ShardingPolicy()):
    """PartitionSpec tree matching a (possibly abstract) params tree."""
    return jax.tree_util.tree_map_with_path(
        lambda path, leaf: param_spec(cfg, mesh, _path_str(path), leaf.shape,
                                      policy),
        params_tree)


def state_pspecs(cfg: ModelConfig, mesh: Mesh, state_tree,
                 policy: ShardingPolicy = ShardingPolicy()):
    """TrainState(params, OptState(mu, nu, step)) spec tree."""
    from repro.models.api import TrainState
    from repro.optim.adamw import OptState
    p = params_pspecs(cfg, mesh, state_tree.params, policy)
    mu = params_pspecs(cfg, mesh, state_tree.opt.mu, policy)
    nu = params_pspecs(cfg, mesh, state_tree.opt.nu, policy)
    return TrainState(p, OptState(mu, nu, P()))


def batch_pspecs(cfg: ModelConfig, mesh: Mesh, batch_tree):
    """Shard the leading batch dim of every input over the dp axes."""
    dpx = dp_axes(mesh)
    dspec = dpx if len(dpx) > 1 else dpx[0]

    def spec(leaf):
        nd = len(leaf.shape)
        sizes = mesh_axis_sizes(mesh)
        dp = 1
        for a in dpx:
            dp *= sizes[a]
        if leaf.shape[0] % dp == 0:
            return P(dspec, *((None,) * (nd - 1)))
        return P(*((None,) * nd))

    return jax.tree.map(spec, batch_tree)


def cache_pspecs(cfg: ModelConfig, mesh: Mesh, cache_tree,
                 policy: ShardingPolicy = ShardingPolicy()):
    """Decode caches: batch over dp; SP over sequence when batch==1.

    Attn k/v: (G, B, S, Hkv, Dh)  |  encdec: (L, B, S, Hkv, Dh)
    mamba:    conv (G, B, dc, di), ssm (G, B, di, ds)
    rwkv:     tm_x/cm_x (G, B, D), state (G, B, H, hs, hs)
    """
    sizes = mesh_axis_sizes(mesh)
    tp = sizes.get(policy.tp_axis, 1)
    dpx = dp_axes(mesh)
    dspec = dpx if len(dpx) > 1 else dpx[0]
    dp = 1
    for a in dpx:
        dp *= sizes[a]
    tpa = policy.tp_axis

    def spec_with_path(path, leaf):
        name = _path_str(path).rsplit("/", 1)[-1]
        nd = len(leaf.shape)
        B = leaf.shape[1]
        batch_ok = B % dp == 0
        bspec = dspec if batch_ok else None
        if name in ("k", "v", "xk", "xv"):
            S = leaf.shape[2]
            seq_axes = []
            if not batch_ok and policy.seq_shard_caches and S % dp == 0:
                seq_axes.extend(dpx)    # SP over data (batch=1 long ctx)
            hspec = tpa if _div(cfg.n_kv_heads, tp) else None
            if (hspec is None and policy.seq_shard_caches
                    and S % (tp * max(dp if seq_axes else 1, 1)) == 0):
                # kv heads don't divide tp: shard the SEQUENCE over the
                # model axis instead — flash-decode partial softmax
                # merges with the psum XLA inserts. Without this the
                # cache replicates across tp and blows HBM (grok
                # decode_32k: 66 GiB/chip -> 4.2 GiB/chip).
                seq_axes.append(tpa)
            sspec = (tuple(seq_axes) if len(seq_axes) > 1
                     else (seq_axes[0] if seq_axes else None))
            return P(None, bspec, sspec, hspec, None)
        if name == "conv":
            return P(None, bspec, None,
                     tpa if _div(cfg.d_inner, tp) else None)
        if name == "ssm":
            return P(None, bspec,
                     tpa if _div(cfg.d_inner, tp) else None, None)
        if name in ("tm_x", "cm_x"):
            return P(None, bspec, None)
        if name == "state":
            return P(None, bspec, *((None,) * (nd - 2)))
        return P(*((None,) * nd))

    return jax.tree_util.tree_map_with_path(spec_with_path, cache_tree)


def to_shardings(mesh: Mesh, spec_tree):
    """NamedShardings over ``mesh``'s devices with ``Auto`` axes, whatever
    axis types the caller's mesh has (see ``launch/mesh.py``)."""
    mesh = auto_axes(mesh)
    return jax.tree.map(lambda s: NamedSharding(mesh, s), spec_tree,
                        is_leaf=lambda x: isinstance(x, P))
