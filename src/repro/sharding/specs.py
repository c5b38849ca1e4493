"""PartitionSpecs for BOTH halves of the repo.

Sim half (the digital twin): replica-batched fleet pytrees
(``SimState``/``Scenario``/``Policy``/``TelemetrySummary``) shard their
leading replica axis across a 1-D fleet mesh — ``fleet_pspecs`` /
``replicated_pspecs`` / ``fleet_shardings`` below, consumed by
``core.fleet.run_fleet(..., mesh=...)`` and ``rl.distributed`` (both
call ``jax.shard_map`` directly).

LM half: parameter PartitionSpecs derived from param *names* and shapes.

Megatron-style TP over the 'model' axis + ZeRO-3/FSDP over the data axes:

  emb (V, D)            -> P(tp, fsdp)     vocab-parallel embedding
  head (D, V)           -> P(fsdp, tp)
  wq/wk/wv (D, H*hd)    -> P(fsdp, tp)     column-parallel
  wo (H*hd, D)          -> P(tp, fsdp)     row-parallel
  wi/wg (D, F)          -> P(fsdp, tp)
  wo2 (F, D)            -> P(tp, fsdp)
  router (D, E)         -> P(fsdp, None)
  experts (E, D, F)     -> P(tp, fsdp, None) when E % |tp| == 0 (EP)
                           else P(None, fsdp, tp) (TP inside experts)
  mamba in_proj (D,2di) -> P(fsdp, tp); out_proj (di, D) -> P(tp, fsdp)
  scalars/norms/biases  -> replicated
  stacked layer leading axis (superblock repeats) -> None prepended
"""

from __future__ import annotations

from typing import Any, Dict

import jax
import numpy as np
from jax.sharding import PartitionSpec as P

from repro.configs.base import ModelConfig
from repro.models import spec as S
from repro.sharding.ctx import ShardCtx
from repro.utils.tree import tree_map_with_path_names

# --------------------------------------------------------------- sim half
FLEET_AXIS = "replica"   # canonical fleet-mesh axis name (launch.mesh)


def fleet_pspecs(tree: Any, axis: str = FLEET_AXIS) -> Any:
    """PartitionSpec pytree sharding every leaf's LEADING axis over the
    fleet mesh axis — the spec for replica-batched sim pytrees (batched
    ``SimState``/``Scenario``/``Policy``, per-replica PRNG keys, fleet
    telemetry). Leaves are uniform on the replica axis by construction
    (``run_fleet`` broadcasts/stacks them), so one rule covers the tree."""
    return jax.tree.map(lambda _: P(axis), tree)


def replicated_pspecs(tree: Any) -> Any:
    """Fully-replicated PartitionSpec pytree — for ``Statics`` (node
    tables, trace bank, scenario defaults) and other shared constants
    every shard reads but none owns."""
    return jax.tree.map(lambda _: P(), tree)


def fleet_shardings(mesh, tree: Any, axis: str = FLEET_AXIS) -> Any:
    """NamedSharding pytree for ``jax.device_put``-ing a replica-batched
    fleet pytree onto ``mesh`` (see ``core.fleet.shard_fleet``)."""
    from jax.sharding import NamedSharding

    return jax.tree.map(lambda _: NamedSharding(mesh, P(axis)), tree)


# ---------------------------------------------------------------- LM half
# param base-name -> (logical axes per dim), for unstacked shapes
_COL = ("fsdp", "tp")   # (in, out-sharded)
_ROW = ("tp", "fsdp")   # (in-sharded, out)
_RULES: Dict[str, tuple] = {
    "emb": ("tp", "fsdp"),
    "head": _COL,
    "wq": _COL, "wk": _COL, "wv": _COL, "wo": _ROW,
    "xq": _COL, "xk": _COL, "xv": _COL, "xo": _ROW,
    "wi": _COL, "wg": _COL, "wo2": _ROW,
    "router": ("fsdp", None),
    "in_proj": _COL,
    "out_proj": _ROW,
    "x_proj": ("tp", None),
    "dt_w": (None, "tp"),
    "A_log": ("tp", None),
    "conv_w": (None, "tp"),
    "up": _COL,
    "down": _ROW,
    "w": ("fsdp", None),
    "r": (None, None, None),
}
# per-di vectors live on the tp axis
_TP_VECTORS = {"conv_b", "dt_b", "D_skip", "ln_inner_mamba"}


def _dims_divisible(shape, axes, ctx: ShardCtx, mesh_axis_sizes) -> bool:
    for dim, ax in zip(shape, axes):
        if ax is None:
            continue
        size = mesh_axis_sizes[ax]
        if dim % size != 0:
            return False
    return True


def _expert_rule(cfg: ModelConfig, name: str, tp_size: int):
    ep = cfg.moe.n_experts % max(tp_size, 1) == 0 and cfg.moe.n_experts >= tp_size
    if name in ("e_wg", "e_wi"):
        return ("tp", "fsdp", None) if ep else (None, "fsdp", "tp")
    if name == "e_wo":
        return ("tp", None, "fsdp") if ep else (None, "tp", "fsdp")
    raise KeyError(name)


def param_pspecs(cfg: ModelConfig, ctx: ShardCtx, mesh=None) -> Any:
    """Pytree of PartitionSpec mirroring ``model_param_specs(cfg)``."""
    specs = S.model_param_specs(cfg)
    if mesh is not None:
        sizes = dict(zip(mesh.axis_names, mesh.devices.shape))
    else:
        sizes = None
    tp_size = sizes["model"] if sizes and "model" in sizes else 16

    def logical_for(name: str, shape) -> tuple:
        base = name.rsplit("/", 1)[-1]
        if base in ("e_wg", "e_wi", "e_wo"):
            return _expert_rule(cfg, base, tp_size)
        if base in _TP_VECTORS and len(shape) == 1:
            return ("tp",)
        rule = _RULES.get(base)
        if rule is None or len(rule) != len(shape):
            return tuple(None for _ in shape)
        return rule

    def one(name: str, sds) -> P:
        shape = sds.shape
        stacked = (
            name.startswith(("body/", "xattn_body/"))
            or "/layers/" in name
            or name.startswith("encoder/layers")
        )
        core_shape = shape[1:] if stacked else shape
        logical = logical_for(name, core_shape)
        if not ctx.fsdp:
            logical = tuple(None if a == "fsdp" else a for a in logical)
        if not ctx.expert_parallel and name.rsplit("/", 1)[-1].startswith("e_w"):
            logical = tuple(None if a == "tp" and i == 0 else a
                            for i, a in enumerate(logical))
        axes = [ctx.axis(a) for a in logical]
        # drop shardings that do not divide (keeps XLA from padding params)
        if sizes is not None:
            for i, (dim, ax) in enumerate(zip(core_shape, axes)):
                if ax is None:
                    continue
                n = int(np.prod([sizes[a] for a in (ax if isinstance(ax, tuple) else (ax,))]))
                if dim % n != 0:
                    axes[i] = None
        if stacked:
            axes = [None] + axes
        return P(*axes)

    return tree_map_with_path_names(one, specs)


def batch_pspec(ctx: ShardCtx) -> P:
    return P(ctx.axis("dp"))


def batch_pspecs(cfg: ModelConfig, shape, ctx: ShardCtx):
    """PartitionSpecs mirroring models.batch_specs(cfg, shape)."""
    dp = ctx.axis("dp")
    if shape.mode in ("train", "prefill"):
        out = {"tokens": P(dp, None)}
        if shape.mode == "train":
            out["labels"] = P(dp, None)
        if cfg.n_vision_tokens:
            out["vision"] = P(dp, None, None)
        if cfg.enc_dec:
            out["audio"] = P(dp, None, None)
        return out
    small_batch = ctx.decode_kv_shard == "seq2d"
    return {
        "tokens": P(None if small_batch else dp, None),
        "cache": cache_pspecs(cfg, ctx),
        "cache_len": P(),
    }


def cache_pspecs(cfg: ModelConfig, ctx: ShardCtx):
    """PartitionSpec tree mirroring models.cache_specs (decode caches)."""
    from repro.models.model import cache_specs

    template = cache_specs(cfg, 8, 64)   # structure only; shapes irrelevant
    kv = ctx.kv_cache_pspec()
    dp = None if ctx.decode_kv_shard == "seq2d" else ctx.axis("dp")
    tp = ctx.tp if ctx.enabled else None

    def one(name, sds):
        base = name.rsplit("/", 1)[-1]
        stacked = name.startswith("body/")
        nd = len(sds.shape) - (1 if stacked else 0)
        if base in ("k", "v"):
            spec = list(kv) + [None] * (4 - len(kv))
        elif base in ("xk", "xv"):
            spec = [dp, None, None, None]
        elif base == "conv":
            spec = [dp, None, tp]
        elif base == "ssm":
            spec = [dp, tp, None]
        elif base in ("C", "n"):
            spec = [dp] + [None] * (nd - 1)
        else:   # m, c, h and other small per-batch states
            spec = [dp] + [None] * (nd - 1)
        spec = spec[:nd] + [None] * (nd - len(spec))
        if stacked:
            spec = [None] + spec
        return P(*spec)

    return tree_map_with_path_names(one, template)


def train_state_pspecs(cfg: ModelConfig, ctx: ShardCtx, optimizer, mesh=None):
    """PartitionSpecs for a TrainState built by repro.train.state."""
    p_specs = param_pspecs(cfg, ctx, mesh)
    opt_specs = optimizer.state_pspecs(S.model_param_specs(cfg), p_specs)
    return {
        "params": p_specs,
        "opt": opt_specs,
        "step": P(),
    }
