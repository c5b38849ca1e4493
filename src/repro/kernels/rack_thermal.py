"""Per-rack thermal-update Pallas kernel (the cooling loop's hot pass).

With thermals enabled the twin folds a node->rack heat reduction plus a
first-order RC temperature relaxation into every simulation tick — and the
macro engine re-runs it once per fast-forwarded tick, so it sits on the
same per-tick critical path as the power chain. This kernel fuses the
scatter and the RC update into one VMEM pass (grid = rack blocks): each
rack block builds its heat from the (N,) node table via a one-hot
contraction on the MXU — the same trick as
``node_power.power_scatter_pallas`` — and relaxes its temperatures without
materializing the (R,) heat intermediate in HBM.

Validated against ``ref.rack_thermal_ref`` (bitwise on CPU: both paths
reduce through the identical one-hot matmul).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl


def _rack_thermal_kernel(
    heat_ref, rack_ref,          # (Np,) node heat + rack ids, full
    sup_ref,                     # (1,) supply temperature
    t_ref, rth_ref,              # (br,) per-rack blocks
    newt_ref, rheat_ref,         # (br,) outputs
    *,
    block_r: int,
    alpha: float,
):
    j = pl.program_id(0)
    ids = j * block_r + jax.lax.broadcasted_iota(jnp.int32, (1, block_r), 1)
    onehot = (rack_ref[...][:, None] == ids).astype(jnp.float32)   # (Np, br)
    # HIGHEST: the MXU's default f32 pass rounds operands to bf16, which
    # would cut rack heat to ~3 significant digits
    heat = jnp.dot(heat_ref[...][None, :].astype(jnp.float32), onehot,
                   precision=jax.lax.Precision.HIGHEST,
                   preferred_element_type=jnp.float32)[0]
    t = t_ref[...].astype(jnp.float32)
    t_ss = sup_ref[0] + heat * rth_ref[...]
    new_t = t + jnp.float32(alpha) * (t_ss - t)
    newt_ref[...] = new_t.astype(newt_ref.dtype)
    rheat_ref[...] = heat.astype(rheat_ref.dtype)


def rack_thermal_pallas(
    node_heat_w: jax.Array,    # (N,) per-node input power
    node_rack: jax.Array,      # (N,) int32 rack ids
    rack_outlet_c: jax.Array,  # (R,)
    supply_c: jax.Array,       # scalar
    rack_r_th: jax.Array,      # (R,)
    *,
    alpha: float,
    interpret: bool,
    block_r: int = 128,
):
    """Returns (new_outlet_c, rack_heat_w), each (R,). vmap adds a leading
    grid dim, so vectorized replicas batch for free."""
    n = node_heat_w.shape[0]
    r = rack_outlet_c.shape[0]
    block_r = min(block_r, r)
    pad_r = (-r) % block_r
    if pad_r:
        padR = lambda a: jnp.pad(a, (0, pad_r))
        rack_outlet_c, rack_r_th = padR(rack_outlet_c), padR(rack_r_th)
    pad_n = (-n) % 128                   # lane-align the node table
    if pad_n:
        # padded nodes get rack id -1 -> match no one-hot column, heat 0
        node_heat_w = jnp.pad(node_heat_w, (0, pad_n))
        node_rack = jnp.pad(node_rack, (0, pad_n), constant_values=-1)
    nb = (r + pad_r) // block_r

    kernel = functools.partial(_rack_thermal_kernel, block_r=block_r,
                               alpha=alpha)
    full = pl.BlockSpec((n + pad_n,), lambda j: (0,))
    blk = pl.BlockSpec((block_r,), lambda j: (j,))
    new_t, rheat = pl.pallas_call(
        kernel,
        grid=(nb,),
        in_specs=[full, full, pl.BlockSpec((1,), lambda j: (0,)), blk, blk],
        out_specs=[blk, blk],
        out_shape=[jax.ShapeDtypeStruct((r + pad_r,), jnp.float32)] * 2,
        interpret=interpret,
        name="rack_thermal",
    )(node_heat_w, node_rack, jnp.reshape(supply_c, (1,)).astype(jnp.float32),
      rack_outlet_c, rack_r_th)
    return new_t[:r], rheat[:r]
