"""Node power-chain Pallas kernel (the simulator's per-step hot loop).

For batched-RL rollouts the twin evaluates the power chain for every node
of every vectorized environment every step: (E, N) utilization fractions
-> IT power -> rectifier-efficiency parabola -> conversion loss. Fused
into a single VMEM pass (grid = (env blocks, node blocks)): the inputs
are read once from HBM, two outputs written once — no intermediate
arrays, which is the memory-bound optimum (the XLA path materializes the
eta and load_frac temporaries).

Validated against ``ref.node_power_ref``. ``power_scatter_pallas`` goes
one step further and fuses the job-table placement scatter into the same
pass (oracle: ``ref.power_scatter_ref``).

TPU layout: every operand is 2-D with a lane-aligned last dim. Per-node
constants travel as one stacked (rows, N) array, so no block is a 1-D
vector (Mosaic tiles a 1-D f32[n] as T(1024), which a 128-lane block
cannot match).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def _round_up(x: int, m: int) -> int:
    return -(-x // m) * m


def _power_kernel(
    cpu_ref, gpu_ref, up_ref,            # (be, bn)
    node_ref,                            # (4, bn): idle, cpu_dyn, gpu_dyn, max_w
    it_ref, inp_ref,                     # (be, bn)
    *,
    rect_peak: float,
    rect_load: float,
    rect_curv: float,
    conv_eff: float,
):
    idle, cdyn = node_ref[0:1, :], node_ref[1:2, :]
    gdyn, maxw = node_ref[2:3, :], node_ref[3:4, :]
    it = (idle + cpu_ref[...] * cdyn + gpu_ref[...] * gdyn) * up_ref[...]
    load = jnp.clip(it / jnp.maximum(maxw, 1.0), 0.0, 1.2)
    eta = jnp.clip(rect_peak - rect_curv * jnp.square(load - rect_load), 0.5, 1.0)
    it_ref[...] = it
    inp_ref[...] = it / (eta * conv_eff)


def node_power_pallas(
    cpu_frac: jax.Array,      # (E, N)
    gpu_frac: jax.Array,      # (E, N)
    idle_w: jax.Array,        # (N,)
    cpu_dyn_w: jax.Array,
    gpu_dyn_w: jax.Array,
    node_up: jax.Array,       # (E, N)
    node_max_w: jax.Array,    # (N,)
    *,
    rect_peak: float,
    rect_load: float,
    rect_curv: float,
    conv_eff: float,
    interpret: bool,
    block_n: int = 512,
):
    squeeze = cpu_frac.ndim == 1
    if squeeze:
        cpu_frac, gpu_frac, node_up = (
            cpu_frac[None], gpu_frac[None], node_up[None]
        )
    e, n = cpu_frac.shape
    # lanes: node blocks of a multiple of 128; sublanes: env blocks of 8
    # (or all envs when there are fewer than 8)
    block_n = min(_round_up(block_n, 128), _round_up(n, 128))
    block_e = 8 if e >= 8 else e
    n_pad, e_pad = _round_up(n, block_n), _round_up(e, block_e)
    f32 = jnp.float32

    def per_env(a):
        return jnp.pad(a.astype(f32), ((0, e_pad - e), (0, n_pad - n)))

    # node_max_w pads with 1.0 so padded lanes never divide by zero
    node = jnp.stack([
        jnp.pad(idle_w.astype(f32), (0, n_pad - n)),
        jnp.pad(cpu_dyn_w.astype(f32), (0, n_pad - n)),
        jnp.pad(gpu_dyn_w.astype(f32), (0, n_pad - n)),
        jnp.pad(node_max_w.astype(f32), (0, n_pad - n), constant_values=1.0),
    ])

    kernel = functools.partial(
        _power_kernel, rect_peak=rect_peak, rect_load=rect_load,
        rect_curv=rect_curv, conv_eff=conv_eff,
    )
    env_blk = pl.BlockSpec((block_e, block_n), lambda i, j: (i, j))
    it, inp = pl.pallas_call(
        kernel,
        grid=(e_pad // block_e, n_pad // block_n),
        in_specs=[env_blk, env_blk, env_blk,
                  pl.BlockSpec((4, block_n), lambda i, j: (0, j))],
        out_specs=[env_blk, env_blk],
        out_shape=[jax.ShapeDtypeStruct((e_pad, n_pad), f32)] * 2,
        interpret=interpret,
        name="node_power",
    )(per_env(cpu_frac), per_env(gpu_frac), per_env(node_up), node)
    it, inp = it[:e, :n], inp[:e, :n]
    if squeeze:
        it, inp = it[0], inp[0]
    return it, inp


# ---------------------------------------------------------------------------
# fused placement-scatter + power chain: job table -> per-node IT power in
# one pass. The host-side scatter-add (node_loads) materialized two (N,)
# load arrays in HBM before the power kernel could run; here the grid
# walks the (J*K,) placement table in blocks, contracts each block's
# one-hot against the per-slot amounts on the MXU into a VMEM accumulator,
# and applies the power chain on the last block without leaving VMEM.
def _power_scatter_kernel(
    place_ref,                     # (1, bjk) int32 node ids, -1 = unused
    amt_ref,                       # (2, bjk) utilized cpu cores / gpus
    node_ref,                      # (8, Np) stacked per-node constants
    it_ref, inp_ref, cf_ref, gf_ref,   # (1, Np)
    acc_ref,                       # (2, Np) VMEM load accumulator
    *,
    rect_peak: float,
    rect_load: float,
    rect_curv: float,
    conv_eff: float,
):
    k = pl.program_id(0)

    @pl.when(k == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    n_pad, bjk = acc_ref.shape[1], place_ref.shape[1]
    # transposed one-hot (Np, bjk): node ids down the sublanes, slots along
    # the lanes, so the placement row broadcasts without a relayout
    ids = jax.lax.broadcasted_iota(jnp.int32, (n_pad, bjk), 0)
    onehot_t = (ids == place_ref[...]).astype(jnp.float32)
    # HIGHEST: the MXU's default f32 pass rounds operands to bf16, which
    # would cut per-node loads to ~3 significant digits
    acc_ref[...] += jax.lax.dot_general(
        amt_ref[...], onehot_t, (((1,), (1,)), ((), ())),
        precision=jax.lax.Precision.HIGHEST,
        preferred_element_type=jnp.float32)

    @pl.when(k == pl.num_programs(0) - 1)
    def _finish():
        cap_c, cap_g = node_ref[0:1, :], node_ref[1:2, :]
        idle, cdyn, gdyn = node_ref[2:3, :], node_ref[3:4, :], node_ref[4:5, :]
        up, maxw = node_ref[5:6, :], node_ref[6:7, :]
        cf = jnp.clip(acc_ref[0:1, :] / jnp.maximum(cap_c, 1e-6), 0.0, 1.0)
        gf = jnp.clip(acc_ref[1:2, :] / jnp.maximum(cap_g, 1e-6), 0.0, 1.0)
        it = (idle + cf * cdyn + gf * gdyn) * up
        load = jnp.clip(it / jnp.maximum(maxw, 1.0), 0.0, 1.2)
        eta = jnp.clip(rect_peak - rect_curv * jnp.square(load - rect_load),
                       0.5, 1.0)
        it_ref[...] = it
        inp_ref[...] = it / (eta * conv_eff)
        cf_ref[...] = cf
        gf_ref[...] = gf


def power_scatter_pallas(
    place_flat: jax.Array,    # (JK,) int32 node ids; -1 = unused slot
    cpu_abs: jax.Array,       # (JK,) utilized cpu cores per slot
    gpu_abs: jax.Array,       # (JK,)
    cap_cpu: jax.Array,       # (N,)
    cap_gpu: jax.Array,       # (N,)
    idle_w: jax.Array,        # (N,)
    cpu_dyn_w: jax.Array,
    gpu_dyn_w: jax.Array,
    node_up: jax.Array,       # (N,)
    node_max_w: jax.Array,    # (N,)
    *,
    rect_peak: float,
    rect_load: float,
    rect_curv: float,
    conv_eff: float,
    interpret: bool,
    block_jk: int = 512,
):
    """Returns (node_it_w, node_input_w, cpu_frac, gpu_frac), each (N,).

    Validated against ``ref.power_scatter_ref``. vmap adds a leading grid
    dim, so the vectorized twin batches replicas for free. ``block_jk``
    slots per grid step bound the one-hot to (N rounded up to 128) x
    ``block_jk`` f32 in VMEM, whatever the job-table size.
    """
    n = idle_w.shape[0]
    jk = place_flat.shape[0]
    f32 = jnp.float32
    n_pad = _round_up(n, 128)
    block_jk = min(_round_up(block_jk, 128), _round_up(jk, 128))
    jk_pad = _round_up(jk, block_jk)

    # padded slots point at no node (id -1) and carry zero amounts
    place = jnp.pad(place_flat.astype(jnp.int32), (0, jk_pad - jk),
                    constant_values=-1)[None, :]
    amt = jnp.pad(jnp.stack([cpu_abs, gpu_abs]).astype(f32),
                  ((0, 0), (0, jk_pad - jk)))

    def padn(a, v=0.0):
        return jnp.pad(a.astype(f32), (0, n_pad - n), constant_values=v)

    # padded nodes: zero load, zero power; max_w 1.0 avoids div-by-zero
    node = jnp.stack([padn(cap_cpu), padn(cap_gpu), padn(idle_w),
                      padn(cpu_dyn_w), padn(gpu_dyn_w), padn(node_up),
                      padn(node_max_w, 1.0), jnp.zeros((n_pad,), f32)])

    kernel = functools.partial(
        _power_scatter_kernel, rect_peak=rect_peak, rect_load=rect_load,
        rect_curv=rect_curv, conv_eff=conv_eff,
    )
    row = pl.BlockSpec((1, n_pad), lambda k: (0, 0))
    outs = pl.pallas_call(
        kernel,
        grid=(jk_pad // block_jk,),
        in_specs=[pl.BlockSpec((1, block_jk), lambda k: (0, k)),
                  pl.BlockSpec((2, block_jk), lambda k: (0, k)),
                  pl.BlockSpec((8, n_pad), lambda k: (0, 0))],
        out_specs=[row] * 4,
        out_shape=[jax.ShapeDtypeStruct((1, n_pad), f32)] * 4,
        scratch_shapes=[pltpu.VMEM((2, n_pad), f32)],
        interpret=interpret,
        name="power_scatter",
    )(place, amt, node)
    return tuple(o[0, :n] for o in outs)
