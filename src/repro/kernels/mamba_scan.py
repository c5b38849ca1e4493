"""Selective-scan (Mamba) Pallas kernel, TPU target.

TPU adaptation of the CUDA selective-scan: instead of a warp-level scan,
the sequence is chunked; the grid is (batch, d_inner blocks, chunks) with
the innermost axis sequential, carrying the (bdi, d_state) SSM state in
VMEM scratch across chunks. The channel dimension is tiled to lanes
(bdi = 512 default, multiple of 128); d_state (16) rides the sublane dim.
Within a chunk the recurrence s_t = exp(dt*A)*s + dt*B*x runs as a
``fori_loop`` over time steps entirely in VMEM/registers — no HBM traffic
for intermediate states, one HBM read per input element and one write per
output element (the memory-bound optimum for this op).

Validated against ``ref.selective_scan_ref`` (chunked associative scan).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def _scan_kernel(
    x_ref, dt_ref, a_ref, b_ref, c_ref,   # (1,L,bdi),(1,L,bdi),(bdi,ds),(1,L,ds),(1,L,ds)
    y_ref, sf_ref,                        # (1,L,bdi), (1,bdi,ds) final state
    s_ref,                                # VMEM scratch (bdi, ds) f32
    *,
    chunk: int,
):
    cj = pl.program_id(2)
    nc = pl.num_programs(2)

    @pl.when(cj == 0)
    def _init():
        s_ref[...] = jnp.zeros_like(s_ref)

    a = a_ref[...]                                     # (bdi, ds)

    def body(t, s):
        xt = x_ref[0, t, :].astype(jnp.float32)        # (bdi,)
        dtt = dt_ref[0, t, :].astype(jnp.float32)      # (bdi,)
        bt = b_ref[0, t, :].astype(jnp.float32)        # (ds,)
        ct = c_ref[0, t, :].astype(jnp.float32)        # (ds,)
        decay = jnp.exp(dtt[:, None] * a)              # (bdi, ds)
        s = decay * s + (dtt * xt)[:, None] * bt[None, :]
        y = jnp.sum(s * ct[None, :], axis=1)           # (bdi,)
        y_ref[0, t, :] = y.astype(y_ref.dtype)
        return s

    s = jax.lax.fori_loop(0, chunk, body, s_ref[...])
    s_ref[...] = s

    @pl.when(cj == nc - 1)
    def _final():
        sf_ref[0, ...] = s_ref[...]


def selective_scan_pallas(
    x: jax.Array,        # (Ba, S, di) f32
    dt: jax.Array,       # (Ba, S, di)
    A: jax.Array,        # (di, ds)
    B: jax.Array,        # (Ba, S, ds)
    C: jax.Array,        # (Ba, S, ds)
    *,
    chunk: int = 64,
    block_di: int = 512,
    interpret: bool,
):
    ba, s, di = x.shape
    ds = A.shape[-1]
    chunk = min(chunk, s)
    block_di = min(block_di, di)
    assert di % block_di == 0
    pad = (-s) % chunk
    if pad:
        x = jnp.pad(x, ((0, 0), (0, pad), (0, 0)))
        dt = jnp.pad(dt, ((0, 0), (0, pad), (0, 0)))
        B = jnp.pad(B, ((0, 0), (0, pad), (0, 0)))
        C = jnp.pad(C, ((0, 0), (0, pad), (0, 0)))
    s_orig, s = s, s + pad
    nc, ndi = s // chunk, di // block_di

    kernel = functools.partial(_scan_kernel, chunk=chunk)
    y, sf = pl.pallas_call(
        kernel,
        grid=(ba, ndi, nc),
        in_specs=[
            pl.BlockSpec((1, chunk, block_di), lambda b, d, c: (b, c, d)),
            pl.BlockSpec((1, chunk, block_di), lambda b, d, c: (b, c, d)),
            pl.BlockSpec((block_di, ds), lambda b, d, c: (d, 0)),
            pl.BlockSpec((1, chunk, ds), lambda b, d, c: (b, c, 0)),
            pl.BlockSpec((1, chunk, ds), lambda b, d, c: (b, c, 0)),
        ],
        out_specs=[
            pl.BlockSpec((1, chunk, block_di), lambda b, d, c: (b, c, d)),
            pl.BlockSpec((1, block_di, ds), lambda b, d, c: (b, d, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((ba, s, di), x.dtype),
            jax.ShapeDtypeStruct((ba, di, ds), jnp.float32),
        ],
        scratch_shapes=[pltpu.VMEM((block_di, ds), jnp.float32)],
        interpret=interpret,
    )(x, dt, A, B, C)
    return y[:, :s_orig], sf
