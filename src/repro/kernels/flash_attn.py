"""Flash attention forward kernel (Pallas, TPU target).

TPU adaptation of the FlashAttention online-softmax contraction:
- grid = (batch*kv_heads*rep, num_q_blocks, num_kv_blocks); the last grid
  axis is sequential on TPU, so the (m, l, acc) running statistics live in
  VMEM scratch that persists across KV blocks;
- BlockSpecs tile Q/K/V into (block_q x head_dim)/(block_k x head_dim)
  VMEM tiles (head_dim = 64..256 = MXU-friendly lane counts; block sizes
  default 512/1024 so a (bq x bk) f32 score tile ~2 MB fits VMEM);
- GQA without materializing repeated KV: the KV index_map folds the
  query-group factor (kv head = bh // rep);
- causal + sliding-window masks are applied per-tile from absolute
  positions (the fully-masked-tile case is ``pl.when``-skipped).

Gradients: ``ops.flash_attention`` wraps this with jax.custom_vjp whose
backward is the jnp chunked-online-softmax reference (same math, XLA),
keeping training differentiable everywhere while the TPU forward uses the
kernel. Validated against ``ref.attention_ref`` in interpret mode.
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1e30


def _fwd_kernel(
    q_ref, k_ref, v_ref,            # (1, bq, hd), (1, bk, hd), (1, bk, hd)
    o_ref,                          # (1, bq, hd)
    acc_ref, m_ref, l_ref,          # VMEM scratch
    *,
    causal: bool,
    window: int,
    block_q: int,
    block_k: int,
    sm_scale: float,
    q_off: int,
):
    qi = pl.program_id(1)
    kj = pl.program_id(2)
    nk = pl.num_programs(2)

    @pl.when(kj == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    def _compute():
        q_pos = qi * block_q + q_off + jax.lax.broadcasted_iota(
            jnp.int32, (block_q, block_k), 0
        )
        k_pos = kj * block_k + jax.lax.broadcasted_iota(
            jnp.int32, (block_q, block_k), 1
        )
        d = q_pos - k_pos
        q = q_ref[0].astype(jnp.float32)
        k = k_ref[0].astype(jnp.float32)
        v = v_ref[0].astype(jnp.float32)
        s = jax.lax.dot_general(
            q, k, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32
        ) * sm_scale                                   # (bq, bk)
        mask = jnp.ones((block_q, block_k), jnp.bool_)
        if causal:
            mask &= d >= 0
        if window > 0:
            mask &= d < window
        s = jnp.where(mask, s, NEG_INF)

        m_prev = m_ref[...]
        l_prev = l_ref[...]
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=1))
        p = jnp.exp(s - m_new[:, None])
        alpha = jnp.exp(m_prev - m_new)
        l_new = l_prev * alpha + jnp.sum(p, axis=1)
        acc_ref[...] = acc_ref[...] * alpha[:, None] + jax.lax.dot_general(
            p, v, (((1,), (0,)), ((), ())), preferred_element_type=jnp.float32
        )
        m_ref[...] = m_new
        l_ref[...] = l_new

    if causal:
        # tile-level skip: tiles entirely above the causal diagonal do no
        # work (the TPU grid still visits them; compute is gated)
        live = (kj * block_k) <= (qi * block_q + q_off + block_q - 1)
        if window > 0:
            live &= (kj + 1) * block_k > (qi * block_q + q_off - window)
        pl.when(live)(_compute)
    else:
        _compute()

    @pl.when(kj == nk - 1)
    def _out():
        l = jnp.maximum(l_ref[...], 1e-30)
        o_ref[0, ...] = (acc_ref[...] / l[:, None]).astype(o_ref.dtype)


def flash_attention_fwd(
    q: jax.Array,       # (B, Sq, H, hd)
    k: jax.Array,       # (B, Sk, Kv, hd)
    v: jax.Array,
    *,
    causal: bool = True,
    window: int = 0,
    block_q: int = 512,
    block_k: int = 1024,
    interpret: bool,
) -> jax.Array:
    b, sq, h, hd = q.shape
    sk, kvh = k.shape[1], k.shape[2]
    rep = h // kvh
    block_q = min(block_q, sq)
    block_k = min(block_k, sk)
    assert sq % block_q == 0 and sk % block_k == 0
    nq, nk = sq // block_q, sk // block_k

    # layout: fold heads into the leading grid axis
    qf = q.transpose(0, 2, 1, 3).reshape(b * h, sq, hd)
    kf = k.transpose(0, 2, 1, 3).reshape(b * kvh, sk, hd)
    vf = v.transpose(0, 2, 1, 3).reshape(b * kvh, sk, hd)

    kernel = functools.partial(
        _fwd_kernel, causal=causal, window=window,
        block_q=block_q, block_k=block_k, sm_scale=1.0 / math.sqrt(hd),
        q_off=sk - sq,
    )
    out = pl.pallas_call(
        kernel,
        grid=(b * h, nq, nk),
        in_specs=[
            pl.BlockSpec((1, block_q, hd), lambda bh, i, j: (bh, i, 0)),
            pl.BlockSpec((1, block_k, hd), lambda bh, i, j, rep=rep: (bh // rep, j, 0)),
            pl.BlockSpec((1, block_k, hd), lambda bh, i, j, rep=rep: (bh // rep, j, 0)),
        ],
        out_specs=pl.BlockSpec((1, block_q, hd), lambda bh, i, j: (bh, i, 0)),
        out_shape=jax.ShapeDtypeStruct((b * h, sq, hd), q.dtype),
        scratch_shapes=[
            # (bq, hd) f32 accumulator + (bq,) running max / denom in VMEM
            pltpu.VMEM((block_q, hd), jnp.float32),
            pltpu.VMEM((block_q,), jnp.float32),
            pltpu.VMEM((block_q,), jnp.float32),
        ],
        interpret=interpret,
    )(qf, kf, vf)
    return out.reshape(b, h, sq, hd).transpose(0, 2, 1, 3)
