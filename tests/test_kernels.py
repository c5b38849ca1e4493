"""Pallas kernel sweeps: shapes x dtypes x masks vs the pure-jnp oracles
(interpret mode on CPU; same kernels run compiled on TPU).
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels import ops, ref

RNG = np.random.default_rng(7)


def _mk_qkv(b, sq, sk, h, kv, hd, dtype):
    q = jnp.asarray(RNG.normal(size=(b, sq, h, hd)), dtype)
    k = jnp.asarray(RNG.normal(size=(b, sk, kv, hd)), dtype)
    v = jnp.asarray(RNG.normal(size=(b, sk, kv, hd)), dtype)
    return q, k, v


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize(
    "b,sq,sk,h,kv,hd,causal,window,bq,bk",
    [
        (2, 128, 128, 4, 2, 16, True, 0, 32, 64),
        (1, 256, 256, 4, 4, 32, True, 64, 64, 64),
        (2, 64, 128, 2, 1, 16, True, 0, 32, 32),
        (1, 64, 64, 8, 8, 64, False, 0, 64, 64),
        (1, 512, 512, 2, 2, 16, True, 128, 128, 128),
    ],
)
def test_flash_attention_sweep(b, sq, sk, h, kv, hd, causal, window, bq, bk, dtype):
    q, k, v = _mk_qkv(b, sq, sk, h, kv, hd, dtype)
    out = ops.flash_attention(q, k, v, causal, window, bq, bk)
    want = ref.attention_ref(q, k, v, causal=causal, window=window)
    tol = 2e-5 if dtype == jnp.float32 else 2e-2
    np.testing.assert_allclose(
        np.asarray(out, np.float32), np.asarray(want, np.float32),
        atol=tol, rtol=tol,
    )


def test_flash_attention_grads_match_reference():
    q, k, v = _mk_qkv(2, 128, 128, 4, 2, 16, jnp.float32)

    def loss_k(fn, *args):
        return (fn(*args) ** 2).sum()

    g1 = jax.grad(lambda q, k, v: loss_k(
        lambda *a: ops.flash_attention(*a, True, 32, 32, 64), q, k, v
    ), argnums=(0, 1, 2))(q, k, v)
    g2 = jax.grad(lambda q, k, v: loss_k(
        lambda *a: ref.attention_ref(*a, causal=True, window=32), q, k, v
    ), argnums=(0, 1, 2))(q, k, v)
    for a, b in zip(g1, g2):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   atol=5e-5, rtol=5e-4)


@pytest.mark.parametrize("ba,s,di,ds,chunk", [
    (2, 64, 128, 8, 16),
    (1, 128, 512, 16, 64),
    (3, 32, 256, 4, 32),
])
def test_selective_scan_sweep(ba, s, di, ds, chunk):
    x = jnp.asarray(RNG.normal(size=(ba, s, di)), jnp.float32)
    dt = jnp.asarray(RNG.uniform(1e-3, 0.1, (ba, s, di)), jnp.float32)
    A = -jnp.asarray(RNG.uniform(0.5, 2.0, (di, ds)), jnp.float32)
    B = jnp.asarray(RNG.normal(size=(ba, s, ds)), jnp.float32)
    C = jnp.asarray(RNG.normal(size=(ba, s, ds)), jnp.float32)
    y, sf = ops.selective_scan(x, dt, A, B, C, chunk)
    y2, sf2 = ref.selective_scan_ref(x, dt, A, B, C, chunk=8)
    np.testing.assert_allclose(np.asarray(y), np.asarray(y2), atol=1e-4, rtol=1e-4)
    np.testing.assert_allclose(np.asarray(sf), np.asarray(sf2), atol=1e-4, rtol=1e-4)


def test_selective_scan_matches_sequential():
    """The chunked oracle itself must equal a naive per-step recurrence."""
    ba, s, di, ds = 1, 16, 8, 4
    x = jnp.asarray(RNG.normal(size=(ba, s, di)), jnp.float32)
    dt = jnp.asarray(RNG.uniform(1e-3, 0.1, (ba, s, di)), jnp.float32)
    A = -jnp.asarray(RNG.uniform(0.5, 2.0, (di, ds)), jnp.float32)
    B = jnp.asarray(RNG.normal(size=(ba, s, ds)), jnp.float32)
    C = jnp.asarray(RNG.normal(size=(ba, s, ds)), jnp.float32)
    y, sf = ref.selective_scan_ref(x, dt, A, B, C, chunk=4)
    st = jnp.zeros((ba, di, ds))
    ys = []
    for t in range(s):
        yt, st = ref.selective_scan_step_ref(st, x[:, t], dt[:, t], A, B[:, t], C[:, t])
        ys.append(yt)
    np.testing.assert_allclose(np.asarray(y), np.stack(ys, 1), atol=1e-5, rtol=1e-5)
    np.testing.assert_allclose(np.asarray(sf), np.asarray(st), atol=1e-5, rtol=1e-5)


@pytest.mark.parametrize("e,n,block", [(1, 64, 32), (4, 100, 64), (2, 672, 512)])
def test_node_power_sweep(e, n, block):
    cpu = jnp.asarray(RNG.uniform(0, 1, (e, n)), jnp.float32)
    gpu = jnp.asarray(RNG.uniform(0, 1, (e, n)), jnp.float32)
    up = jnp.asarray(RNG.integers(0, 2, (e, n)), jnp.float32)
    idle = jnp.asarray(RNG.uniform(80, 300, (n,)), jnp.float32)
    cd = jnp.asarray(RNG.uniform(100, 400, (n,)), jnp.float32)
    gd = jnp.asarray(RNG.uniform(0, 600, (n,)), jnp.float32)
    mx = idle + cd + gd
    kw = dict(rect_peak=0.965, rect_load=0.55, rect_curv=0.12, conv_eff=0.975)
    from repro.kernels.node_power import node_power_pallas

    it, inp = node_power_pallas(cpu, gpu, idle, cd, gd, up, mx,
                                block_n=block, interpret=True, **kw)
    it2, inp2 = ref.node_power_ref(cpu, gpu, idle, cd, gd, up, mx, **kw)
    np.testing.assert_allclose(np.asarray(it), np.asarray(it2), rtol=1e-5)
    np.testing.assert_allclose(np.asarray(inp), np.asarray(inp2), rtol=1e-5)
