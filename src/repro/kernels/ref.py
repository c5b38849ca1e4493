"""Pure-jnp oracles for every Pallas kernel (and shared model math).

These are the correctness references the kernel tests sweep against, and
the XLA fallback paths the models use on CPU / in the dry-run.
"""

from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp

NEG_INF = -1e30


# ---------------------------------------------------------------------------
# flash attention oracle: small, fully materialized
def attention_ref(q, k, v, *, causal=True, window=0):
    """q: (B,Sq,H,hd); k,v: (B,Sk,Kv,hd). Returns (B,Sq,H,hd)."""
    b, sq, h, hd = q.shape
    sk, kv = k.shape[1], k.shape[2]
    rep = h // kv
    k = jnp.repeat(k, rep, axis=2)
    v = jnp.repeat(v, rep, axis=2)
    logits = jnp.einsum("bqhd,bkhd->bhqk", q.astype(jnp.float32), k.astype(jnp.float32))
    logits /= jnp.sqrt(jnp.float32(hd))
    dpos = (jnp.arange(sq)[:, None] + (sk - sq)) - jnp.arange(sk)[None, :]
    mask = jnp.ones((sq, sk), bool)
    if causal:
        mask &= dpos >= 0
    if window > 0:
        mask &= dpos < window
    logits = jnp.where(mask[None, None], logits, NEG_INF)
    p = jax.nn.softmax(logits, axis=-1)
    return jnp.einsum("bhqk,bkhd->bqhd", p, v.astype(jnp.float32)).astype(q.dtype)


# ---------------------------------------------------------------------------
# selective scan (mamba) oracle
def selective_scan_ref(x, dt, A, B, C, *, chunk: int = 64):
    """Chunked associative selective scan.

    x:  (Ba, S, di)   gated input
    dt: (Ba, S, di)   positive step sizes (already softplus'd)
    A:  (di, ds)      negative state matrix (A = -exp(A_log))
    B:  (Ba, S, ds)   input mix
    C:  (Ba, S, ds)   output mix
    returns y: (Ba, S, di), final_state: (Ba, di, ds)

    Recurrence: s_t = exp(dt_t * A) * s_{t-1} + dt_t * B_t * x_t
                y_t = sum_ds (s_t * C_t)
    """
    ba, s, di = x.shape
    ds = A.shape[-1]
    chunk = min(chunk, s)
    pad = (-s) % chunk
    if pad:
        # dt=0 on padded steps -> decay=1, contribution=0: state unchanged
        x = jnp.pad(x, ((0, 0), (0, pad), (0, 0)))
        dt = jnp.pad(dt, ((0, 0), (0, pad), (0, 0)))
        B = jnp.pad(B, ((0, 0), (0, pad), (0, 0)))
        C = jnp.pad(C, ((0, 0), (0, pad), (0, 0)))
    s_orig, s = s, s + pad
    nchunks = s // chunk

    xr = x.reshape(ba, nchunks, chunk, di)
    dtr = dt.reshape(ba, nchunks, chunk, di)
    Br = B.reshape(ba, nchunks, chunk, ds)
    Cr = C.reshape(ba, nchunks, chunk, ds)

    @partial(jax.checkpoint, policy=jax.checkpoint_policies.nothing_saveable)
    def chunk_body(state, inp):
        xc, dtc, Bc, Cc = inp  # (Ba, chunk, ...)
        a = jnp.exp(dtc[..., None] * A)                        # (Ba,c,di,ds)
        b = (dtc * xc)[..., None] * Bc[:, :, None, :]          # (Ba,c,di,ds)

        def combine(l, r):
            al, bl = l
            ar, br = r
            return al * ar, bl * ar + br

        a_cum, b_cum = jax.lax.associative_scan(combine, (a, b), axis=1)
        st = a_cum * state[:, None] + b_cum                    # (Ba,c,di,ds)
        y = jnp.einsum("bcds,bcs->bcd", st, Cc)
        return st[:, -1], y

    def scan_body(state, inp):
        state, y = chunk_body(state, inp)
        return state, y

    s0 = jnp.zeros((ba, di, ds), x.dtype)
    final, ys = jax.lax.scan(
        scan_body,
        s0,
        (
            xr.transpose(1, 0, 2, 3),
            dtr.transpose(1, 0, 2, 3),
            Br.transpose(1, 0, 2, 3),
            Cr.transpose(1, 0, 2, 3),
        ),
    )
    y = ys.transpose(1, 0, 2, 3).reshape(ba, s, di)
    return y[:, :s_orig], final


def selective_scan_step_ref(state, x, dt, A, B, C):
    """Single decode step. state: (Ba,di,ds); x,dt: (Ba,di); B,C: (Ba,ds)."""
    a = jnp.exp(dt[..., None] * A)
    state = a * state + (dt * x)[..., None] * B[:, None, :]
    y = jnp.einsum("bds,bs->bd", state, C)
    return y, state


# ---------------------------------------------------------------------------
# node power chain oracle (the simulator's per-step hot loop, which runs for
# every node of every vmapped environment): IT power from utilization
# fractions -> rectifier-efficiency parabola -> conversion loss.
def node_power_ref(
    cpu_frac,         # (..., N) utilized fraction of node CPU capacity
    gpu_frac,         # (..., N)
    idle_w,           # (N,)
    cpu_dyn_w,        # (N,)
    gpu_dyn_w,        # (N,)
    node_up,          # (..., N) 1.0 if node is healthy
    node_max_w,       # (N,)
    *,
    rect_peak: float,
    rect_load: float,
    rect_curv: float,
    conv_eff: float,
):
    """Returns (node_it_w, node_input_w) with the leading env batch dims of
    cpu_frac. eta(load) = clip(peak - curv*(load - peak_load)^2, 0.5, 1)."""
    it = idle_w + cpu_frac * cpu_dyn_w + gpu_frac * gpu_dyn_w
    it = it * node_up
    load_frac = jnp.clip(it / jnp.maximum(node_max_w, 1.0), 0.0, 1.2)
    eta_rect = jnp.clip(
        rect_peak - rect_curv * jnp.square(load_frac - rect_load), 0.5, 1.0
    )
    input_w = it / (eta_rect * conv_eff)
    return it, input_w


def rack_thermal_ref(
    node_heat_w,      # (N,) per-node input power (all of it becomes heat)
    node_rack,        # (N,) int32 rack id per node, in [0, R)
    rack_outlet_c,    # (R,) current outlet temperatures
    supply_c,         # scalar cooling supply temperature
    rack_r_th,        # (R,) degC per W of rack heat
    *,
    alpha: float,     # per-tick RC relaxation factor 1 - exp(-dt/tau)
):
    """Fused rack-heat scatter + first-order RC outlet-temp update oracle.

    T' = T + alpha * (supply + heat * R_th - T). The node->rack reduction
    uses the same one-hot matmul as the Pallas kernel (not segment_sum) so
    both paths accumulate in the identical order and agree bitwise on CPU.
    The contraction runs at ``Precision.HIGHEST``: the TPU default would
    round the heat operand to bf16.
    Returns (new_outlet_c, rack_heat_w), each (R,).
    """
    r = rack_outlet_c.shape[0]
    onehot = (node_rack[:, None] == jnp.arange(r, dtype=jnp.int32)[None, :])
    heat = jnp.dot(node_heat_w[None, :].astype(jnp.float32),
                   onehot.astype(jnp.float32),
                   precision=jax.lax.Precision.HIGHEST,
                   preferred_element_type=jnp.float32)[0]
    t_ss = supply_c + heat * rack_r_th
    new_t = rack_outlet_c + jnp.float32(alpha) * (t_ss - rack_outlet_c)
    return new_t, heat


def power_scatter_ref(
    place_flat,       # (J*K,) int32 node ids, -1 = unused placement slot
    cpu_abs,          # (J*K,) absolute utilized cpu cores per slot
    gpu_abs,          # (J*K,) absolute utilized gpus per slot
    cap_cpu,          # (N,) node cpu capacity
    cap_gpu,          # (N,)
    idle_w,           # (N,)
    cpu_dyn_w,        # (N,)
    gpu_dyn_w,        # (N,)
    node_up,          # (N,) 1.0 if node is healthy
    node_max_w,       # (N,)
    *,
    rect_peak: float,
    rect_load: float,
    rect_curv: float,
    conv_eff: float,
):
    """Fused placement-scatter + power-chain oracle: job table -> per-node
    IT/input power and load fractions in one logical pass.

    Returns (node_it_w, node_input_w, cpu_frac, gpu_frac), each (N,).
    """
    N = idle_w.shape[0]
    safe = jnp.where(place_flat >= 0, place_flat, 0)   # invalid slots add 0
    cpu_node = jnp.zeros((N,), jnp.float32).at[safe].add(cpu_abs, mode="drop")
    gpu_node = jnp.zeros((N,), jnp.float32).at[safe].add(gpu_abs, mode="drop")
    cpu_frac = jnp.clip(cpu_node / jnp.maximum(cap_cpu, 1e-6), 0.0, 1.0)
    gpu_frac = jnp.clip(gpu_node / jnp.maximum(cap_gpu, 1e-6), 0.0, 1.0)
    it, input_w = node_power_ref(
        cpu_frac, gpu_frac, idle_w, cpu_dyn_w, gpu_dyn_w, node_up,
        node_max_w, rect_peak=rect_peak, rect_load=rect_load,
        rect_curv=rect_curv, conv_eff=conv_eff,
    )
    return it, input_w, cpu_frac, gpu_frac
