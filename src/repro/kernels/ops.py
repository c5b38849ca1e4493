"""Jit'd public wrappers around the Pallas kernels.

- Interpret mode is chosen by the backend alone (``interpret_mode``): the
  Pallas interpreter on the CPU backend, compiled Mosaic kernels on a TPU.
  The ``*_pallas`` entry points take ``interpret`` without a default, so a
  caller that bypasses these wrappers states the mode it wants.
- ``flash_attention`` is differentiable: forward = Pallas kernel, backward
  = jax.vjp through the jnp chunked-online-softmax reference (identical
  math; the TPU backward kernel is an optimization left to ops parity).
"""

from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp

from repro.kernels import ref as _ref
from repro.kernels.flash_attn import flash_attention_fwd
from repro.kernels.mamba_scan import selective_scan_pallas
from repro.kernels.node_power import node_power_pallas, power_scatter_pallas
from repro.kernels.rack_thermal import rack_thermal_pallas


def interpret_mode() -> bool:
    """True on the CPU backend (Pallas interpreter), False elsewhere."""
    return jax.default_backend() == "cpu"


# ---------------------------------------------------------------------------
@partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5, 6))
def flash_attention(q, k, v, causal=True, window=0, block_q=512, block_k=1024):
    return flash_attention_fwd(
        q, k, v, causal=causal, window=window,
        block_q=block_q, block_k=block_k, interpret=interpret_mode(),
    )


def _fa_fwd(q, k, v, causal, window, block_q, block_k):
    out = flash_attention(q, k, v, causal, window, block_q, block_k)
    return out, (q, k, v)


def _fa_bwd(causal, window, block_q, block_k, res, g):
    q, k, v = res
    from repro.models.layers import attention_chunked

    def f(q, k, v):
        return attention_chunked(
            q, k, v, causal=causal, window=window,
            block_q=block_q, block_k=block_k,
        )

    _, vjp = jax.vjp(f, q, k, v)
    return vjp(g)


flash_attention.defvjp(_fa_fwd, _fa_bwd)


# ---------------------------------------------------------------------------
@partial(jax.custom_vjp, nondiff_argnums=(5,))
def selective_scan(x, dt, A, B, C, chunk=64):
    return selective_scan_pallas(
        x, dt, A, B, C, chunk=chunk, interpret=interpret_mode()
    )


def _ss_fwd(x, dt, A, B, C, chunk):
    out = selective_scan(x, dt, A, B, C, chunk)
    return out, (x, dt, A, B, C)


def _ss_bwd(chunk, res, g):
    x, dt, A, B, C = res
    gy, gs = g

    def f(x, dt, A, B, C):
        return _ref.selective_scan_ref(x, dt, A, B, C, chunk=chunk)

    _, vjp = jax.vjp(f, x, dt, A, B, C)
    return vjp((gy, gs))


selective_scan.defvjp(_ss_fwd, _ss_bwd)


# ---------------------------------------------------------------------------
def node_power(cpu_frac, gpu_frac, idle_w, cpu_dyn_w, gpu_dyn_w, node_up,
               node_max_w, *, rect_peak, rect_load, rect_curv, conv_eff):
    return node_power_pallas(
        cpu_frac, gpu_frac, idle_w, cpu_dyn_w, gpu_dyn_w, node_up, node_max_w,
        rect_peak=rect_peak, rect_load=rect_load, rect_curv=rect_curv,
        conv_eff=conv_eff, interpret=interpret_mode(),
    )


def power_scatter(place_flat, cpu_abs, gpu_abs, cap_cpu, cap_gpu, idle_w,
                  cpu_dyn_w, gpu_dyn_w, node_up, node_max_w, *,
                  rect_peak, rect_load, rect_curv, conv_eff):
    """Fused placement-scatter + power chain (job table -> per-node power).
    Returns (node_it_w, node_input_w, cpu_frac, gpu_frac)."""
    return power_scatter_pallas(
        place_flat, cpu_abs, gpu_abs, cap_cpu, cap_gpu, idle_w, cpu_dyn_w,
        gpu_dyn_w, node_up, node_max_w,
        rect_peak=rect_peak, rect_load=rect_load, rect_curv=rect_curv,
        conv_eff=conv_eff, interpret=interpret_mode(),
    )


def rack_thermal(node_heat_w, node_rack, rack_outlet_c, supply_c, rack_r_th,
                 *, alpha):
    """Fused rack-heat scatter + RC outlet-temp update (core.thermal).
    Returns (new_outlet_c, rack_heat_w)."""
    return rack_thermal_pallas(
        node_heat_w, node_rack, rack_outlet_c, supply_c, rack_r_th,
        alpha=alpha, interpret=interpret_mode(),
    )
