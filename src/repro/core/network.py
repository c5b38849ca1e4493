"""Inter-job network congestion model (Lassen-style bytes-in/out coupling,
paper refs [7],[14]): aggregate running-job traffic vs. bisection bandwidth
gives a global contention factor that slows every communicating job's
progress — which in turn stretches runtimes and energy.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.configs.sim import SimConfig
from repro.core.state import RUNNING, SimState, Statics


def congestion_slowdown(cfg: SimConfig, state: SimState, statics: Statics):
    """Returns (per-job progress rate in (0,1], network load fraction)."""
    running = (state.jstate == RUNNING).astype(jnp.float32)
    # banked (W, J) traffic table: gather this replica's row through the
    # traced workload id; a streamed trace's (n,) table through each
    # slot's trace id (see Statics docstring)
    if statics.trace is not None:
        net_tx = statics.net_tx[jnp.maximum(state.stream.tid, 0)]
    elif statics.net_tx.ndim == 1:
        net_tx = statics.net_tx
    else:
        net_tx = statics.net_tx[state.workload]
    # jobs spanning n nodes inject n * net_tx GB/s into the fabric
    tx = net_tx * state.n_nodes.astype(jnp.float32) * running
    load = jnp.sum(tx) / jnp.maximum(cfg.bisection_gbps, 1e-6)
    over = jnp.maximum(load - cfg.congestion_knee, 0.0)
    factor = 1.0 + over ** cfg.congestion_exp
    # only network-active jobs are slowed; CPU-bound jobs keep full rate
    slowed = 1.0 / factor
    rate = jnp.where(net_tx > 0, slowed, 1.0)
    return jnp.where(running > 0, rate, 0.0), load
