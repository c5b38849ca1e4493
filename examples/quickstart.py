"""Quickstart: build the MIT-SuperCloud-style digital twin, replay a
workload, print RAPS-style runtime stats (paper Fig. 2 top-left).

  PYTHONPATH=src python examples/quickstart.py
"""

import os
import sys

sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", "src"))

import jax

from repro.configs.sim import tx_gaia
from repro.core import build_statics, init_state, load_jobs, run_episode, summary
from repro.data import synth_workload
from repro.utils.compile_cache import enable_compile_cache


def main():
    # TX-GAIA twin: 448 dual-V100 nodes + 224 CPU nodes, multi-tenant
    cfg = tx_gaia(max_jobs=256, max_nodes_per_job=16)
    jobs, bank = synth_workload(cfg, n_jobs=200, horizon_s=3600.0, seed=0)
    statics = build_statics(cfg, bank)
    state = load_jobs(init_state(cfg, statics, jax.random.key(0)), jobs)

    print(f"twin: {cfg.name} ({cfg.n_nodes} nodes), 200 jobs, 1h horizon")
    final, outs = jax.jit(
        lambda s: run_episode(cfg, statics, s, 3600, "replay")
    )(state)

    s = summary(final)
    print("\n--- simulation runtime stats (dt=1s, trace quanta=10s) ---")
    for k, v in s.items():
        print(f"  {k:22s} {v:,.3f}")
    p = outs.facility_w
    print(f"  peak facility power    {float(p.max())/1e3:,.1f} kW")
    print(f"  min facility power     {float(p.min())/1e3:,.1f} kW")
    print(f"  power swing            {float(p.max()-p.min())/1e3:,.1f} kW "
          "(the utility-scale swing problem motivating the paper)")


if __name__ == "__main__":
    enable_compile_cache()
    main()
