"""Policy x scenario sweep: ONE jitted call simulates a fleet of
datacenter replicas crossing scheduling policies (selection x placement,
policy-as-data — zero recompiles across the grid) with heterogeneous grid
scenarios — parametric diurnal carbon, trace-driven carbon (synthetic
grid-operator feed), demand-response power-cap events, heatwaves — and
compares sustainability outcomes per (policy, scenario) cell.

  PYTHONPATH=src python examples/scenario_sweep.py [--steps 1200]
      [--selects fcfs,sjf] [--places first_fit,green]
"""

import argparse
import os
import sys

sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", "src"))

import jax
import numpy as np

from repro.configs.sim import tiny_cluster
from repro.core import (
    build_statics,
    fleet_summary,
    init_state,
    load_jobs,
    policy_grid,
    policy_scenario_grid,
    run_fleet,
)
from repro.data import synth_grid_trace, synth_workload
from repro.scenarios import (
    carbon_trace,
    default_scenario,
    demand_response,
    heatwave,
    solar_heavy,
)
from repro.utils.compile_cache import enable_compile_cache


def build_scenarios(cfg, horizon_s):
    """5 scenario families (>= 3 distinct kinds: parametric carbon,
    trace-driven carbon, scheduled power-cap event)."""
    values, dt = synth_grid_trace("carbon", horizon_s * 4, dt=60.0, seed=1)
    nameplate = 1.3 * cfg.nameplate_it_w
    return [
        ("diurnal", default_scenario(cfg)),
        ("solar_heavy", solar_heavy(cfg)),
        ("carbon_trace", carbon_trace(cfg, values, dt)),
        ("demand_response", demand_response(
            cfg, cap_w=0.45 * nameplate, event_start_s=horizon_s * 0.3,
            event_len_s=horizon_s * 0.3)),
        ("heatwave", heatwave(cfg)),
    ]


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--steps", type=int, default=1200)
    ap.add_argument("--selects", default="fcfs,sjf,easy",
                    help="comma-separated job-selection policies")
    ap.add_argument("--places", default="first_fit,green",
                    help="comma-separated node-placement strategies")
    args = ap.parse_args()

    cfg = tiny_cluster()
    horizon = args.steps * cfg.dt
    jobs, bank = synth_workload(cfg, 32, horizon * 0.75, seed=0)
    statics = build_statics(cfg, bank)
    state = load_jobs(init_state(cfg, statics, jax.random.key(0)), jobs)

    scn_items = build_scenarios(cfg, horizon)
    scn_names = [n for n, _ in scn_items]
    selects = [s.strip() for s in args.selects.split(",") if s.strip()]
    places = [p.strip() for p in args.places.split(",") if p.strip()]
    pol_names, grid = policy_grid(selects, places)
    # cross the policy grid with the scenario axis: replica i runs policy
    # i // S under scenario i % S, all inside ONE compiled vmapped call —
    # policies are traced (select_id, place_id) data, so the grid costs a
    # single XLA compile no matter how many cells it has
    pols, scns = policy_scenario_grid(grid, [s for _, s in scn_items])
    R = len(pol_names) * len(scn_names)
    print(f"fleet: {len(pol_names)} policies x {len(scn_names)} scenarios "
          f"= {R} replicas x {args.steps} steps, one jitted vmap+scan call")
    # summary_only: windowed reductions in the scan carry — fleet memory is
    # O(replicas), independent of --steps (full per-step traces: drop it)
    finals, tel = run_fleet(cfg, statics, state, args.steps,
                            scenarios=scns, policies=pols, summary_only=True)
    rows = fleet_summary(finals)
    cell = [(p, s) for p in pol_names for s in scn_names]

    print(f"\n{'policy':22s} {'scenario':16s} {'energy_kwh':>11s} "
          f"{'carbon_kg':>10s} {'cost_usd':>9s} {'completed':>9s} "
          f"{'peak_kw':>8s}")
    peak_w = np.asarray(tel.max_facility_w)
    for i, (p, s) in enumerate(cell):
        r = rows[i]
        print(f"{p:22s} {s:16s} {r['energy_kwh']:11.3f} "
              f"{r['carbon_kg']:10.3f} {r['elec_cost_usd']:9.4f} "
              f"{r['completed']:9.1f} {peak_w[i] / 1e3:8.2f}")


if __name__ == "__main__":
    enable_compile_cache()
    main()
