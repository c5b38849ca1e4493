"""Trace replay + rescheduling of a SuperCloud-schema dataset.

Writes a synthetic dataset in the MIT SuperCloud CSV schema (the real one
is not downloadable offline), parses it with the schema-faithful loader,
replays the recorded schedule, then re-schedules the same jobs under
FCFS / SJF / EASY-backfill and compares sustainability metrics — the
paper's core "tool to study optimal scheduling policies" workflow. The
trace holds more jobs than the job table has slots, so it streams through
the table: each freed slot takes the next job of the trace.

  PYTHONPATH=src python examples/replay_supercloud.py
"""

import os
import sys
import tempfile

sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", "src"))

import jax

from repro.configs.sim import tx_gaia
from repro.core import build_statics, init_state, load_jobs, run_episode, summary
from repro.data import load_supercloud, write_supercloud_csvs
from repro.utils.compile_cache import enable_compile_cache


def main():
    cfg = tx_gaia(max_jobs=64, max_nodes_per_job=8)
    path = tempfile.mkdtemp(prefix="supercloud_")
    write_supercloud_csvs(path, cfg, n_jobs=96, horizon_s=1800.0, seed=42)
    print(f"synthetic SuperCloud dataset at {path}:")
    for f in sorted(os.listdir(path)):
        print(f"  {f} ({os.path.getsize(os.path.join(path, f)):,} bytes)")

    jobs, bank = load_supercloud(path, cfg)
    statics = build_statics(cfg, bank, jobs=jobs)     # 96 jobs, 64 slots
    state = load_jobs(init_state(cfg, statics, jax.random.key(0)), jobs)

    print(f"\n{'policy':10s} {'completed':>9s} {'energy kWh':>11s} "
          f"{'carbon kg':>9s} {'slowdown':>8s} {'wait s':>8s} {'PUE':>6s}")
    for sched in ("replay", "fcfs", "sjf", "easy", "priority"):
        fs, _ = jax.jit(
            lambda s, sc=sched: run_episode(cfg, statics, s, 5400, sc)
        )(state)
        s = summary(fs, statics=statics)
        print(f"{sched:10s} {s['completed']:9.0f} {s['energy_kwh']:11.1f} "
              f"{s['carbon_kg']:9.2f} {s['mean_slowdown']:8.2f} "
              f"{s['mean_wait_s']:8.0f} {s['avg_pue']:6.3f}")


if __name__ == "__main__":
    enable_compile_cache()
    main()
