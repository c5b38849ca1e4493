"""Virtual benchmarking of a speculative system (paper: "ExaDigiT can
create a virtual cloud system ... virtual prototyping of hardware/software
and virtual benchmarking of speculative systems").

The analytic performance model (Calculon-analogue) turns the assigned LM
architectures into datacenter jobs; the twin then answers a what-if:
how do energy, carbon and throughput change if the cooling plant degrades
(higher wet-bulb) or the rectifiers are upgraded?

  PYTHONPATH=src python examples/virtual_cloud.py
"""

import os
import sys

sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", "src"))

import jax

from repro.configs.sim import tx_gaia
from repro.core import build_statics, init_state, load_jobs, run_episode, summary
from repro.perfmodel import lm_jobs_workload, lm_training_job
from repro.utils.compile_cache import enable_compile_cache


def main():
    print("=== LM jobs from the performance model (Calculon-analogue) ===")
    for arch in ("qwen3-4b", "mixtral-8x22b", "gemma3-1b"):
        j = lm_training_job(arch, "train_4k", n_chips=64, token_budget=5e8)
        print(f"  {arch:15s} step={j['step_s']*1e3:7.1f} ms "
              f"dur={j['duration_s']/60:6.1f} min util={j['gpu_util']:.2f} "
              f"net={j['net_tx_gbps']:6.1f} GB/s bound={j['dominant']}")

    cfg = tx_gaia(max_jobs=64, max_nodes_per_job=16)
    jobs, bank = lm_jobs_workload(
        cfg, ["qwen3-4b", "mixtral-8x22b", "gemma3-1b", "granite-3-8b"],
        n_jobs=32, horizon_s=3600.0, seed=7,
    )

    scenarios = {
        "baseline": {},
        "hot day (+8C wetbulb)": {"wetbulb_mean_c": 24.0},
        "smart rectifiers": {"rect_eff_peak": 0.985, "rect_eff_curv": 0.04},
        "degraded network": {"bisection_gbps": 200.0, "congestion_knee": 0.2},
        "demand response 300kW": {"power_cap_w": 300_000.0},
    }
    print("\n=== what-if scenarios on the twin (same workload) ===")
    print(f"{'scenario':24s} {'energy kWh':>10s} {'carbon kg':>9s} "
          f"{'PUE':>6s} {'completed':>9s}")
    for name, overrides in scenarios.items():
        c = tx_gaia(max_jobs=64, max_nodes_per_job=16, **overrides)
        statics = build_statics(c, bank)
        st = load_jobs(init_state(c, statics, jax.random.key(0)), jobs)
        fs, _ = jax.jit(lambda s, c=c, st_=statics:
                        run_episode(c, st_, s, 5400, "easy"))(st)
        s = summary(fs)
        print(f"{name:24s} {s['energy_kwh']:10.1f} {s['carbon_kg']:9.2f} "
              f"{s['avg_pue']:6.3f} {s['completed']:9.0f}")


if __name__ == "__main__":
    enable_compile_cache()
    main()
