"""End-to-end driver (the paper's central experiment, Fig. 2): train a PPO
agent to schedule jobs on the datacenter twin for an energy/carbon/
throughput reward, then compare the learned policy against the classical
schedulers.

  PYTHONPATH=src python examples/rl_scheduler.py            # ~5 min CPU
  PYTHONPATH=src python examples/rl_scheduler.py --fast     # smoke
"""

import argparse
import os
import sys

sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", "src"))

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs.sim import tiny_cluster
from repro.core import build_statics, init_state, load_jobs, run_episode, summary
from repro.data import synth_workload
from repro.envs import SchedEnv
from repro.rl import ActorCritic, PPOConfig, ppo_train
from repro.utils.compile_cache import enable_compile_cache


def evaluate_policy(env, policy, params, key, episodes=4):
    """Greedy rollout of the learned policy; returns per-episode stats."""
    totals = []
    for e in range(episodes):
        st, obs = env.reset(jax.random.fold_in(key, e))
        ret, energy, carbon, done_jobs = 0.0, 0.0, 0.0, 0.0
        for _ in range(env.episode_steps):
            logits, _ = policy.apply(params, obs)
            st, obs, r, d, info = env.step(st, jnp.argmax(logits))
            ret += float(r)
            energy += float(info["energy_kwh"])
            carbon += float(info["carbon_kg"])
            done_jobs += float(info["completed"])
        totals.append((ret, energy, carbon, done_jobs))
    return np.mean(totals, axis=0)


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--fast", action="store_true")
    ap.add_argument("--iterations", type=int, default=40)
    args = ap.parse_args()
    iters = 4 if args.fast else args.iterations

    cfg = tiny_cluster(sched_max_candidates=4)
    wls = [synth_workload(cfg, 40, 1500.0, seed=s) for s in range(4)]
    env = SchedEnv(cfg, wls, episode_steps=24, sim_steps_per_action=15)
    print(f"env: obs={env.obs_dim} actions={env.n_actions} "
          f"({cfg.n_nodes}-node twin)")

    hist_rewards = []
    params, hist = ppo_train(
        env,
        cfg=PPOConfig(n_envs=8, rollout_len=24, lr=3e-4),
        n_iterations=iters,
        log=lambda it, s: (
            hist_rewards.append(s["mean_episode_return"]),
            print(f"  it {it:3d} episodic_return={s['mean_episode_return']:8.2f}"),
        ),
    )
    first = np.mean(hist_rewards[:3])
    last = np.mean(hist_rewards[-3:])
    print(f"\nPPO reward: first3={first:.2f} -> last3={last:.2f} "
          f"({'improved' if last > first else 'no improvement yet'})")

    # learned policy vs classical schedulers on the same workload
    policy = ActorCritic(env.obs_dim, env.n_actions)
    ret, energy, carbon, jobs_done = evaluate_policy(
        env, policy, params, jax.random.key(99))
    print(f"\nRL policy   : jobs={jobs_done:5.1f} energy={energy:7.2f} kWh "
          f"carbon={carbon:6.2f} kg")

    jobs, bank = wls[0]
    statics = build_statics(cfg, bank)
    st = load_jobs(init_state(cfg, statics, jax.random.key(0)), jobs)
    horizon = env.episode_steps * env.sim_steps_per_action
    for sched in ("fcfs", "sjf", "easy"):
        fs, _ = jax.jit(
            lambda s, sc=sched: run_episode(cfg, statics, s, horizon, sc)
        )(st)
        s = summary(fs)
        print(f"{sched:12s}: jobs={s['completed']:5.1f} "
              f"energy={s['energy_kwh']:7.2f} kWh "
              f"carbon={s['carbon_kg']:6.2f} kg")


if __name__ == "__main__":
    enable_compile_cache()
    main()
