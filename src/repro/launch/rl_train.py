"""RL scheduler training driver (the paper's Fig. 2 pipeline): build the
TX-GAIA (or tiny) twin, wrap it in the Gym-style env, train PPO, write the
reward history + a power trace under the learned policy.

  PYTHONPATH=src python -m repro.launch.rl_train --cluster tiny \
      --iterations 30 --out experiments/rl
"""

from __future__ import annotations

import argparse
import json
import os

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs.sim import tiny_cluster, tx_gaia
from repro.data import synth_workload
from repro.envs import SchedEnv
from repro.rl import PPOConfig, ppo_train
from repro.utils.compile_cache import enable_compile_cache


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--cluster", default="tiny", choices=["tiny", "tx-gaia"])
    ap.add_argument("--iterations", type=int, default=30)
    ap.add_argument("--n-envs", type=int, default=8)
    ap.add_argument("--rollout", type=int, default=32)
    ap.add_argument("--episode-steps", type=int, default=32)
    ap.add_argument("--n-jobs", type=int, default=40)
    ap.add_argument("--horizon", type=float, default=1800.0)
    ap.add_argument("--n-workloads", type=int, default=4)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--lr", type=float, default=3e-4)
    ap.add_argument("--out", default="")
    ap.add_argument("--ckpt", default="")
    ap.add_argument("--resume", action="store_true")
    args = ap.parse_args(argv)

    if args.cluster == "tiny":
        cfg = tiny_cluster(sched_max_candidates=4)
    else:
        cfg = tx_gaia(max_jobs=256, max_nodes_per_job=16)

    wls = [
        synth_workload(cfg, args.n_jobs, args.horizon, seed=args.seed + s)
        for s in range(args.n_workloads)
    ]
    env = SchedEnv(cfg, wls, episode_steps=args.episode_steps,
                   sim_steps_per_action=15)
    print(f"cluster={cfg.name} nodes={cfg.n_nodes} obs={env.obs_dim} "
          f"actions={env.n_actions}")

    ppo_cfg = PPOConfig(n_envs=args.n_envs, rollout_len=args.rollout,
                        lr=args.lr)
    history = []

    def log(it, stats):
        history.append({"iteration": it, **stats})
        print(f"it {it:3d} ep_return={stats['mean_episode_return']:8.2f} "
              f"reward={stats['mean_reward']:7.3f} "
              f"kl={stats['approx_kl']:.4f}")

    params, hist = ppo_train(
        env, cfg=ppo_cfg, n_iterations=args.iterations, seed=args.seed,
        log=log, checkpoint_dir=args.ckpt or None, resume=args.resume,
    )

    if args.out:
        os.makedirs(args.out, exist_ok=True)
        with open(os.path.join(args.out, "ppo_history.json"), "w") as f:
            json.dump(history, f, indent=1)
        # paper Fig 2 (bottom-right): power trace under the learned policy
        from repro.rl.policy import ActorCritic

        policy = ActorCritic(env.obs_dim, env.n_actions)
        st, obs = env.reset(jax.random.key(123))

        def step(carry, _):
            st, obs, key = carry
            key, k = jax.random.split(key)
            logits, _ = policy.apply(params, obs)
            action = jnp.argmax(logits)
            st, obs, r, d, info = env.step(st, action)
            return (st, obs, key), (info["facility_w"], r)

        (_, _, _), (pw, rw) = jax.lax.scan(
            step, (st, obs, jax.random.key(7)), None,
            length=args.episode_steps,
        )
        np.save(os.path.join(args.out, "power_trace_rl.npy"), np.asarray(pw))
        print(f"wrote {args.out}/ppo_history.json and power_trace_rl.npy")
    return params, history


if __name__ == "__main__":
    enable_compile_cache()
    main()
