"""Sharded-path tests. jax locks the device count at first init, so these
run in a subprocess with xla_force_host_platform_device_count=8."""

import os
import subprocess
import sys
import textwrap

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _run_sub(code: str, timeout=560):
    env = dict(os.environ)
    env["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
    env["PYTHONPATH"] = os.path.join(ROOT, "src")
    r = subprocess.run(
        [sys.executable, "-c", textwrap.dedent(code)],
        capture_output=True, text=True, timeout=timeout, env=env,
    )
    assert r.returncode == 0, f"STDOUT:\n{r.stdout}\nSTDERR:\n{r.stderr}"
    return r.stdout


def test_sharded_train_step_matches_unsharded():
    """FSDP+TP on a (2,4) mesh must produce the same loss trajectory as the
    single-device run (numerical tolerance)."""
    out = _run_sub("""
        import jax, jax.numpy as jnp, numpy as np
        from jax.sharding import AxisType, NamedSharding
        from repro.configs import get_arch, reduced
        from repro.configs.base import ShapeConfig
        from repro.data.synth_lm import lm_batch_at
        from repro.models import init_params
        from repro.optim import AdamW
        from repro.sharding.ctx import make_ctx, UNSHARDED
        from repro.sharding.specs import batch_pspecs
        from repro.train.state import train_state_pspecs
        from repro.train.train_step import make_train_step

        cfg = reduced(get_arch("qwen3-4b"))
        opt = AdamW(lr=1e-3)
        params = init_params(cfg, jax.random.key(0))
        state0 = {"params": params, "opt": opt.init(params), "step": jnp.int32(0)}
        data = lambda i: lm_batch_at(i, vocab=cfg.vocab, batch=8, seq_len=64)

        # unsharded reference
        stepu = jax.jit(make_train_step(cfg, opt))
        su = state0
        ref = []
        for i in range(3):
            su, m = stepu(su, data(i))
            ref.append(float(m["loss"]))

        # sharded
        mesh = jax.make_mesh((2, 4), ("data", "model"),
                             axis_types=(AxisType.Auto,) * 2)
        ctx = make_ctx(False, tp_size=4, dp_size=2)
        shape = ShapeConfig("t", 64, 8, "train")
        sps = train_state_pspecs(cfg, ctx, opt, mesh)
        bps = batch_pspecs(cfg, shape, ctx)
        ns = lambda t: jax.tree.map(lambda p: NamedSharding(mesh, p), t)
        with mesh:
            steps = jax.jit(make_train_step(cfg, opt, ctx),
                            in_shardings=(ns(sps), ns(bps)),
                            out_shardings=(ns(sps), None))
            ss = jax.device_put(state0, ns(sps))
            got = []
            for i in range(3):
                ss, m = steps(ss, jax.device_put(data(i), ns(bps)))
                got.append(float(m["loss"]))
        np.testing.assert_allclose(ref, got, rtol=2e-3, atol=2e-3)
        print("LOSSES", ref, got)
    """)
    assert "LOSSES" in out


def test_elastic_checkpoint_restore_across_mesh_shapes():
    """Checkpoint written from a (2,4) mesh restores onto (8,1) and (1,1)
    (elastic scaling / shrink-to-recover)."""
    out = _run_sub("""
        import jax, jax.numpy as jnp, numpy as np, tempfile
        from jax.sharding import AxisType, NamedSharding
        from repro.checkpoint import restore, save
        from repro.configs import get_arch, reduced
        from repro.models import init_params
        from repro.optim import AdamW
        from repro.sharding.ctx import make_ctx
        from repro.train.state import train_state_pspecs

        cfg = reduced(get_arch("granite-3-8b"))
        opt = AdamW()
        params = init_params(cfg, jax.random.key(1))
        state = {"params": params, "opt": opt.init(params), "step": jnp.int32(3)}
        d = tempfile.mkdtemp()

        mesh1 = jax.make_mesh((2, 4), ("data", "model"),
                             axis_types=(AxisType.Auto,) * 2)
        ctx1 = make_ctx(False, tp_size=4)
        ns1 = jax.tree.map(lambda p: NamedSharding(mesh1, p),
                           train_state_pspecs(cfg, ctx1, opt, mesh1))
        sharded = jax.device_put(state, ns1)
        save(d, 3, sharded)

        mesh2 = jax.make_mesh((8, 1), ("data", "model"),
                             axis_types=(AxisType.Auto,) * 2)
        ctx2 = make_ctx(False, tp_size=1)
        ns2 = jax.tree.map(lambda p: NamedSharding(mesh2, p),
                           train_state_pspecs(cfg, ctx2, opt, mesh2))
        like = jax.tree.map(lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype), state)
        restored = restore(d, 3, like, shardings=ns2)
        for a, b in zip(jax.tree.leaves(state), jax.tree.leaves(restored)):
            np.testing.assert_allclose(np.asarray(a, np.float32),
                                       np.asarray(b, np.float32))
        print("ELASTIC OK")
    """)
    assert "ELASTIC OK" in out


def test_fleet_with_thermals_shards_across_devices():
    """run_fleet with the cooling loop enabled, replica axis device-put
    across all 8 host devices: the sharded sweep must match the
    single-device run replica by replica (rack temps, throttle seconds and
    the standard accounting all thread through vmap + sharding)."""
    out = _run_sub("""
        import jax, jax.numpy as jnp, numpy as np
        from jax.sharding import AxisType, NamedSharding, PartitionSpec as P
        from repro.configs.sim import tiny_cluster
        from repro.core import build_statics, init_state, load_jobs, run_fleet
        from repro.data import synth_workload
        from repro.scenarios import sample_scenarios

        cfg = tiny_cluster(thermal_enabled=True, rack_tau_s=120.0,
                           thermal_trip_c=22.0, throttle_start_c=20.0,
                           throttle_full_c=30.0)
        jobs, bank = synth_workload(cfg, 24, 600.0, seed=0)
        statics = build_statics(cfg, bank)
        state = load_jobs(init_state(cfg, statics, jax.random.key(0)), jobs)
        scns = sample_scenarios(cfg, 8, seed=3)

        fs_ref, tel_ref = run_fleet(cfg, statics, state, 400, "fcfs",
                                    scenarios=scns, summary_only=True)

        mesh = jax.make_mesh((8,), ("replica",), axis_types=(AxisType.Auto,))
        shard = lambda t: jax.device_put(
            t, jax.tree.map(lambda _: NamedSharding(mesh, P("replica")), t))
        fs_sh, tel_sh = run_fleet(cfg, statics, state, 400, "fcfs",
                                  scenarios=shard(scns), summary_only=True)

        hot = np.asarray(fs_ref.peak_rack_c) >= cfg.thermal_trip_c
        assert hot.any(), "no replica crossed the trip threshold"
        for f in fs_ref._fields:
            a, b = getattr(fs_ref, f), getattr(fs_sh, f)
            if a is None:   # the streamed-admission carry: resident here
                assert b is None, f
                continue
            if f == "key":
                a, b = jax.random.key_data(a), jax.random.key_data(b)
            np.testing.assert_allclose(
                np.asarray(a), np.asarray(b), rtol=1e-5, atol=1e-6,
                err_msg=f"fleet field {f} diverged under sharding")
        print("FLEET_THERMAL OK")
    """)
    assert "FLEET_THERMAL OK" in out


def test_distributed_ppo_module_trains():
    """repro.rl.distributed: shard_map PPO on a SchedEnv fleet with int8
    grad all-reduce, scanned outer loop, ppo_train-shaped history."""
    out = _run_sub("""
        import jax
        from repro.configs.sim import tiny_cluster
        from repro.data import synth_workload
        from repro.envs import SchedEnv
        from repro.launch.mesh import make_fleet_mesh
        from repro.rl.distributed import distributed_ppo_train
        from repro.rl.ppo import PPOConfig

        cfg = tiny_cluster(sched_max_candidates=4)
        wls = [synth_workload(cfg, 16, 600.0, seed=s) for s in range(2)]
        env = SchedEnv(cfg, wls, episode_steps=6, sim_steps_per_action=5)
        mesh = make_fleet_mesh(8)   # axis defaults to the mesh's own name
        params, hist = distributed_ppo_train(
            env, mesh, cfg=PPOConfig(n_envs=8, rollout_len=6, n_epochs=1,
                                     n_minibatches=1),
            n_iterations=3, compress=True, sync_every=2)
        assert len(hist) == 3
        import numpy as np
        # same per-iteration stat interface as ppo_train (+ total loss)
        for k in ("loss", "mean_reward", "mean_episode_return",
                  "mean_episode_len", "mean_value", "pg_loss", "v_loss",
                  "entropy", "approx_kl"):
            assert all(np.isfinite(h[k]) for h in hist), k
        print("DIST_PPO OK")
    """)
    assert "DIST_PPO OK" in out


def test_distributed_ppo_with_compressed_psum():
    """shard_map DP PPO gradient step with int8-compressed all-reduce."""
    out = _run_sub("""
        import jax, jax.numpy as jnp, numpy as np
        from jax.sharding import AxisType, PartitionSpec as P
        from repro.optim.compress import compressed_psum
        from repro.rl.policy import ActorCritic

        mesh = jax.make_mesh((8,), ("data",), axis_types=(AxisType.Auto,))
        pol = ActorCritic(16, 4)
        params = pol.init(jax.random.key(0))
        obs = jax.random.normal(jax.random.key(1), (64, 16))
        tgt = jax.random.normal(jax.random.key(2), (64,))

        def local_grads(params, obs, tgt):
            def loss(p):
                return jnp.mean((pol.apply(p, obs)[1] - tgt) ** 2)
            return jax.grad(loss)(params)

        def step_local(params, obs, tgt):
            # mark params shard-varying so jax.grad stays LOCAL (otherwise
            # shard_map AD inserts its own psum and we'd reduce twice)
            params = jax.tree.map(
                lambda p: jax.lax.pcast(p, "data", to="varying"), params)
            g = local_grads(params, obs, tgt)
            g, _ = compressed_psum(g, "data")
            return g

        step = jax.shard_map(
            step_local, mesh=mesh,
            in_specs=(jax.tree.map(lambda _: P(), params), P("data"),
                      P("data")),
            out_specs=jax.tree.map(lambda _: P(), params))
        g_c = step(params, obs, tgt)
        g_ref = local_grads(params, obs, tgt)  # full-batch reference
        err = max(float(jnp.max(jnp.abs(a - b)))
                  for a, b in zip(jax.tree.leaves(g_c), jax.tree.leaves(g_ref)))
        print("ERR", err)
        assert err < 0.05
    """)
    assert "ERR" in out


def test_sharded_fleet_bit_identical_to_vmapped():
    """run_fleet(mesh=...) vs the vmapped path, macro engine ON with
    thermals AND faults enabled: final states (including the PRNG
    streams), telemetry and fleet_summary must match BITWISE — the shard
    boundary only changes which device hosts each replica's while-loop,
    never a single op in it (the split/fold_in key schedule runs on the
    host before the compiled call, shared by both paths). Two replicas
    per device: a one-replica shard lets XLA:CPU drop the unit batch dim
    and re-fuse a per-node reduction, which can move one accumulator by an
    ulp against the batched program."""
    out = _run_sub("""
        import jax, jax.numpy as jnp, numpy as np
        from repro.configs.sim import tiny_cluster
        from repro.core import (build_statics, fleet_summary, init_state,
                                load_jobs, run_fleet)
        from repro.data import synth_workload
        from repro.launch.mesh import make_fleet_mesh
        from repro.scenarios import sample_scenarios

        cfg = tiny_cluster(thermal_enabled=True, node_mtbf_hours=0.5,
                           node_repair_hours=0.2, rack_mtbf_hours=1.5,
                           rack_repair_hours=0.3, ckpt_interval_s=240.0,
                           ckpt_overhead_s=20.0, max_job_retries=3)
        jobs, bank = synth_workload(cfg, 32, 900.0, seed=0)
        statics = build_statics(cfg, bank)
        st = load_jobs(init_state(cfg, statics, jax.random.key(0)), jobs)
        scns = sample_scenarios(cfg, 16, seed=7)

        sv, tv = run_fleet(cfg, statics, st, 400, "fcfs", scenarios=scns,
                           macro=True, summary_only=True)
        mesh = make_fleet_mesh(8)
        ss, ts = run_fleet(cfg, statics, st, 400, "fcfs", scenarios=scns,
                           macro=True, summary_only=True, mesh=mesh)

        assert float(jnp.sum(sv.n_killed)) > 0, "faults never fired"
        for f in sv._fields:
            a, b = getattr(sv, f), getattr(ss, f)
            if f == "key":   # the per-replica PRNG streams themselves
                a, b = jax.random.key_data(a), jax.random.key_data(b)
            assert np.array_equal(np.asarray(a), np.asarray(b)), \\
                f"state field {f} not bit-identical under sharding"
        for f in tv._fields:
            assert np.array_equal(np.asarray(getattr(tv, f)),
                                  np.asarray(getattr(ts, f))), \\
                f"telemetry field {f} not bit-identical under sharding"
        for dv, ds in zip(fleet_summary(sv, tv), fleet_summary(ss, ts)):
            assert dv == ds
        print("SHARDED_BITWISE OK")
    """)
    assert "SHARDED_BITWISE OK" in out


def test_sharded_fleet_uneven_replicas_loud_error():
    """R not divisible by the mesh size must raise before tracing — a
    silent pad would fabricate replicas whose summaries pollute sweep
    statistics."""
    out = _run_sub("""
        import jax
        from repro.configs.sim import tiny_cluster
        from repro.core import build_statics, init_state, load_jobs, run_fleet
        from repro.data import synth_workload
        from repro.launch.mesh import make_fleet_mesh
        from repro.scenarios import sample_scenarios

        cfg = tiny_cluster()
        jobs, bank = synth_workload(cfg, 8, 300.0, seed=0)
        statics = build_statics(cfg, bank)
        st = load_jobs(init_state(cfg, statics, jax.random.key(0)), jobs)
        mesh = make_fleet_mesh(8)
        try:
            run_fleet(cfg, statics, st, 10, "fcfs",
                      scenarios=sample_scenarios(cfg, 6, seed=3), mesh=mesh)
        except ValueError as e:
            assert "6 replicas" in str(e) and "8" in str(e), e
            print("UNEVEN_LOUD OK")
        else:
            raise SystemExit("6 replicas across 8 devices did not raise")

        # wrong axis name is equally loud
        try:
            run_fleet(cfg, statics, st, 10, "fcfs",
                      scenarios=sample_scenarios(cfg, 8, seed=3),
                      mesh=mesh, mesh_axis="data")
        except ValueError as e:
            assert "data" in str(e), e
            print("AXIS_LOUD OK")
        else:
            raise SystemExit("bogus mesh_axis did not raise")
    """)
    assert "UNEVEN_LOUD OK" in out and "AXIS_LOUD OK" in out
