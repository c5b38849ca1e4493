"""Smoke run of the twin's main path on one TPU chip.

    python chip_smoke.py               # five phases on jax.devices()[0]
    python chip_smoke.py --four-chips  # the sharded paths on a 4-chip host

Phases, each through the entry points a user calls, at the size of the
machine the paper studies (``tx_gaia()`` defaults: 672 nodes, 512
resident jobs of up to 64 nodes each):

1. replay   a SuperCloud-schema trace written from ``--seed`` and read
            back through validated ingestion, replayed for a simulated
            hour per-tick and macro-stepped; checked against each other
            and against the same per-tick episode on the host CPU;
2. kernels  the same replay with thermals on, through the Pallas power
            and thermal kernels (compiled, not interpreted) vs the XLA
            path, and the XLA path vs the host CPU;
3. stack    one episode with thermals, node and rack faults,
            checkpoint/retry and the serving pool all on, macro vs
            per-tick;
4. fleet    a 64-replica policy x scenario sweep; every summary finite,
            replica 0 equal to its own single episode;
5. ppo      three PPO iterations on 64 ``SchedEnv`` replicas.

Each phase prints one JSON line with its compile seconds, steady
(post-compile) seconds, summary and comparison results; any failed check
raises. The last line is ``{"ok": true, "device": {...}}``. The script
exits non-zero before any phase when JAX's default backend is not a TPU.
"""

from __future__ import annotations

import argparse
import dataclasses
import json
import math
import os
import sys
import tempfile
import time
from contextlib import contextmanager

sys.path.insert(0, os.path.join(os.path.dirname(os.path.abspath(__file__)),
                                "src"))

import jax  # noqa: E402

# the replay phase's reference runs on the host CPU next to the chip, so
# the CPU backend must come up too (before anything touches a backend)
if jax.config.jax_platforms and "cpu" not in jax.config.jax_platforms:
    jax.config.update("jax_platforms", jax.config.jax_platforms + ",cpu")

import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402

from chipbench.clock import CompileClock  # noqa: E402
from repro.configs.sim import tx_gaia  # noqa: E402
from repro.core import (  # noqa: E402
    build_statics,
    fleet_summary,
    init_state,
    load_jobs,
    run_episode,
    run_fleet,
    summary,
)
from repro.core.fleet import policy_scenario_grid  # noqa: E402
from repro.core.placement import policy_grid  # noqa: E402
from repro.data import (  # noqa: E402
    load_supercloud,
    synth_workload,
    write_supercloud_csvs,
)
from repro.envs import SchedEnv  # noqa: E402
from repro.rl import PPOConfig, ppo_train  # noqa: E402
from repro.scenarios import diurnal_serving, sample_scenarios  # noqa: E402
from repro.utils.hlo import tpu_kernel_calls  # noqa: E402

# Every float check below compares field by field with the normwise
# relative error max|a - b| / max(1, max|b|) <= FLOAT_RTOL. Why 1e-5:
# the two sides run the same f32 model through different programs (macro
# vs per-tick, kernel vs XLA, chip vs host CPU), so per-tick terms differ
# by a few ulp where reduction order, fusion or a transcendental differ.
# On a v5e at tx_gaia() size the largest such error is 4.7e-6, in
# loss_energy_kwh (input minus IT power, a ~20x cancellation, summed in
# f32 over 3600 ticks); every other field stays under 5e-7. With the
# macro engine's job->node gemm at the TPU's default precision (bf16
# operands) the same replay is off by 3.0e-4 in flops_integral and
# 4.9e-5 in reward: 1e-5 sits between the two.
FLOAT_RTOL = 1e-5

# SimState fields that carry discrete state (queue, placement, counters,
# event times, PRNG stream): equal bit for bit in every comparison
EXACT_STATE = ("t", "key", "jstate", "submit_t", "start_t", "end_t",
               "n_nodes", "part", "placement", "n_failures", "n_completed",
               "n_killed", "n_failed", "n_steps", "degrade_level",
               "node_up", "workload")

FULL_STACK = dict(
    thermal_enabled=True,
    # fault rates scaled from the 16-node test cluster to 672 nodes so
    # the fleet sees a similar number of faults per hour
    node_mtbf_hours=21.0, node_repair_hours=0.2,
    rack_mtbf_hours=10.0, rack_repair_hours=0.3,
    ckpt_interval_s=240.0, ckpt_overhead_s=20.0,
    max_job_retries=2, requeue_backoff_s=60.0,
    # serving pool and queue scaled 16x from the same test
    serving_enabled=True, serving_nodes=64, serving_concurrency=4.0,
    serving_service_s=3.0, serving_queue_cap=960.0, serving_timeout_s=20.0,
    serving_slo_s=6.0, serving_wake_s=90.0, serving_max_retries=2,
    serving_backoff_s=5.0,
)


# ----------------------------------------------------------------- timing
class Phase:
    """Accumulates one phase's timed runs and checks into its JSON line."""

    def __init__(self, name: str, clock: CompileClock):
        self.name, self.clock = name, clock
        self.runs, self.checks, self.summary = {}, {}, {}

    @contextmanager
    def run(self, label: str):
        t0 = time.time()
        yield
        t1 = time.time()
        comp = self.clock.compile_s(t0, t1)
        self.runs[label] = {"compile_s": comp, "steady_s": t1 - t0 - comp}

    def line(self) -> dict:
        chip = [r for k, r in self.runs.items() if not k.startswith("cpu")]
        return {"phase": self.name,
                "compile_s": sum(r["compile_s"] for r in chip),
                "steady_s": sum(r["steady_s"] for r in chip),
                "runs": self.runs, "summary": self.summary,
                "checks": self.checks}


# ------------------------------------------------------------- comparison
def _host(x):
    """Host copy of a leaf; an absent field (None: a subsystem that is
    off, or the streamed-admission carry on the resident path) stays an
    object array that compares equal only to another absent one."""
    if x is not None and jnp.issubdtype(x.dtype, jax.dtypes.prng_key):
        x = jax.random.key_data(x)
    return np.asarray(jax.device_get(x))


def compare(a, b, what: str, exact=(), skip=()) -> dict:
    """Field-by-field comparison of two NamedTuples (SimState,
    TelemetrySummary): fields in ``exact`` bit for bit, every other field
    within FLOAT_RTOL normwise. Raises on the first violation; returns the
    largest float error seen."""
    worst, worst_field = 0.0, None
    for f in a._fields:
        if f in skip:
            continue
        x, y = _host(getattr(a, f)), _host(getattr(b, f))
        if f in exact or not np.issubdtype(x.dtype, np.floating):
            if not np.array_equal(x, y):
                raise AssertionError(f"{what}: {f} differs")
            continue
        x, y = x.astype(np.float64), y.astype(np.float64)
        if not (np.isfinite(x).all() and np.isfinite(y).all()):
            if not np.array_equal(x, y):
                raise AssertionError(f"{what}: {f} differs in non-finites")
            fin = np.isfinite(x)
            x, y = x[fin], y[fin]
        if x.size == 0:
            continue
        err = float(np.max(np.abs(x - y)) / max(1.0, float(np.max(np.abs(y)))))
        if err > FLOAT_RTOL:
            raise AssertionError(
                f"{what}: {f} normwise error {err:.3g} > {FLOAT_RTOL}")
        if err > worst:
            worst, worst_field = err, f
    return {"ok": True, "max_err": worst, "max_err_field": worst_field}


def compare_episode(ref, got, what: str) -> dict:
    """(SimState, TelemetrySummary) pairs; the macro skip accounting
    (``macro_steps``) differs by design and is not compared."""
    return {"state": compare(ref[0], got[0], what + " state", EXACT_STATE),
            "telemetry": compare(ref[1], got[1], what + " telemetry",
                                 skip=("macro_steps",))}


# ------------------------------------------------------------------ phases
def replay_workload(cfg, n_ticks: int, seed: int, workdir: str):
    """SuperCloud-schema CSVs written from ``seed`` (``max_jobs`` jobs whose
    arrivals span the whole episode, so the queue never runs dry) and read
    back through strict validated ingestion."""
    horizon = n_ticks * cfg.dt / 0.9       # synth arrivals stop at 0.9 h
    write_supercloud_csvs(workdir, cfg, cfg.max_jobs, horizon, seed)
    jobs, bank, report = load_supercloud(workdir, cfg, validate="strict",
                                         return_report=True)
    n_bad = sum(r.n_quarantined for r in report.values())
    if n_bad:
        raise AssertionError(f"ingestion quarantined {n_bad} rows")
    return jobs, bank


def _episode_inputs(cfg, jobs, bank, seed, scenario=None):
    statics = build_statics(cfg, bank, scenario=scenario)
    state = load_jobs(init_state(cfg, statics, jax.random.key(seed)), jobs)
    return statics, state


def _summary_of(fs, tel) -> dict:
    s = summary(fs, tel)
    keys = ("completed", "killed_by_failures", "jobs_failed_terminal",
            "energy_kwh", "avg_pue", "carbon_kg", "mean_wait_s",
            "peak_rack_outlet_c", "macro_skip_ratio")
    return {k: s[k] for k in keys if k in s}


def _episode(cfg, statics, n_ticks, **kw):
    return jax.jit(lambda s: run_episode(cfg, statics, s, n_ticks, "fcfs",
                                         **kw))


def phase_replay(cfg, *, n_ticks: int, seed: int, workdir: str,
                 ref_device, clock: CompileClock) -> dict:
    ph = Phase("replay", clock)
    jobs, bank = replay_workload(cfg, n_ticks, seed, workdir)
    statics, state = _episode_inputs(cfg, jobs, bank, seed)
    with ph.run("pertick"):
        tick = jax.block_until_ready(
            _episode(cfg, statics, n_ticks, summary_only=True)(state))
    with ph.run("macro"):
        mac = jax.block_until_ready(
            _episode(cfg, statics, n_ticks, macro=True)(state))
    ph.checks["macro_vs_pertick"] = compare_episode(tick, mac, "macro")
    with jax.default_device(ref_device), ph.run("cpu_pertick"):
        statics_c, state_c = _episode_inputs(cfg, jobs, bank, seed)
        ref = jax.block_until_ready(
            _episode(cfg, statics_c, n_ticks, summary_only=True)(state_c))
    ph.checks["chip_vs_cpu"] = compare_episode(ref, tick, "chip vs cpu")
    if float(tick[0].n_completed) <= 0:
        raise AssertionError("replay completed no job")
    ph.summary = {"ticks": n_ticks, "jobs": int(len(jobs["submit_t"])),
                  "pertick": _summary_of(*tick), "macro": _summary_of(*mac)}
    return ph.line()


def phase_kernels(cfg, *, n_ticks: int, seed: int, workdir: str,
                  ref_device, clock: CompileClock) -> dict:
    ph = Phase("kernels", clock)
    cfg = dataclasses.replace(cfg, thermal_enabled=True)
    jobs, bank = replay_workload(cfg, n_ticks, seed, workdir)
    statics, state = _episode_inputs(cfg, jobs, bank, seed)
    with ph.run("xla"):
        ref = jax.block_until_ready(
            _episode(cfg, statics, n_ticks, summary_only=True)(state))
    # the thermal path's node->rack contraction is off in the replay
    # phase: hold the chip's XLA thermal path to the host CPU here
    with jax.default_device(ref_device), ph.run("cpu_xla"):
        statics_c, state_c = _episode_inputs(cfg, jobs, bank, seed)
        cpu = jax.block_until_ready(
            _episode(cfg, statics_c, n_ticks, summary_only=True)(state_c))
    ph.checks["xla_vs_cpu"] = compare_episode(cpu, ref, "xla vs cpu")
    with ph.run("kernels"):
        ker = _episode(cfg, statics, n_ticks, summary_only=True,
                       use_power_kernel=True,
                       use_thermal_kernel=True).lower(state).compile()
        got = jax.block_until_ready(ker(state))
    if jax.default_backend() == "tpu":
        calls = tpu_kernel_calls(ker.as_text())
        if not {"power_scatter", "rack_thermal"} <= set(calls):
            raise AssertionError(f"compiled kernels missing: found {calls}")
        ph.checks["tpu_custom_call"] = calls
    ph.checks["kernels_vs_xla"] = compare_episode(ref, got, "kernels")
    ph.summary = {"ticks": n_ticks, "xla": _summary_of(*ref),
                  "kernels": _summary_of(*got)}
    return ph.line()


def full_stack_inputs(cfg, n_ticks: int, seed: int, **overrides):
    cfg = dataclasses.replace(cfg, **{**FULL_STACK, **overrides})
    scale = cfg.serving_nodes / 4.0          # the test cluster's pool is 4
    scn = diurnal_serving(cfg, peak_rps=8.0 * scale, base_frac=0.05,
                          period_s=n_ticks * cfg.dt, burst_start_s=600.0,
                          burst_len_s=200.0, burst_mult=4.0)
    # the test's 24 jobs per 16 nodes, capped by the job table
    jobs, bank = synth_workload(cfg, min(cfg.max_jobs, 24 * cfg.n_nodes // 16),
                                n_ticks * cfg.dt / 2, seed=seed)
    statics, state = _episode_inputs(cfg, jobs, bank, seed, scenario=scn)
    # half the pool asleep with the target at full size: the first tick
    # opens a wake batch, so the wake-completion breakpoint is exercised
    state = state._replace(srv_active=jnp.float32(cfg.serving_nodes / 2))
    return cfg, statics, state


def phase_full_stack(cfg, *, n_ticks: int, seed: int,
                     clock: CompileClock, **overrides) -> dict:
    ph = Phase("full_stack", clock)
    cfg, statics, state = full_stack_inputs(cfg, n_ticks, seed, **overrides)
    with ph.run("pertick"):
        tick = jax.block_until_ready(
            _episode(cfg, statics, n_ticks, summary_only=True)(state))
    with ph.run("macro"):
        mac = jax.block_until_ready(
            _episode(cfg, statics, n_ticks, macro=True)(state))
    ph.checks["macro_vs_pertick"] = compare_episode(tick, mac, "macro")
    fs = tick[0]
    fired = {k: float(getattr(fs, k)) for k in (
        "n_killed", "srv_completed", "srv_shed", "srv_retried",
        "srv_dropped", "thermal_throttle_s")}
    if fired["n_killed"] <= 0 or fired["srv_completed"] <= 0:
        raise AssertionError(f"full stack did not exercise faults and "
                             f"serving: {fired}")
    ph.summary = {"ticks": n_ticks, "fired": fired,
                  "macro": _summary_of(*mac)}
    return ph.line()


def phase_fleet(cfg, *, n_ticks: int, seed: int, workdir: str,
                n_scenarios: int, selects, places,
                clock: CompileClock) -> dict:
    ph = Phase("fleet", clock)
    jobs, bank = replay_workload(cfg, n_ticks, seed, workdir)
    statics, state = _episode_inputs(cfg, jobs, bank, seed)
    _, pols = policy_grid(selects, places)
    pols, scns = policy_scenario_grid(
        pols, sample_scenarios(cfg, n_scenarios, seed=seed))
    R = int(pols.select.shape[0])
    with ph.run("fleet"):
        fs, tel = jax.block_until_ready(run_fleet(
            cfg, statics, state, n_ticks, policies=pols, scenarios=scns,
            macro=True, summary_only=True))
    rows = fleet_summary(fs, tel)
    bad = [i for i, r in enumerate(rows)
           if not all(math.isfinite(v) for v in r.values())]
    if bad:
        raise AssertionError(f"non-finite summaries in replicas {bad}")
    # replica 0 on its own: the key run_fleet hands it, its scenario and
    # policy, through plain run_episode
    one = lambda t: jax.tree.map(lambda x: x[0], t)
    st0 = state._replace(key=jax.random.split(state.key, R)[0])
    stt0 = statics._replace(scenario=one(scns))
    with ph.run("replica0"):
        single = jax.block_until_ready(jax.jit(lambda s: run_episode(
            cfg, stt0, s, n_ticks, one(pols), macro=True))(st0))
    ph.checks["replica0_vs_single"] = compare_episode(
        single, (one(fs), one(tel)), "replica 0")
    energy = np.array([r["energy_kwh"] for r in rows])
    ph.summary = {"replicas": R, "ticks": n_ticks,
                  "replica_ticks": R * n_ticks,
                  "completed_min": min(r["completed"] for r in rows),
                  "energy_kwh_min": float(energy.min()),
                  "energy_kwh_max": float(energy.max()),
                  "macro_skip_ratio_mean": float(np.mean(
                      [r["macro_skip_ratio"] for r in rows]))}
    return ph.line()


def ppo_env(cfg, *, n_jobs: int, horizon_s: float, seed: int,
            episode_steps: int = 32) -> SchedEnv:
    wls = [synth_workload(cfg, n_jobs, horizon_s, seed=seed + s)
           for s in range(4)]
    return SchedEnv(cfg, wls, episode_steps=episode_steps,
                    sim_steps_per_action=15)


PPO_STATS = ("mean_reward", "mean_episode_return", "mean_episode_len",
             "mean_value", "pg_loss", "v_loss", "entropy", "approx_kl")


def _check_history(hist, n_iterations, what):
    if len(hist) != n_iterations:
        raise AssertionError(f"{what}: {len(hist)} iterations logged")
    for h in hist:
        missing = [k for k in PPO_STATS if k not in h]
        if missing:
            raise AssertionError(f"{what}: stats missing {missing}")
        if not all(math.isfinite(v) for v in h.values()):
            raise AssertionError(f"{what}: non-finite stats {h}")


def phase_ppo(cfg, *, n_envs: int, rollout_len: int, n_iterations: int,
              n_jobs: int, horizon_s: float, seed: int,
              clock: CompileClock) -> dict:
    ph = Phase("ppo", clock)
    env = ppo_env(cfg, n_jobs=n_jobs, horizon_s=horizon_s, seed=seed)
    with ph.run("train"):
        params, hist = ppo_train(
            env, cfg=PPOConfig(n_envs=n_envs, rollout_len=rollout_len),
            n_iterations=n_iterations, seed=seed)
        jax.block_until_ready(params)
    _check_history(hist, n_iterations, "ppo_train")
    ph.summary = {"envs": n_envs, "rollout_len": rollout_len,
                  "iterations": n_iterations,
                  "env_transitions": n_envs * rollout_len * n_iterations,
                  "last": hist[-1]}
    return ph.line()


# --------------------------------------------------------- four-chip path
def phase_sharded_fleet(cfg, *, n_devices: int, replicas: int,
                        n_ticks: int, seed: int, clock: CompileClock,
                        **overrides) -> dict:
    """run_fleet on a fleet mesh vs the vmapped path (``mesh=None``) with
    macro, thermals and faults on.

    Bitwise against the vmapped path run on each device's block of
    replicas: the shard boundary moves no op, so the same R/D-lane program
    gives the same bits. Against one vmapped call over all R replicas the
    discrete state is equal and floats agree within FLOAT_RTOL: XLA:TPU
    tiles a per-node reduction by batch size, so an R-lane and an R/D-lane
    program may sum in another order."""
    from repro.launch.mesh import make_fleet_mesh

    ph = Phase("sharded_fleet", clock)
    faults = {k: FULL_STACK[k] for k in (
        "node_mtbf_hours", "node_repair_hours", "rack_mtbf_hours",
        "rack_repair_hours", "ckpt_interval_s", "ckpt_overhead_s",
        "max_job_retries")}
    cfg = dataclasses.replace(cfg, thermal_enabled=True,
                              **{**faults, **overrides})
    jobs, bank = synth_workload(cfg, cfg.max_jobs, n_ticks * cfg.dt,
                                seed=seed)
    statics, state = _episode_inputs(cfg, jobs, bank, seed)
    scns = sample_scenarios(cfg, replicas, seed=seed)
    block = replicas // n_devices

    def fleet(lo, hi, **kw):
        """Replicas lo..hi of one replica-batched fleet, each with its own
        key, so a block run alone gets the keys it gets inside the whole
        fleet. The batched state is donated, so every call builds it."""
        st = jax.tree.map(
            lambda a: jnp.broadcast_to(a, (hi - lo,) + jnp.shape(a)),
            state._replace(key=None))
        st = st._replace(key=jax.random.split(state.key, replicas)[lo:hi])
        return run_fleet(cfg, statics, st, n_ticks, "fcfs",
                         scenarios=jax.tree.map(lambda a: a[lo:hi], scns),
                         macro=True, summary_only=True, **kw)

    with ph.run("vmapped"):
        full = jax.block_until_ready(fleet(0, replicas))
    with ph.run("vmapped_blocks"):
        blocks = jax.block_until_ready(
            [fleet(i, i + block) for i in range(0, replicas, block)])
    blocks = jax.tree.map(lambda *xs: jnp.concatenate(xs), *blocks)
    with ph.run("sharded"):
        sharded = jax.block_until_ready(
            fleet(0, replicas, mesh=make_fleet_mesh(n_devices)))
    ss, ts = sharded
    devs = {s.device for s in ss.t.addressable_shards
            if s.data.shape[0] == block}
    if len(devs) != n_devices:
        raise AssertionError(
            f"replicas landed on {len(devs)} of {n_devices} devices")
    if float(jnp.sum(full[0].n_killed)) <= 0:
        raise AssertionError("faults never fired")
    for a, b, what in zip(blocks, sharded, ("state", "telemetry")):
        for f in a._fields:
            x, y = _host(getattr(a, f)), _host(getattr(b, f))
            if not np.array_equal(x, y):
                raise AssertionError(
                    f"sharded {what} field {f} not bitwise vs the vmapped "
                    f"blocks: max |diff| {np.max(np.abs(x - y))}")
    if fleet_summary(*blocks) != fleet_summary(ss, ts):
        raise AssertionError("sharded fleet_summary differs")
    ph.checks["sharded_vs_vmapped_blocks"] = {"ok": True, "bitwise": True,
                                              "devices": len(devs)}
    ph.checks["sharded_vs_vmapped"] = compare_episode(full, sharded,
                                                      "sharded vs vmapped")
    ph.summary = {"replicas": replicas, "ticks": n_ticks,
                  "replicas_per_device": block,
                  "killed_total": float(jnp.sum(full[0].n_killed))}
    return ph.line()


def phase_distributed_ppo(cfg, *, n_devices: int, n_envs: int,
                          rollout_len: int, n_iterations: int, n_jobs: int,
                          horizon_s: float, seed: int,
                          clock: CompileClock) -> dict:
    from repro.launch.mesh import make_fleet_mesh
    from repro.rl.distributed import distributed_ppo_train

    ph = Phase("distributed_ppo", clock)
    env = ppo_env(cfg, n_jobs=n_jobs, horizon_s=horizon_s, seed=seed)
    with ph.run("train"):
        params, hist = distributed_ppo_train(
            env, make_fleet_mesh(n_devices),
            cfg=PPOConfig(n_envs=n_envs, rollout_len=rollout_len),
            n_iterations=n_iterations, seed=seed)
        jax.block_until_ready(params)
    _check_history(hist, n_iterations, "distributed_ppo_train")
    ph.summary = {"devices": n_devices, "envs": n_envs,
                  "iterations": n_iterations, "last": hist[-1]}
    return ph.line()


# -------------------------------------------------------------------- main
def _emit(line: dict) -> None:
    print(json.dumps(line), flush=True)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--four-chips", action="store_true",
                    help="run only the sharded fleet and distributed PPO "
                         "on a 4-chip mesh")
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args(argv)

    backend = jax.default_backend()
    if backend != "tpu":
        print(f"chip_smoke: default backend is {backend!r}, not a TPU",
              file=sys.stderr)
        return 2
    need = 4 if args.four_chips else 1
    if len(jax.devices()) < need:
        print(f"chip_smoke: need {need} TPU devices, have "
              f"{len(jax.devices())}", file=sys.stderr)
        return 2

    from repro.utils.compile_cache import enable_compile_cache

    enable_compile_cache()
    cfg, seed = tx_gaia(), args.seed
    with CompileClock() as clock, \
            tempfile.TemporaryDirectory() as tmp:
        if args.four_chips:
            _emit(phase_sharded_fleet(cfg, n_devices=4, replicas=64,
                                      n_ticks=900, seed=seed, clock=clock))
            _emit(phase_distributed_ppo(
                cfg, n_devices=4, n_envs=64, rollout_len=32,
                n_iterations=2, n_jobs=256, horizon_s=1800.0, seed=seed,
                clock=clock))
        else:
            _emit(phase_replay(cfg, n_ticks=3600, seed=seed,
                               workdir=os.path.join(tmp, "replay"),
                               ref_device=jax.devices("cpu")[0],
                               clock=clock))
            _emit(phase_kernels(cfg, n_ticks=3600, seed=seed,
                                workdir=os.path.join(tmp, "kernels"),
                                ref_device=jax.devices("cpu")[0],
                                clock=clock))
            _emit(phase_full_stack(cfg, n_ticks=1800, seed=seed,
                                   clock=clock))
            _emit(phase_fleet(cfg, n_ticks=900, seed=seed,
                              workdir=os.path.join(tmp, "fleet"),
                              n_scenarios=8,
                              selects=("fcfs", "sjf", "priority", "easy"),
                              places=("first_fit", "best_fit"),
                              clock=clock))
            _emit(phase_ppo(cfg, n_envs=64, rollout_len=32, n_iterations=3,
                            n_jobs=256, horizon_s=1800.0, seed=seed,
                            clock=clock))
    dev = jax.devices()[0]
    _emit({"ok": True, "device": {"platform": dev.platform,
                                  "kind": dev.device_kind,
                                  "count": len(jax.devices())}})
    return 0


if __name__ == "__main__":
    sys.exit(main())
