"""Fleet-scale scenario-sweep benchmark: aggregate env-steps/sec of the
vmapped twin (``run_fleet``) vs replica count, with heterogeneous grid
scenarios (the workload the ROADMAP's "as many scenarios as you can
imagine" north-star asks for).

``bench_fleet_sharded`` adds the device-sharded path (``run_fleet(mesh=
...)``): the same macro fleet on 8 host devices vs single-device vmap,
including a lockstep-ADVERSARIAL workload — one contiguous shard of
cap-event-dense replicas whose quiet horizons collapse to tens of ticks
while everyone else fast-forwards — where the vmapped while-loop pays the
busy replicas' trip count for every lane and sharding confines it to one
device. Every sharded row carries a ``match_vmapped`` derived field
(bitwise final-state equality, asserted). It needs at least 2 devices in
the calling process and raises otherwise: a child process would contend
with its parent for an accelerator."""

from __future__ import annotations

import time
from typing import List, Tuple

import jax

Row = Tuple[str, float, str]


def bench_fleet() -> List[Row]:
    import numpy as np

    from repro.configs.sim import tiny_cluster
    from repro.core import build_statics, init_state, load_jobs, run_fleet
    from repro.data import synth_workload
    from repro.scenarios import sample_scenarios

    cfg = tiny_cluster()
    jobs, bank = synth_workload(cfg, 32, 900.0, seed=0)
    statics = build_statics(cfg, bank)
    st = load_jobs(init_state(cfg, statics, jax.random.key(0)), jobs)
    n_steps = 200

    rows: List[Row] = []
    base_sps = None
    for R in (1, 16, 64, 256):
        scns = sample_scenarios(cfg, R, seed=R)

        def run(state):
            return run_fleet(cfg, statics, state, n_steps, "fcfs",
                             scenarios=scns)

        fs, _ = run(st)  # compile
        jax.block_until_ready(fs.t)
        t0 = time.perf_counter()
        n_rep = 3
        for _ in range(n_rep):
            fs, _ = run(st)
        jax.block_until_ready(fs.t)
        dt = (time.perf_counter() - t0) / n_rep

        sps = n_steps * R / dt
        if base_sps is None:
            base_sps = sps
        n_capped = int(np.sum(np.asarray(scns.power_cap.cap_w).max(-1) > 0))
        rows.append((
            f"fleet_{R}replicas", dt / n_steps * 1e6,
            f"agg_steps_per_s={sps:,.0f};speedup_vs_1={sps/base_sps:.1f}x;"
            f"dr_scenarios={n_capped}/{R}",
        ))

    # constant-memory telemetry: summary_only carries windowed reductions in
    # the scan instead of stacking 16 StepOut fields x n_steps x R
    R, long_steps = 64, 2000
    scns = sample_scenarios(cfg, R, seed=R)

    def run_summary(state):
        return run_fleet(cfg, statics, state, long_steps, "fcfs",
                         scenarios=scns, summary_only=True)

    fs, tel = run_summary(st)
    jax.block_until_ready(fs.t)
    t0 = time.perf_counter()
    fs, tel = run_summary(st)
    jax.block_until_ready(fs.t)
    dt = time.perf_counter() - t0
    out_floats = sum(int(np.size(np.asarray(x))) for x in tel)
    rows.append((
        f"fleet_{R}replicas_summary_only_{long_steps}steps",
        dt / long_steps * 1e6,
        f"agg_steps_per_s={long_steps*R/dt:,.0f};"
        f"telemetry_floats={out_floats} (vs {long_steps*R*16} stacked)",
    ))
    return rows


def bench_fleet_sharded(smoke: bool = False) -> List[Row]:
    """Sharded-vs-vmapped fleet rows; needs >= 2 jax devices in this
    process (on a CPU host, set
    ``XLA_FLAGS=--xla_force_host_platform_device_count=8`` before jax
    starts, as the CI multidevice job does)."""
    n = len(jax.devices())
    if n < 2:
        raise RuntimeError(
            f"bench_fleet_sharded needs >= 2 devices, this process has {n}; "
            "on a CPU host force host devices with "
            "XLA_FLAGS=--xla_force_host_platform_device_count=8")
    import numpy as np

    from repro.configs.sim import tiny_cluster
    from repro.core import build_statics, init_state, load_jobs, run_fleet
    from repro.data import synth_workload
    from repro.launch.mesh import make_fleet_mesh
    from repro.scenarios import sample_scenarios
    from repro.scenarios.events import cap_events
    from repro.scenarios.scenario import default_scenario, stack_scenarios

    D = min(8, len(jax.devices()))
    mesh = make_fleet_mesh(D)
    cfg = tiny_cluster()
    jobs, bank = synth_workload(cfg, 32, 900.0, seed=0)
    statics = build_statics(cfg, bank)
    st = load_jobs(init_state(cfg, statics, jax.random.key(0)), jobs)
    R = 2 * D if smoke else 8 * D
    n_steps = 600 if smoke else 3000
    n_rep = 2 if smoke else 3

    def timed(fn):
        fs, _ = fn()                         # compile
        jax.block_until_ready(fs.t)
        t0 = time.perf_counter()
        for _ in range(n_rep):
            fs, tel = fn()
        jax.block_until_ready(fs.t)
        return (time.perf_counter() - t0) / n_rep, fs, tel

    def match(a, b):
        for f in a._fields:
            x, y = getattr(a, f), getattr(b, f)
            if f == "key":
                x, y = jax.random.key_data(x), jax.random.key_data(y)
            if not np.array_equal(np.asarray(x), np.asarray(y)):
                return False
        return True

    rows: List[Row] = []
    workloads = [
        # heterogeneous-but-benign sweep: horizons vary mildly
        ("uniform", sample_scenarios(cfg, R, seed=11)),
    ]
    # lockstep-adversarial: the LAST R/D replicas (= exactly one contiguous
    # shard under the replica-axis NamedSharding) carry a cap edge every
    # 20 simulated seconds, so their macro quiet horizons collapse to ~10
    # ticks while everyone else's span arrival gaps and the episode tail.
    # Under vmap every lane pays the busy trip count; sharded, only one
    # device does.
    edges = np.arange(10.0, n_steps * cfg.dt - 20.0, 20.0)
    busy = default_scenario(cfg)._replace(power_cap=cap_events(
        edges, edges + 10.0, [cfg.nameplate_it_w * 1.3 * 0.7] * len(edges),
        base_cap_w=cfg.power_cap_w))
    quiet = default_scenario(cfg)
    workloads.append((
        "adversarial",
        stack_scenarios([quiet] * (R - R // D) + [busy] * (R // D))))

    for tag, scns in workloads:
        def vmapped(scns=scns):
            return run_fleet(cfg, statics, st, n_steps, "fcfs",
                             scenarios=scns, macro=True, summary_only=True)

        def sharded(scns=scns):
            return run_fleet(cfg, statics, st, n_steps, "fcfs",
                             scenarios=scns, macro=True, summary_only=True,
                             mesh=mesh)

        dt_v, fs_v, _ = timed(vmapped)
        dt_s, fs_s, _ = timed(sharded)
        ok = match(fs_v, fs_s)
        assert ok, f"sharded fleet diverged from vmapped on {tag} workload"
        suffix = "" if not smoke else "_smoke"
        rows.append((
            f"fleet_vmapped_{R}replicas_macro_{tag}{suffix}",
            dt_v / n_steps * 1e6,
            f"agg_steps_per_s={n_steps*R/dt_v:,.0f}",
        ))
        rows.append((
            f"fleet_sharded_{R}replicas_macro_{tag}{suffix}",
            dt_s / n_steps * 1e6,
            f"agg_steps_per_s={n_steps*R/dt_s:,.0f};devices={D};"
            f"speedup_vs_vmapped={dt_v/dt_s:.2f}x;match_vmapped={ok}",
        ))
    return rows
