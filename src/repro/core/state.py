"""Simulator state: fixed-shape pytrees so the whole datacenter twin is a
pure `step(state, action) -> state` function under jit/vmap/scan.

Job lifecycle: EMPTY -> QUEUED -> RUNNING -> DONE, plus the terminal
FAILED state for jobs whose retry budget is exhausted
(``cfg.max_job_retries``; see ``core.faults``).

A trace longer than the job table streams through it: ``Statics.trace``
holds the whole trace, and each tick's admission stage (``core.sim``)
refills DONE and FAILED slots with the next trace jobs, in trace order.
``SimState.stream`` carries the slots' trace ids, the cursor and the
outcome of every job that has left the table (``trace_records``). A trace
that fits the table takes the resident path, where both are ``None``.
"""

from __future__ import annotations

from typing import Any, Dict, NamedTuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs.sim import SimConfig
from repro.scenarios.scenario import Scenario, default_scenario

EMPTY, QUEUED, RUNNING, DONE, FAILED = 0, 1, 2, 3, 4
NRES = 3  # cpu cores, gpus, mem_gb


class Trace(NamedTuple):
    """The job columns of a streamed trace, in trace order, on the device
    (``load_jobs``' columns, one entry per trace job)."""

    submit_t: jax.Array        # (n,)
    dur: jax.Array             # (n,)
    n_nodes: jax.Array         # (n,) int32
    req: jax.Array             # (NRES, n)
    part: jax.Array            # (n,) int32
    priority: jax.Array        # (n,)
    ckpt_interval: jax.Array   # (n,)
    # due[c] = min(submit_t[c:]), inf at c = n: the earliest submit time
    # among the jobs the cursor has not admitted yet
    due: jax.Array             # (n + 1,)


class Stream(NamedTuple):
    """Streamed-admission carry of one replica."""

    tid: jax.Array             # (J,) int32 trace id of each slot's job
    cursor: jax.Array          # int32: the next trace job to admit
    # outcome of each trace job as it left the table; the table holds
    # the newer outcome of every job still in it (``trace_records``)
    jstate: jax.Array          # (n,) int32
    start_t: jax.Array         # (n,)
    end_t: jax.Array           # (n,)


class Statics(NamedTuple):
    """Per-node constants + telemetry bank; NOT carried through the scan.

    The telemetry bank comes in two layouts:

    - unbatched — ``cpu_trace``/``gpu_trace`` are (J, Q) and ``net_tx`` is
      (J,): one workload, ``SimState.workload`` is ignored;
    - banked — a leading workload axis W ((W, J, Q) / (W, J)): ONE shared
      bank serves every vmapped replica/env, and each ``SimState`` selects
      its slice through the traced ``workload`` id. Trace lookups
      (``core.power.job_utilization``, ``core.network``) gather through the
      id, so per-env state stays O(sim), not O(bank);
    - streamed — (n, Q) / (n,) with more rows than the job table has
      slots: one row per trace job, gathered through the slot's trace id
      (``SimState.stream.tid``), and ``trace`` holds the trace's job
      columns.
    """

    capacity: jax.Array        # (NRES, N)
    node_type: jax.Array       # (N,) int32
    idle_w: jax.Array          # (N,)
    cpu_dyn_w: jax.Array       # (N,)
    gpu_dyn_w: jax.Array       # (N,)
    node_max_w: jax.Array      # (N,)
    peak_gflops: jax.Array     # (N,)
    # thermal twin topology (core.thermal): which rack each node sits in,
    # each rack's steady-state thermal resistance [degC/W] derived from the
    # design delta-T at nameplate, and the rack IT nameplate itself
    node_rack: jax.Array       # (N,) int32 in [0, R)
    rack_r_th: jax.Array       # (R,) degC per W of rack heat
    rack_cap_w: jax.Array      # (R,) sum of member node_max_w
    # telemetry bank: per-job utilization profiles at trace-quanta resolution
    cpu_trace: jax.Array       # (J, Q) in [0,1], or (W, J, Q) banked
    gpu_trace: jax.Array       # (J, Q) / (W, J, Q)
    net_tx: jax.Array          # (J,) GB/s per job, or (W, J) banked
    # grid context: carbon/price/wetbulb signals + power-cap events
    scenario: Scenario
    # the whole trace when it streams through the table; None otherwise
    trace: Trace | None = None


class SimState(NamedTuple):
    t: jax.Array               # scalar f32 seconds
    key: jax.Array             # PRNG key
    # nodes
    free: jax.Array            # (NRES, N)
    node_up: jax.Array         # (N,) f32 {0,1}
    repair_t: jax.Array        # (N,) time at which a down node returns
    # job table
    jstate: jax.Array          # (J,) int32
    submit_t: jax.Array        # (J,)
    start_t: jax.Array         # (J,)
    end_t: jax.Array           # (J,)
    dur_est: jax.Array         # (J,) requested walltime [s]
    work_left: jax.Array       # (J,) remaining work [s of unimpeded progress]
    n_nodes: jax.Array         # (J,) int32
    req: jax.Array             # (NRES, J) per-node demand
    part: jax.Array            # (J,) int32 partition tag = node-type index a
    #                            job belongs to; -1 = any (no partition)
    priority: jax.Array        # (J,)
    placement: jax.Array       # (J, K) int32 node ids; -1 = unused slot
    n_failures: jax.Array      # (J,) int32 restarts due to node failures
    # accumulators
    energy_kwh: jax.Array      # facility-side
    it_energy_kwh: jax.Array
    loss_energy_kwh: jax.Array  # rectification+conversion losses
    cool_energy_kwh: jax.Array
    carbon_kg: jax.Array
    elec_cost_usd: jax.Array   # facility energy x price signal
    flops_integral: jax.Array  # GFLOP delivered (utilization-weighted)
    n_completed: jax.Array
    n_killed: jax.Array
    sum_wait: jax.Array
    sum_slowdown: jax.Array
    sum_power_w: jax.Array     # for mean power
    n_steps: jax.Array
    # thermal twin carry (core.thermal): per-rack outlet temps (first-order
    # RC lag) + episode accumulators. Present even with thermal_enabled
    # off — the pytree structure must not depend on the model flag — but
    # then never written after init.
    rack_outlet_c: jax.Array   # (R,)
    thermal_throttle_s: jax.Array  # seconds with any rack derated
    peak_rack_c: jax.Array     # running max outlet temp
    # resilience twin carry (core.faults): event-sampled absolute failure
    # times (inf with faults off — exact macro breakpoints, zero per-tick
    # PRNG draws), per-job checkpoint intervals, the current
    # degradation-ladder level, and lost-work accounting. Present even
    # with resilience off (pytree structure is flag-independent) but then
    # never written after init.
    next_fail_t: jax.Array     # (N,) absolute next node-fault time [s]
    rack_fail_t: jax.Array     # (R,) absolute next rack-fault time [s]
    ckpt_interval: jax.Array   # (J,) checkpoint period [s]; <=0 = none
    degrade_level: jax.Array   # scalar int32 ladder level (0..4)
    lost_node_s: jax.Array     # node-seconds of killed/evicted progress
    n_failed: jax.Array        # jobs gone terminal FAILED
    # serving twin carry (core.serving): fluid request mass per attempt
    # tier, backoff-retry buckets with absolute re-injection times, the
    # autoscaled inference pool (wake clock is an absolute time — an
    # exact macro breakpoint), and SLO accounting accumulators. Present
    # even with serving off (pytree structure is flag-independent) but
    # then never written after init.
    srv_queue: jax.Array       # (B+1,) queued mass per attempt tier
    srv_inflight: jax.Array    # in-service request mass
    srv_retry_q: jax.Array     # (B+1,) mass waiting out backoff per tier
    srv_retry_t: jax.Array     # (B+1,) absolute re-injection times (inf)
    srv_active: jax.Array      # awake serving nodes
    srv_wake_n: jax.Array      # nodes mid-wake
    srv_wake_t: jax.Array      # absolute wake completion time (inf)
    srv_target: jax.Array      # autoscale target (RL action)
    srv_admit_thresh: jax.Array  # admitted queue fraction (RL action)
    srv_arrived: jax.Array     # request-mass accumulators
    srv_completed: jax.Array
    srv_shed: jax.Array        # terminal: queue-cap overflow
    srv_dropped: jax.Array     # terminal: retry budget exhausted
    srv_retried: jax.Array
    srv_slo_viol: jax.Array    # completed mass over the SLO
    srv_lat_sum: jax.Array     # mass-weighted latency integral [req*s]
    srv_lat_hist: jax.Array    # (8,) completion mass per log-2 SLO bucket
    # which workload this replica runs: index into a banked Statics trace
    # bank ((W, J, Q) leading axis); ignored when the bank is unbatched.
    # Scalar int32 — O(1) per env, vs. the O(J*Q) per-env bank copy the
    # pre-bank-indexed env carried.
    workload: jax.Array
    # streamed admission (trace longer than the table); None otherwise,
    # so the resident program carries nothing of it
    stream: Stream | None = None


def build_statics(
    cfg: SimConfig,
    trace_bank: Dict[str, Any] | None = None,
    scenario: Scenario | None = None,
    jobs: Dict[str, np.ndarray] | None = None,
) -> Statics:
    """Expand per-type node constants into per-node arrays.

    A 2-D bank with more rows than ``cfg.max_jobs`` streams its trace
    through the job table: ``jobs``, the trace's columns (as passed to
    ``load_jobs``, one per bank row), is then required and goes on the
    device whole as ``Statics.trace``."""
    caps, types, idle, cdyn, gdyn, nmax, gflops = [], [], [], [], [], [], []
    for ti, t in enumerate(cfg.node_types):
        for _ in range(t.count):
            caps.append([t.cpu_cores, t.gpus, t.mem_gb])
            types.append(ti)
            idle.append(t.idle_w + t.gpus * t.gpu_idle_w)
            cdyn.append(t.cpu_dyn_w)
            gdyn.append(t.gpus * t.gpu_dyn_w)
            nmax.append(t.idle_w + t.gpus * t.gpu_idle_w + t.cpu_dyn_w + t.gpus * t.gpu_dyn_w)
            gflops.append(t.peak_gflops)
    J = cfg.max_jobs
    if trace_bank is None:
        q = 8
        trace_bank = {
            "cpu": np.zeros((J, q), np.float32),
            "gpu": np.zeros((J, q), np.float32),
            "net_tx": np.zeros((J,), np.float32),
        }
    # rack topology: consecutive index blocks (nodes are emitted type-major,
    # so racks are type-homogeneous except at type boundaries); R_th per
    # rack from the design delta-T at the rack's IT nameplate
    node_rack = np.arange(cfg.n_nodes, dtype=np.int32) // cfg.nodes_per_rack
    rack_cap = np.zeros((cfg.n_racks,), np.float32)
    np.add.at(rack_cap, node_rack, np.array(nmax, np.float32))
    rack_r_th = cfg.rack_dt_full_load_c / np.maximum(rack_cap, 1.0)
    rows = np.shape(trace_bank["cpu"])
    trace = None
    if len(rows) == 2 and rows[0] > J:
        trace = _trace_columns(cfg, jobs, rows[0])
    return Statics(
        capacity=jnp.asarray(np.array(caps, np.float32).T),
        node_type=jnp.asarray(np.array(types, np.int32)),
        idle_w=jnp.asarray(np.array(idle, np.float32)),
        cpu_dyn_w=jnp.asarray(np.array(cdyn, np.float32)),
        gpu_dyn_w=jnp.asarray(np.array(gdyn, np.float32)),
        node_max_w=jnp.asarray(np.array(nmax, np.float32)),
        peak_gflops=jnp.asarray(np.array(gflops, np.float32)),
        node_rack=jnp.asarray(node_rack),
        rack_r_th=jnp.asarray(rack_r_th),
        rack_cap_w=jnp.asarray(rack_cap),
        cpu_trace=jnp.asarray(trace_bank["cpu"], jnp.float32),
        gpu_trace=jnp.asarray(trace_bank["gpu"], jnp.float32),
        net_tx=jnp.asarray(trace_bank["net_tx"], jnp.float32),
        scenario=scenario if scenario is not None else default_scenario(cfg),
        trace=trace,
    )


def _trace_columns(cfg: SimConfig, jobs, n: int) -> Trace:
    from repro.utils.errors import ConfigError

    if jobs is None or len(jobs["submit_t"]) != n:
        got = "no jobs" if jobs is None else f"{len(jobs['submit_t'])} jobs"
        raise ConfigError(
            f"a bank of {n} rows streams its trace through the "
            f"{cfg.max_jobs}-slot job table: pass its {n} jobs as jobs= "
            f"(got {got})")
    submit = np.asarray(jobs["submit_t"], np.float32)
    due = np.append(np.minimum.accumulate(submit[::-1])[::-1], np.inf)
    f32 = lambda k, d: jnp.asarray(jobs.get(k, np.full(n, d)), jnp.float32)
    return Trace(
        submit_t=jnp.asarray(submit),
        dur=f32("dur", 0.0),
        n_nodes=jnp.asarray(jobs["n_nodes"], jnp.int32),
        req=jnp.asarray(jobs["req"], jnp.float32),
        part=jnp.asarray(jobs.get("part", -np.ones(n)), jnp.int32),
        priority=f32("priority", 0.0),
        ckpt_interval=f32("ckpt_interval", cfg.ckpt_interval_s),
        due=jnp.asarray(due, jnp.float32),
    )


def init_state(cfg: SimConfig, statics: Statics, key: jax.Array) -> SimState:
    from repro.core.thermal import supply_temp
    from repro.scenarios.signals import eval_signal

    N = cfg.n_nodes
    J = cfg.max_jobs
    K = cfg.max_nodes_per_job
    f = jnp.float32
    zJ = jnp.zeros((J,), f)
    # racks start at the cooling supply temperature (the idle steady state
    # sans heat); the RC update pulls them toward the loaded steady state
    supply0 = supply_temp(cfg, eval_signal(statics.scenario.wetbulb, f(0.0)))
    # event-sampled fault clocks: absolute exponential first-failure times.
    # Python-gated on the MTBF knobs so fault-free configs consume zero
    # PRNG (the stored key — and thus every downstream draw — is unchanged
    # vs. pre-resilience builds).
    next_fail = jnp.full((N,), jnp.inf, f)
    rack_fail = jnp.full((cfg.n_racks,), jnp.inf, f)
    if cfg.node_mtbf_hours > 0:
        key, kn = jax.random.split(key)
        next_fail = jax.random.exponential(kn, (N,)) * f(
            cfg.node_mtbf_hours * 3600.0)
    if cfg.rack_mtbf_hours > 0:
        key, kr = jax.random.split(key)
        rack_fail = jax.random.exponential(kr, (cfg.n_racks,)) * f(
            cfg.rack_mtbf_hours * 3600.0)
    return SimState(
        t=f(0.0),
        key=key,
        free=statics.capacity,
        node_up=jnp.ones((N,), f),
        repair_t=jnp.zeros((N,), f),
        jstate=jnp.zeros((J,), jnp.int32),
        submit_t=zJ,
        start_t=zJ,
        end_t=zJ,
        dur_est=zJ,
        work_left=zJ,
        n_nodes=jnp.zeros((J,), jnp.int32),
        req=jnp.zeros((NRES, J), f),
        part=-jnp.ones((J,), jnp.int32),
        priority=zJ,
        placement=-jnp.ones((J, K), jnp.int32),
        n_failures=jnp.zeros((J,), jnp.int32),
        energy_kwh=f(0.0),
        it_energy_kwh=f(0.0),
        loss_energy_kwh=f(0.0),
        cool_energy_kwh=f(0.0),
        carbon_kg=f(0.0),
        elec_cost_usd=f(0.0),
        flops_integral=f(0.0),
        n_completed=f(0.0),
        n_killed=f(0.0),
        sum_wait=f(0.0),
        sum_slowdown=f(0.0),
        sum_power_w=f(0.0),
        n_steps=f(0.0),
        rack_outlet_c=supply0 * jnp.ones((cfg.n_racks,), f),
        thermal_throttle_s=f(0.0),
        peak_rack_c=supply0,
        next_fail_t=next_fail,
        rack_fail_t=rack_fail,
        ckpt_interval=jnp.full((J,), f(cfg.ckpt_interval_s)),
        degrade_level=jnp.int32(0),
        lost_node_s=f(0.0),
        n_failed=f(0.0),
        srv_queue=jnp.zeros((cfg.serving_max_retries + 1,), f),
        srv_inflight=f(0.0),
        srv_retry_q=jnp.zeros((cfg.serving_max_retries + 1,), f),
        srv_retry_t=jnp.full((cfg.serving_max_retries + 1,), jnp.inf, f),
        srv_active=f(float(cfg.serving_nodes)),
        srv_wake_n=f(0.0),
        srv_wake_t=f(jnp.inf),
        srv_target=f(float(cfg.serving_nodes)),
        srv_admit_thresh=f(cfg.serving_admit_thresh),
        srv_arrived=f(0.0),
        srv_completed=f(0.0),
        srv_shed=f(0.0),
        srv_dropped=f(0.0),
        srv_retried=f(0.0),
        srv_slo_viol=f(0.0),
        srv_lat_sum=f(0.0),
        srv_lat_hist=jnp.zeros((8,), f),
        workload=jnp.int32(0),
    )


def load_jobs(state: SimState, jobs: Dict[str, np.ndarray],
              *, validate: str = "strict") -> SimState:
    """Install a workload (from the trace loader or synthesizer) into the
    job table. ``jobs`` fields: submit_t, dur, n_nodes, req (NRES, J'),
    priority, optionally ``part`` (int32 node-type index per job;
    -1 = any — the tag the ``partition`` placement enforces), and
    optionally ``ckpt_interval`` (per-job checkpoint period [s] overriding
    ``cfg.ckpt_interval_s``; <=0 = no checkpoints).

    A workload of more jobs than the table has slots streams: its first
    ``max_jobs`` jobs fill the table, and ``state.stream`` starts the
    cursor after them with every trace job's outcome QUEUED. The statics
    must then hold the same trace (``build_statics(..., jobs=)``).

    The jobs dict is validated (``data.validate.validate_jobs``) before
    touching the table: a NaN duration or negative request would
    otherwise corrupt every downstream accumulator silently. ``validate``
    is ``"strict"`` (default; raises ``TraceValidationError`` naming the
    offending job indices), ``"repair"`` (drops bad jobs), or ``"off"``.
    Traced inputs (e.g. a jobs dict built inside jit) skip validation —
    host-level checks cannot see tracer values.
    """
    traced = any(
        isinstance(v, jax.core.Tracer) for v in jax.tree.leaves(jobs))
    if validate != "off" and not traced:
        from repro.data.validate import validate_jobs

        jobs, _ = validate_jobs(jobs, mode=validate)
    J = state.jstate.shape[0]
    n = len(jobs["submit_t"])
    if n > J:
        f = jnp.float32
        state = state._replace(stream=Stream(
            tid=jnp.arange(J, dtype=jnp.int32), cursor=jnp.int32(J),
            jstate=jnp.full((n,), QUEUED, jnp.int32),
            start_t=jnp.zeros((n,), f), end_t=jnp.zeros((n,), f)))
        jobs = {k: v[..., :J] if k == "req" else v[:J]
                for k, v in jobs.items()}
        n = J
    sl = slice(0, n)
    if "ckpt_interval" in jobs:
        state = state._replace(ckpt_interval=state.ckpt_interval.at[sl].set(
            jnp.asarray(jobs["ckpt_interval"], jnp.float32)))
    return state._replace(
        jstate=state.jstate.at[sl].set(QUEUED),
        submit_t=state.submit_t.at[sl].set(jnp.asarray(jobs["submit_t"], jnp.float32)),
        dur_est=state.dur_est.at[sl].set(jnp.asarray(jobs["dur"], jnp.float32)),
        work_left=state.work_left.at[sl].set(jnp.asarray(jobs["dur"], jnp.float32)),
        n_nodes=state.n_nodes.at[sl].set(jnp.asarray(jobs["n_nodes"], jnp.int32)),
        req=state.req.at[:, sl].set(jnp.asarray(jobs["req"], jnp.float32)),
        part=state.part.at[sl].set(jnp.asarray(
            jobs.get("part", -np.ones(n)), jnp.int32)),
        priority=state.priority.at[sl].set(
            jnp.asarray(jobs.get("priority", np.zeros(n)), jnp.float32)
        ),
    )


def trace_records(state: SimState) -> Dict[str, np.ndarray]:
    """Host copy of each job's outcome, in trace order: ``state`` (the
    jstate code), ``start`` and ``end`` times. A streamed replica merges
    the jobs still in the table over the record of those that left it;
    jobs not admitted yet read QUEUED, 0, 0. On the resident path these
    are the table's own columns."""
    s = jax.device_get({"jstate": state.jstate, "start_t": state.start_t,
                        "end_t": state.end_t, "stream": state.stream})
    if s["stream"] is None:
        rec = {k: np.asarray(s[k]) for k in ("jstate", "start_t", "end_t")}
    else:
        st = s["stream"]
        tid = np.asarray(st.tid)
        held = tid >= 0
        rec = {}
        for k in ("jstate", "start_t", "end_t"):
            rec[k] = np.array(getattr(st, k))
            rec[k][tid[held]] = np.asarray(s[k])[held]
    return {"state": rec["jstate"], "start": rec["start_t"],
            "end": rec["end_t"]}
