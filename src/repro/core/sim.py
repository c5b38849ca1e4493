"""The RAPS trace-replay / rescheduling simulator step and episode runner.

``make_step(cfg, statics, scheduler)`` closes over the static datacenter
description and returns a pure jit-able ``step(state, action) ->
(state, StepOut)``; an episode is ``lax.scan`` over steps, so the whole
digital twin vmaps across thousands of parallel datacenters for RL.

Scheduling is a two-stage engine: job *selection*
(``core.schedulers``: replay/fcfs/sjf/priority/easy, or the external RL
action) x node *placement* (``core.placement``: first_fit/best_fit/
spread/partition/green). ``scheduler`` is either a policy name (eager,
one Python branch baked into the trace) or a ``placement.Policy`` of
traced (select_id, place_id) int32s resolved by ``lax.switch`` inside the
compiled step — pass the Policy as a jit *argument* and one compilation
serves the entire selection x placement grid.

Step order (matches RAPS' fixed-dt loop):
  1. node failures / repairs (MTBF process)       [optional]
  2. job completions -> free resources, stats
  3. admission of the next trace jobs into freed slots [streamed traces]
  4. scheduling: up to `starts_per_step` dispatch attempts via the policy
  5. progress running jobs (network-congestion-aware rate)
  6. power chain + energy/carbon/stat accumulation

Each stage of the tick and of the macro step is traced under a
``jax.named_scope`` (``tick.*``, ``macro.*``; docs/performance.md,
"Profiling the twin by stage"), so every op of the compiled program
carries its stage in ``op_name``. The names are trace-time metadata only:
the compiled program is the same with or without them.
"""

from __future__ import annotations

import functools
from typing import NamedTuple, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs.sim import SimConfig
from repro.core import faults as flt
from repro.core import placement as plc
from repro.core import schedulers as sched
from repro.core import serving as srv
from repro.core import thermal as thm
from repro.core.faults import release_jobs as _release
from repro.core.network import congestion_slowdown
from repro.core.placement import Policy
from repro.core.power import (
    PowerOut,
    compute_power,
    job_utilization,
    node_counts,
    power_from_fracs,
    use_dense_scatter,
)
from repro.scenarios.events import next_cap_event, power_cap_at
from repro.scenarios.signals import eval_signal
from repro.core.state import (
    DONE,
    EMPTY,
    FAILED,
    NRES,
    QUEUED,
    RUNNING,
    SimState,
    Statics,
)


class StepOut(NamedTuple):
    facility_w: jax.Array
    it_w: jax.Array
    pue: jax.Array
    util: jax.Array            # fraction of up-node cores|gpus busy
    queue_len: jax.Array
    running: jax.Array
    completed_now: jax.Array
    energy_kwh_step: jax.Array
    carbon_kg_step: jax.Array
    net_load: jax.Array
    reward: jax.Array
    # grid-signal telemetry (scenario engine)
    carbon_gkwh: jax.Array     # instantaneous grid carbon intensity
    price_usd_kwh: jax.Array   # instantaneous electricity price
    power_cap_w: jax.Array     # effective facility cap (0 = uncapped)
    cost_usd_step: jax.Array   # electricity cost of this step
    throttle: jax.Array        # DVFS clock fraction applied [floor, 1]
    # thermal twin telemetry (core.thermal); with thermal_enabled off these
    # report the static plant (constant rack temps, wetbulb-only COP, 0)
    rack_max_c: jax.Array      # hottest rack outlet this tick
    cop: jax.Array             # cooling plant COP in effect
    thermal_throttle_s_step: jax.Array  # dt if any rack was derated else 0
    # resilience twin telemetry (core.faults); zeros with resilience off
    killed_now: jax.Array      # jobs killed by node loss this tick
    lost_node_s_step: jax.Array  # node-seconds of progress destroyed
    degrade_level: jax.Array   # effective ladder level in force (f32)
    # serving twin telemetry (core.serving); None (empty pytree nodes)
    # with serving off so scan carries/stacked outputs are unchanged
    srv_arrived_step: jax.Array | None = None
    srv_completed_step: jax.Array | None = None
    srv_shed_step: jax.Array | None = None
    srv_dropped_step: jax.Array | None = None
    srv_retried_step: jax.Array | None = None
    srv_slo_viol_step: jax.Array | None = None
    srv_latency_s: jax.Array | None = None   # fluid sojourn estimate
    srv_queue_len: jax.Array | None = None   # post-flow queued mass
    srv_active_nodes: jax.Array | None = None
    srv_lat_hist_step: jax.Array | None = None  # (8,) per-tick histogram
    # streamed admission (``_admit``); None on the resident path and on
    # the macro engine's fast ticks, which admit nothing
    admitted_step: jax.Array | None = None   # trace jobs admitted
    overflow_step: jax.Array | None = None   # 1.0 if a pending job is due


def _scoped(name: str):
    """Decorator: the function traced under ``jax.named_scope(name)``."""
    def wrap(fn):
        @functools.wraps(fn)
        def scoped(*args, **kw):
            with jax.named_scope(name):
                return fn(*args, **kw)

        return scoped

    return wrap


def _parse_weights(reward_weights) -> Tuple[
        float, float, float, float, float, float, float]:
    if len(reward_weights) not in (4, 5, 6, 7):
        from repro.utils.errors import ConfigError

        raise ConfigError(
            "reward_weights must have 4 to 7 entries "
            "(w_thr, w_en, w_co2, w_q[, w_cost[, w_lost[, w_slo]]]); got "
            f"{len(reward_weights)}")
    w_thr, w_en, w_co2, w_q = reward_weights[:4]
    w_cost = reward_weights[4] if len(reward_weights) >= 5 else 0.0
    w_lost = reward_weights[5] if len(reward_weights) >= 6 else 0.0
    w_slo = reward_weights[6] if len(reward_weights) == 7 else 0.0
    return w_thr, w_en, w_co2, w_q, w_cost, w_lost, w_slo


def _make_tail(cfg: SimConfig, statics: Statics, reward_weights,
               use_thermal_kernel: bool = False):
    """The per-tick accounting tail shared by the full step and the
    macro-step fast tick: grid signals at ``state.t``, thermal derating +
    the rack RC update (when ``cfg.thermal_enabled``), the DVFS throttle,
    job progress, energy/carbon/cost accumulation, reward and ``StepOut``.

    Keeping this a single code path is what makes fast-forwarded ticks
    bit-identical to per-tick quiet ticks — both run EXACTLY these float
    ops in this order; they differ only in where the inputs (power chain,
    congestion rate, queue/util counts) come from. ``thermal_enabled``
    and ``resilience_on`` are Python bools, so with both off the tail
    compiles to byte-for-byte the legacy program.

    ``killed_now``/``lost_now`` are the fault engine's per-tick kill and
    lost-work scalars — the full step passes them through, fast ticks
    pass nothing (faults fire only on event ticks, so zeros are exact).
    """
    (w_thr, w_en, w_co2, w_q, w_cost, w_lost,
     w_slo) = _parse_weights(reward_weights)
    scn = statics.scenario
    nameplate = max(cfg.nameplate_it_w, 1.0)
    # serving reward scale: the pool's full-rate request budget per tick
    srv_rate_scale = max(
        cfg.serving_nodes * cfg.serving_concurrency
        / max(cfg.serving_service_s, 1e-9) * cfg.dt, 1e-9)

    def tail(
        state: SimState,
        p: PowerOut,
        rate: jax.Array,          # pre-throttle per-job progress rate (J,)
        net_load: jax.Array,
        n_done: jax.Array,        # int32 completions this tick
        queued: jax.Array,
        running: jax.Array,
        util: jax.Array,
        killed_now: jax.Array | None = None,
        lost_now: jax.Array | None = None,
        shed_now: jax.Array | None = None,
        dropped_now: jax.Array | None = None,
        retried_now: jax.Array | None = None,
    ) -> Tuple[SimState, StepOut]:
        if killed_now is None:
            killed_now = jnp.float32(0.0)
        if lost_now is None:
            lost_now = jnp.float32(0.0)
        if cfg.serving_on and shed_now is None:
            # fast ticks: the discrete sweep fires only on full event
            # ticks, so zeros are exact (core.serving)
            shed_now = dropped_now = retried_now = jnp.float32(0.0)
        # --- grid signals at t (scenario engine)
        carbon_g = eval_signal(scn.carbon, state.t)          # gCO2/kWh
        price = eval_signal(scn.price, state.t)              # $/kWh
        cap_w = power_cap_at(scn.power_cap, state.t)         # W; 0 = uncapped
        wb = eval_signal(scn.wetbulb, state.t)               # degC

        if cfg.thermal_enabled:
            # --- thermal feedback (core.thermal): derate from the PREVIOUS
            # tick's outlet temps (explicit one-tick control lag), then
            # re-close the plant chain with the dynamic COP(wetbulb, load).
            # Only the node DYNAMIC power throttles — idle power burns at
            # any clock — and input power scales with IT (the rectifier-eta
            # shift under derating is second-order; docs/thermal.md).
            with jax.named_scope("tick.thermal"):
                th_r = thm.rack_throttle(cfg, state.rack_outlet_c)   # (R,)
                node_th = th_r[statics.node_rack]                    # (N,)
                node_idle = statics.idle_w * state.node_up
                node_dyn = jnp.maximum(p.node_it_w - node_idle, 0.0)
                node_it = node_idle + node_th * node_dyn
                node_input = p.node_input_w * (
                    node_it / jnp.maximum(p.node_it_w, 1e-9))
                it_w = jnp.sum(node_it)
                input_w = jnp.sum(node_input)
                dyn_tot = jnp.sum(node_dyn)
                gscale = jnp.where(
                    dyn_tot > 0.0,
                    jnp.sum(node_th * node_dyn) / jnp.maximum(dyn_tot, 1e-9),
                    1.0)
                cop = thm.cooling_cop(cfg, wb, it_w / nameplate)
                cooling_w = input_w / cop
                facility_w = input_w + cooling_w
                pue = jnp.where(it_w > 1.0,
                                facility_w / jnp.maximum(it_w, 1.0), 1.0)
                p = p._replace(
                    node_it_w=node_it, node_input_w=node_input, it_w=it_w,
                    input_w=input_w, cooling_w=cooling_w,
                    facility_w=facility_w, pue=pue, gflops=p.gflops * gscale)
                # synchronous ranks run at the slowest clock over a job's
                # nodes
                rate = rate * thm.job_thermal_rate(state, statics, node_th)
        else:
            # telemetry-only mirror of power.finish_power's static plant
            # (dead for the accumulators, so the legacy math is untouched)
            cop = jnp.maximum(
                cfg.cop_base + cfg.cop_wetbulb_coef * (wb - cfg.wetbulb_ref_c),
                cfg.cop_min)

        if cfg.resilience_on:
            # --- graceful-degradation ladder (core.faults): levels >=
            # THROTTLE clock-throttle dynamic power and progress exactly
            # like the DVFS cap does (idle power burns at any clock);
            # the periodic checkpoint-write cost drags per-job progress
            # while power keeps burning. Constant across a quiet macro
            # segment (outage edges are breakpoints, degrade_level only
            # changes at decision ticks), so fast ticks re-running this
            # are exact.
            dg_lvl = flt.effective_level(cfg, state, statics)
            dg = flt.degrade_clock(cfg, dg_lvl)
            dg_on = dg_lvl >= flt.LVL_THROTTLE
            idle_dg = jnp.sum(statics.idle_w * state.node_up)
            dyn_dg = jnp.maximum(p.it_w - idle_dg, 0.0)
            r_dg = (idle_dg + dg * dyn_dg) / jnp.maximum(p.it_w, 1.0)
            r_dg = jnp.where(dg_on, r_dg, 1.0)
            p = p._replace(
                it_w=p.it_w * r_dg, input_w=p.input_w * r_dg,
                cooling_w=p.cooling_w * r_dg, facility_w=p.facility_w * r_dg,
                gflops=p.gflops * jnp.where(dg_on, dg, 1.0),
            )
            rate = rate * jnp.where(dg_on, dg, 1.0)
            if cfg.ckpt_overhead_s > 0:
                rate = rate * flt.ckpt_drag(cfg, state)
            dg_level_f = dg_lvl.astype(jnp.float32)
        else:
            dg_level_f = jnp.float32(0.0)

        if cfg.serving_on:
            # --- serving-pool power (core.serving): joins the plant
            # chain BEFORE the DVFS cap so the cap throttles batch and
            # serving dynamic power together; the pool's awake-idle +
            # sleep floor joins the unthrottleable idle base below. The
            # pool rides the same plant COP but heats no batch rack
            # (the RC update stays on p.node_input_w).
            srv_it, srv_in, srv_cool, srv_idle = srv.serving_power(
                cfg, state, cop)
            it2 = p.it_w + srv_it
            fac2 = p.facility_w + srv_in + srv_cool
            p = p._replace(
                it_w=it2, input_w=p.input_w + srv_in,
                cooling_w=p.cooling_w + srv_cool, facility_w=fac2,
                pue=jnp.where(it2 > 1.0,
                              fac2 / jnp.maximum(it2, 1.0), 1.0))

        # --- demand response: DVFS-throttle to the facility power cap
        # (DCFlex-style [3]; linear dynamic-power/progress model). The cap
        # is a traced value so scheduled events switch inside one compiled
        # step; `capped` gates the rescale exactly off when uncapped.
        capped = cap_w > 0.0
        idle_total = jnp.sum(statics.idle_w * state.node_up)
        if cfg.serving_on:
            idle_total = idle_total + srv_idle
        dyn = jnp.maximum(p.it_w - idle_total, 0.0)
        # facility ~ it * overhead; solve idle + a*dyn <= cap/overhead
        overhead = p.facility_w / jnp.maximum(p.it_w, 1.0)
        cap_it = cap_w / jnp.maximum(overhead, 1e-6)
        throttle = jnp.clip(
            (cap_it - idle_total) / jnp.maximum(dyn, 1.0),
            cfg.throttle_floor, 1.0,
        )
        throttle = jnp.where(capped, throttle, 1.0)
        r = (idle_total + throttle * dyn) / jnp.maximum(p.it_w, 1.0)
        r = jnp.where(capped, r, 1.0)
        p = p._replace(
            it_w=p.it_w * r, input_w=p.input_w * r,
            cooling_w=p.cooling_w * r, facility_w=p.facility_w * r,
            gflops=p.gflops * throttle,
        )

        # --- progress (congestion- and throttle-aware)
        rate = rate * throttle
        state = state._replace(work_left=state.work_left - rate * cfg.dt)
        dt_h = cfg.dt / 3600.0
        e_step = p.facility_w * dt_h / 1000.0                # kWh
        it_step = p.it_w * dt_h / 1000.0
        loss_step = (p.input_w - p.it_w) * dt_h / 1000.0
        cool_step = p.cooling_w * dt_h / 1000.0
        co2_step = e_step * carbon_g / 1000.0                # kg
        cost_step = e_step * price                           # $

        state = state._replace(
            energy_kwh=state.energy_kwh + e_step,
            it_energy_kwh=state.it_energy_kwh + it_step,
            loss_energy_kwh=state.loss_energy_kwh + loss_step,
            cool_energy_kwh=state.cool_energy_kwh + cool_step,
            carbon_kg=state.carbon_kg + co2_step,
            elec_cost_usd=state.elec_cost_usd + cost_step,
            flops_integral=state.flops_integral + p.gflops * cfg.dt,
            sum_power_w=state.sum_power_w + p.facility_w,
            n_steps=state.n_steps + 1.0,
        )

        if cfg.serving_on:
            # --- continuous request-mass flow (core.serving): arrivals,
            # admission, completions, SLO accounting — every tick,
            # shared by fast ticks, so macro stays bit-identical
            (state, srv_arr, srv_comp, srv_viol, srv_w, srv_q,
             srv_hist) = srv.serving_flow(cfg, state, statics, throttle)

        if cfg.thermal_enabled:
            # --- rack RC update: post-cap per-node input power (IT plus
            # conversion losses, all of it room heat) relaxes each rack
            # toward its loaded steady state. Committed LAST, so this
            # tick's derate used the pre-update temps (the one-tick lag).
            with jax.named_scope("tick.thermal"):
                new_t, _ = thm.rack_thermal_update(
                    cfg, statics, state.rack_outlet_c, p.node_input_w * r,
                    thm.supply_temp(cfg, wb), use_kernel=use_thermal_kernel)
                th_step = jnp.where(jnp.any(th_r < 1.0), cfg.dt, 0.0)
                state = state._replace(
                    rack_outlet_c=new_t,
                    thermal_throttle_s=state.thermal_throttle_s + th_step,
                    peak_rack_c=jnp.maximum(state.peak_rack_c,
                                            jnp.max(new_t)))
                rack_max = jnp.max(new_t)
        else:
            rack_max = jnp.max(state.rack_outlet_c)
            th_step = jnp.float32(0.0)

        # reward: throughput-positive, energy/carbon/queue-negative,
        # normalized to O(1) per step; the lost-work penalty charges the
        # node-seconds a kill destroyed against the fleet's node-second
        # budget for the tick
        reward = (
            w_thr * n_done
            - w_en * e_step / jnp.maximum(cfg.n_nodes * 0.4 * dt_h, 1e-9) * 0.1
            - w_co2 * co2_step / jnp.maximum(cfg.n_nodes * 0.15 * dt_h, 1e-9) * 0.1
            - w_q * queued * 0.01
            - w_cost * cost_step
            / jnp.maximum(cfg.n_nodes * 0.4 * dt_h * cfg.price_mean_usd_kwh, 1e-9)
            * 0.1
            - w_lost * lost_now / jnp.maximum(cfg.n_nodes * cfg.dt, 1e-9)
        )

        srv_out = {}
        if cfg.serving_on:
            # SLO penalty normalized by the pool's full-rate request
            # budget for the tick; shed/dropped mass counts as violated —
            # a ladder that sheds its way out of latency trouble still
            # pays, so goodput is the objective the policy faces
            reward = reward - w_slo * (
                srv_viol + shed_now + dropped_now) / srv_rate_scale
            srv_out = dict(
                srv_arrived_step=srv_arr, srv_completed_step=srv_comp,
                srv_shed_step=shed_now, srv_dropped_step=dropped_now,
                srv_retried_step=retried_now, srv_slo_viol_step=srv_viol,
                srv_latency_s=srv_w, srv_queue_len=srv_q,
                srv_active_nodes=state.srv_active,
                srv_lat_hist_step=srv_hist,
            )

        out = StepOut(
            facility_w=p.facility_w, it_w=p.it_w, pue=p.pue, util=util,
            queue_len=queued, running=running, completed_now=n_done,
            energy_kwh_step=e_step, carbon_kg_step=co2_step,
            net_load=net_load, reward=reward,
            carbon_gkwh=carbon_g, price_usd_kwh=price, power_cap_w=cap_w,
            cost_usd_step=cost_step, throttle=throttle,
            rack_max_c=rack_max, cop=cop, thermal_throttle_s_step=th_step,
            killed_now=killed_now, lost_node_s_step=lost_now,
            degrade_level=dg_level_f,
            **srv_out,
        )
        return state, out

    return _scoped("tick.tail")(tail)


def _counts_and_util(state: SimState, statics: Statics):
    """(queued, running, util) telemetry scalars — constant across a quiet
    segment, so the fast tick caches them at segment start."""
    running = jnp.sum(state.jstate == RUNNING).astype(jnp.float32)
    queued = jnp.sum(sched.queued_mask(state)).astype(jnp.float32)
    up = jnp.maximum(jnp.sum(state.node_up), 1.0)
    busy = jnp.sum(
        (statics.capacity[0] - state.free[0]) / jnp.maximum(statics.capacity[0], 1e-6)
        * state.node_up
    )
    return queued, running, busy / up


# ---------------------------------------------------------------------------
# Node failures/repairs, outages and the degradation ladder live in
# ``core.faults`` (event-sampled clocks — exact macro breakpoints, zero
# per-tick PRNG draws; the old inline Bernoulli sweep is gone, and with
# it the unclamped dt/mtbf probability it handed jax.random.bernoulli).
# ``_release`` is re-exported from there: dispatch/completions below and
# the fault engine's kill path must share one resource-return routine.


def _complete_jobs(cfg: SimConfig, state: SimState) -> Tuple[SimState, jax.Array]:
    done_now = (state.jstate == RUNNING) & (state.work_left <= 0.0)
    free = _release(state.free, state, done_now)
    wait = jnp.maximum(state.start_t - state.submit_t, 0.0)
    run = jnp.maximum(state.t - state.start_t, cfg.dt)
    slowdown = jnp.maximum((wait + run) / run, 1.0)
    n_done = jnp.sum(done_now)
    state = state._replace(
        free=free,
        jstate=jnp.where(done_now, DONE, state.jstate),
        end_t=jnp.where(done_now, state.t, state.end_t),
        placement=jnp.where(done_now[:, None], -1, state.placement),
        n_completed=state.n_completed + n_done,
        sum_wait=state.sum_wait + jnp.sum(jnp.where(done_now, wait, 0.0)),
        sum_slowdown=state.sum_slowdown + jnp.sum(jnp.where(done_now, slowdown, 0.0)),
    )
    return state, n_done


def _admit(state: SimState, statics: Statics):
    """Streamed admission: every slot that holds no live job (DONE,
    FAILED, EMPTY) takes the next trace job, in trace order, as QUEUED;
    the job it held leaves its outcome in ``stream``'s record. Returns
    (state, jobs admitted, overflow): overflow is 1.0 when a trace job
    whose submit time has passed is still outside the table, which can
    only happen with every slot holding a live job."""
    tr, st = statics.trace, state.stream
    n = tr.submit_t.shape[0]
    free = ((state.jstate == DONE) | (state.jstate == FAILED)
            | (state.jstate == EMPTY))
    new = st.cursor + jnp.cumsum(free.astype(jnp.int32)) - 1
    take = free & (new < n)
    g = jnp.where(take, new, 0)
    gone = jnp.where(take & (st.tid >= 0), st.tid, n)   # n: dropped
    cursor = st.cursor + jnp.sum(take.astype(jnp.int32))

    def col(old, trace_col):
        return jnp.where(take, trace_col[g], old)

    state = state._replace(
        jstate=jnp.where(take, QUEUED, state.jstate),
        submit_t=col(state.submit_t, tr.submit_t),
        start_t=jnp.where(take, 0.0, state.start_t),
        end_t=jnp.where(take, 0.0, state.end_t),
        dur_est=col(state.dur_est, tr.dur),
        work_left=col(state.work_left, tr.dur),
        n_nodes=col(state.n_nodes, tr.n_nodes),
        req=jnp.where(take[None, :], tr.req[:, g], state.req),
        part=col(state.part, tr.part),
        priority=col(state.priority, tr.priority),
        n_failures=jnp.where(take, 0, state.n_failures),
        ckpt_interval=col(state.ckpt_interval, tr.ckpt_interval),
        stream=st._replace(
            tid=jnp.where(take, new, st.tid), cursor=cursor,
            jstate=st.jstate.at[gone].set(state.jstate, mode="drop"),
            start_t=st.start_t.at[gone].set(state.start_t, mode="drop"),
            end_t=st.end_t.at[gone].set(state.end_t, mode="drop")),
    )
    admitted = jnp.sum(take).astype(jnp.float32)
    return state, admitted, _overflowing(statics, state).astype(jnp.float32)


def _overflowing(statics: Statics, state: SimState) -> jax.Array:
    """A due trace job is outside the table: every tick counts an overflow
    until a slot frees, so none of them may be fast-forwarded."""
    return statics.trace.due[state.stream.cursor] <= state.t


def _check_stream(statics: Statics, state: SimState) -> None:
    """Loud error when a streamed trace meets a resident state or the
    other way round, or the two hold traces of different lengths."""
    from repro.utils.errors import ConfigError

    trace, stream = statics.trace, state.stream
    if (trace is None) != (stream is None) or (
            trace is not None
            and trace.submit_t.shape != stream.jstate.shape):
        raise ConfigError(
            "statics and state disagree on the streamed trace: build both "
            "from the same jobs (build_statics(..., jobs=), load_jobs)")


def _try_start(cfg: SimConfig, state: SimState, job: jax.Array,
               place_fn) -> SimState:
    """Attempt to place & start `job` via the placement stage `place_fn`
    (state, job) -> (row, ok); no-op when job < 0 or infeasible."""
    j = jnp.maximum(job, 0)
    row, ok = place_fn(state, j)
    ok = ok & (job >= 0) & (state.jstate[j] == QUEUED)
    valid = (row >= 0) & ok
    safe = jnp.where(valid, row, 0)
    amounts = state.req[:, j][:, None] * valid[None, :]      # (R,K)
    free = state.free.at[:, safe].add(-amounts, mode="drop")
    return state._replace(
        free=jnp.where(ok, free, state.free).reshape(state.free.shape),
        jstate=state.jstate.at[j].set(jnp.where(ok, RUNNING, state.jstate[j])),
        start_t=state.start_t.at[j].set(jnp.where(ok, state.t, state.start_t[j])),
        placement=state.placement.at[j].set(
            jnp.where(ok, jnp.where(valid, row, -1), state.placement[j])
        ),
    )


def make_step(
    cfg: SimConfig,
    statics: Statics,
    scheduler: str | Policy = "fcfs",
    *,
    placement: str | None = None,
    starts_per_step: int = 2,
    reward_weights: Tuple[float, ...] = (1.0, 1.0, 1.0, 0.05),
    use_power_kernel: bool = False,
    use_thermal_kernel: bool = False,
):
    """Returns step(state, action) -> (state, StepOut).

    ``scheduler``: a selection name ('replay'|'fcfs'|'sjf'|'priority'|
    'easy'), 'rl' (external action-driven selection), 'none' (no dispatch
    at all — failures/completions/progress/power only; the RL env's idle
    sub-steps between agent decisions, where the pre-split step paid a
    full candidate-ranking + placement pass per sub-step for a guaranteed
    no-op), or a ``placement.Policy`` of traced (select_id, place_id)
    int32s — the policy-as-data mode where ``lax.switch`` resolves both
    stages inside one compiled step (the Policy carries the placement id,
    so combining it with an explicit ``placement=`` is a loud error).
    ``placement``: node-placement strategy name (``core.placement``) for
    the eager string/'rl' modes; default 'first_fit'.
    ``action``: int32 — for the 'rl' scheduler, index into
    ``rl_candidates`` (k = no-op at index k); ignored otherwise.
    reward_weights = (w_throughput, w_energy, w_carbon, w_queue[, w_cost]);
    w_cost scales the electricity-price penalty (default 0 — off).
    """
    policy_mode = isinstance(scheduler, Policy)
    if not policy_mode and scheduler not in ("rl", "none") \
            and scheduler not in sched.SCHEDULERS:
        raise KeyError(f"unknown scheduler {scheduler}")
    if policy_mode and placement is not None:
        from repro.utils.errors import ConfigError

        raise ConfigError(
            f"both a Policy scheduler and placement={placement!r} given — "
            "the Policy carries the placement id, so the string would be "
            "silently ignored; pass exactly one")
    if placement is None:
        placement = "first_fit"
    if placement not in plc.PLACEMENTS:
        raise KeyError(f"unknown placement {placement}")
    tail = _make_tail(cfg, statics, reward_weights,
                      use_thermal_kernel=use_thermal_kernel)
    streamed = statics.trace is not None

    if cfg.thermal_enabled or cfg.resilience_on:
        # dispatch-only gates folded into node_up through ONE seam, so
        # every selection/placement feasibility check — all five
        # placement strategies, EASY's backfill window, fits_now_mask —
        # sees them while power/progress still run the nodes:
        # - thermal: tripped racks accept no NEW jobs
        #   (core.thermal.node_trip_ok; the continuous throttle handles
        #   hot-but-running racks);
        # - resilience: degradation-ladder levels >= LVL_GATE (RL drain/
        #   gate actions, outage brownouts) block all new dispatch.
        def _dispatch_view(s: SimState) -> SimState:
            nu = s.node_up
            if cfg.thermal_enabled:
                ok = thm.node_trip_ok(cfg, s, statics)
                nu = jnp.where(ok, nu, 0.0)
            if cfg.resilience_on:
                gated = flt.effective_level(cfg, s, statics) >= flt.LVL_GATE
                nu = jnp.where(gated, 0.0, nu)
            return s._replace(node_up=nu)
    else:
        def _dispatch_view(s: SimState) -> SimState:
            return s

    if policy_mode:
        def place_fn(s, j):
            return plc.place_job(_dispatch_view(s), statics, j,
                                 scheduler.place)
    else:
        eager_place = plc.PLACEMENTS[placement]

        def place_fn(s, j):
            return eager_place(_dispatch_view(s), statics, j)

    def step(state: SimState, action: jax.Array) -> Tuple[SimState, StepOut]:
        # the clock advance is named with the completions, which stamp it
        with jax.named_scope("tick.complete"):
            state = state._replace(t=state.t + cfg.dt)
        if cfg.resilience_on:
            with jax.named_scope("tick.faults"):
                state, killed_now, lost_now = flt.apply_faults(cfg, state,
                                                               statics)
        else:
            killed_now = lost_now = None
        if cfg.serving_on:
            # discrete overload ladder: autoscale, retry re-injection,
            # timeout/admission/shed cascade (full event ticks only;
            # bitwise fixpoint on quiet ticks — core.serving)
            with jax.named_scope("tick.serving"):
                state, shed_now, dropped_now, retried_now = \
                    srv.apply_serving(cfg, state, statics)
        else:
            shed_now = dropped_now = retried_now = None
        with jax.named_scope("tick.complete"):
            state, n_done = _complete_jobs(cfg, state)
        if streamed:
            _check_stream(statics, state)
            with jax.named_scope("tick.admit"):
                state, admitted, overflow = _admit(state, statics)

        # --- dispatch
        with jax.named_scope("tick.dispatch"):
            if not policy_mode and scheduler == "none":
                pass    # idle sub-step: no selection, no placement
            elif not policy_mode and scheduler == "rl":
                cands = sched.rl_candidates(cfg, state)          # (k,)
                k = cands.shape[0]
                job = jnp.where(action < k,
                                cands[jnp.clip(action, 0, k - 1)], -1)
                state = _try_start(cfg, state, job, place_fn)
            else:
                # single fori_loop wavefront: the jaxpr holds ONE copy of
                # the select+place body regardless of starts_per_step (the
                # unrolled loop grew trace size/compile time linearly with
                # attempts). Selection sees the placement backend's node
                # eligibility (PLACEMENT_MASKS registry, e.g. partition
                # tags) so it never picks a job placement rejects.
                # Eligibility depends only on part/node_type —
                # loop-invariant, so it is computed once per step, not per
                # dispatch attempt.
                if policy_mode:
                    node_mask = plc.placement_node_mask(state, statics,
                                                        scheduler.place)

                    def select(c, s):
                        return sched.select_job(c, _dispatch_view(s), statics,
                                                scheduler.select, node_mask)
                else:
                    eager_select = sched.SCHEDULERS[scheduler]
                    mask_fn = plc.PLACEMENT_MASKS[placement]
                    node_mask = None if mask_fn is None else mask_fn(state,
                                                                     statics)

                    def select(c, s):
                        return eager_select(c, _dispatch_view(s), statics,
                                            node_mask)

                def dispatch(_, s: SimState) -> SimState:
                    return _try_start(cfg, s, select(cfg, s), place_fn)

                state = jax.lax.fori_loop(0, starts_per_step, dispatch, state)

        # --- power chain (pre-throttle) + progress rate + telemetry counts;
        # the shared accounting tail does the rest (signals, throttle,
        # progress, accumulation, reward)
        with jax.named_scope("tick.power"):
            p: PowerOut = compute_power(cfg, state, statics,
                                        use_kernel=use_power_kernel)
        with jax.named_scope("tick.load"):
            rate, net_load = congestion_slowdown(cfg, state, statics)
            queued, running, util = _counts_and_util(state, statics)
        state, out = tail(state, p, rate, net_load, n_done, queued, running,
                          util, killed_now, lost_now, shed_now, dropped_now,
                          retried_now)
        if streamed:
            out = out._replace(admitted_step=admitted, overflow_step=overflow)
        return state, out

    return step


class TelemetrySummary(NamedTuple):
    """Windowed reductions of ``StepOut`` — the constant-memory telemetry
    carried through the scan instead of stacking 16 fields per step.

    Totals are sums over the window; ``mean_*`` are per-step means and
    ``max_*`` maxima. ``n_steps`` is the window length.
    """

    # additive totals
    completed: jax.Array
    energy_kwh: jax.Array
    carbon_kg: jax.Array
    cost_usd: jax.Array
    reward: jax.Array
    thermal_throttle_s: jax.Array  # seconds any rack was thermally derated
    killed: jax.Array          # jobs killed by node loss (core.faults)
    lost_node_s: jax.Array     # node-seconds of progress destroyed
    # serving twin (core.serving): windowed request-mass totals + the
    # log-2 latency histogram the SLO quantiles come from; None (empty
    # pytree nodes) with serving off
    srv_arrived: jax.Array
    srv_completed: jax.Array
    srv_shed: jax.Array
    srv_dropped: jax.Array
    srv_retried: jax.Array
    srv_slo_viol: jax.Array
    srv_lat_sum: jax.Array     # mass-weighted latency integral [req*s]
    srv_lat_hist: jax.Array    # (8,) completion mass per log-2 SLO bucket
    # per-step means
    mean_facility_w: jax.Array
    mean_it_w: jax.Array
    mean_pue: jax.Array
    mean_util: jax.Array
    mean_queue_len: jax.Array
    mean_running: jax.Array
    mean_net_load: jax.Array
    mean_carbon_gkwh: jax.Array
    mean_price_usd_kwh: jax.Array
    mean_throttle: jax.Array
    # with thermal_enabled, ``mean_pue`` above becomes the DYNAMIC PUE
    # (COP responds to wetbulb AND IT load) and these two activate:
    mean_cop: jax.Array        # cooling-plant COP (wetbulb x load aware)
    # extremes
    max_facility_w: jax.Array
    max_queue_len: jax.Array
    max_rack_c: jax.Array      # hottest rack outlet over the window
    n_steps: jax.Array
    # macro-stepping skip accounting: how many ticks ran the full event
    # step (dispatch/completions/failures machinery) vs. the fast-forward
    # path. Per-tick runs have macro_steps == n_steps (skip ratio 1); a
    # macro run's speedup potential is n_steps / macro_steps.
    macro_steps: jax.Array
    # streamed admission (a trace longer than the job table); None on the
    # resident path. ``admitted``: trace jobs that entered the table;
    # ``admit_overflow``: ticks on which a due trace job found no slot
    # (every slot held a live job), a replay that no longer follows the
    # trace; ``live_slot_ticks``: slots holding a live (submitted, not
    # finished) job, summed over full event ticks.
    admitted: jax.Array | None = None
    admit_overflow: jax.Array | None = None
    live_slot_ticks: jax.Array | None = None


_SRV_TELEM = ("srv_arrived", "srv_completed", "srv_shed", "srv_dropped",
              "srv_retried", "srv_slo_viol", "srv_lat_sum", "srv_lat_hist")
_ADMIT_TELEM = ("admitted", "admit_overflow", "live_slot_ticks")


def telem_zero(cfg: SimConfig, statics: Statics) -> TelemetrySummary:
    """The empty raw accumulator of one replica of ``cfg`` over
    ``statics`` (``run_segment``'s ``acc`` at an episode's start)."""
    z = jnp.float32(0.0)
    acc = TelemetrySummary(*([z] * len(TelemetrySummary._fields)))
    if not cfg.resilience_on:
        # With the fault engine off the killed/lost accumulators would be
        # constant zeros — but even two dead loop-carried leaves perturb
        # XLA's scan-body codegen enough to shift float rounding elsewhere
        # in the step (observed: 1e-6 work_left drift on the thermal
        # macro-vs-per-tick bit-identity pin). ``None`` is an EMPTY pytree
        # node, so the compiled carry is leaf-for-leaf the legacy program;
        # ``telem_finalize`` restores concrete zeros for consumers.
        acc = acc._replace(killed=None, lost_node_s=None)
    if cfg.serving_on:
        acc = acc._replace(srv_lat_hist=jnp.zeros((8,), jnp.float32))
    else:
        # same XLA-codegen hazard as killed/lost above: the serving
        # accumulators ride as empty nodes when the plane is off
        acc = acc._replace(**{f: None for f in _SRV_TELEM})
    if statics.trace is None:
        acc = acc._replace(**{f: None for f in _ADMIT_TELEM})
    return acc


@_scoped("tick.telemetry")
def _telem_update(acc: TelemetrySummary, out: StepOut,
                  macro_inc: jax.Array | float = 1.0,
                  resilience_on: bool = True,
                  serving_on: bool = False) -> TelemetrySummary:
    # mean_* fields hold running sums until telem_finalize divides by n.
    # The killed/lost (and serving) adds are Python-gated: with the engine
    # off the addends are constant zeros, but even dead adds perturb XLA's
    # scan-body codegen enough to shift float rounding elsewhere in the
    # step — gating keeps the legacy per-tick program (and its bit-pinned
    # outputs) intact. The admission counters move on the streamed path's
    # full ticks only, which alone carry ``admitted_step``.
    full_admit = out.admitted_step is not None
    return TelemetrySummary(
        completed=acc.completed + out.completed_now,
        srv_arrived=acc.srv_arrived + out.srv_arrived_step
        if serving_on else acc.srv_arrived,
        srv_completed=acc.srv_completed + out.srv_completed_step
        if serving_on else acc.srv_completed,
        srv_shed=acc.srv_shed + out.srv_shed_step
        if serving_on else acc.srv_shed,
        srv_dropped=acc.srv_dropped + out.srv_dropped_step
        if serving_on else acc.srv_dropped,
        srv_retried=acc.srv_retried + out.srv_retried_step
        if serving_on else acc.srv_retried,
        srv_slo_viol=acc.srv_slo_viol + out.srv_slo_viol_step
        if serving_on else acc.srv_slo_viol,
        srv_lat_sum=acc.srv_lat_sum
        + out.srv_completed_step * out.srv_latency_s
        if serving_on else acc.srv_lat_sum,
        srv_lat_hist=acc.srv_lat_hist + out.srv_lat_hist_step
        if serving_on else acc.srv_lat_hist,
        energy_kwh=acc.energy_kwh + out.energy_kwh_step,
        carbon_kg=acc.carbon_kg + out.carbon_kg_step,
        cost_usd=acc.cost_usd + out.cost_usd_step,
        reward=acc.reward + out.reward,
        thermal_throttle_s=acc.thermal_throttle_s
        + out.thermal_throttle_s_step,
        killed=acc.killed + out.killed_now if resilience_on else acc.killed,
        lost_node_s=acc.lost_node_s + out.lost_node_s_step
        if resilience_on else acc.lost_node_s,
        mean_facility_w=acc.mean_facility_w + out.facility_w,
        mean_it_w=acc.mean_it_w + out.it_w,
        mean_pue=acc.mean_pue + out.pue,
        mean_util=acc.mean_util + out.util,
        mean_queue_len=acc.mean_queue_len + out.queue_len,
        mean_running=acc.mean_running + out.running,
        mean_net_load=acc.mean_net_load + out.net_load,
        mean_carbon_gkwh=acc.mean_carbon_gkwh + out.carbon_gkwh,
        mean_price_usd_kwh=acc.mean_price_usd_kwh + out.price_usd_kwh,
        mean_throttle=acc.mean_throttle + out.throttle,
        mean_cop=acc.mean_cop + out.cop,
        max_facility_w=jnp.maximum(acc.max_facility_w, out.facility_w),
        max_queue_len=jnp.maximum(acc.max_queue_len, out.queue_len),
        max_rack_c=jnp.maximum(acc.max_rack_c, out.rack_max_c),
        n_steps=acc.n_steps + 1.0,
        macro_steps=acc.macro_steps + macro_inc,
        admitted=acc.admitted + out.admitted_step
        if full_admit else acc.admitted,
        admit_overflow=acc.admit_overflow + out.overflow_step
        if full_admit else acc.admit_overflow,
        live_slot_ticks=acc.live_slot_ticks + out.queue_len + out.running
        if full_admit else acc.live_slot_ticks,
    )


def telem_finalize(acc: TelemetrySummary) -> TelemetrySummary:
    """The episode summary of a raw accumulator: running sums of the
    ``mean_*`` fields divided by the ticks, and concrete zeros for the
    fields a disabled subsystem carried as empty nodes."""
    n = jnp.maximum(acc.n_steps, 1.0)
    acc = acc._replace(**{
        f: getattr(acc, f) / n
        for f in TelemetrySummary._fields if f.startswith("mean_")
    })
    if acc.killed is None:   # resilience off: carried as empty nodes
        acc = acc._replace(killed=jnp.float32(0.0),
                           lost_node_s=jnp.float32(0.0))
    if acc.srv_arrived is None:  # serving off: carried as empty nodes
        acc = acc._replace(
            **{f: jnp.float32(0.0) for f in _SRV_TELEM[:-1]},
            srv_lat_hist=jnp.zeros((8,), jnp.float32))
    return acc


# ---------------------------------------------------------------------------
# Macro-stepping: fast-forward quiet ticks with exact segment accounting.
#
# A tick is QUIET when advancing it changes no machine state: no queued job
# becomes newly visible/eligible to selection, no running job completes, no
# node fails or returns from repair, no cap-schedule breakpoint is crossed,
# and the last dispatch attempt proved the current queue unservable. Across
# a quiet segment the running set, placement, free pool and congestion rate
# are all constant — only time, per-job remaining work, the trace-quanta
# utilization indices and the continuous grid signals move. The fast tick
# therefore re-runs ONLY the shared accounting tail (exact signal-grid
# integration through the nonlinear COP/throttle consumers, which is why a
# closed-form segment integral cannot replace it) plus a cheap utilization
# -> power refresh, and skips the dispatch wavefront, completion sweep and
# telemetry-count machinery entirely.

_BIG_T = jnp.float32(jnp.inf)

# Longest quiet horizon a macro step looks ahead (ticks), and how many fast
# ticks of the count-matrix power path one chunked gemm covers.
HORIZON_CAP = 4096
CHUNK_TICKS = 16

# SimState leaves a fast tick may change; everything else provably keeps
# its segment-start value, so the commit-select only touches these.
_FAST_FIELDS = (
    "t", "work_left", "energy_kwh", "it_energy_kwh", "loss_energy_kwh",
    "cool_energy_kwh", "carbon_kg", "elec_cost_usd", "flops_integral",
    "sum_power_w", "n_steps",
)


def _fast_fields(cfg: SimConfig) -> tuple:
    """Fast-tick-mutable SimState leaves for this config: the thermal
    carry joins only when the cooling loop is on (the thermal-off tail
    never writes it, and keeping the commit-select identical preserves the
    legacy program byte-for-byte); likewise the serving flow leaves only
    when the serving plane is on."""
    ff = _FAST_FIELDS
    if cfg.thermal_enabled:
        ff = ff + ("rack_outlet_c", "thermal_throttle_s", "peak_rack_c")
    if cfg.serving_on:
        ff = ff + ("srv_queue", "srv_inflight", "srv_arrived",
                   "srv_completed", "srv_slo_viol", "srv_lat_sum",
                   "srv_lat_hist")
    return ff


def _horizon_parts(cfg: SimConfig, state: SimState, statics: Statics,
                   rate: jax.Array, dispatch_on: bool, replay_gated: bool,
                   eligibility_vis: bool, max_ticks: int):
    """(next_event_t, visible_now, k_time, k_complete): the earliest
    deterministic breakpoint strictly after ``state.t``, whether a
    dispatch-visible queued job exists right now, and the conservative
    quiet-tick counts from time-events and from completions."""
    t = state.t
    q = state.jstate == QUEUED
    # arrivals: the queued count (telemetry + reward) changes when a
    # submit time is crossed; selection visibility changes with it
    next_t = jnp.min(jnp.where(q & (state.submit_t > t),
                               state.submit_t, _BIG_T))
    if statics.trace is not None:
        # a trace job not admitted yet falls due (an overflow, counted on
        # full ticks only); jobs in the table are covered above
        due = statics.trace.due[state.stream.cursor]
        next_t = jnp.minimum(next_t, jnp.where(due > t, due, _BIG_T))
    visible_now = jnp.bool_(False)
    if dispatch_on:
        vis_t = state.submit_t
        if eligibility_vis:
            # eager replay: a queued job is only dispatchable once BOTH
            # its submit and its recorded start (priority) are crossed
            vis_t = jnp.maximum(state.submit_t, state.priority)
        visible_now = jnp.any(q & (vis_t <= t))
    if dispatch_on and replay_gated:
        # replay eligibility: a queued job becomes dispatchable when its
        # recorded start (carried in `priority`) is crossed
        next_t = jnp.minimum(next_t, jnp.min(jnp.where(
            q & (state.priority > t), state.priority, _BIG_T)))
    # node repairs return capacity at recorded times
    next_t = jnp.minimum(next_t, jnp.min(jnp.where(
        state.node_up < 0.5, state.repair_t, _BIG_T)))
    # demand-response cap windows open/close at schedule breakpoints
    next_t = jnp.minimum(next_t, next_cap_event(statics.scenario.power_cap, t))
    if cfg.resilience_on:
        # event-sampled fault clocks + outage-window edges are exact
        # breakpoints (core.faults keeps every clock strictly future)
        next_t = jnp.minimum(
            next_t, flt.next_fault_event(cfg, state, statics, t))
    if cfg.serving_on:
        # serving clock breakpoints: autoscale wake completions, retry
        # re-injections, traffic-burst window edges (core.serving) —
        # the discrete sweep runs on full ticks only
        next_t = jnp.minimum(
            next_t, srv.next_serving_event(cfg, state, statics, t))

    kf = jnp.float32(max_ticks)
    k_time = jnp.where(jnp.isfinite(next_t),
                       jnp.floor((next_t - t) / cfg.dt - 1e-6), kf)
    # completions: per-tick progress never exceeds rate * dt (throttle <=
    # 1), so floor(work/(rate*dt)) - 1 ticks can never cross zero — the -1
    # margin also absorbs float drift of the per-tick subtraction chain
    run_m = state.jstate == RUNNING
    ticks_c = jnp.where(
        run_m,
        jnp.floor(state.work_left / (jnp.maximum(rate, 1e-9) * cfg.dt)) - 1.0,
        kf,
    )
    k_complete = jnp.min(ticks_c)
    return (next_t, visible_now,
            jnp.clip(k_time, 0.0, kf).astype(jnp.int32),
            jnp.clip(k_complete, 0.0, kf).astype(jnp.int32))


def quiet_horizon(
    cfg: SimConfig,
    statics: Statics,
    state: SimState,
    scheduler: str | Policy = "fcfs",
    *,
    max_ticks: int = HORIZON_CAP,
    assume_undispatchable: bool | jax.Array = False,
) -> jax.Array:
    """Number of ticks after ``state.t`` guaranteed quiet (int32 >= 0).

    The horizon is the min over the next arrival (submit crossing), next
    replay-eligibility crossing, next completion (conservative: assumes
    full-rate progress, minus one tick of float margin), next node repair,
    next cap-schedule breakpoint, the next submit time of a streamed trace
    job not admitted yet, and — with the fault engine on — the
    next event-sampled fault-clock crossing / outage-window edge
    (``core.faults.next_fault_event``), clamped to ``max_ticks``.
    Faults are EXACT breakpoints: the clocks are absolute times redrawn
    only when they fire, so fast-forwarded ticks consume no randomness
    and the PRNG stream stays bit-identical (the old per-tick Bernoulli
    model had to be replayed tick-by-tick during fast-forward, which
    forfeited the macro speedup whenever faults were enabled).

    ``assume_undispatchable``: queued-but-visible jobs normally force a
    zero horizon (selection might start one any tick). When the caller
    has just run a full dispatch tick that started NOTHING, the visible
    queue is proven unservable — every selection policy's pick is
    constant between events for a frozen machine state — and fast-forward
    may proceed; pass True (the macro engine does) to encode that proof.

    With ``cfg.thermal_enabled`` the trip gate makes dispatch eligibility
    temperature-dependent, so a *thermal breakpoint* joins the min: a
    conservative tick count within which no rack can cross
    ``thermal_trip_c`` (``core.thermal.thermal_crossing_horizon``; the
    RC update is a contraction, so the bound follows from the box the
    temperatures are confined to). The macro engine additionally detects
    actual crossings authoritatively per fast tick — this bound only
    keeps segments short enough that the detection stays cheap.
    """
    policy_mode = isinstance(scheduler, Policy)
    dispatch_on = policy_mode or scheduler != "none"
    replay_gated = policy_mode or scheduler == "replay"
    eligibility_vis = (not policy_mode) and scheduler == "replay"
    rate, _ = congestion_slowdown(cfg, state, statics)
    next_t, visible_now, k_time, k_complete = _horizon_parts(
        cfg, state, statics, rate, dispatch_on, replay_gated,
        eligibility_vis, max_ticks)
    blocked = visible_now & ~jnp.asarray(assume_undispatchable)
    if statics.trace is not None:
        blocked = blocked | _overflowing(statics, state)
    horizon = jnp.where(blocked, 0, jnp.minimum(k_time, k_complete))
    if cfg.thermal_enabled and dispatch_on:
        horizon = jnp.minimum(horizon, thm.thermal_crossing_horizon(
            cfg, statics, state, max_ticks))
    if cfg.serving_on:
        # queue-threshold crossings: conservative arrival-envelope bound
        # + a zero horizon when the queue is already over a threshold
        # (core.serving; the macro engine also detects crossings
        # authoritatively per committed fast tick)
        horizon = jnp.minimum(horizon, srv.serving_crossing_horizon(
            cfg, state, statics, max_ticks))
        horizon = jnp.where(srv.serving_trigger(cfg, state), 0, horizon)
    return horizon


def make_macro_step(
    cfg: SimConfig,
    statics: Statics,
    scheduler: str | Policy = "fcfs",
    *,
    placement: str | None = None,
    starts_per_step: int = 2,
    reward_weights: Tuple[float, ...] = (1.0, 1.0, 1.0, 0.05),
    use_power_kernel: bool = False,
    use_thermal_kernel: bool = False,
    update=None,
):
    """Returns ``macro_step(state, acc, max_ticks) -> (state, acc, ticks)``:
    ONE full event tick (identical to ``make_step``'s, with action -1)
    followed by a fused fast-forward through the quiet segment, never past
    ``max_ticks`` total ticks (the caller's episode/telemetry-window/agent
    -decision boundary).

    Exactness: fast ticks advance time sequentially and re-run the SAME
    accounting tail as the full step, so job/queue state is bit-identical
    to per-tick stepping, fault clocks fire at exact breakpoint ticks
    with the identical PRNG stream (quiet ticks consume zero randomness;
    core.faults), and accumulators are bit-identical on configs where the
    power path is shared (the dense-scatter budget, i.e. every test-sized
    config). On
    larger configs the fast tick refreshes per-node loads through a
    per-segment job->node count matrix — one ``CHUNK_TICKS``-wide gemm
    instead of a J*K scatter per tick; the different summation order
    leaves energy/cost/carbon within float-accumulation tolerance of the
    per-tick path (job/queue
    state stays exact whenever the facility is uncapped, since then
    throttle == 1.0 exactly and progress never consumes power terms).

    ``update(acc, out, macro_inc)`` folds each tick's ``StepOut`` into the
    caller's accumulator (default: ``TelemetrySummary`` update; the RL env
    passes its info-dict reducer). ``macro_inc`` is 1.0 for the event tick
    and 0.0 for fast ticks — the skip-ratio telemetry.
    """
    step = make_step(cfg, statics, scheduler, placement=placement,
                     starts_per_step=starts_per_step,
                     reward_weights=reward_weights,
                     use_power_kernel=use_power_kernel,
                     use_thermal_kernel=use_thermal_kernel)
    tail = _make_tail(cfg, statics, reward_weights,
                      use_thermal_kernel=use_thermal_kernel)
    policy_mode = isinstance(scheduler, Policy)
    dispatch_on = policy_mode or scheduler != "none"
    replay_gated = policy_mode or scheduler == "replay"
    eligibility_vis = (not policy_mode) and scheduler == "replay"
    # thermal breakpoints: the trip gate makes DISPATCH eligibility depend
    # on rack temps, which keep evolving across fast ticks. A segment must
    # therefore end the tick a rack crosses thermal_trip_c (either
    # direction): detection is authoritative — each committed fast tick
    # compares its pre/post trip sets — and stopping AFTER the crossing
    # tick is exact because a tick's dispatch reads the temps its
    # PREDECESSOR committed (the tail's one-tick control lag), so the
    # crossing tick itself was still quiet under the old trip set. Without
    # dispatch there is no trip consumer and thermals stay breakpoint-free.
    thermal_gate = cfg.thermal_enabled and dispatch_on
    trip_c = jnp.float32(cfg.thermal_trip_c)
    fast_fields = _fast_fields(cfg)
    N = cfg.n_nodes
    C = CHUNK_TICKS
    # shared power path (bit-identical to the full step) whenever the
    # per-tick scatter is already the dense contraction; the chunked
    # count-matrix gemm otherwise (see docstring)
    shared_power = use_dense_scatter(cfg.max_jobs * cfg.max_nodes_per_job, N)
    if update is None:
        def update(acc, out, macro_inc=1.0):
            return _telem_update(acc, out, macro_inc,
                                 resilience_on=cfg.resilience_on,
                                 serving_on=cfg.serving_on)
    else:
        update = _scoped("tick.telemetry")(update)

    @_scoped("macro.power_chunk")
    def power_chunk(s: SimState, cnt):
        """(ts, PowerOut-with-leading-C-axis) for the next C ticks under a
        frozen machine state: utilization only drifts through the
        trace-quanta index, so per-node loads for the whole chunk are ONE
        gemm against the per-segment job->node count matrix instead of C
        scatters — the arithmetic-intensity trick that makes fast ticks
        ~O(scalar). The chain itself is the shared ``power_from_fracs``
        (vmapped over the chunk), so the rectifier/COP model has a single
        source of truth."""
        ts = s.t + cfg.dt * jnp.arange(1, C + 1, dtype=jnp.float32)
        cpu_u, gpu_u = jax.vmap(
            lambda t: job_utilization(cfg, s._replace(t=t), statics)
        )(ts)                                                      # (C, J)
        loads = jnp.matmul(
            jnp.concatenate([cpu_u * s.req[0][None, :],
                             gpu_u * s.req[1][None, :]]),
            cnt, precision=jax.lax.Precision.HIGHEST)              # (2C, N)
        cpu_frac = jnp.clip(
            loads[:C] / jnp.maximum(statics.capacity[0], 1e-6), 0, 1)
        gpu_frac = jnp.clip(
            loads[C:] / jnp.maximum(statics.capacity[1], 1e-6), 0, 1)
        p = jax.vmap(
            lambda t, cf, gf: power_from_fracs(
                cfg, s._replace(t=t), statics, cf, gf)
        )(ts, cpu_frac, gpu_frac)
        return ts, p

    streamed = statics.trace is not None

    def macro_step(state: SimState, acc, max_ticks):
        with jax.named_scope("macro.event"):
            was_queued = state.jstate == QUEUED
            if streamed:
                tid0 = state.stream.tid
            state, out = step(state, jnp.int32(-1))
            acc = update(acc, out, 1.0)
            now_running = state.jstate == RUNNING
            started = jnp.any(was_queued & now_running)
            if streamed:
                # a slot refilled this tick may have started its new job
                started = started | jnp.any(
                    (state.stream.tid != tid0) & now_running)

        # --- segment constants (all provably frozen across quiet ticks).
        with jax.named_scope("macro.horizon"):
            # NB the per-tick program may contract net_load's division (a
            # reciprocal multiply) and the telemetry add into one FMA where
            # this one does not, so telemetry means can skew by ulps vs
            # per-tick runs (the documented float-accumulation tolerance);
            # job/queue state never consumes it
            rate, net_load = congestion_slowdown(cfg, state, statics)
            next_event_t, visible_now, k_time, _ = _horizon_parts(
                cfg, state, statics, rate, dispatch_on, replay_gated,
                eligibility_vis, HORIZON_CAP)
            # dispatch gate: if the full tick started something AND jobs are
            # still visible, the leftovers may now be servable — keep per-tick
            # stepping. A start that DRAINED the queue, or a no-start with a
            # visible queue (proven unservable: selection picks are
            # t-independent for a frozen machine state, EASY's backfill window
            # only shrinks, replay-eligibility crossings are event
            # boundaries), both allow fast-forward. Completions are peeked per
            # tick (authoritative), so the budget only carries the
            # deterministic time-event horizon.
            k_quiet = jnp.minimum(k_time, max_ticks - 1)
            if thermal_gate:
                # conservative thermal-crossing horizon (belt to the per-tick
                # detection's suspenders: keeps segments from even entering
                # the neighborhood of a trip crossing un-checked)
                k_quiet = jnp.minimum(k_quiet, thm.thermal_crossing_horizon(
                    cfg, statics, state, HORIZON_CAP))
            blocked = started & visible_now
            if streamed:
                blocked = blocked | _overflowing(statics, state)
            if cfg.serving_on:
                # arrival-envelope bound on queue-threshold crossings, and
                # stay per-tick while the queue sits over a threshold (the
                # next tick's sweep WILL move mass): overload IS the event
                k_quiet = jnp.minimum(k_quiet, srv.serving_crossing_horizon(
                    cfg, state, statics, HORIZON_CAP))
                blocked = blocked | srv.serving_trigger(cfg, state)
            budget = jnp.where(blocked, 0, k_quiet)
            queued, running, util = _counts_and_util(state, statics)

        def peek_stop(s, t_next):
            # authoritative, side-effect free: an event tick is NOT
            # committed here; the next full step replays it. Faults need
            # no peek at all — their clocks are deterministic absolute
            # times already folded into next_event_t, and quiet ticks
            # consume zero randomness (the Bernoulli replay that used to
            # run here per fast tick is gone; core.faults).
            stop = jnp.any((s.jstate == RUNNING) & (s.work_left <= 0.0))
            return stop | (t_next >= next_event_t)

        def commit(s, a, i, stop, t_next, p: PowerOut):
            ns, o = tail(s._replace(t=t_next), p, rate, net_load,
                         jnp.int32(0), queued, running, util)
            na = update(a, o, 0.0)
            s = s._replace(**{
                f: _where_leaf(stop, getattr(s, f), getattr(ns, f))
                for f in fast_fields
            })
            a = jax.tree.map(lambda old, new: jnp.where(stop, old, new),
                             a, na)
            return s, a, i + jnp.where(stop, 0, 1)

        if shared_power:
            # small configs: per-tick compute_power IS the full step's
            # dense-contraction path — bit-identical accumulators
            def body(c):
                s, a, i, _ = c
                t_next = s.t + cfg.dt
                stop = peek_stop(s, t_next)
                with jax.named_scope("tick.power"):
                    p = compute_power(cfg, s._replace(t=t_next), statics,
                                      use_kernel=use_power_kernel)
                was_hot = s.rack_outlet_c >= trip_c
                s, a, i = commit(s, a, i, stop, t_next, p)
                go = ~stop
                if thermal_gate:   # authoritative trip-crossing breakpoint
                    go &= ~jnp.any((s.rack_outlet_c >= trip_c) != was_hot)
                if cfg.serving_on:  # authoritative overload breakpoint
                    go &= ~srv.serving_trigger(cfg, s)
                return (s, a, i, go)

            with jax.named_scope("macro.fast"):
                state, acc, took, _ = jax.lax.while_loop(
                    lambda c: c[3] & (c[2] < budget), body,
                    (state, acc, jnp.int32(0), budget > 0))
            return state, acc, 1 + took

        # large configs: per-segment job->node count matrix + chunked
        # power precompute; the inner tick body is then O(scalar) + the
        # O(J) progress/peek ops
        with jax.named_scope("macro.count_matrix"):
            cnt = node_counts(state.placement, N)

        def inner_body(c):
            s, a, i, j, _, chk = c
            ts, pc = chk
            t_next = ts[j]
            stop = peek_stop(s, t_next)
            p = jax.tree.map(lambda x: x[j], pc)
            was_hot = s.rack_outlet_c >= trip_c
            s, a, i = commit(s, a, i, stop, t_next, p)
            go = ~stop
            if thermal_gate:       # authoritative trip-crossing breakpoint
                go &= ~jnp.any((s.rack_outlet_c >= trip_c) != was_hot)
            if cfg.serving_on:     # authoritative overload breakpoint
                go &= ~srv.serving_trigger(cfg, s)
            return (s, a, i, j + 1, go, chk)

        def outer_body(c):
            s, a, i, go = c
            chk = power_chunk(s, cnt)
            s, a, i, _, go, _ = jax.lax.while_loop(
                lambda c: c[4] & (c[2] < budget) & (c[3] < C), inner_body,
                (s, a, i, jnp.int32(0), go, chk))
            return (s, a, i, go)

        with jax.named_scope("macro.fast"):
            state, acc, took, _ = jax.lax.while_loop(
                lambda c: c[3] & (c[2] < budget), outer_body,
                (state, acc, jnp.int32(0), budget > 0))
        return state, acc, 1 + took

    return macro_step


def _where_leaf(pred, old, new):
    """jnp.where that also handles typed PRNG key arrays."""
    if jnp.issubdtype(jnp.result_type(old), jax.dtypes.prng_key):
        return jax.random.wrap_key_data(
            jnp.where(pred, jax.random.key_data(old),
                      jax.random.key_data(new)),
            impl=jax.random.key_impl(old))
    return jnp.where(pred, old, new)


def _macro_advance(mstep, state: SimState, acc, n_ticks: int):
    """Drive ``mstep(state, acc, max_ticks) -> (state, acc, ticks)`` until
    exactly ``n_ticks`` ticks are committed: each macro step is clamped to
    the ticks left, so the boundary is a forced breakpoint."""
    def body(c):
        s, a, ticks = c
        s, a, took = mstep(s, a, n_ticks - ticks)
        return (s, a, ticks + took)

    s, a, _ = jax.lax.while_loop(
        lambda c: c[2] < n_ticks, body, (state, acc, jnp.int32(0)))
    return s, a


def _eager_checks(state: SimState) -> bool:
    """Whether the ``REPRO_CHECKIFY`` per-step harness instruments this
    call: on, and ``state`` concrete (a traced caller re-checks itself)."""
    from repro.utils import invariants

    return invariants.enabled() and not isinstance(state.t, jax.core.Tracer)


def _advancer(cfg: SimConfig, statics: Statics, scheduler, macro: bool,
              check: bool, kw: dict):
    """``advance(state, acc, n_ticks)``, the one loop behind every episode
    driver: the macro while-loop over ``(state, acc, ticks)``, or a per-tick
    scan folding each ``StepOut`` into the raw accumulator ``acc``. With
    ``acc=None`` (per-tick only) the scan stacks the per-tick ``StepOut``s
    instead. ``check`` runs the machine-invariant suite after every
    committed step."""
    from repro.utils import invariants

    if macro:
        fn = make_macro_step(cfg, statics, scheduler, **kw)
    else:
        fn = make_step(cfg, statics, scheduler, **kw)
    if check:
        raw = fn

        def fn(*args):
            out = raw(*args)
            invariants.check_state(cfg, statics, out[0])
            return out

    if macro:
        return functools.partial(_macro_advance, fn)

    def advance(state, acc, n_ticks):
        if acc is None:
            return jax.lax.scan(lambda s, _: fn(s, jnp.int32(-1)), state,
                                None, length=n_ticks)

        def accum_body(carry, _):
            s, a = carry
            s, out = fn(s, jnp.int32(-1))
            return (s, _telem_update(
                a, out, resilience_on=cfg.resilience_on,
                serving_on=cfg.serving_on)), None

        return jax.lax.scan(accum_body, (state, acc), None,
                            length=n_ticks)[0]

    return advance


def _checkified(check: bool, go, *args):
    """``go(*args)``, under ``checkify`` when ``check`` (raising on the
    first violating tick)."""
    if not check:
        return go(*args)
    from jax.experimental import checkify

    err, out = checkify.checkify(go)(*args)
    err.throw()
    return out


def run_episode(
    cfg: SimConfig,
    statics: Statics,
    state: SimState,
    n_steps: int,
    scheduler: str | Policy = "fcfs",
    *,
    telemetry_every: int = 1,
    summary_only: bool = False,
    macro: bool = False,
    snapshot_every_s: float | None = None,
    snapshot_dir: str | None = None,
    resume_from: str | None = None,
    snapshot_keep: int = 3,
    **kw,
) -> Tuple[SimState, StepOut | TelemetrySummary]:
    """Scan `n_steps` of the twin under a non-RL policy.

    ``scheduler`` may be a policy name or a traced ``placement.Policy``
    (policy-as-data): jit a wrapper taking the Policy as an argument and
    the whole selection x placement grid shares ONE compiled executable.

    Telemetry modes (both static, so each compiles once):
      - default: stacked per-step ``StepOut`` — O(n_steps * 16) memory;
      - ``telemetry_every=k``: one ``TelemetrySummary`` per k-step window
        (stacked, length ``n_steps // k``) — O(n_steps/k) memory;
      - ``summary_only=True``: a single episode-wide ``TelemetrySummary``
        accumulated in the scan carry — O(1) memory in ``n_steps``.
    A summary is ``telem_zero`` -> ``run_segment`` over the window ->
    ``telem_finalize``; windows are a ``lax.scan`` of that.

    ``macro=True`` drives the episode with ``make_macro_step``: quiet
    ticks (no arrival/completion/dispatch/failure/cap breakpoint) are
    fast-forwarded with exact segment accounting — the big win for
    replay-shaped workloads (see docs/performance.md "Macro-stepping").
    Ticks can no longer be stacked per step, so telemetry is episode-wide
    (``summary_only`` is implied) or windowed via ``telemetry_every``;
    window edges clamp the fast-forward horizon, so windowed results stay
    tick-aligned with the per-tick path.

    With ``REPRO_CHECKIFY=1`` (``utils.invariants``; hard-enabled in CI)
    and an eager call (un-traced ``state``), every committed step runs
    the machine-invariant suite — resource conservation, placement/
    jstate consistency, finite accumulators, bounded rack temps — via
    ``checkify``, raising on the first violating tick. Traced callers
    (e.g. ``run_fleet``'s inner jit) skip the per-step harness; the
    fleet runner re-checks final states eagerly instead.

    Durability (``checkpoint.episode``): ``snapshot_every_s=T`` writes a
    crash-atomic snapshot (SimState + raw telemetry accumulator + run
    fingerprint) every ~T simulated seconds to ``snapshot_dir``;
    ``resume_from=dir`` resumes from the newest snapshot there —
    bit-identical to the uninterrupted run (fingerprint mismatch raises
    ``CheckpointError``). Requires an episode-wide summary
    (``summary_only=True`` or ``macro=True`` with ``telemetry_every<=1``)
    and an eager (un-jitted) call; with snapshotting off this path adds
    literally nothing to the traced step.
    """
    from repro.utils.errors import ConfigError

    if summary_only and telemetry_every > 1:
        raise ConfigError(
            "summary_only=True is episode-wide; it conflicts with "
            f"telemetry_every={telemetry_every} (pick one)"
        )
    if telemetry_every > 1 and n_steps % telemetry_every:
        raise ConfigError(
            f"n_steps={n_steps} not divisible by "
            f"telemetry_every={telemetry_every}"
        )
    if snapshot_every_s is not None or resume_from is not None \
            or snapshot_dir is not None:
        from repro.checkpoint.episode import run_episode_snapshotted

        return run_episode_snapshotted(
            cfg, statics, state, n_steps, scheduler,
            telemetry_every=telemetry_every, summary_only=summary_only,
            macro=macro, snapshot_every_s=snapshot_every_s,
            snapshot_dir=snapshot_dir, resume_from=resume_from,
            snapshot_keep=snapshot_keep, kw=kw)
    check = _eager_checks(state)
    advance = _advancer(cfg, statics, scheduler, macro, check, kw)

    def window(state, n):
        state, acc = advance(state, telem_zero(cfg, statics), n)
        return state, telem_finalize(acc)

    if telemetry_every > 1:
        def go(state):
            return jax.lax.scan(
                lambda s, _: window(s, telemetry_every), state, None,
                length=n_steps // telemetry_every)
    elif macro or summary_only:
        def go(state):
            return window(state, n_steps)
    else:
        def go(state):
            return advance(state, None, n_steps)

    return _checkified(check, go, state)


def run_segment(
    cfg: SimConfig,
    statics: Statics,
    state: SimState,
    acc: TelemetrySummary,
    n_ticks: int,
    scheduler: str | Policy = "fcfs",
    *,
    macro: bool = False,
    **kw,
) -> Tuple[SimState, TelemetrySummary]:
    """Advance ``n_ticks`` carrying a RAW ``TelemetrySummary`` accumulator.

    This is ``run_episode(summary_only=True)`` (or ``macro=True``) cut at
    an arbitrary tick boundary: both run the same loop, but the
    accumulator enters un-zeroed and leaves un-finalized, so a sequence
    of segments threaded through ``(state, acc)`` reproduces the
    single-call episode bit-for-bit — the host-level primitive
    snapshot/resume (checkpoint.episode) is built on. Seed ``acc`` with
    ``telem_zero(cfg, statics)`` and apply ``telem_finalize`` once after
    the last segment. Segment edges clamp the macro fast-forward exactly
    like ``telemetry_every`` window edges, so job/queue state and the
    PRNG stream stay bit-identical to the uninterrupted run (the
    skip-accounting diagnostics ``n_steps``/``macro_steps`` count the
    forced boundary breakpoints, same as windowed telemetry).

    The ``REPRO_CHECKIFY=1`` invariant harness instruments eager calls
    per committed step, exactly as in ``run_episode``.
    """
    check = _eager_checks(state)
    advance = _advancer(cfg, statics, scheduler, macro, check, kw)
    return _checkified(check, lambda s, a: advance(s, a, n_ticks), state,
                       acc)


def summary_columns(state: SimState,
                    telemetry: TelemetrySummary | None = None,
                    statics: Statics | None = None) -> dict:
    """Column-wise ``summary``: a dict of float64 numpy arrays with one
    entry per replica, from replica-batched final states (leading replica
    axis on every leaf, e.g. ``run_fleet`` output). Also accepts an
    unbatched state, where every column is 0-d — ``summary`` is that
    special case. ONE device->host transfer covers the whole batch, and
    all per-replica reductions happen as numpy array ops, so
    ``fleet_summary`` on a 1024-replica sweep no longer spends its tail
    in a host-side Python loop over replicas. A streamed replica's jobs
    are not all in its table, so its goodput needs ``statics``."""
    s = jax.device_get(state)
    batched = np.ndim(s.t) == 1

    def f(a):
        return np.asarray(a, np.float64)

    def reduce_tail(a, op=np.sum):
        # reduce every axis except the replica axis (all axes when
        # unbatched) — covers per-job state axes and telemetry windows
        x = f(a)
        return op(x, axis=tuple(range(1, x.ndim)) if batched else None)

    n = np.maximum(f(s.n_completed), 1.0)
    cols = {
        "t_end_s": f(s.t),
        "completed": f(s.n_completed),
        "killed_by_failures": f(s.n_killed),
        "energy_kwh": f(s.energy_kwh),
        "it_energy_kwh": f(s.it_energy_kwh),
        "loss_energy_kwh": f(s.loss_energy_kwh),
        "cooling_energy_kwh": f(s.cool_energy_kwh),
        "carbon_kg": f(s.carbon_kg),
        "elec_cost_usd": f(s.elec_cost_usd),
        "mean_power_w": f(s.sum_power_w) / np.maximum(f(s.n_steps), 1.0),
        "mean_wait_s": f(s.sum_wait) / n,
        "mean_slowdown": f(s.sum_slowdown) / n,
        "gflops_per_watt": (
            f(s.flops_integral) / 3600.0 / 1000.0
            / np.maximum(f(s.energy_kwh), 1e-9)
        ),
        "avg_pue": f(s.energy_kwh) / np.maximum(f(s.it_energy_kwh), 1e-9),
        # thermal twin (core.thermal); with thermal_enabled off these
        # report the supply-temperature initial condition and 0
        "peak_rack_outlet_c": f(s.peak_rack_c),
        "thermal_throttle_s": f(s.thermal_throttle_s),
    }
    # resilience twin (core.faults): goodput vs throughput. "Useful" work
    # is the node-seconds of completed jobs; lost_node_seconds is what
    # kills destroyed (since-last-checkpoint for retries, whole jobs for
    # terminal failures). goodput_frac = useful / (useful + lost) — the
    # fraction of delivered node-seconds that produced finished jobs.
    if s.stream is None:
        useful = reduce_tail(
            (np.asarray(s.jstate) == DONE) * f(s.dur_est) * f(s.n_nodes))
    else:
        if statics is None:
            from repro.utils.errors import ConfigError

            raise ConfigError("a streamed replica's summary needs its "
                              "statics: summary(state, telemetry, statics)")
        from repro.core.state import trace_records

        tr = jax.device_get(statics.trace)
        useful = np.sum((trace_records(s)["state"] == DONE)
                        * f(tr.dur) * f(tr.n_nodes))
    lost = f(s.lost_node_s)
    cols["lost_node_seconds"] = lost
    cols["jobs_failed_terminal"] = f(s.n_failed)
    cols["goodput_node_s"] = useful
    cols["goodput_frac"] = useful / np.maximum(useful + lost, 1e-9)
    # serving twin (core.serving): request accounting from the state
    # accumulators (zeros with serving off) + SLO quantiles from the
    # episode latency histogram. goodput_requests = completed mass that
    # met the SLO; shed/dropped are the terminal overload-ladder losses.
    n_req = np.maximum(f(s.srv_completed), 1e-9)
    cols["srv_arrived"] = f(s.srv_arrived)
    cols["srv_completed"] = f(s.srv_completed)
    cols["srv_shed"] = f(s.srv_shed)
    cols["srv_dropped"] = f(s.srv_dropped)
    cols["srv_retried"] = f(s.srv_retried)
    cols["srv_mean_latency_s"] = f(s.srv_lat_sum) / n_req
    cols["srv_slo_violation_frac"] = f(s.srv_slo_viol) / n_req
    cols["srv_goodput_requests"] = f(s.srv_completed) - f(s.srv_slo_viol)
    hist = f(s.srv_lat_hist)                    # (..., 8)
    tot = np.maximum(hist.sum(-1, keepdims=True), 1e-9)
    c = np.cumsum(hist, -1) / tot
    # bucket i spans serving_slo_s * [2^(i-4), 2^(i-3)); quantiles are
    # reported at the upper edge in SLO units (the summary has no cfg)
    edge = 2.0 ** (np.arange(8, dtype=np.float64) - 3.0)
    any_req = hist.sum(-1) > 0.0                # no completions -> 0.0
    cols["srv_p50_latency_x_slo"] = np.where(
        any_req, edge[np.argmax(c >= 0.5, axis=-1)], 0.0)
    cols["srv_p99_latency_x_slo"] = np.where(
        any_req, edge[np.argmax(c >= 0.99, axis=-1)], 0.0)
    if telemetry is not None:
        # macro-stepping skip accounting (satellite of the macro engine):
        # how much of the episode the engine fast-forwarded. Windowed
        # telemetry (telemetry_every=k) arrives with a window axis after
        # the replica one — summing it recovers the episode totals.
        tl = jax.device_get(telemetry)
        ticks = reduce_tail(tl.n_steps)
        full = reduce_tail(tl.macro_steps)
        cols["ticks_simulated"] = ticks
        cols["macro_steps_taken"] = full
        cols["macro_skip_ratio"] = ticks / np.maximum(full, 1.0)
        # cooling-plant telemetry (tick-weighted across windows)
        cols["mean_cop"] = (
            reduce_tail(f(tl.mean_cop) * f(tl.n_steps))
            / np.maximum(ticks, 1.0))
        cols["max_rack_outlet_c"] = reduce_tail(tl.max_rack_c, op=np.max)
    return cols


def summary(state: SimState,
            telemetry: TelemetrySummary | None = None,
            statics: Statics | None = None) -> dict:
    """Scalar episode summary of one (unbatched) final state — the 0-d
    special case of ``summary_columns``."""
    return {k: float(v)
            for k, v in summary_columns(state, telemetry, statics).items()}
