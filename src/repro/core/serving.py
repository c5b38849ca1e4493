"""Fluid online-inference serving plane (docs/serving.md).

The "heavy traffic from millions of users" workload: requests are
continuous MASS, not job-table entities — a deterministic fluid
approximation of an M/G/c queue driven by ``Scenario.traffic`` (request
rate [req/s]) scaled by ``Scenario.bursts`` flash-crowd windows. A pool
of ``cfg.serving_nodes`` inference nodes — disjoint from the batch
fleet, power injected into the shared plant chain — serves the mass
with a prefill/decode-blended utilization profile derived from the
roofline model (``perfmodel.workload_gen.serving_profile``).

Two code paths, split exactly like the fault engine (``core.faults``):

- the CONTINUOUS flow — arrivals, admission into service, completions,
  the fluid latency estimator and SLO accounting (``serving_flow``) and
  the pool's power draw (``serving_power``) — runs in the shared
  accounting tail every tick (``core.sim._make_tail``), so macro fast
  ticks reproduce it bit-identically;
- the DISCRETE overload ladder (``apply_serving``) — autoscale
  wake/sleep, retry re-injection, queue timeouts, admission control,
  hard load shedding — runs on full event ticks ONLY, and every phase
  is a bitwise fixpoint when untriggered.

Overload ladder (first resort first):

1. admission control: queue mass above ``srv_admit_thresh *
   serving_queue_cap`` (a schedulable threshold) bounces to a
   backoff-retry bucket instead of waiting;
2. per-request timeout: queue mass that cannot reach service within
   ``serving_timeout_s`` at the pool's full rate times out into the
   same retry path;
3. capped exponential-backoff retry: mass bounced from attempt tier r
   waits ``retry_backoff(cfg, r+1)`` (the PR 7 requeue rule applied to
   request tiers) and re-enters the queue at the absolute time stored
   in ``srv_retry_t`` — an exact macro breakpoint; mass bounced out of
   the top tier has exhausted its retry budget and is DROPPED
   (terminal);
4. hard shedding: queue mass above ``serving_queue_cap`` is SHED
   terminally — the bound that keeps the admission queue finite;
5. autoscale: ``srv_target`` (an RL action) wakes/sleeps pool nodes.
   Wakes take ``serving_wake_s`` (absolute completion time
   ``srv_wake_t`` — another exact breakpoint); scale-down is instant
   but DRAINS (already-admitted mass completes; only new admissions
   need awake capacity); asleep nodes burn ``serving_sleep_w`` — the
   SPARS power-management tradeoff.

Macro-exactness contract (the PR 6/7 bar):

- TIME-type events (wake completions, retry re-injections, burst-window
  edges) are absolute times folded into the quiet-horizon min via
  ``next_serving_event`` — fast ticks never run the discrete sweep, so
  a segment must end strictly before any of them fire;
- THRESHOLD-type events (the queue crossing the admission/timeout/shed
  bounds as arrivals accumulate) are detected authoritatively on each
  committed fast tick (``serving_trigger``; the thermal ``was_hot``
  pattern). Stopping AFTER the crossing tick is exact because the sweep
  reads predecessor-committed state — on the crossing tick itself the
  per-tick path's sweep was still a fixpoint;
- ``serving_crossing_horizon`` additionally bounds segment length by
  the worst-case arrival rate (traffic-signal envelope x largest burst
  multiplier), belt to the per-tick detection's suspenders. Sustained
  overload degrades to per-tick stepping by construction (every tick
  triggers) — the correct regime: overload IS the event.

Zero PRNG draws anywhere — the serving plane is deterministic fluid
flow, so the key stream is untouched and macro bit-identity holds
trivially on the PRNG side.
"""

from __future__ import annotations

from typing import Tuple

import jax
import jax.numpy as jnp

from repro.configs.sim import SimConfig
from repro.core.state import SimState, Statics
from repro.scenarios.events import burst_mult_at, next_burst_event
from repro.scenarios.signals import eval_signal, signal_bounds

_INF = jnp.float32(jnp.inf)


def retry_backoff(cfg: SimConfig, attempt) -> jax.Array:
    """Backoff [s] before a request's ``attempt``-th try (attempt >= 1):
    ``base * mult**(attempt-1)``, capped at ``serving_backoff_cap_s`` —
    strictly increasing until the cap (tests/test_serving.py pins both
    properties)."""
    a = jnp.maximum(jnp.asarray(attempt, jnp.float32) - 1.0, 0.0)
    b = jnp.float32(cfg.serving_backoff_s) * jnp.power(
        jnp.float32(cfg.serving_backoff_mult), a)
    return jnp.minimum(b, jnp.float32(cfg.serving_backoff_cap_s))


def _allowed_queue(
    cfg: SimConfig, active: jax.Array, admit_thresh: jax.Array
) -> Tuple[jax.Array, jax.Array]:
    """(allowed, q_cap): the admission-control bound — the schedulable
    threshold, tightened by the timeout-reach capacity (queue mass the
    pool can start within ``serving_timeout_s`` at full clock) — and the
    hard-shed bound. ``serving_trigger`` mirrors these expressions
    exactly so threshold crossings can never be missed."""
    q_cap = jnp.float32(cfg.serving_queue_cap)
    allowed = admit_thresh * q_cap
    if cfg.serving_timeout_s > 0:
        svc = max(cfg.serving_service_s, 1e-9)
        serve_full = active * jnp.float32(cfg.serving_concurrency / svc)
        reach = serve_full * jnp.float32(
            max(cfg.serving_timeout_s - svc, 0.0))
        allowed = jnp.minimum(allowed, reach)
    return allowed, q_cap


def apply_serving(
    cfg: SimConfig, state: SimState, statics: Statics
) -> Tuple[SimState, jax.Array, jax.Array, jax.Array]:
    """One discrete serving sweep (full event ticks only): autoscale
    wake completion + target reconciliation, retry re-injection, then
    the shed/timeout/admission cascade. Returns
    ``(state, shed_now, dropped_now, retried_now)`` in request mass.

    Invariants the macro engine relies on:

    - on a tick where no wake/retry clock is due and the queue is under
      every threshold, the whole update is a bitwise fixpoint (adds of
      0.0, multiplies by 1.0, untaken wheres);
    - every clock left behind is strictly future or +inf, so the
      ``> t`` guard in ``next_serving_event`` never hides a pending one;
    - no PRNG use.
    """
    t = state.t
    f32 = jnp.float32

    # --- (5) autoscale: wake completion, then target reconciliation.
    # Scale-down is instant (drain semantics) and cancels any in-flight
    # wake; a new wake batch starts only when none is in flight — the
    # full tick after a completion (a breakpoint) picks up any deficit
    # left, so one scalar wake clock suffices.
    woke = t >= state.srv_wake_t
    active = jnp.where(woke, state.srv_active + state.srv_wake_n,
                       state.srv_active)
    wake_n = jnp.where(woke, 0.0, state.srv_wake_n)
    wake_t = jnp.where(woke, _INF, state.srv_wake_t)
    target = jnp.clip(state.srv_target, 0.0, f32(cfg.serving_nodes))
    down = target < active
    wake_n = jnp.where(down, 0.0, wake_n)
    wake_t = jnp.where(down, _INF, wake_t)
    active = jnp.where(down, target, active)
    deficit = jnp.maximum(target - active - wake_n, 0.0)
    start = (deficit > 0.0) & (wake_n <= 0.0)
    wake_n = jnp.where(start, deficit, wake_n)
    wake_t = jnp.where(start, t + f32(cfg.serving_wake_s), wake_t)

    # --- (3) retry re-injection: due buckets pour back into their
    # attempt tier at the absolute time the backoff rule scheduled.
    due = t >= state.srv_retry_t
    queue = state.srv_queue + jnp.where(due, state.srv_retry_q, 0.0)
    retry_q = jnp.where(due, 0.0, state.srv_retry_q)
    retry_t = jnp.where(due, _INF, state.srv_retry_t)

    # --- (4) hard shed first (the queue bound is absolute), then
    # (1)+(2) the admission/timeout bounce. Mass leaves every tier
    # proportionally; tier r bounces into retry bucket r+1 (the attempt
    # counter) and the top tier — out of retry budget — drops.
    q_tot = jnp.sum(queue)
    allowed, q_cap = _allowed_queue(cfg, active, state.srv_admit_thresh)
    eps = f32(1e-9)
    shed_now = jnp.maximum(q_tot - q_cap, 0.0)
    queue = queue * (1.0 - shed_now / jnp.maximum(q_tot, eps))
    q_kept = q_tot - shed_now
    bounce = jnp.maximum(q_kept - allowed, 0.0)
    bfrac = bounce / jnp.maximum(q_kept, eps)
    moved = queue * bfrac
    queue = queue * (1.0 - bfrac)
    inc = jnp.concatenate([jnp.zeros((1,), f32), moved[:-1]])
    dropped_now = moved[-1]
    retried_now = jnp.sum(moved[:-1])
    backoff = retry_backoff(cfg, jnp.arange(inc.shape[0]))
    got = inc > 0.0
    retry_t = jnp.where(got, jnp.minimum(retry_t, t + backoff), retry_t)
    # the select (a no-op: inc >= 0) keeps ``queue * bfrac`` from feeding
    # this add directly. XLA:CPU contracts a multiply and an add that share
    # a fusion into one FMA, and whether they share one depends on the
    # program around the step: the per-tick and macro programs rounded
    # this sum differently on the same event tick
    retry_q = retry_q + jnp.where(got, inc, 0.0)

    state = state._replace(
        srv_queue=queue, srv_retry_q=retry_q, srv_retry_t=retry_t,
        srv_active=active, srv_wake_n=wake_n, srv_wake_t=wake_t,
        srv_target=target,
        srv_shed=state.srv_shed + shed_now,
        srv_dropped=state.srv_dropped + dropped_now,
        srv_retried=state.srv_retried + retried_now,
    )
    return state, shed_now, dropped_now, retried_now


def serving_power(
    cfg: SimConfig, state: SimState, cop: jax.Array
) -> Tuple[jax.Array, jax.Array, jax.Array, jax.Array]:
    """(it_w, input_w, cooling_w, idle_w) of the serving pool this tick,
    from pool state + current in-flight occupancy. Dynamic power blends
    the prefill/decode utilization profile and scales with occupancy;
    awake (and waking) nodes burn idle power at any clock — it joins
    the DVFS cap's unthrottleable floor — and asleep nodes burn the
    sleep wattage, the SPARS tradeoff."""
    f32 = jnp.float32
    cap_conc = state.srv_active * f32(cfg.serving_concurrency)
    occ = jnp.clip(state.srv_inflight / jnp.maximum(cap_conc, 1e-9),
                   0.0, 1.0)
    phase_util = (cfg.serving_prefill_frac * cfg.serving_prefill_util
                  + (1.0 - cfg.serving_prefill_frac)
                  * cfg.serving_decode_util)
    asleep = jnp.maximum(
        f32(cfg.serving_nodes) - state.srv_active - state.srv_wake_n, 0.0)
    idle_w = ((state.srv_active + state.srv_wake_n)
              * f32(cfg.serving_node_idle_w)
              + asleep * f32(cfg.serving_sleep_w))
    dyn_w = (state.srv_active * f32(cfg.serving_node_dyn_w)
             * f32(phase_util) * occ)
    it_w = idle_w + dyn_w
    input_w = it_w / f32(cfg.rect_eff_peak * cfg.conv_eff)
    cooling_w = input_w / cop
    return it_w, input_w, cooling_w, idle_w


def serving_flow(
    cfg: SimConfig, state: SimState, statics: Statics, throttle: jax.Array
):
    """One tick of the continuous request-mass flow — runs in the shared
    accounting tail, so macro fast ticks reproduce it bit-identically:
    arrivals from the traffic signal (x burst multiplier) into attempt
    tier 0, completions out of the in-flight mass at the (DVFS/thermal)
    throttled service rate, admission of queued mass into freed
    concurrency, and the fluid latency estimator feeding the SLO
    accounting. Returns ``(state, arrive, comp, viol, w_est, q_after,
    hist_step)``."""
    f32 = jnp.float32
    scn = statics.scenario
    lam = (jnp.maximum(eval_signal(scn.traffic, state.t), 0.0)
           * burst_mult_at(scn.bursts, state.t))
    arrive = lam * f32(cfg.dt)
    svc = f32(max(cfg.serving_service_s, 1e-9))
    cap_conc = state.srv_active * f32(cfg.serving_concurrency)
    comp = state.srv_inflight * jnp.clip(throttle * f32(cfg.dt) / svc,
                                         0.0, 1.0)
    inflight = state.srv_inflight - comp
    queue = state.srv_queue.at[0].add(arrive)
    q_tot = jnp.sum(queue)
    room = jnp.maximum(cap_conc - inflight, 0.0)
    admit = jnp.minimum(q_tot, room)
    queue = queue * (1.0 - admit / jnp.maximum(q_tot, f32(1e-9)))
    inflight = inflight + admit
    q_after = q_tot - admit
    # fluid sojourn estimate for mass completing this tick: residual
    # queue wait at the throttled full-pool service rate plus the
    # (clock-stretched) service time itself
    serve_rate = cap_conc * throttle / svc
    w_est = (q_after / jnp.maximum(serve_rate, 1e-9)
             + svc / jnp.maximum(throttle, 1e-9))
    viol = comp * (w_est > f32(cfg.serving_slo_s)).astype(f32)
    # log-2 latency histogram around the SLO: bucket i spans
    # serving_slo_s * [2^(i-4), 2^(i-3)); quantiles are reported at the
    # bucket upper edge in SLO units (core.sim.summary_columns)
    ratio = w_est / f32(max(cfg.serving_slo_s, 1e-9))
    idx = jnp.clip(
        jnp.floor(jnp.log2(jnp.maximum(ratio, 1e-9))).astype(jnp.int32) + 4,
        0, 7)
    hist_step = jnp.zeros((8,), f32).at[idx].add(comp)
    state = state._replace(
        srv_queue=queue,
        srv_inflight=inflight,
        srv_arrived=state.srv_arrived + arrive,
        srv_completed=state.srv_completed + comp,
        srv_slo_viol=state.srv_slo_viol + viol,
        srv_lat_sum=state.srv_lat_sum + comp * w_est,
        srv_lat_hist=state.srv_lat_hist + hist_step,
    )
    return state, arrive, comp, viol, w_est, q_after, hist_step


def serving_trigger(cfg: SimConfig, state: SimState) -> jax.Array:
    """Would the next full tick's ``apply_serving`` cascade move mass?
    THRESHOLD-type causes only (clock events are horizon breakpoints):
    queue mass strictly above the admission/timeout/shed bound.
    Evaluated on committed state after each macro fast tick (the
    thermal ``was_hot`` pattern): True ends the segment so the sweep
    runs on the following full tick exactly as the per-tick path would.
    False positives are safe (the sweep is then a fixpoint); the
    expression mirrors ``_allowed_queue`` so false negatives cannot
    happen."""
    allowed, q_cap = _allowed_queue(cfg, state.srv_active,
                                    state.srv_admit_thresh)
    return jnp.sum(state.srv_queue) > jnp.minimum(allowed, q_cap)


def next_serving_event(
    cfg: SimConfig, state: SimState, statics: Statics, t: jax.Array
) -> jax.Array:
    """Earliest serving TIME-type breakpoint strictly after ``t``
    (``inf`` when none): the autoscale wake completion, any pending
    retry re-injection, or a traffic-burst window edge — same contract
    as ``next_fault_event``. The discrete sweep runs on full ticks
    only, so the macro engine must never fast-forward past one."""
    nxt = jnp.where(state.srv_wake_t > t, state.srv_wake_t, _INF)
    nxt = jnp.minimum(nxt, jnp.min(
        jnp.where(state.srv_retry_t > t, state.srv_retry_t, _INF)))
    return jnp.minimum(nxt, next_burst_event(statics.scenario.bursts, t))


def serving_crossing_horizon(
    cfg: SimConfig, state: SimState, statics: Statics, max_ticks
) -> jax.Array:
    """Conservative tick count within which arrivals cannot push the
    queue across the nearest overload threshold: headroom / (worst-case
    rate x dt) minus one tick of float margin. Inside a quiet segment
    the queue only grows through arrivals (admission drains it; retry
    re-injections are clock breakpoints that already end the segment),
    and the arrival rate is bounded by the traffic signal's envelope
    times the burst multiplier in force at ``t`` — sound because burst
    edges are hard breakpoints (``next_serving_event``), so a segment
    never crosses a multiplier change. Belt to ``serving_trigger``'s
    suspenders, like ``thermal_crossing_horizon``.
    """
    scn = statics.scenario
    _, hi = signal_bounds(scn.traffic)
    lam_hi = jnp.maximum(hi, 0.0) * burst_mult_at(scn.bursts, state.t)
    allowed, q_cap = _allowed_queue(cfg, state.srv_active,
                                    state.srv_admit_thresh)
    headroom = jnp.maximum(
        jnp.minimum(allowed, q_cap) - jnp.sum(state.srv_queue), 0.0)
    per_tick = lam_hi * jnp.float32(cfg.dt)
    kf = jnp.float32(max_ticks)
    k = jnp.where(per_tick > 0.0,
                  jnp.floor(headroom / jnp.maximum(per_tick, 1e-9)) - 1.0,
                  kf)
    return jnp.clip(k, 0.0, kf).astype(jnp.int32)
