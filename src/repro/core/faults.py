"""Hierarchical, macro-compatible fault engine (docs/resilience.md).

Replaces the old inline per-tick Bernoulli failure sweep with
*event-sampled* fault clocks: ``SimState.next_fail_t`` (per node) and
``SimState.rack_fail_t`` (per rack — a cooling-loop/PDU fault downs the
whole rack at once) hold ABSOLUTE exponential next-failure times, redrawn
only when they fire. Scenario-scheduled grid brownouts / maintenance
windows (``scenarios.events.OutageSchedule``) add deterministic forced
outages and degradation levels on top.

Why event-sampled: every fault is now an exact, predictable breakpoint
(``next_fault_event``) that ``core.sim.quiet_horizon`` folds into the
macro-stepping segment bound, and the PRNG key advances ONLY on ticks
where a clock actually fires — so fast-forwarded quiet ticks consume
zero randomness and ``macro=True`` stays bit-identical (state + PRNG
stream) to per-tick stepping with faults on. The old Bernoulli sweep had
to be replayed per tick during fast-forward, forfeiting the macro
speedup exactly when faults were enabled; it also handed
``jax.random.bernoulli`` an unclamped ``dt/mtbf`` probability that
exceeded 1 for coarse ``dt`` against short MTBFs. Both problems vanish
with the clock formulation (an exponential inter-arrival time is valid
at any ``dt``).

Job resilience semantics on a kill (``apply_faults``):

- restart from the last simulated checkpoint: ``work_left`` rewinds to
  ``dur_est - ckpt_kept`` (progress floored to the checkpoint grid), not
  all the way to ``dur_est``; the periodic checkpoint-write cost is
  charged continuously as a progress drag (``ckpt_drag``, consumed by
  the accounting tail) so power burns at full rate while wall-clock
  progress slows;
- retry budget: a job killed more than ``cfg.max_job_retries`` times
  goes terminal ``FAILED`` (0 = unbounded, the legacy rule);
- requeue backoff: retried jobs wait ``requeue_backoff_s * mult**(n-1)``
  before re-eligibility, implemented by advancing ``submit_t`` — which
  reuses the arrival-breakpoint and ``queued_mask`` machinery untouched;
- lost-work accounting: ``lost_node_s`` integrates the node-seconds of
  progress destroyed by kills (since-last-checkpoint for retries, the
  whole job for terminal failures) — the goodput-vs-throughput gap
  surfaced by ``summary()``.

The graceful-degradation ladder (throttle -> dispatch-gate -> drain ->
checkpoint-evict) is a scalar level: the max of the RL-schedulable
``SimState.degrade_level`` and any active outage window's forced level.
Levels >= ``LVL_THROTTLE`` clock-throttle dynamic power and progress,
>= ``LVL_GATE`` block new dispatch (via ``make_step``'s dispatch view),
and ``LVL_EVICT`` checkpoint-evicts running jobs (requeued with progress
intact — the graceful alternative to losing since-checkpoint work when
the thermal/power emergency kills nodes for real).
"""

from __future__ import annotations

from typing import Tuple

import jax
import jax.numpy as jnp

from repro.configs.sim import SimConfig
from repro.core.state import FAILED, QUEUED, RUNNING, SimState, Statics
from repro.scenarios.events import (
    next_outage_event,
    outage_down,
    outage_level_at,
)

# graceful-degradation ladder levels (ordered: each includes the previous)
LVL_NORMAL, LVL_THROTTLE, LVL_GATE, LVL_DRAIN, LVL_EVICT = 0, 1, 2, 3, 4

_INF = jnp.float32(jnp.inf)


def effective_level(cfg: SimConfig, state: SimState,
                    statics: Statics) -> jax.Array:
    """Current ladder level (int32 scalar): the max of the schedulable
    ``state.degrade_level`` and any active outage window's forced level.
    Within a quiet macro segment this is constant — outage edges are
    breakpoints and ``degrade_level`` only changes at decision ticks."""
    lvl = state.degrade_level if cfg.degrade_enabled else jnp.int32(0)
    if cfg.outages_enabled:
        lvl = jnp.maximum(
            lvl, outage_level_at(statics.scenario.outages, state.t))
    return lvl


def degrade_clock(cfg: SimConfig, lvl: jax.Array) -> jax.Array:
    """Clock fraction the ladder imposes on dynamic power + progress:
    1.0 below THROTTLE, ``degrade_throttle_frac`` at THROTTLE/GATE,
    the DVFS floor at DRAIN and above (run out the checkpoints, burn as
    little as possible)."""
    return jnp.where(
        lvl >= LVL_DRAIN, jnp.float32(cfg.throttle_floor),
        jnp.where(lvl >= LVL_THROTTLE,
                  jnp.float32(cfg.degrade_throttle_frac), jnp.float32(1.0)))


def ckpt_kept(state: SimState, prog: jax.Array) -> jax.Array:
    """(J,) work surviving a kill: progress floored to the job's
    checkpoint grid (0 when the job never checkpoints — the legacy
    restart-from-zero rule)."""
    iv = state.ckpt_interval
    return jnp.where(iv > 0.0,
                     jnp.floor(prog / jnp.maximum(iv, 1e-9)) * iv, 0.0)


def ckpt_drag(cfg: SimConfig, state: SimState) -> jax.Array:
    """(J,) progress-rate multiplier charging the periodic checkpoint
    write: of every ``interval + overhead`` seconds of wall clock, only
    ``interval`` advance the job — power keeps burning throughout, so
    energy-per-completed-job rises with checkpoint frequency."""
    iv = state.ckpt_interval
    ov = jnp.float32(cfg.ckpt_overhead_s)
    return jnp.where(iv > 0.0, iv / (iv + ov), 1.0)


def release_jobs(free: jax.Array, state: SimState,
                 mask: jax.Array) -> jax.Array:
    """Add back resources of jobs in `mask` (J,) to the free pool.

    Routed through ``power.scatter_add_nodes`` with the unmasked jobs'
    slots dropped: small configs get the dense one-hot contraction (under
    vmap the XLA scatter-add runs a generic per-env scatter loop on CPU,
    while the contraction is one batched matmul — this sits on the
    RL-rollout hot path, every completion sweep of every sub-step of every
    env); larger ones the scatter-add on the CPU and, on accelerators,
    ``req @ job_node_counts`` (their scatter-add is a serial sort-and-sum
    over every slot of the job table). Integer requests add back exactly
    on every path."""
    from repro.core.power import scatter_add_nodes

    place = jnp.where(mask[:, None], state.placement, -1)
    return scatter_add_nodes(place, state.req, free.shape[1], base=free)


def next_fault_event(cfg: SimConfig, state: SimState, statics: Statics,
                     t: jax.Array) -> jax.Array:
    """Earliest fault breakpoint strictly after ``t`` (``inf`` when
    none): the next node/rack clock crossing or outage-window edge.
    ``apply_faults`` keeps every clock strictly in the future (fires
    redraw, absorbed fires included), so the ``> t`` guard never hides a
    pending event — this is what makes faults exact macro breakpoints."""
    nxt = _INF
    if cfg.node_mtbf_hours > 0:
        nxt = jnp.minimum(nxt, jnp.min(jnp.where(
            state.next_fail_t > t, state.next_fail_t, _INF)))
    if cfg.rack_mtbf_hours > 0:
        nxt = jnp.minimum(nxt, jnp.min(jnp.where(
            state.rack_fail_t > t, state.rack_fail_t, _INF)))
    if cfg.outages_enabled:
        nxt = jnp.minimum(
            nxt, next_outage_event(statics.scenario.outages, t))
    return nxt


def _where_key(pred, new, old):
    """Select between PRNG keys (typed or raw uint32) with a predicate."""
    if jnp.issubdtype(jnp.result_type(old), jax.dtypes.prng_key):
        return jax.random.wrap_key_data(
            jnp.where(pred, jax.random.key_data(new),
                      jax.random.key_data(old)),
            impl=jax.random.key_impl(old))
    return jnp.where(pred, new, old)


def apply_faults(
    cfg: SimConfig, state: SimState, statics: Statics
) -> Tuple[SimState, jax.Array, jax.Array]:
    """One fault tick: fire due clocks, apply forced outages, repair,
    kill/evict/requeue jobs. Returns ``(state, killed_now, lost_now)``
    where ``killed_now`` counts jobs killed by node loss this tick and
    ``lost_now`` the node-seconds of progress destroyed.

    Invariants the macro engine relies on (tests/test_faults.py):

    - every clock in the returned state is strictly future (fires are
      redrawn past their repair, absorbed fires on already-down nodes
      included), so ``next_fault_event`` sees every pending event;
    - the PRNG key advances ONLY when a clock fires (forced outages and
      repairs are deterministic), so quiet ticks consume zero randomness;
    - on a tick with no crossing, no repair due and no window edge, the
      whole update is a fixpoint — fast-forwarding past such ticks is
      exact. Mid-window repairs are impossible by construction: a down
      node inside an active maintenance window has ``repair_t`` maxed to
      the window end at the window-start breakpoint, so nodes never flap
      up inside a window (which would be an unpredictable breakpoint).
    """
    t = state.t
    f32 = jnp.float32
    N = state.node_up.shape[0]
    R = state.rack_fail_t.shape[0]
    up = state.node_up > 0.5
    node_on = cfg.node_mtbf_hours > 0
    rack_on = cfg.rack_mtbf_hours > 0

    # --- deterministic outage context (no RNG)
    if cfg.outages_enabled:
        forced, forced_end = outage_down(
            statics.scenario.outages, t, statics.node_rack)
    else:
        forced = jnp.zeros((N,), bool)
        forced_end = jnp.zeros((N,), f32)
    lvl = effective_level(cfg, state, statics)

    # --- event-sampled clock crossings + redraws. Fires on already-down
    # nodes are "absorbed": the node stays down, its repair may extend,
    # and the clock still redraws — keeping next_fail_t always future.
    node_cross = (t >= state.next_fail_t) if node_on \
        else jnp.zeros((N,), bool)
    rack_fire = (t >= state.rack_fail_t) if rack_on \
        else jnp.zeros((R,), bool)

    key = state.key
    next_fail_t, rack_fail_t = state.next_fail_t, state.rack_fail_t
    repair_draw = rack_repair_draw = None
    if node_on or rack_on:
        any_fire = jnp.any(node_cross) | jnp.any(rack_fire)
        nk, *ks = jax.random.split(state.key,
                                   1 + 2 * (int(node_on) + int(rack_on)))
        ks = iter(ks)
        if node_on:
            repair_draw = jax.random.exponential(next(ks), (N,)) * f32(
                cfg.node_repair_hours * 3600.0)
            fail_draw = jax.random.exponential(next(ks), (N,)) * f32(
                cfg.node_mtbf_hours * 3600.0)
            next_fail_t = jnp.where(
                node_cross, t + repair_draw + fail_draw, state.next_fail_t)
        if rack_on:
            rack_repair_draw = jax.random.exponential(next(ks), (R,)) * f32(
                cfg.rack_repair_hours * 3600.0)
            rack_fail_draw = jax.random.exponential(next(ks), (R,)) * f32(
                cfg.rack_mtbf_hours * 3600.0)
            rack_fail_t = jnp.where(
                rack_fire, t + rack_repair_draw + rack_fail_draw,
                state.rack_fail_t)
        key = _where_key(any_fire, nk, state.key)

    member_fire = rack_fire[statics.node_rack] if rack_on \
        else jnp.zeros((N,), bool)

    # --- repair times: max over the firing causes, merged with the
    # node's standing repair if it is already down (stale repair_t of UP
    # nodes must not leak in). Forced windows extend ALL down members to
    # at least the window end, so no node flaps up mid-window.
    old_eff = jnp.where(up, 0.0, state.repair_t)
    cand = jnp.zeros((N,), f32)
    if node_on:
        cand = jnp.where(node_cross, t + repair_draw, cand)
    if rack_on:
        cand = jnp.maximum(cand, jnp.where(
            member_fire, t + rack_repair_draw[statics.node_rack], 0.0))
    if cfg.outages_enabled:
        cand = jnp.maximum(cand, jnp.where(forced, forced_end, 0.0))
    repair_t = jnp.where(cand > 0.0, jnp.maximum(old_eff, cand),
                         state.repair_t)

    # --- downs first, then repairs (the legacy ordering)
    down_mask = node_cross | member_fire | forced
    newly_down = down_mask & up
    node_up = jnp.where(down_mask, 0.0, state.node_up)
    repaired = (node_up < 0.5) & (t >= repair_t)
    node_up = jnp.where(repaired, 1.0, node_up)

    # --- kill running jobs touching newly-downed nodes; checkpoint-evict
    # the rest when the ladder says so
    place = state.placement
    valid = place >= 0
    on_down = jnp.any(
        jnp.where(valid, newly_down[jnp.where(valid, place, 0)], False),
        axis=1,
    ) & (state.jstate == RUNNING)
    if cfg.degrade_enabled or cfg.outages_enabled:
        evict = (state.jstate == RUNNING) & ~on_down & (lvl >= LVL_EVICT)
    else:
        evict = jnp.zeros_like(on_down)
    free = release_jobs(state.free, state, on_down | evict)

    # --- checkpoint-restart accounting: killed jobs rewind to their last
    # checkpoint (the since-checkpoint slice is lost work); evicted jobs
    # take a final on-demand checkpoint and keep all progress
    prog = jnp.maximum(state.dur_est - state.work_left, 0.0)
    kept = ckpt_kept(state, prog)
    work_left = jnp.where(on_down, state.dur_est - kept, state.work_left)

    # --- retry budget + terminal FAILED
    n_fail_new = state.n_failures + on_down.astype(jnp.int32)
    if cfg.max_job_retries > 0:
        exhausted = on_down & (n_fail_new > cfg.max_job_retries)
    else:
        exhausted = jnp.zeros_like(on_down)
    requeue = (on_down & ~exhausted) | evict
    jstate = jnp.where(exhausted, FAILED,
                       jnp.where(requeue, QUEUED, state.jstate))

    # --- requeue backoff: advancing submit_t reuses the arrival
    # breakpoint + queued_mask machinery untouched. Python-gated: with
    # backoff off, submit_t (and thus wait-time statistics) keep the
    # legacy original-submission baseline.
    submit_t = state.submit_t
    if cfg.requeue_backoff_s > 0:
        backoff = f32(cfg.requeue_backoff_s) * jnp.power(
            f32(cfg.requeue_backoff_mult),
            jnp.maximum(n_fail_new - 1, 0).astype(f32))
        submit_t = jnp.where(on_down & ~exhausted, t + backoff, submit_t)

    # --- scrub per-job fields so a requeued job is indistinguishable
    # from a freshly queued one (stale start_t was the audit finding)
    start_t = jnp.where(requeue | exhausted, 0.0, state.start_t)
    end_t = jnp.where(exhausted, t, state.end_t)
    placement = jnp.where((on_down | evict | exhausted)[:, None], -1, place)

    # --- lost-work accounting (goodput vs throughput): retries lose the
    # since-checkpoint slice, terminal failures the whole job, graceful
    # evictions nothing
    lost = jnp.where(on_down, prog - kept, 0.0)
    lost = jnp.where(exhausted, prog, lost)
    lost_now = jnp.sum(lost * state.n_nodes.astype(f32))
    killed_now = jnp.sum(on_down).astype(f32)

    state = state._replace(
        key=key, node_up=node_up, repair_t=repair_t, free=free,
        jstate=jstate, submit_t=submit_t, start_t=start_t, end_t=end_t,
        work_left=work_left, placement=placement,
        n_failures=n_fail_new,
        next_fail_t=next_fail_t, rack_fail_t=rack_fail_t,
        n_killed=state.n_killed + killed_now,
        n_failed=state.n_failed + jnp.sum(exhausted),
        lost_node_s=state.lost_node_s + lost_now,
    )
    return state, killed_now, lost_now
