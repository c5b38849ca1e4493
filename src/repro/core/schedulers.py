"""Job-selection policies — stage (a) of the two-stage policy engine.

Each policy looks at the job table and returns the index of the queued job
to attempt next (or -1). Node placement is the second, independent stage
(``repro.core.placement``): selection answers *which job*, placement
answers *which nodes*. The RL policy is external: its action picks among
the top ``sched_max_candidates`` FCFS-ordered queue candidates (or no-op).

Policies mirror RAPS' production-Slurm-matching set [Maiterth et al. 2025]:
replay | fcfs | sjf | priority | easy (FCFS + EASY backfill).

Policy-as-data: every selection carries an int32 id (``SELECT_IDS``) and
``select_job`` resolves a *traced* id via ``lax.switch`` — one compiled
``step`` then serves the whole selection grid (see ``core.placement`` for
the matching placement ids and the combined ``Policy`` encoding).
"""

from __future__ import annotations

from typing import Tuple

import jax
import jax.numpy as jnp

from repro.configs.sim import SimConfig
from repro.core import power
from repro.core.state import QUEUED, RUNNING, NRES, SimState, Statics

BIG = 1e18


def queued_mask(state: SimState) -> jax.Array:
    return (state.jstate == QUEUED) & (state.submit_t <= state.t)


def feasible_nodes(state: SimState, job: jax.Array) -> jax.Array:
    """(N,) bool: nodes that can host one rank of `job` right now."""
    req = state.req[:, job]                       # (NRES,)
    ok = jnp.all(state.free >= req[:, None], axis=0)
    return ok & (state.node_up > 0.5)


def capacity_feasible_nodes(state: SimState, statics: Statics,
                            job: jax.Array) -> jax.Array:
    """(N,) bool: up nodes whose *total* capacity can host one rank of
    `job` — i.e. nodes that could host it once their current tenants leave
    (a CPU node can never host a GPU job, busy or not)."""
    req = state.req[:, job]                       # (NRES,)
    ok = jnp.all(statics.capacity >= req[:, None], axis=0)
    return ok & (state.node_up > 0.5)


def partition_ok(part: jax.Array, node_type: jax.Array) -> jax.Array:
    """THE TX-GAIA partition rule (single source — placement and selection
    both derive from it): tag -1 = any node; otherwise the node type must
    match. Broadcasts (scalar tag vs (N,), or (J,1) vs (1,N))."""
    return (part < 0) | (node_type == part)


def partition_mask_all(state: SimState, statics: Statics) -> jax.Array:
    """(J, N) bool: per-job node eligibility under partition semantics.
    The batched form of ``placement.partition_mask``; ``make_step`` feeds
    it to selection as ``node_mask`` when the active placement enforces
    partitions, so EASY never picks a job placement will reject."""
    return partition_ok(state.part[:, None], statics.node_type[None, :])


def fits_now_mask(state: SimState,
                  node_mask: jax.Array | None = None) -> jax.Array:
    """(J,) bool: jobs whose whole-node request is satisfiable against the
    CURRENT free pool (enough feasible up nodes, optionally restricted to
    ``node_mask`` (J, N) — the placement backend's eligibility). Used to
    keep EASY's backfill from wasting a dispatch attempt on an infeasible
    candidate."""
    ok = jnp.all(state.free[:, None, :] >= state.req[:, :, None], axis=0)
    ok = ok & (state.node_up > 0.5)[None, :]                 # (J, N)
    if node_mask is not None:
        ok = ok & node_mask
    return jnp.sum(ok, axis=1) >= state.n_nodes


def first_fit(state: SimState, job: jax.Array, K: int) -> Tuple[jax.Array, jax.Array]:
    """Choose `n_nodes[job]` lowest-index feasible nodes, sort-free.

    O(N + K log N) cumsum ranking instead of the O(N log N) argsort: the
    rank of a feasible node among feasible nodes is ``cumsum(ok) - 1``
    (feasibility order == index order), so the node filling placement slot
    ``s`` is the first index where the monotone cumsum reaches ``s + 1`` —
    a binary search, no sort and no scatter. Bit-equivalent to
    ``first_fit_argsort`` (property-tested).

    Returns (placement_row (K,), feasible bool).
    """
    ok = feasible_nodes(state, job)
    n_req = state.n_nodes[job]
    csum = jnp.cumsum(ok)
    slots = jnp.arange(K)
    idx = jnp.searchsorted(csum, slots + 1).astype(jnp.int32)
    row = jnp.where(slots < n_req, idx, -1)
    enough = csum[-1] >= n_req
    return jnp.where(enough, row, -1), enough


def first_fit_argsort(state: SimState, job: jax.Array, K: int) -> Tuple[jax.Array, jax.Array]:
    """Legacy argsort placement — kept as the equivalence oracle for
    ``first_fit`` (tests + ``benchmarks/bench_dispatch.py``)."""
    N = state.free.shape[1]
    ok = feasible_nodes(state, job)
    n_req = state.n_nodes[job]
    order = jnp.argsort(jnp.where(ok, 0, 1) * N + jnp.arange(N))  # feasible first
    slots = jnp.arange(K)
    row = jnp.where(slots < n_req, order[:K], -1)
    enough = jnp.sum(ok) >= n_req
    return jnp.where(enough, row, -1), enough


# --------------------------------------------------------------------------
# candidate orderings — uniform signature (cfg, state, statics[, node_mask])
# -> job id. ``node_mask`` (J, N) is the placement backend's node
# eligibility (None = every node): only EASY consults it, but the uniform
# signature keeps the policy-as-data switch branches interchangeable.
def _masked_argmin(score: jax.Array, mask: jax.Array) -> jax.Array:
    s = jnp.where(mask, score, BIG)
    idx = jnp.argmin(s)
    return jnp.where(jnp.any(mask), idx, -1)


def _pick(state: SimState, score: jax.Array, mask: jax.Array) -> jax.Array:
    """The masked job of least ``score``, ties to the earliest in trace
    order. On the resident path slot order is trace order; a streamed
    trace reuses slots, so there ties go to the least trace id."""
    if state.stream is None:
        return _masked_argmin(score, mask)
    s = jnp.where(mask, score, BIG)
    tie = mask & (s == jnp.min(s))
    last = jnp.iinfo(jnp.int32).max
    idx = jnp.argmin(jnp.where(tie, state.stream.tid, last))
    return jnp.where(jnp.any(mask), idx, -1)


def select_fcfs(cfg: SimConfig, state: SimState, statics: Statics,
                node_mask: jax.Array | None = None) -> jax.Array:
    return _pick(state, state.submit_t, queued_mask(state))


def select_sjf(cfg: SimConfig, state: SimState, statics: Statics,
               node_mask: jax.Array | None = None) -> jax.Array:
    return _pick(state, state.dur_est, queued_mask(state))


def select_priority(cfg: SimConfig, state: SimState, statics: Statics,
                    node_mask: jax.Array | None = None) -> jax.Array:
    return _pick(state, -state.priority, queued_mask(state))


def select_replay(cfg: SimConfig, state: SimState, statics: Statics,
                  node_mask: jax.Array | None = None) -> jax.Array:
    """Replay: dispatch in recorded start order — priority carries the
    recorded start time; a job becomes eligible once t >= recorded start."""
    m = queued_mask(state) & (state.priority <= state.t)
    return _pick(state, state.priority, m)


def shadow_time(cfg: SimConfig, state: SimState, statics: Statics,
                head: jax.Array,
                node_mask: jax.Array | None = None) -> jax.Array:
    """EASY reservation: earliest time the head job could start, assuming
    running jobs release their nodes at their walltime estimates.

    Approximation (standard in queueing sims): sort running jobs' estimated
    end times; find when cumulative released *whole-node* count reaches the
    head job's requirement given currently-free feasible nodes. Only
    releases of HEAD-FEASIBLE nodes count: a CPU-node release can never
    satisfy a GPU head job, so crediting it (as the pre-fix code did)
    made the backfill window optimistically wrong on heterogeneous
    clusters.
    """
    running = state.jstate == RUNNING
    est_end = jnp.where(running, state.start_t + state.dur_est, BIG)
    head_ok = capacity_feasible_nodes(state, statics, head)   # (N,)
    free_ok = feasible_nodes(state, head)
    if node_mask is not None:
        head_ok = head_ok & node_mask[head]
        free_ok = free_ok & node_mask[head]
    # nodes each running job will release THAT COULD HOST THE HEAD
    rel_nodes = power.flagged_counts(state.placement, head_ok)
    rel_nodes = jnp.where(running, rel_nodes, 0.0)
    order = jnp.argsort(est_end)
    cum = jnp.cumsum(rel_nodes[order])
    free_now = jnp.sum(free_ok)
    need = jnp.maximum(state.n_nodes[head].astype(jnp.float32) - free_now, 0.0)
    reached = cum >= need
    first = jnp.argmax(reached)
    t_shadow = jnp.where(jnp.any(reached), est_end[order][first], BIG)
    return jnp.where(need > 0, t_shadow, state.t)


def select_easy(cfg: SimConfig, state: SimState, statics: Statics,
                node_mask: jax.Array | None = None) -> jax.Array:
    """FCFS head first; if head infeasible, backfill any queued job that (a)
    fits NOW, and (b) finishes before the head's shadow time. Every
    feasibility check honors ``node_mask`` (the placement backend's node
    eligibility, e.g. partition) so EASY never selects a job the placement
    stage would reject — which would waste the dispatch attempt."""
    head = select_fcfs(cfg, state, statics)

    def with_head(head):
        head_ok = feasible_nodes(state, head)
        if node_mask is not None:
            head_ok = head_ok & node_mask[head]
        head_fits = jnp.sum(head_ok) >= state.n_nodes[head]

        def backfill(_):
            t_sh = shadow_time(cfg, state, statics, head, node_mask)
            # candidate must be currently feasible (an infeasible pick
            # turns the whole dispatch attempt into a no-op), fit before
            # the reservation, and not be the head
            m = queued_mask(state) & fits_now_mask(state, node_mask)
            fits_window = (state.t + state.dur_est) <= t_sh
            not_head = jnp.arange(m.shape[0]) != head
            cand = _pick(state, state.submit_t, m & fits_window & not_head)
            return cand

        return jax.lax.cond(head_fits, lambda _: head, backfill, None)

    return jax.lax.cond(head >= 0, with_head, lambda _: jnp.int32(-1),
                        jnp.int32(jnp.maximum(head, 0)))


SCHEDULERS = {
    "replay": select_replay,
    "fcfs": select_fcfs,
    "sjf": select_sjf,
    "priority": select_priority,
    "easy": select_easy,
}

# policy-as-data ids: position in SCHEDULERS (insertion-ordered) — the
# branch order of the `select_job` lax.switch
SELECT_IDS = {name: i for i, name in enumerate(SCHEDULERS)}


def select_job(cfg: SimConfig, state: SimState, statics: Statics,
               select_id: jax.Array,
               node_mask: jax.Array | None = None) -> jax.Array:
    """Resolve a *traced* int32 selection id to a job pick via
    ``lax.switch`` — every selection policy lives in ONE compiled step, so
    sweeping the selection axis costs zero recompiles. ``node_mask`` is
    the active placement backend's (J, N) node eligibility (or None)."""
    branches = tuple(
        (lambda fn: (lambda s: fn(cfg, s, statics, node_mask)))(fn)
        for fn in SCHEDULERS.values()
    )
    return jax.lax.switch(select_id, branches, state)


def rl_candidates(cfg: SimConfig, state: SimState) -> jax.Array:
    """Top-k FCFS-ordered queued jobs the RL agent chooses among. (k,) int.

    ``lax.top_k`` (O(J log k)) instead of a full O(J log J) argsort; both
    break ties by lowest index, so the candidate order is unchanged.
    """
    k = cfg.sched_max_candidates
    m = queued_mask(state)
    score = jnp.where(m, state.submit_t, BIG)
    _, idx = jax.lax.top_k(-score, k)
    ok = jnp.take(m, idx)
    return jnp.where(ok, idx.astype(jnp.int32), -1)
