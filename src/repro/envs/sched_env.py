"""OpenAI-Gym-style (pure functional) scheduling environment over the twin.

Action space: Discrete(k+1) — dispatch queue-candidate i in [0,k), or k =
no-op. Observations: fixed-size float vector of global datacenter features
+ per-candidate job features. Reward: the sim's energy/carbon/throughput
mix (paper: "the reward function combines energy consumption, carbon
footprint, and job throughput").

The env is a pytree-in/pytree-out (reset, step) pair -> vmap over
thousands of parallel datacenters, lax.scan over time, shard_map across
the mesh for distributed PPO. The sharded path is live, not aspirational:
``rl.distributed.distributed_ppo_train(env, launch.mesh.make_fleet_mesh())``
splits the ``n_envs`` replicas across devices with the same
replica-axis PartitionSpecs ``core.fleet.run_fleet(mesh=...)`` uses —
because ``EnvState`` is sim-state only (shared ``Statics`` stays
replicated, see below), each shard's rollout moves O(local envs x
sim-state) and only PPO gradients cross the wire.

Lightweight-state design (the RL-rollout hot path):

- ``EnvState`` is just ``(sim, step_count)``. The trace bank, node tables
  and scenario live in ONE shared ``Statics`` closed over by ``step``;
  the bank is stacked (W, J, Q) and each env selects its workload through
  the traced ``sim.workload`` int32 (``core.power`` gathers through it).
  Auto-reset therefore moves O(sim-state) per env — the previous design
  carried a full per-env ``Statics`` copy, so every vmapped env duplicated
  its (J, Q) bank slice and every reset paid the bank gather.
- ``step`` runs ONE dispatch sub-step (the agent's action) followed by
  ``sim_steps_per_action - 1`` idle sub-steps compiled WITHOUT the
  selection/placement stages (``make_step(..., "none")``) — bit-equivalent
  to the old always-dispatch scan whose non-zero sub-steps forced a no-op
  through the full candidate-ranking + placement pipeline.
- With ``macro=True`` (default) the idle sub-steps are ONE macro advance
  (``core.sim.make_macro_step``) clamped to the agent-decision boundary:
  quiet ticks between events fast-forward with exact segment accounting
  instead of running the completion/power machinery per tick (the
  scanned per-tick path is the degenerate every-tick-is-an-event case,
  kept under ``macro=False`` as the equivalence oracle).
- ``observe`` is fused: the per-node-type Python loop is a one-hot
  reduction, invariants (nameplate, capacity maxima, type one-hots,
  placement one-hot) are precomputed at construction, and candidate
  placement feasibility resolves the backend mask once per observation
  instead of once per candidate.
"""

from __future__ import annotations

from typing import Any, Dict, NamedTuple, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs.sim import SimConfig
from repro.core import faults as flt
from repro.core import placement as plc
from repro.core import schedulers as sched
from repro.core import thermal
from repro.core.sim import _macro_advance, make_macro_step, make_step
from repro.data.bank import stack_workloads
from repro.scenarios import Scenario, eval_signal, power_cap_at
from repro.core.state import (
    QUEUED,
    RUNNING,
    SimState,
    Statics,
    build_statics,
    init_state,
    load_jobs,
)

# The observation layout — the single spec ``observe`` and ``obs_dim`` are
# both derived from, so the two cannot drift (the old ``10 + ...``
# hardcoding silently desynced when a global feature was added/removed).
GLOBAL_FEATURES = (
    "sin_day", "cos_day", "carbon", "price", "cap_frac",
    "queued_frac", "running_frac", "nodes_up_frac", "day_frac",
    "episode_progress",
)
# thermal-twin features, appended to the globals ONLY when
# ``cfg.thermal_enabled`` (the layout — and thus any pinned obs — is
# unchanged with the cooling loop off): hottest/mean rack outlet as a
# fraction of the dispatch trip threshold, the worst rack clock, and the
# fraction of racks currently refusing new jobs
THERMAL_FEATURES = ("rack_hot_frac", "rack_mean_frac",
                    "throttle_min", "tripped_frac")
# resilience-twin features, appended ONLY when ``cfg.resilience_on``
# (faults/outages/ladder off -> layout and pinned obs unchanged): the
# active degradation rung, fault-kill and terminal-failure counts as
# fractions of the job table, and lost node-seconds normalized by a
# node-day of fleet capacity
RESILIENCE_FEATURES = ("degrade_frac", "killed_frac",
                       "failed_frac", "lost_frac")
# serving-twin features, appended ONLY when ``cfg.serving_on`` (serving
# off -> layout and pinned obs unchanged): pool load (queue + in-flight
# over total buffering), queue depth vs the shed cap, the fluid latency
# estimate in SLO units, awake/waking pool fractions, and the current
# (schedulable) admission threshold
SERVING_FEATURES = ("srv_util", "srv_queue_frac", "srv_latency_slo",
                    "srv_active_frac", "srv_waking_frac",
                    "srv_admit_thresh")
# per-node-type features: free fraction of each resource
TYPE_FEATURES = ("cpu_free", "gpu_free", "mem_free")
CANDIDATE_FEATURES = (
    "valid", "wait_h", "dur_h", "n_nodes",
    "req_cpu", "req_gpu", "energy_proxy", "feasible_frac",
)


class EnvState(NamedTuple):
    """Per-env rollout state: the sim (which carries the traced workload
    id) plus the episode step counter — NO per-env Statics/bank copy."""

    sim: SimState
    step_count: jax.Array


class SchedEnv:
    """Constructed from a *bank* of workloads (numpy); reset samples one."""

    def __init__(
        self,
        cfg: SimConfig,
        workloads,                    # list of (jobs, bank) tuples
        *,
        episode_steps: int = 512,
        sim_steps_per_action: int = 15,
        reward_weights=(1.0, 1.0, 1.0, 0.05),
        scenario: Scenario | None = None,
        placement: str = "first_fit",
        macro: bool = True,
    ):
        self.cfg = cfg
        self.reward_weights = tuple(reward_weights)
        if placement not in plc.PLACEMENTS:
            raise KeyError(f"unknown placement {placement}")
        self.placement = placement
        # one-hot placement-backend encoding appended to the global obs so
        # one trained policy can condition on (and transfer across) the
        # placement stage it schedules against
        self._place_onehot = jnp.zeros((len(plc.PLACEMENTS),), jnp.float32
                                       ).at[plc.PLACE_IDS[placement]].set(1.0)
        self.episode_steps = episode_steps
        self.k = cfg.sched_max_candidates
        # with the degradation ladder schedulable, 5 extra actions set
        # state.degrade_level to rung 0..4 (NORMAL..EVICT) before the
        # dispatch sub-step runs; layout is k dispatches, k = no-op,
        # k+1+r = "set rung r" (off -> Discrete(k+1), unchanged); with
        # serving on, 4 more actions follow the ladder block: autoscale
        # the pool target down/up by serving_scale_step and nudge the
        # admission threshold down/up by 0.05
        self.n_actions = (self.k + 1 + (5 if cfg.degrade_enabled else 0)
                         + (4 if cfg.serving_on else 0))
        self.sim_steps_per_action = sim_steps_per_action

        # ONE shared Statics: stacked (W, J, Q) trace bank + stacked job
        # tables; envs select their workload via the traced sim.workload id
        jobs, bank = stack_workloads(cfg, workloads)
        self._jobs = {name: jnp.asarray(a) for name, a in jobs.items()}
        self.n_workloads = len(workloads)
        self._statics = build_statics(cfg, bank, scenario=scenario)

        # step functions are built ONCE (the old per-call make_step rebuilt
        # the closures on every Python invocation): one dispatching step
        # for the agent's action, one dispatch-free step for the idle
        # sub-steps between actions
        self._step_rl = make_step(cfg, self._statics, "rl",
                                  placement=placement,
                                  reward_weights=reward_weights)
        self._step_idle = make_step(cfg, self._statics, "none",
                                    reward_weights=reward_weights)
        # macro idle advance: ONE event-driven fast-forward between agent
        # decisions instead of N-1 scanned per-tick idle sub-steps
        self.macro = macro
        self._macro_idle = make_macro_step(
            cfg, self._statics, "none", reward_weights=reward_weights,
            update=lambda acc, out, _inc: self._acc_of(acc, out),
        ) if macro else None

        # observation invariants (constant per env instance)
        st = self._statics
        self._nameplate = jnp.maximum(jnp.sum(st.node_max_w), 1.0)
        self._cap_max = jnp.maximum(
            jnp.max(st.capacity, axis=1, keepdims=True), 1e-6)   # (NRES, 1)
        self._type_onehot = (
            st.node_type[None, :] == jnp.arange(cfg.n_types)[:, None]
        ).astype(jnp.float32)                                    # (T, N)
        self._cap_type = jnp.sum(
            st.capacity[:, None, :] * self._type_onehot[None], axis=-1
        )                                                        # (NRES, T)
        self._mask_fn = plc.PLACEMENT_MASKS[placement]
        self.obs_dim = int(self._obs_spec())

    @property
    def statics(self) -> Statics:
        """The single shared Statics (banked trace, node tables, scenario)."""
        return self._statics

    # ------------------------------------------------------------------ api
    def reset(self, key: jax.Array) -> Tuple[EnvState, jax.Array]:
        kw, ks = jax.random.split(key)
        w = jax.random.randint(kw, (), 0, self.n_workloads)
        sim = init_state(self.cfg, self._statics, ks)
        n = self._jobs["n_valid"][w]
        J = self.cfg.max_jobs
        idx = jnp.arange(J)
        valid = idx < n
        part = self._jobs.get("part")
        sim = sim._replace(
            workload=w.astype(jnp.int32),
            jstate=jnp.where(valid, QUEUED, 0).astype(jnp.int32),
            submit_t=self._jobs["submit_t"][w],
            dur_est=self._jobs["dur"][w],
            work_left=self._jobs["dur"][w],
            n_nodes=jnp.where(valid, self._jobs["n_nodes"][w], 0).astype(jnp.int32),
            req=self._jobs["req"][w],
            part=(sim.part if part is None
                  else jnp.where(valid, part[w], -1).astype(jnp.int32)),
            priority=self._jobs["priority"][w],
        )
        st = EnvState(sim=sim, step_count=jnp.int32(0))
        return st, self.observe(st)

    @staticmethod
    def _acc_of(acc, out):
        return {
            "reward": acc["reward"] + out.reward,
            "completed": acc["completed"] + out.completed_now,
            "energy_kwh": acc["energy_kwh"] + out.energy_kwh_step,
            "carbon_kg": acc["carbon_kg"] + out.carbon_kg_step,
            "facility_w": out.facility_w,
            "queue_len": out.queue_len,
        }

    def step(
        self, st: EnvState, action: jax.Array
    ) -> Tuple[EnvState, jax.Array, jax.Array, jax.Array, Dict[str, jax.Array]]:
        acc_of = self._acc_of

        # sub-step 0 dispatches the agent's action; the remaining
        # sub-steps advance the twin with the dispatch stage compiled OUT
        # (a bit-equivalent split: the old path forced a no-op action
        # through candidate ranking + placement on every sub-step).
        # Reductions accumulate in the scan carry (constant memory).
        action = jnp.asarray(action, jnp.int32)
        sim0 = st.sim
        if self.cfg.degrade_enabled:
            # ladder actions: a > k sets the degradation rung (held until
            # changed) and dispatches nothing this decision
            is_lvl = action > self.k
            if self.cfg.serving_on:
                is_lvl = is_lvl & (action <= self.k + 5)
            rung = jnp.clip(action - self.k - 1, 0, flt.LVL_EVICT)
            sim0 = sim0._replace(degrade_level=jnp.where(
                is_lvl, rung, sim0.degrade_level).astype(jnp.int32))
            action = jnp.where(is_lvl, self.k, action)
        if self.cfg.serving_on:
            # serving actions trail the ladder block: 0/1 scale the pool
            # target down/up, 2/3 nudge the admission threshold down/up;
            # the new target/threshold is held until changed and the
            # decision dispatches nothing (quiet updates are bitwise
            # no-ops so a non-serving action leaves the fields untouched)
            base = self.k + (5 if self.cfg.degrade_enabled else 0)
            is_srv = action > base
            code = action - base - 1
            stepn = jnp.float32(self.cfg.serving_scale_step)
            tgt2 = jnp.clip(
                sim0.srv_target
                + jnp.where(code == 1, stepn, 0.0)
                - jnp.where(code == 0, stepn, 0.0),
                0.0, float(self.cfg.serving_nodes))
            th2 = jnp.clip(
                sim0.srv_admit_thresh
                + 0.05 * (jnp.where(code == 3, 1.0, 0.0)
                          - jnp.where(code == 2, 1.0, 0.0)),
                0.05, 1.0)
            sim0 = sim0._replace(
                srv_target=jnp.where(is_srv, tgt2, sim0.srv_target),
                srv_admit_thresh=jnp.where(
                    is_srv, th2, sim0.srv_admit_thresh))
            action = jnp.where(is_srv, self.k, action)
        sim, out = self._step_rl(sim0, action)
        z = jnp.float32(0.0)
        acc = acc_of({"reward": z, "completed": z, "energy_kwh": z,
                      "carbon_kg": z, "facility_w": z, "queue_len": z}, out)

        n_idle = self.sim_steps_per_action - 1
        if self.macro and n_idle > 0:
            # one macro advance clamped to the agent-decision boundary:
            # full steps only on event ticks, quiet ticks fast-forwarded
            sim, acc = _macro_advance(self._macro_idle, sim, acc, n_idle)
        else:
            def sub(carry, _):
                s, a = carry
                s, o = self._step_idle(s, jnp.int32(-1))
                return (s, acc_of(a, o)), None

            (sim, acc), _ = jax.lax.scan(
                sub, (sim, acc), None, length=n_idle,
            )
        reward = acc["reward"]
        st = EnvState(sim=sim, step_count=st.step_count + 1)
        done = st.step_count >= self.episode_steps
        info = {
            "facility_w": acc["facility_w"],
            "queue_len": acc["queue_len"],
            "completed": acc["completed"],
            "energy_kwh": acc["energy_kwh"],
            "carbon_kg": acc["carbon_kg"],
        }
        return st, self.observe(st), reward, done, info

    # ------------------------------------------------------------ features
    def _obs_spec(self) -> int:
        thermal = len(THERMAL_FEATURES) if self.cfg.thermal_enabled else 0
        resil = len(RESILIENCE_FEATURES) if self.cfg.resilience_on else 0
        srv = len(SERVING_FEATURES) if self.cfg.serving_on else 0
        return (len(GLOBAL_FEATURES) + thermal + resil + srv
                + len(plc.PLACEMENTS)
                + len(TYPE_FEATURES) * self.cfg.n_types
                + len(CANDIDATE_FEATURES) * self.k)

    def observe(self, st: EnvState) -> jax.Array:
        cfg, sim, statics = self.cfg, st.sim, self._statics
        day = 2 * jnp.pi * sim.t / cfg.day_seconds
        queued = jnp.sum(sched.queued_mask(sim)).astype(jnp.float32)
        running = jnp.sum(sim.jstate == RUNNING).astype(jnp.float32)
        scn = statics.scenario
        co2 = eval_signal(scn.carbon, sim.t) / max(cfg.carbon_mean, 1.0)
        price = eval_signal(scn.price, sim.t) / max(cfg.price_mean_usd_kwh, 1e-6)
        # cap as a fraction of nameplate node power; 1 = effectively uncapped
        cap_w = power_cap_at(scn.power_cap, sim.t)
        cap_frac = jnp.where(
            cap_w > 0, jnp.minimum(cap_w / self._nameplate, 1.0), 1.0)
        glob = dict(
            sin_day=jnp.sin(day), cos_day=jnp.cos(day), carbon=co2,
            price=price, cap_frac=cap_frac,
            queued_frac=queued / cfg.max_jobs,
            running_frac=running / cfg.max_jobs,
            nodes_up_frac=jnp.sum(sim.node_up) / cfg.n_nodes,
            day_frac=sim.t / cfg.day_seconds,
            episode_progress=(st.step_count.astype(jnp.float32)
                              / max(self.episode_steps, 1)),
        )
        assert tuple(glob) == GLOBAL_FEATURES
        glob = jnp.stack([glob[name] for name in GLOBAL_FEATURES])

        if cfg.thermal_enabled:
            # rack temps + throttle state so the policy can learn
            # thermally-aware dispatch (place away from hot racks, hold
            # jobs through trip windows)
            trip = max(cfg.thermal_trip_c, 1e-6)
            th_r = thermal.rack_throttle(cfg, sim.rack_outlet_c)   # (R,)
            therm = dict(
                rack_hot_frac=jnp.max(sim.rack_outlet_c) / trip,
                rack_mean_frac=jnp.mean(sim.rack_outlet_c) / trip,
                throttle_min=jnp.min(th_r),
                tripped_frac=jnp.mean(
                    (sim.rack_outlet_c >= cfg.thermal_trip_c
                     ).astype(jnp.float32)),
            )
            assert tuple(therm) == THERMAL_FEATURES
            glob = jnp.concatenate(
                [glob, jnp.stack([therm[n] for n in THERMAL_FEATURES])])

        if cfg.resilience_on:
            # fault/lost-work state so the policy can learn resilience-
            # aware control (drain ahead of maintenance windows, hold the
            # ladder rung through brownouts, requeue-aware dispatch)
            resil = dict(
                degrade_frac=(flt.effective_level(cfg, sim, statics)
                              .astype(jnp.float32) / float(flt.LVL_EVICT)),
                killed_frac=sim.n_killed / cfg.max_jobs,
                failed_frac=sim.n_failed / cfg.max_jobs,
                lost_frac=sim.lost_node_s
                / (cfg.n_nodes * cfg.day_seconds),
            )
            assert tuple(resil) == RESILIENCE_FEATURES
            glob = jnp.concatenate(
                [glob, jnp.stack([resil[n] for n in RESILIENCE_FEATURES])])

        if cfg.serving_on:
            # serving-pool state so the policy can learn overload control
            # (wake capacity ahead of the diurnal peak, tighten admission
            # under backlog, sleep the pool through the trough)
            conc_cap = sim.srv_active * cfg.serving_concurrency
            q_tot = jnp.sum(sim.srv_queue)
            svc = max(cfg.serving_service_s, 1e-9)
            w_est = (q_tot / jnp.maximum(conc_cap / svc, 1e-9)) + svc
            srv = dict(
                srv_util=(sim.srv_inflight + q_tot)
                / jnp.maximum(conc_cap + cfg.serving_queue_cap, 1e-9),
                srv_queue_frac=q_tot / max(cfg.serving_queue_cap, 1e-9),
                srv_latency_slo=jnp.minimum(
                    w_est / max(cfg.serving_slo_s, 1e-9), 10.0),
                srv_active_frac=sim.srv_active
                / max(cfg.serving_nodes, 1),
                srv_waking_frac=sim.srv_wake_n
                / max(cfg.serving_nodes, 1),
                srv_admit_thresh=sim.srv_admit_thresh,
            )
            assert tuple(srv) == SERVING_FEATURES
            glob = jnp.concatenate(
                [glob, jnp.stack([srv[n] for n in SERVING_FEATURES])])

        # per-node-type free fractions, fused: the python per-(type,
        # resource) loop of scalar reductions becomes one one-hot
        # contraction (values unchanged: the masks are exact {0,1} floats)
        free_up = sim.free * sim.node_up                         # (NRES, N)
        free_type = jnp.sum(
            free_up[:, None, :] * self._type_onehot[None], axis=-1
        )                                                        # (NRES, T)
        per_type = (free_type / jnp.maximum(self._cap_type, 1e-6)
                    ).T.reshape(-1)             # type-major, resource-minor

        cands = sched.rl_candidates(cfg, sim)               # (k,)
        safe = jnp.maximum(cands, 0)
        valid = (cands >= 0).astype(jnp.float32)
        wait = jnp.maximum(sim.t - sim.submit_t[safe], 0.0) / 3600.0
        dur = sim.dur_est[safe] / 3600.0
        nn = sim.n_nodes[safe].astype(jnp.float32) / cfg.max_nodes_per_job
        reqf = sim.req[:, safe] / self._cap_max              # (NRES, k)
        # estimated energy proxy: nodes * dur * mean gpu util request
        eproxy = nn * dur
        # feasibility under the ACTIVE placement backend (e.g. partition
        # masks out wrong-type nodes), so the agent sees what placement
        # will actually accept; the backend mask is resolved ONCE per
        # observation, not once per candidate
        ok = jax.vmap(lambda j: sched.feasible_nodes(sim, j))(safe)  # (k, N)
        if self._mask_fn is not None:
            ok = ok & self._mask_fn(sim, statics)[safe]
        if cfg.thermal_enabled:
            # tripped racks refuse dispatch (core.sim applies the same
            # gate through _dispatch_view) — show the agent the truth
            ok = ok & thermal.node_trip_ok(cfg, sim, statics)[None, :]
        feasible = jnp.sum(ok, axis=1).astype(jnp.float32) / cfg.n_nodes
        cand = dict(
            valid=valid, wait_h=wait * valid, dur_h=dur * valid,
            n_nodes=nn * valid, req_cpu=reqf[0] * valid,
            req_gpu=reqf[1] * valid, energy_proxy=eproxy * valid,
            feasible_frac=feasible * valid,
        )
        assert tuple(cand) == CANDIDATE_FEATURES
        cand_feats = jnp.concatenate(
            [cand[name] for name in CANDIDATE_FEATURES])
        return jnp.concatenate(
            [glob, self._place_onehot, per_type, cand_feats]
        ).astype(jnp.float32)
