"""Fleet runner: N datacenter replicas, heterogeneous grid scenarios,
heterogeneous scheduling policies AND heterogeneous workload telemetry
(per-replica ids into one shared banked trace), one compiled call —
vmapped on one device, or shard_map-partitioned across a device mesh.

``run_fleet`` broadcasts one initial ``SimState``/``Statics`` across R
replicas, installs a per-replica ``Scenario`` (batched pytree from
``scenarios.stack_scenarios`` / ``sample_scenarios``) and optionally a
per-replica ``placement.Policy`` (batched (select_id, place_id) int32s
from ``placement.stack_policies`` / ``policy_grid``), splits the PRNG key
per replica, and runs ``vmap`` of ``run_episode`` under a single ``jit``
— the policy x scenario sweep engine for the paper's sustainability-policy
studies. Because policies are data (ids resolved by ``lax.switch`` inside
the step), the whole grid costs ONE compilation, not one per policy.

One program serves every fleet call: ``_fleet``, one per-replica body
under ``vmap``, inside a ``shard_map`` only when a mesh is given. A sweep
installs each replica's key and runs ``run_episode``; a segment
(``fleet_segment``, the unit of a snapshotted sweep in
``checkpoint.episode``) threads raw telemetry accumulators through
``run_segment``. Which of the two is static, so each compiles its own
program from the same code.

Memory notes: the replica-batched state and key (or accumulator) buffers
are DONATED to the compiled call (XLA reuses them for the final states),
and the telemetry knobs (``telemetry_every`` / ``summary_only``, forwarded
to ``run_episode``) replace the O(R * n_steps * 16) stacked ``StepOut`` with
windowed or O(R * 16) episode-wide reductions — fleet-sweep memory then no
longer scales with ``n_steps``.

Device sharding (``mesh=``): a single-device ``vmap`` runs every
replica's macro-stepping while-loop in LOCKSTEP — the loop condition
reduces over all R lanes, so one event-busy replica drags every
fast-forwarding replica back to per-tick speed AND per-tick cost (the
full event tick is computed for all lanes on every iteration). Passing a
1-D fleet mesh (``launch.mesh.make_fleet_mesh``) partitions the replica
axis across devices via ``shard_map`` with the same ``vmap`` INSIDE each
shard: lockstep shrinks to R/D lanes, shards with quiet replicas retire
their episodes in a handful of outer iterations regardless of what other
shards are doing (no collectives inside, so each device's while-loops
run their own trip counts), and state/key donation hands XLA per-device
buffers. The per-replica computation — including the PRNG
``split``/``fold_in`` schedule, which happens on the host BEFORE the
compiled call and is shared by both paths — is identical, so sharded
final states / streams / telemetry are bit-identical to the vmapped
path run on each device's block of R/D replicas. Against one vmapped
call over all R replicas the discrete state is equal and floats can
differ by rounding, because the compiler picks a per-node reduction's
order by batch size: on a TPU v5e, 64 vs 16 replicas moved
``flops_integral``; on XLA:CPU the two agree bitwise (pinned by
``tests/test_multidevice.py``) as long as every device holds at least
two replicas, since a one-replica shard drops the unit batch dim.

Host spans: ``run_fleet`` wraps its argument checks and replica batching,
the compiled call's dispatch and the ``REPRO_CHECKIFY`` audit in the
profiler spans ``host.fleet.prepare``, ``host.fleet.call`` and
``host.fleet.audit`` (``jax.profiler.TraceAnnotation``), so a trace names
what the host was doing while the device waited.
"""

from __future__ import annotations

from functools import partial
from typing import Dict, List, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import PartitionSpec

from repro.configs.sim import SimConfig
from repro.core.placement import Policy, make_policy, stack_policies
from repro.core.sim import (
    StepOut,
    TelemetrySummary,
    run_episode,
    run_segment,
    summary_columns,
)
from repro.core.state import SimState, Statics
from repro.scenarios.scenario import Scenario, n_replicas, stack_scenarios
from repro.sharding.specs import (
    FLEET_AXIS,
    fleet_pspecs,
    fleet_shardings,
    replicated_pspecs,
)
from repro.utils import invariants
from repro.utils.errors import ConfigError


def _ensure_batched(scenarios) -> Scenario:
    # NB: Scenario is itself a (Named)tuple — test for it first
    if isinstance(scenarios, Scenario):
        return scenarios
    return stack_scenarios(list(scenarios))


def _as_policy(p) -> Policy:
    # NB: Policy is itself a (Named)tuple — test for it before the
    # (select, place) name-tuple form
    if isinstance(p, Policy):
        return p
    return make_policy(*p)


def _policy_list(policies) -> List[Policy]:
    """Normalize any accepted policies input — a single Policy, a batched
    Policy (leading replica axis, e.g. from ``policy_grid``), or a list of
    Policies / (select, place) name tuples — to a list of scalar
    Policies."""
    if isinstance(policies, Policy):
        if jnp.ndim(policies.select) == 0:
            return [policies]
        return [jax.tree.map(lambda a: a[i], policies)
                for i in range(int(jnp.shape(policies.select)[0]))]
    return [_as_policy(p) for p in policies]


def _ensure_batched_policies(policies) -> Policy:
    if isinstance(policies, Policy) and jnp.ndim(policies.select) == 1:
        return policies
    return stack_policies(_policy_list(policies))


def _scenario_list(scenarios) -> List[Scenario]:
    """Normalize a single Scenario, a batched Scenario (leading replica
    axis), or an iterable of Scenarios to a list of unbatched Scenarios —
    iterating a Scenario NamedTuple directly would yield its FIELDS, not
    its replicas."""
    if isinstance(scenarios, Scenario):
        if jnp.ndim(scenarios.carbon.mean) == 0:
            return [scenarios]
        return [jax.tree.map(lambda a: a[i], scenarios)
                for i in range(n_replicas(scenarios))]
    return list(scenarios)


def policy_scenario_grid(
    policies, scenarios: Scenario | Sequence[Scenario]
) -> Tuple[Policy, Scenario]:
    """Cross P policies x S scenarios -> (batched Policy, batched Scenario)
    of length P*S, ready for ``run_fleet`` (replica i = policy i // S with
    scenario i % S). ``policies``: an already-batched Policy (e.g. from
    ``policy_grid``), Policy instances, or (select, place) name tuples;
    ``scenarios``: an already-batched Scenario (e.g. from
    ``sample_scenarios``) or a list of Scenarios."""
    pols = _policy_list(policies)
    scns = _scenario_list(scenarios)
    crossed = stack_policies([p for p in pols for _ in scns])
    return crossed, stack_scenarios(scns * len(pols))


# Varying-manual-axes checking is off for the fleet's shard_map: the
# per-replica program runs no collective, so the check has nothing to
# guard, and with it on every fresh constant carry of the episode drivers'
# while-loops/scans (tick counters, zeroed telemetry accumulators) would
# have to be pcast to the replica axis to match its varying body output.
_CHECK_VMA = False


# The one fleet program. Module-level so repeated calls with the same
# static config reuse the compiled executable (cfg is a frozen dataclass
# => hashable, and so is ``mesh``; statics / scenarios / policies / state
# / carry are traced). ``state`` and ``carry`` arrive replica-batched and
# are donated: XLA reuses their buffers (per device under a mesh) for the
# results, so they must be distinct buffers.
@partial(jax.jit,
         static_argnames=("cfg", "n_steps", "scheduler", "kw_items", "mesh",
                          "axis", "segment"),
         donate_argnames=("state", "carry"))
def _fleet(cfg, statics, scenarios, policies, state, carry, n_steps,
           scheduler, kw_items, mesh=None, axis=FLEET_AXIS, segment=False):
    """``n_steps`` of every replica: ``vmap`` of one per-replica body, and
    with a ``mesh`` that ``vmap`` inside a ``shard_map`` over ``axis``, so
    each device's R/D-lane while-loops run their own trip counts.

    A sweep (``segment=False``) installs each replica's PRNG key from
    ``carry`` and runs ``run_episode``. A segment (``segment=True``,
    ``fleet_segment``) threads the raw accumulators ``carry`` through
    ``run_segment``; its keys are already in ``state``."""
    kw = dict(kw_items)

    def fleet(statics, scenarios, policies, state, carry):
        def one(scn: Scenario, pol, st: SimState, c):
            stt = statics._replace(scenario=scn)
            who = scheduler if pol is None else pol
            if segment:
                return run_segment(cfg, stt, st, c, n_steps, who, **kw)
            return run_episode(cfg, stt, st._replace(key=c), n_steps, who,
                               **kw)

        return jax.vmap(one)(scenarios, policies, state, carry)

    args = (statics, scenarios, policies, state, carry)
    if mesh is None:
        return fleet(*args)
    # statics replicate, every replica-batched operand splits its leading
    # axis; the output prefix spec P(axis) matches (SimState, StepOut|
    # TelemetrySummary) alike
    return jax.shard_map(
        fleet, mesh=mesh,
        in_specs=(replicated_pspecs(statics),
                  *(fleet_pspecs(a, axis) for a in args[1:])),
        out_specs=PartitionSpec(axis), check_vma=_CHECK_VMA,
    )(*args)


# chipbench/tests/test_tpu_compile.py lowers the fleet program by this name
_fleet_sharded = _fleet


def fleet_segment(cfg: SimConfig, statics: Statics, scenarios: Scenario,
                  policies: Policy | None, state: SimState,
                  acc: TelemetrySummary, n_ticks: int, scheduler: str,
                  kw: dict, *, mesh=None, mesh_axis: str = FLEET_AXIS
                  ) -> Tuple[SimState, TelemetrySummary]:
    """Advance a replica-batched fleet by ``n_ticks``: ``run_segment`` for
    every replica in the fleet's one compiled program, threading raw
    per-replica ``TelemetrySummary`` accumulators ``acc``. ``state``
    carries its keys (split once per run, so resumed streams continue
    exactly); ``scenarios``/``policies``/``scheduler`` are ``run_fleet``'s
    normalised ones and ``kw`` its forwarded keywords, less the
    episode-wide telemetry modes a segment does not have. The unit of a
    snapshotted sweep (``checkpoint.episode``); ``state`` and ``acc`` are
    donated."""
    kw_items = tuple(sorted(
        (k, v) for k, v in kw.items()
        if k not in ("summary_only", "telemetry_every")))
    return _fleet(cfg, statics, scenarios, policies, state, acc, n_ticks,
                  scheduler, kw_items, mesh, mesh_axis, segment=True)


def shard_fleet(tree, mesh, axis: str = FLEET_AXIS):
    """``device_put`` a replica-batched fleet pytree (batched ``SimState``
    / ``Scenario`` / ``Policy`` / per-replica keys) onto ``mesh``, leading
    replica axis split in contiguous blocks across the ``axis`` devices —
    replica i lands on device i // (R / D). Optional for ``run_fleet(...,
    mesh=...)`` (jit reshards automatically) but placing inputs up front
    skips the initial all-to-device scatter on repeated/chained sweeps."""
    return jax.device_put(tree, fleet_shardings(mesh, tree, axis))


def _fleet_inputs(statics, state, scheduler, scenarios, policies,
                  workloads):
    """``run_fleet``'s argument checks and replica batching: (scheduler,
    batched scenarios, batched policies or None, replica-batched state,
    per-replica keys)."""
    if statics.trace is not None:
        raise ConfigError(
            "run_fleet keeps every replica's trace resident in its job "
            f"table; this trace of {statics.trace.submit_t.shape[0]} jobs "
            "streams through it: replay it with run_episode/run_segment")
    if policies is not None and scheduler is not None:
        raise ConfigError(
            f"both scheduler={scheduler!r} and policies= given — policies "
            "carry the selection stage, so the scheduler name would be "
            "silently ignored; pass exactly one")
    if scheduler is None:
        scheduler = "fcfs"
    if policies is not None:
        policies = _ensure_batched_policies(policies)
        P = int(jnp.shape(policies.select)[0])
        if scenarios is None:
            scenarios = stack_scenarios([statics.scenario] * P)
        else:
            scenarios = _ensure_batched(scenarios)
            if n_replicas(scenarios) != P:
                raise ConfigError(
                    f"{P} policies vs {n_replicas(scenarios)} scenarios — "
                    "axes must match; build the cross product with "
                    "policy_scenario_grid(policies, scenarios)")
    elif scenarios is None:
        scenarios = stack_scenarios([statics.scenario])
    else:
        scenarios = _ensure_batched(scenarios)
    R = n_replicas(scenarios)
    if jnp.ndim(state.t) == 0:
        keys = jax.random.split(state.key, R)
        state = jax.tree.map(
            lambda a: jnp.broadcast_to(a, (R,) + jnp.shape(a)), state)
    else:
        if int(jnp.shape(state.t)[0]) != R:
            raise ConfigError(
                f"batched state has {jnp.shape(state.t)[0]} replicas, "
                f"scenarios have {R}")
        # advance each replica's stream into a FRESH buffer: state and keys
        # are both donated, so aliasing keys to the state.key leaf would
        # donate one buffer twice
        keys = jax.vmap(lambda k: jax.random.fold_in(k, 1))(state.key)
    if workloads is not None:
        if jnp.ndim(statics.cpu_trace) != 3:
            raise ConfigError(
                "workloads= needs a banked Statics trace ((W, J, Q) "
                "cpu_trace, e.g. from data.stack_workloads); this statics "
                "carries a single unbatched workload")
        ids_host = np.asarray(workloads, np.int32)   # host data: check here
        if ids_host.shape != (R,):
            raise ConfigError(
                f"workloads has shape {ids_host.shape}, expected ({R},) — "
                "one bank id per replica")
        W = statics.cpu_trace.shape[0]
        lo, hi = int(ids_host.min()), int(ids_host.max())
        if lo < 0 or hi >= W:
            raise ConfigError(
                f"workload ids must be in [0, {W}) for this bank; got "
                f"[{lo}, {hi}] — an out-of-range id would silently clamp "
                "to the edge slice")
        state = state._replace(workload=jnp.asarray(ids_host))
    return scheduler, scenarios, policies, state, keys


def run_fleet(
    cfg: SimConfig,
    statics: Statics,
    state: SimState,
    n_steps: int,
    scheduler: str | None = None,
    *,
    scenarios: Scenario | Sequence[Scenario] | None = None,
    policies: Policy | Sequence[Policy | Tuple[str, str]] | None = None,
    workloads: Sequence[int] | jnp.ndarray | None = None,
    mesh=None,
    mesh_axis: str = FLEET_AXIS,
    snapshot_every_s: float | None = None,
    snapshot_dir: str | None = None,
    resume_from: str | None = None,
    snapshot_keep: int = 3,
    **kw,
) -> Tuple[SimState, StepOut | TelemetrySummary]:
    """Simulate R replicas of the twin for ``n_steps`` in one jitted call.

    ``scheduler``: eager selection-policy name every replica runs
    (default 'fcfs'); mutually exclusive with ``policies`` (which carry
    the selection stage per replica — passing both is a loud error, not
    a silent override).
    ``scenarios``: batched Scenario (leading replica axis), a list of
    Scenarios (stacked here), or None (the statics' own scenario).
    ``policies``: the per-replica POLICY axis — a batched ``Policy``, a
    list of Policies or (select, place) name tuples, or None (every
    replica runs the eager ``scheduler`` string). When both axes are
    given their lengths must already match; build the cross product with
    ``policy_scenario_grid`` (or ``placement.policy_grid`` + scenario
    tiling). Policies are traced data, so ANY mix of selection x
    placement rides the same compiled executable.
    All other statics (node constants, telemetry bank) are shared and
    broadcast; each replica gets its own PRNG stream.

    ``workloads``: per-replica TELEMETRY axis — int32 ids (length R) into
    a *banked* Statics trace ((W, J, Q) ``cpu_trace``, e.g. from
    ``data.stack_workloads``); each replica's trace lookups gather through
    its id, so heterogeneous utilization profiles share ONE bank with no
    per-replica copy. The job *table* still comes from ``state`` (broadcast
    or pre-batched) — ids switch telemetry, not the submitted jobs.
    ``state`` may be a single SimState (broadcast to R replicas here) or
    an already replica-batched one — e.g. the final states of a previous
    ``run_fleet`` call for chained sweeps. A batched state's buffers are
    donated to the compiled call and must not be reused afterwards.

    ``mesh``: a 1-D fleet mesh (``launch.mesh.make_fleet_mesh``) wraps the
    fleet program's ``vmap`` in a shard_map — the replica axis splits in
    contiguous blocks across ``mesh_axis``, so macro while-loops lockstep
    only within a shard (see module docstring) and memory/donation happen
    per device. R must divide evenly by the mesh size (loud error
    otherwise, snapshotted sweeps included — a silent pad would fabricate
    replicas whose summaries leak into sweep statistics). Results are
    bit-identical to ``mesh=None`` run on each device's block of replicas,
    and equal to one ``mesh=None`` call over all of them up to float
    rounding (see the module docstring).

    ``**kw`` forwards to ``run_episode``/``make_step`` — in particular
    ``summary_only=True`` returns per-replica ``TelemetrySummary`` with
    peak memory independent of ``n_steps``, ``telemetry_every=k`` stacks
    one windowed summary per k steps, and ``macro=True`` switches every
    replica to the macro-stepping engine: each replica fast-forwards its
    own quiet segments through the same traced computation (no host
    sync; under ``vmap`` the while-loops run lockstep, so replicas on
    event ticks overlap with replicas fast-forwarding).

    Durability: ``snapshot_every_s`` / ``snapshot_dir`` / ``resume_from``
    mirror ``run_episode``'s snapshot semantics at fleet granularity —
    one crash-atomic snapshot of the whole replica-batched state (keys
    installed, so resumed streams continue exactly) plus raw telemetry
    accumulators; resume is bit-identical to the uninterrupted sweep.
    Each segment runs through ``fleet_segment``, the same fleet program.
    Requires ``summary_only=True`` or ``macro=True``.

    Returns (final_states, outs) with a leading replica axis on every leaf.
    """
    with jax.profiler.TraceAnnotation("host.fleet.prepare"):
        scheduler, scenarios, policies, state, keys = _fleet_inputs(
            statics, state, scheduler, scenarios, policies, workloads)
        R = n_replicas(scenarios)
    if mesh is not None:
        if mesh_axis not in mesh.shape:
            raise ConfigError(
                f"mesh has axes {tuple(mesh.shape)}, no {mesh_axis!r} — "
                "build a fleet mesh with launch.mesh.make_fleet_mesh()")
        n_shards = int(mesh.shape[mesh_axis])
        if R % n_shards:
            raise ConfigError(
                f"{R} replicas do not divide across {n_shards} "
                f"{mesh_axis!r}-axis devices — a silent pad would "
                "fabricate replicas; pick R as a multiple of the mesh "
                "size or shrink the mesh (make_fleet_mesh(n_devices=...))")
    if snapshot_every_s is not None or resume_from is not None \
            or snapshot_dir is not None:
        from repro.checkpoint.episode import run_fleet_snapshotted

        out = run_fleet_snapshotted(
            cfg, statics, scenarios, policies, state, keys, n_steps,
            scheduler, kw, mesh=mesh, mesh_axis=mesh_axis,
            snapshot_every_s=snapshot_every_s, snapshot_dir=snapshot_dir,
            resume_from=resume_from, snapshot_keep=snapshot_keep)
    else:
        with jax.profiler.TraceAnnotation("host.fleet.call"):
            out = _fleet(cfg, statics, scenarios, policies, state, keys,
                         n_steps, scheduler, tuple(sorted(kw.items())),
                         mesh, mesh_axis)
    if invariants.enabled():
        # post-hoc eager audit of every replica's final state (the checks
        # broadcast over the leading replica axis); the per-step checkify
        # suite only instruments un-traced run_episode calls, so this is
        # what REPRO_CHECKIFY buys on the vmapped fleet path
        with jax.profiler.TraceAnnotation("host.fleet.audit"):
            invariants.check_state(cfg, statics, out[0])
    return out


def fleet_summary(
    final_states: SimState,
    telemetry: TelemetrySummary | None = None,
) -> List[Dict[str, float]]:
    """Per-replica ``summary`` dicts from batched final states. Pass the
    per-replica ``TelemetrySummary`` (``summary_only=True`` output) to also
    surface the macro-stepping skip accounting (``ticks_simulated`` /
    ``macro_steps_taken`` / ``macro_skip_ratio``) per replica.

    All reductions run vectorized over the replica axis in
    ``sim.summary_columns`` (one device->host transfer, numpy column
    math); only the final dict-of-floats fan-out is Python, so the host
    tail of a 1024-replica sweep is milliseconds, not the former
    per-replica ``summary`` loop."""
    cols = summary_columns(final_states, telemetry)
    R = int(np.shape(cols["t_end_s"])[0])
    return [{k: float(v[i]) for k, v in cols.items()} for i in range(R)]
