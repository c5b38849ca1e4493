"""RAPS power chain: job utilization -> node IT power -> rectification /
voltage-conversion losses -> cooling (COP model) -> facility power, plus
carbon intensity and GFLOPS/W.

The per-node aggregation is the simulator's compute hot-spot (it runs every
step for every vectorized environment); ``repro.kernels.node_power``
provides the Pallas TPU kernels — including the fused placement-scatter +
power-chain pass (``power_scatter_pallas``) that turns the job table into
per-node IT power in one kernel — with oracles in ``kernels.ref`` used
here on CPU.
"""

from __future__ import annotations

import math
from typing import NamedTuple, Tuple

import jax
import jax.numpy as jnp

from repro.configs.sim import SimConfig
from repro.core.state import RUNNING, SimState, Statics
from repro.kernels.ref import node_power_ref
from repro.scenarios.signals import eval_signal


class PowerOut(NamedTuple):
    node_it_w: jax.Array      # (N,)
    node_input_w: jax.Array   # (N,) after rectifier+conversion losses
    it_w: jax.Array           # scalar
    input_w: jax.Array
    cooling_w: jax.Array
    facility_w: jax.Array
    pue: jax.Array
    gflops: jax.Array         # utilization-weighted delivered GFLOP/s


def _quantum_index(cfg: SimConfig, t, start_t, q: int):
    """Index of the telemetry quantum each job is in at time ``t``: its
    age since start, in whole quanta, clipped to the bank's ``q``."""
    age = jnp.maximum(t - start_t, 0.0)
    return jnp.clip((age / cfg.trace_quanta).astype(jnp.int32), 0, q - 1)


def _bank_rows(state: SimState, statics: Statics):
    """Where each job's row of the telemetry bank lies, in the bank's
    layout: (the row's leading indices, from which a window is sliced;
    ``point(bank, qi)``, which reads quantum ``qi`` of each row by a
    gather of one index per job). The rows are the slots (one workload),
    the workload id and the slots (banked), or the slots' trace ids
    (streamed). The point reads index with the scalar workload id
    (banked) and ``take_along_axis`` (one workload): spelled through the
    window's leading indices, the same gather compiles to another
    program."""
    j = jnp.arange(state.jstate.shape[0])
    if statics.trace is not None:
        rows = jnp.maximum(state.stream.tid, 0)
        return (rows,), lambda bank, qi: bank[rows, qi]
    if statics.cpu_trace.ndim == 3:
        return ((jnp.broadcast_to(state.workload, j.shape), j),
                lambda bank, qi: bank[state.workload, j, qi])
    return (j,), lambda bank, qi: jnp.take_along_axis(
        bank, qi[:, None], axis=1)[:, 0]


def job_utilization(cfg: SimConfig, state: SimState, statics: Statics):
    """Per-job cpu/gpu utilization at current sim time from the telemetry
    bank (quanta-averaged, as RAPS replays traces).

    One J-element gather per resource, one index per job: the event tick
    reads its utilization this way, and so does every tick of the shared
    (per-tick) fast path. A fast-tick chunk of the count-matrix path reads
    a window of quanta per job instead (``job_utilization_chunk``).

    With a banked (W, J, Q) trace (see ``Statics``), the lookup gathers
    through the traced ``state.workload`` id, at the cost of the
    unbatched path, and the bank itself is never copied per env (the
    lightweight-state rollout engine's key invariant). A streamed trace's
    bank has a row per trace job, read through each slot's trace id."""
    running = (state.jstate == RUNNING).astype(jnp.float32)
    qi = _quantum_index(cfg, state.t, state.start_t,
                        statics.cpu_trace.shape[-1])
    point = _bank_rows(state, statics)[1]
    return (point(statics.cpu_trace, qi) * running,
            point(statics.gpu_trace, qi) * running)


def _per_tick_utilization(cfg: SimConfig, state: SimState,
                          statics: Statics, ts: jax.Array):
    """(C, J) utilization at each tick time of ``ts``, gathered tick by
    tick: one index per job and tick."""
    return jax.vmap(lambda t: job_utilization(
        cfg, state._replace(t=t), statics))(ts)


def chunk_window(cfg: SimConfig, n_ticks: int, q: int) -> int:
    """Quanta a job can meet in ``n_ticks`` consecutive ticks: their times
    span (n_ticks - 1) * dt, and one tick more of slack keeps the count
    safe against float32 rounding of the tick times; never more than the
    bank's ``q``. TX-GAIA (16 ticks of 1 s, 10 s quanta): 3."""
    return min(q, math.floor(n_ticks * cfg.dt / cfg.trace_quanta) + 2)


def job_utilization_chunk(cfg: SimConfig, state: SimState,
                          statics: Statics, ts: jax.Array):
    """(C, J) cpu/gpu utilization at each of C increasing tick times ``ts``
    (``dt`` apart) of a frozen job table: bit for bit
    ``vmap(job_utilization)`` over ``ts``.

    Over C ticks a job's quantum index moves through at most
    ``M = chunk_window(cfg, C, Q)`` quanta, so each job's telemetry is
    read as ONE window of M quanta of its bank row, starting at its first
    tick's quantum (a gather whose slice is (1, M); clamped to the row's
    end as ``lax.dynamic_slice`` does), and each tick selects its element
    of the window. The per-tick form gathers C elements a job, one index
    each, and the TPU runs such a gather element by element: at TX-GAIA
    (C = 16, M = 3) the window reads 3 of them. Where M >= C the window
    saves nothing, and the chunk keeps the per-tick gather."""
    C = ts.shape[0]
    q = statics.cpu_trace.shape[-1]
    M = chunk_window(cfg, C, q)
    if M >= C:
        return _per_tick_utilization(cfg, state, statics, ts)
    running = (state.jstate == RUNNING).astype(jnp.float32)
    qi = _quantum_index(cfg, ts[:, None], state.start_t[None, :], q)
    # qi is monotone in t and clipped at q - 1, so qi - start lies in
    # [0, M - 1] whether or not the window start is clamped
    start = jnp.minimum(qi[0], q - M)
    off = qi - start                                              # (C, J)
    lead = _bank_rows(state, statics)[0]
    ones = (1,) * len(lead)

    def window(bank):                                             # (J, M)
        return jax.vmap(lambda *ix: jax.lax.dynamic_slice(
            bank, ix, ones + (M,)).reshape(M))(*lead, start)

    def per_tick(w):                                              # (C, J)
        u = jnp.broadcast_to(w[:, 0], off.shape)
        for m in range(1, M):
            u = jnp.where(off >= m, w[:, m], u)
        return u * running

    return (per_tick(window(statics.cpu_trace)),
            per_tick(window(statics.gpu_trace)))


# Job->node reductions (release into ``free``, per-node loads, the macro
# engine's count matrix) add a per-job amount at each of the job's node
# slots: J*K slots onto N nodes. Three forms, chosen from the shapes and
# from the platform the program is lowered for:
# - the dense (slots, N) one-hot contraction while the one-hot stays under
#   DENSE_SCATTER_ELEMS (~0.5 MB f32): under vmap the XLA scatter-add runs
#   a generic per-env scatter loop on the CPU, while the contraction is one
#   batched matmul (the trick the Pallas power-scatter kernel plays on the
#   MXU). The budget was tuned on the CPU;
# - above it, on the CPU: the memory-free XLA scatter-add over the slots,
#   cheap there (tens of microseconds for TX-GAIA's 32,768);
# - above it, on accelerators: the per-job node-count matrix
#   (``job_node_counts``, one fused compare-and-sum over K) contracted with
#   the per-job amounts. The TPU compiler turns a scatter-add into a sort
#   of the slot ids and a serial segmented sum: 285-325 us per scatter at
#   TX-GAIA size (512 x 64 slots, 928 nodes) on one TPU v5e, against 14 us
#   for a build and under 2 for its contraction.
DENSE_SCATTER_ELEMS = 131072


def use_dense_scatter(n_slots: int, n_nodes: int) -> bool:
    return n_slots * n_nodes <= DENSE_SCATTER_ELEMS


def node_onehot(place_flat: jax.Array, n_nodes: int) -> jax.Array:
    """(slots, N) one-hot of placement node ids; invalid slots (id < 0)
    match no node, so they drop out of the contraction exactly like the
    scatter's ``mode="drop"``."""
    return (place_flat[:, None] == jnp.arange(n_nodes)[None, :]
            ).astype(jnp.float32)


def job_node_counts(placement: jax.Array, n_nodes: int) -> jax.Array:
    """(J, N) f32 count of each job's slots on each node,
    ``cnt[j, n] = sum_k [placement[j, k] == n]`` (invalid slots, id < 0,
    count nowhere). One compare and sum over K that the compiler fuses:
    no scatter, and no (J, K, N) tensor in memory. Counts are small
    integers, exact in f32."""
    with jax.named_scope("tick.node_counts"):
        hit = placement[..., None] == jnp.arange(n_nodes)
        return jnp.sum(hit.astype(jnp.float32), axis=-2)


def _scatter_node_counts(placement: jax.Array, n_nodes: int) -> jax.Array:
    """``job_node_counts`` built by a scatter-add over the J*K slots (the
    CPU's form: a 32,768-slot scatter there is cheaper than the compare)."""
    J = placement.shape[0]
    valid = placement >= 0
    safe = jnp.where(valid, placement, 0)
    return jnp.zeros((J, n_nodes), jnp.float32).at[
        jnp.arange(J)[:, None], safe].add(valid.astype(jnp.float32))


def node_counts(placement: jax.Array, n_nodes: int) -> jax.Array:
    """``job_node_counts`` in the form that is cheap on the platform the
    program is lowered for (the scatter on the CPU, the fused compare
    elsewhere); the counts are exact integers either way."""
    return jax.lax.platform_dependent(
        placement,
        cpu=lambda p: _scatter_node_counts(p, n_nodes),
        default=lambda p: job_node_counts(p, n_nodes))


def _gather_flagged_counts(placement: jax.Array, flags: jax.Array
                           ) -> jax.Array:
    """``flagged_counts`` as a gather of ``flags`` through the J*K slots
    (the CPU's form)."""
    valid = placement >= 0
    safe = jnp.where(valid, placement, 0)
    return jnp.sum(valid & jnp.take(flags, safe), axis=1).astype(jnp.float32)


def _count_flagged_counts(placement: jax.Array, flags: jax.Array
                          ) -> jax.Array:
    """``flagged_counts`` as the node-count matrix contracted with the
    flags (the accelerators' form: the TPU runs the gather element by
    element, ~22 ms for 64 lanes at TX-GAIA size on one v5e, against
    ~1 ms for a 64-lane count build)."""
    cnt = job_node_counts(placement, flags.shape[-1])
    return jnp.matmul(cnt, flags.astype(jnp.float32),
                      precision=jax.lax.Precision.HIGHEST)


def flagged_counts(placement: jax.Array, flags: jax.Array) -> jax.Array:
    """(J,) f32 count of each job's slots whose node is flagged in the
    (N,) bool ``flags`` (slots < 0 count nowhere), in the form that is
    cheap on the platform the program is lowered for (the gather on the
    CPU, the node-count matrix elsewhere); exact small integers either
    way."""
    return jax.lax.platform_dependent(
        placement, flags,
        cpu=_gather_flagged_counts, default=_count_flagged_counts)


def scatter_add_nodes(placement: jax.Array, amounts: jax.Array,
                      n_nodes: int, base: jax.Array | None = None
                      ) -> jax.Array:
    """The job-table -> per-node reduction shared by the power chain
    (``node_loads``) and the release path (``faults.release_jobs``): add
    the per-job ``amounts`` (..., J) at every node slot of ``placement``
    (J, K) onto ``base`` (..., n_nodes) (zeros when None); slots < 0 drop.

    Under the ``use_dense_scatter`` budget this is the dense one-hot
    contraction. Above it the CPU keeps the memory-free XLA scatter-add
    over the slots, and every other platform contracts the amounts with
    ``job_node_counts`` (``_count_path``), because its scatter-add is a
    serial sort-and-sum over all J*K slots. Contractions run at
    ``Precision.HIGHEST``: exact f32 (TPU bf16 / GPU TF32 matmul defaults
    would round, and the result feeds free-pool feasibility checks), so
    integer requests sum exactly into ``free`` on every path."""
    J, K = placement.shape
    if use_dense_scatter(J * K, n_nodes):
        slots = amounts[..., :, None] * (placement >= 0)
        dense = jnp.matmul(slots.reshape(amounts.shape[:-1] + (J * K,)),
                           node_onehot(placement.reshape(-1), n_nodes),
                           precision=jax.lax.Precision.HIGHEST)
        return dense if base is None else base + dense
    if base is None:
        base = jnp.zeros(amounts.shape[:-1] + (n_nodes,), amounts.dtype)
    return jax.lax.platform_dependent(
        placement, amounts, base, cpu=_scatter_path, default=_count_path)


def _scatter_path(placement, amounts, base):
    """``scatter_add_nodes`` above the dense budget, as an XLA scatter-add
    over the J*K slots (the CPU's form)."""
    ids = placement.reshape(-1)
    slots = (amounts[..., :, None] * (placement >= 0)).reshape(
        amounts.shape[:-1] + ids.shape)
    safe = jnp.where(ids >= 0, ids, 0)
    return base.at[..., safe].add(jnp.where(ids >= 0, slots, 0.0),
                                  mode="drop")


def _count_path(placement, amounts, base):
    """``scatter_add_nodes`` above the dense budget, as (..., J) amounts
    contracted with the (J, N) node-count matrix (the accelerators'
    form)."""
    cnt = job_node_counts(placement, base.shape[-1])
    return base + jnp.matmul(amounts, cnt,
                             precision=jax.lax.Precision.HIGHEST)


def placement_amounts(state: SimState, cpu_util: jax.Array,
                      gpu_util: jax.Array):
    """Flattened per-placement-slot absolute utilized resources.

    Returns (place_flat (J*K,) int32, cpu_abs (J*K,), gpu_abs (J*K,)) —
    the job-table form the fused power-scatter kernel consumes directly
    (invalid slots carry place=-1 and zero amounts).
    """
    place = state.placement                       # (J,K)
    w = (place >= 0).astype(jnp.float32)
    cpu_abs = (state.req[0][:, None] * cpu_util[:, None]) * w
    gpu_abs = (state.req[1][:, None] * gpu_util[:, None]) * w
    return place.reshape(-1), cpu_abs.reshape(-1), gpu_abs.reshape(-1)


def node_loads(cfg: SimConfig, state: SimState, statics: Statics,
               cpu_util: jax.Array, gpu_util: jax.Array):
    """Reduce per-job utilized resources onto nodes.

    Returns (cpu_load, gpu_load) as *fractions of node capacity* in [0,1].
    """
    N = statics.capacity.shape[1]
    # utilized absolute resources of each job, on each of its nodes
    loads = scatter_add_nodes(
        state.placement,
        jnp.stack([state.req[0] * cpu_util, state.req[1] * gpu_util]), N)
    cpu_node, gpu_node = loads[0], loads[1]
    cpu_frac = jnp.clip(cpu_node / jnp.maximum(statics.capacity[0], 1e-6), 0, 1)
    gpu_frac = jnp.clip(gpu_node / jnp.maximum(statics.capacity[1], 1e-6), 0, 1)
    return cpu_frac, gpu_frac


# NOTE: the legacy parametric shims `wetbulb_c` / `carbon_intensity` that
# used to live here are gone — `scenarios.default_scenario(cfg)` builds the
# identical sinusoids as Signals and the sim reads `statics.scenario.*`
# (tests/test_scenarios.py pins the equivalence against the closed forms).


def finish_power(cfg: SimConfig, state: SimState, statics: Statics,
                 node_it: jax.Array, node_input: jax.Array,
                 cpu_frac: jax.Array, gpu_frac: jax.Array) -> PowerOut:
    """Per-node IT/input power -> facility totals, cooling (COP model),
    PUE and delivered GFLOP/s. Shared by every power path (eager, Pallas
    kernel, and the macro-step fast tick) so the chain stays bit-identical
    across them."""
    it_w = jnp.sum(node_it)
    input_w = jnp.sum(node_input)
    wb = eval_signal(statics.scenario.wetbulb, state.t)
    cop = jnp.maximum(
        cfg.cop_base + cfg.cop_wetbulb_coef * (wb - cfg.wetbulb_ref_c),
        cfg.cop_min,
    )
    cooling_w = input_w / cop
    facility_w = input_w + cooling_w
    # PUE is undefined at zero IT load (every node down / idle-slept):
    # report the 1.0 ideal instead of facility_w / 1 W blowing up to ~1e5
    pue = jnp.where(it_w > 1.0, facility_w / jnp.maximum(it_w, 1.0), 1.0)
    gflops = jnp.sum(
        statics.peak_gflops * jnp.maximum(cpu_frac, gpu_frac) * state.node_up
    )
    return PowerOut(node_it, node_input, it_w, input_w, cooling_w,
                    facility_w, pue, gflops)


def power_from_fracs(cfg: SimConfig, state: SimState, statics: Statics,
                     cpu_frac: jax.Array, gpu_frac: jax.Array) -> PowerOut:
    """Per-node load fractions -> full power chain (the eager oracle math:
    IT power, rectifier-efficiency parabola, conversion losses)."""
    it = statics.idle_w + cpu_frac * statics.cpu_dyn_w + gpu_frac * statics.gpu_dyn_w
    it = it * state.node_up
    load_frac = jnp.clip(it / jnp.maximum(statics.node_max_w, 1.0), 0.0, 1.2)
    eta = jnp.clip(
        cfg.rect_eff_peak - cfg.rect_eff_curv * jnp.square(load_frac - cfg.rect_eff_load),
        0.5, 1.0,
    )
    node_it, node_input = it, it / (eta * cfg.conv_eff)
    return finish_power(cfg, state, statics, node_it, node_input,
                        cpu_frac, gpu_frac)


def compute_power(cfg: SimConfig, state: SimState, statics: Statics,
                  *, use_kernel: bool = False) -> PowerOut:
    cpu_util, gpu_util = job_utilization(cfg, state, statics)

    if use_kernel:
        # fused path: job table -> per-node IT power in ONE Pallas pass
        # (placement scatter + power chain; no (N,) load intermediates)
        from repro.kernels import ops as kops

        place_flat, cpu_abs, gpu_abs = placement_amounts(
            state, cpu_util, gpu_util)
        node_it, node_input, cpu_frac, gpu_frac = kops.power_scatter(
            place_flat, cpu_abs, gpu_abs, statics.capacity[0],
            statics.capacity[1], statics.idle_w, statics.cpu_dyn_w,
            statics.gpu_dyn_w, state.node_up, statics.node_max_w,
            rect_peak=cfg.rect_eff_peak, rect_load=cfg.rect_eff_load,
            rect_curv=cfg.rect_eff_curv, conv_eff=cfg.conv_eff,
        )
        return finish_power(cfg, state, statics, node_it, node_input,
                            cpu_frac, gpu_frac)

    cpu_frac, gpu_frac = node_loads(cfg, state, statics, cpu_util, gpu_util)
    return power_from_fracs(cfg, state, statics, cpu_frac, gpu_frac)
