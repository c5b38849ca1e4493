"""Thermal-twin differential-oracle suite (docs/thermal.md).

Three implementations of the rack RC cooling loop are pinned against each
other:

- a pure-NumPy float64 oracle (`_np_thermal_oracle`) built from an
  INDEPENDENT formulation (np.add.at segment-sum scatter, not the one-hot
  contraction) — compared at documented float32-accumulation tolerance;
- the eager jnp reference (`kernels.ref.rack_thermal_ref`);
- the fused Pallas kernel (`kernels.rack_thermal`) — compared against the
  reference BITWISE on CPU (both share the one-hot-matmul reduction, so
  interpret-mode Pallas executes the identical float program).

On top of the kernel-level harness: macro-vs-per-tick bit-identity with
the cooling loop enabled (the tentpole guarantee — thermal breakpoints
extend the event-horizon engine without breaking exactness), the
steady-state envelope / crossing-horizon / cooling-energy /
throttle-monotonicity invariants as property tests, and the PUE
zero-IT-load pin.
"""

import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

sys.path.insert(0, os.path.join(os.path.dirname(__file__), ".."))

from _hypothesis_compat import given, settings, st

from repro.configs.sim import tiny_cluster
from repro.core import (
    build_statics,
    init_state,
    load_jobs,
    make_step,
    run_episode,
    summary,
)
from repro.core import thermal as thm
from repro.core.power import compute_power
from repro.data import synth_workload
from repro.kernels import ops as kops
from repro.kernels.ref import rack_thermal_ref

# a config whose racks genuinely ride the throttle ramp AND cross the
# dispatch trip inside a short episode (verified: peak outlet ~24 C)
_STRESS = dict(thermal_enabled=True, rack_tau_s=120.0, thermal_trip_c=22.0,
               throttle_start_c=20.0, throttle_full_c=30.0)


def _stress_setup(seed=8, n_jobs=24):
    cfg = tiny_cluster(**_STRESS)
    jobs, bank = synth_workload(cfg, n_jobs, 600.0, seed=seed)
    statics = build_statics(cfg, bank)
    state = load_jobs(init_state(cfg, statics, jax.random.key(0)), jobs)
    return cfg, statics, state


# ------------------------------------------------------- numpy oracle
def _np_thermal_oracle(heat_seq, node_rack, t0, supply_seq, r_th, alpha):
    """Independent float64 reference: per-tick np.add.at scatter of node
    heat onto racks, then the explicit RC relaxation. Returns the (K, R)
    outlet-temperature trajectory."""
    T = np.asarray(t0, np.float64).copy()
    r_th = np.asarray(r_th, np.float64)
    out = []
    for heat, sup in zip(heat_seq, supply_seq):
        rack_heat = np.zeros(T.shape[0], np.float64)
        np.add.at(rack_heat, np.asarray(node_rack), np.asarray(heat, np.float64))
        T = T + alpha * (sup + rack_heat * r_th - T)
        out.append(T.copy())
    return np.stack(out)


def _rand_case(rng, n, r):
    heat = (rng.random(n, dtype=np.float32) * 800.0).astype(np.float32)
    rack = (rng.integers(0, r, n)).astype(np.int32)
    t0 = (18.0 + rng.random(r) * 10.0).astype(np.float32)
    r_th = (rng.random(r) * 1e-3 + 1e-4).astype(np.float32)
    return heat, rack, t0, r_th


@pytest.mark.parametrize("n,r", [(16, 1), (100, 7), (512, 16), (672, 21)])
def test_rack_thermal_kernel_bitwise_vs_ref(n, r):
    """Pallas kernel vs eager reference: BITWISE on CPU — same one-hot
    contraction, same RC arithmetic, interpret-mode Pallas runs the
    identical float program (padding lanes must be exactly inert)."""
    rng = np.random.default_rng(n * 31 + r)
    heat, rack, t0, r_th = _rand_case(rng, n, r)
    sup = jnp.float32(16.5)
    alpha = 0.117
    ref_t, ref_h = jax.jit(
        lambda h, t: rack_thermal_ref(h, rack, t, sup, r_th, alpha=alpha)
    )(heat, t0)
    ker_t, ker_h = jax.jit(
        lambda h, t: kops.rack_thermal(h, rack, t, sup, r_th, alpha=alpha)
    )(heat, t0)
    np.testing.assert_array_equal(np.asarray(ref_t), np.asarray(ker_t))
    np.testing.assert_array_equal(np.asarray(ref_h), np.asarray(ker_h))


@pytest.mark.parametrize("n,r,ticks", [(64, 4, 200), (256, 8, 120)])
def test_numpy_oracle_pins_scanned_paths(n, r, ticks):
    """The float64 NumPy oracle pins BOTH jitted scan paths (reference and
    Pallas) over a long trajectory. Tolerance (not bitwise) is the
    documented bound: the oracle sums in a different order and in float64;
    the RC update is a contraction so float32 drift stays ~1e-5 relative.
    The two jnp paths must still agree with EACH OTHER bitwise."""
    rng = np.random.default_rng(7 * n + ticks)
    _, rack, t0, r_th = _rand_case(rng, n, r)
    heat_seq = (rng.random((ticks, n), dtype=np.float32) * 600.0)
    supply_seq = (16.0 + 4.0 * np.sin(np.arange(ticks) / 30.0)).astype(np.float32)
    alpha = 0.035

    def scan_with(fn):
        def body(T, inp):
            h, s = inp
            T, _ = fn(h, rack, T, s, r_th, alpha=alpha)
            return T, T
        _, traj = jax.lax.scan(body, jnp.asarray(t0),
                               (jnp.asarray(heat_seq), jnp.asarray(supply_seq)))
        return traj

    traj_ref = np.asarray(jax.jit(lambda: scan_with(rack_thermal_ref))())
    traj_ker = np.asarray(jax.jit(lambda: scan_with(kops.rack_thermal))())
    np.testing.assert_array_equal(np.asarray(traj_ref), np.asarray(traj_ker))

    traj_np = _np_thermal_oracle(heat_seq, rack, t0, supply_seq, r_th, alpha)
    np.testing.assert_allclose(np.asarray(traj_ref), traj_np,
                               rtol=1e-5, atol=1e-4)


def test_sim_tail_matches_kernel_tail():
    """make_step(use_thermal_kernel=True) must track the reference-tail
    episode within float tolerance: the kernel is a drop-in inside the
    SAME tail, but inside the fused step XLA is free to reassociate the
    reference one-hot dot with its neighbors, so episode-level equality is
    the documented ~1e-5 bound (the standalone kernel-vs-ref comparison
    above stays bitwise)."""
    cfg, statics, state = _stress_setup()
    step_r = make_step(cfg, statics, "fcfs")
    step_k = make_step(cfg, statics, "fcfs", use_thermal_kernel=True)

    def run(step, s):
        def body(s, _):
            s, out = step(s, jnp.int32(-1))
            return s, out.rack_max_c
        return jax.lax.scan(body, s, None, length=300)

    fs_r, tr_r = jax.jit(lambda s: run(step_r, s))(state)
    fs_k, tr_k = jax.jit(lambda s: run(step_k, s))(state)
    np.testing.assert_allclose(np.asarray(tr_r), np.asarray(tr_k),
                               rtol=1e-5, atol=1e-5)
    for f in fs_r._fields:
        a, b = getattr(fs_r, f), getattr(fs_k, f)
        if f == "key":
            a, b = jax.random.key_data(a), jax.random.key_data(b)
        a, b = np.asarray(a), np.asarray(b)
        if np.issubdtype(a.dtype, np.floating):
            np.testing.assert_allclose(a, b, rtol=1e-5, atol=1e-5,
                                       err_msg=f"field {f}")
        else:
            np.testing.assert_array_equal(a, b, err_msg=f"field {f}")


# ------------------------------------------- macro-stepping exactness
@pytest.mark.parametrize("scheduler", ["fcfs", "easy"])
def test_macro_bit_identical_with_thermals(scheduler):
    """The tentpole acceptance bar: with the cooling loop ON (racks
    crossing the dispatch trip mid-episode), macro=True matches per-tick
    stepping bit-for-bit — state, accumulators, rack temps, PRNG stream."""
    cfg, statics, state = _stress_setup()
    fs, tel = jax.jit(lambda s: run_episode(
        cfg, statics, s, 1500, scheduler, summary_only=True))(state)
    fs2, tel2 = jax.jit(lambda s: run_episode(
        cfg, statics, s, 1500, scheduler, macro=True))(state)
    # the episode genuinely crossed the trip threshold
    assert float(fs.peak_rack_c) >= cfg.thermal_trip_c
    assert float(fs.thermal_throttle_s) > 0.0
    for f in fs._fields:
        a, b = getattr(fs, f), getattr(fs2, f)
        if f == "key":
            a, b = jax.random.key_data(a), jax.random.key_data(b)
        np.testing.assert_array_equal(
            np.asarray(a), np.asarray(b),
            err_msg=f"state field {f} diverged under macro with thermals")
    for f in tel._fields:
        if f == "macro_steps":
            continue
        if getattr(tel, f) is None:   # streamed-admission counters: absent
            assert getattr(tel2, f) is None, f   # on the resident path
            continue
        np.testing.assert_allclose(
            np.asarray(getattr(tel, f)), np.asarray(getattr(tel2, f)),
            rtol=1e-6, atol=1e-9, err_msg=f"telemetry {f}")
    # the engine still fast-forwards despite the extra breakpoint type
    assert float(tel2.macro_steps) < 1500


def test_thermal_telemetry_surfaces():
    cfg, statics, state = _stress_setup()
    fs, outs = jax.jit(lambda s: run_episode(
        cfg, statics, s, 600, "fcfs"))(state)
    # peak tracker == max over the per-tick telemetry
    np.testing.assert_allclose(float(fs.peak_rack_c),
                               float(jnp.max(outs.rack_max_c)), rtol=1e-6)
    _, tel = jax.jit(lambda s: run_episode(
        cfg, statics, s, 600, "fcfs", summary_only=True))(state)
    s = summary(fs, tel)
    assert s["peak_rack_outlet_c"] >= cfg.cooling_supply_min_c
    assert s["thermal_throttle_s"] >= 0.0
    assert s["mean_cop"] >= cfg.cop_min


# ----------------------------------------------------- property tests
@settings(max_examples=12, deadline=None)
@given(seed=st.integers(0, 400))
def test_throttle_monotone_in_temperature(seed):
    """rack_throttle is monotone non-increasing in outlet temperature and
    bounded in [thermal_throttle_floor, 1]."""
    cfg = tiny_cluster(**_STRESS)
    rng = np.random.default_rng(seed)
    t1 = (10.0 + rng.random(16) * 60.0).astype(np.float32)
    t2 = t1 + (rng.random(16) * 20.0).astype(np.float32)   # t2 >= t1
    th1 = np.asarray(thm.rack_throttle(cfg, jnp.asarray(t1)))
    th2 = np.asarray(thm.rack_throttle(cfg, jnp.asarray(t2)))
    assert (th2 <= th1 + 1e-7).all()
    for th in (th1, th2):
        assert (th >= cfg.thermal_throttle_floor - 1e-7).all()
        assert (th <= 1.0 + 1e-7).all()


@settings(max_examples=6, deadline=None)
@given(seed=st.integers(0, 5))
def test_temps_bounded_by_steady_state_envelope(seed):
    """Every rack temperature stays inside the box spanned by its initial
    value and the extreme steady states (wetbulb bounds x zero-to-max
    heat) — the contraction property thermal_crossing_horizon builds on."""
    from repro.scenarios.signals import signal_bounds

    cfg = tiny_cluster(**_STRESS)
    jobs, bank = synth_workload(cfg, 24, 600.0, seed=seed)
    statics = build_statics(cfg, bank)
    state = load_jobs(init_state(cfg, statics, jax.random.key(seed)), jobs)
    t0 = np.asarray(state.rack_outlet_c)
    fs, outs = jax.jit(lambda s: run_episode(
        cfg, statics, s, 800, "fcfs"))(state)
    wb_lo, wb_hi = signal_bounds(statics.scenario.wetbulb)
    sup_lo = float(thm.supply_temp(cfg, wb_lo))
    sup_hi = float(thm.supply_temp(cfg, wb_hi))
    heat_hi = np.asarray(statics.rack_cap_w) * 1.2 / (0.5 * cfg.conv_eff)
    ss_hi = sup_hi + heat_hi * np.asarray(statics.rack_r_th)
    lo = min(sup_lo, float(t0.min())) - 1e-3
    hi = max(float(ss_hi.max()), float(t0.max())) + 1e-3
    assert lo <= float(jnp.min(fs.rack_outlet_c))
    assert float(fs.peak_rack_c) <= hi
    assert float(jnp.max(outs.rack_max_c)) <= hi


def _check_crossing_horizon(seed, warm):
    """Property: within thermal_crossing_horizon ticks, NO rack crosses
    the dispatch trip threshold in either direction — macro-stepping may
    fast-forward that far without changing dispatch eligibility."""
    cfg, statics, state = _stress_setup(seed=seed)
    step = make_step(cfg, statics, "fcfs")
    if warm:
        def wbody(s, _):
            s, _o = step(s, jnp.int32(-1))
            return s, None
        state, _ = jax.lax.scan(wbody, state, None, length=warm)
    k = int(thm.thermal_crossing_horizon(cfg, statics, state, 256))
    assert 0 <= k <= 256
    if k == 0:
        return
    hot0 = np.asarray(state.rack_outlet_c) >= cfg.thermal_trip_c

    def body(s, _):
        s, _o = step(s, jnp.int32(-1))
        changed = jnp.any(
            (s.rack_outlet_c >= cfg.thermal_trip_c) != jnp.asarray(hot0))
        return s, changed
    _, changed = jax.jit(lambda s: jax.lax.scan(
        body, s, None, length=k))(state)
    assert not bool(np.asarray(changed).any()), (
        f"trip crossing inside predicted horizon k={k} "
        f"(seed={seed}, warm={warm})")


@settings(max_examples=10, deadline=None)
@given(seed=st.integers(0, 4), warm=st.integers(0, 600))
def test_crossing_horizon_never_overshoots(seed, warm):
    _check_crossing_horizon(seed, warm)


@settings(max_examples=4, deadline=None)
@given(seed=st.integers(0, 3))
def test_cooling_energy_conservation(seed):
    """The integrated cooling accumulator equals the per-tick cooling
    power implied by (facility_w, cop): cooling = facility / (1 + cop)
    holds exactly through the cap throttle (both scale by r)."""
    cfg = tiny_cluster(**_STRESS)
    jobs, bank = synth_workload(cfg, 24, 600.0, seed=seed)
    statics = build_statics(cfg, bank)
    state = load_jobs(init_state(cfg, statics, jax.random.key(0)), jobs)
    fs, outs = jax.jit(lambda s: run_episode(
        cfg, statics, s, 700, "fcfs"))(state)
    cool_w = np.asarray(outs.facility_w) / (1.0 + np.asarray(outs.cop))
    kwh = float(np.sum(cool_w) * cfg.dt / 3600.0 / 1000.0)
    np.testing.assert_allclose(float(fs.cool_energy_kwh), kwh, rtol=1e-4)
    # and the energy ledger still closes: facility = it + losses + cooling
    total = (float(fs.it_energy_kwh) + float(fs.loss_energy_kwh)
             + float(fs.cool_energy_kwh))
    np.testing.assert_allclose(float(fs.energy_kwh), total, rtol=1e-4)


# ------------------------------------------------------------ PUE edge
def test_pue_defined_at_zero_it_load():
    """compute_power at zero IT load (every node down): PUE reports the
    1.0 ideal instead of facility/1W garbage (the old max(it,1) edge)."""
    cfg = tiny_cluster()
    jobs, bank = synth_workload(cfg, 4, 300.0, seed=0)
    statics = build_statics(cfg, bank)
    state = load_jobs(init_state(cfg, statics, jax.random.key(0)), jobs)
    state = state._replace(node_up=jnp.zeros_like(state.node_up))
    p = jax.jit(lambda s: compute_power(cfg, s, statics))(state)
    assert float(p.it_w) == 0.0
    assert float(p.pue) == 1.0
    # and an episode from that state keeps PUE finite and >= 1 everywhere
    _, outs = jax.jit(lambda s: run_episode(cfg, statics, s, 50, "none"))(state)
    pue = np.asarray(outs.pue)
    assert np.isfinite(pue).all() and (pue >= 1.0 - 1e-6).all()
