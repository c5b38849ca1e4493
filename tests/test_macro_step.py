"""Macro-stepping engine equivalence suite.

The contract (docs/performance.md "Macro-stepping"): a macro episode is
the per-tick episode with quiet ticks fast-forwarded —

- job/queue state (jstate, placement, free pool, times, counters, PRNG
  stream) is EXACT: on dense-scatter-budget configs every accumulator is
  bit-identical too, because fast ticks run the same compiled power chain
  and the same accounting tail;
- on large configs (chunked count-matrix power path) and for telemetry
  reductions whose fusion context differs between the two compiled
  programs (net_load's cross-job sum), energy/cost/carbon accounting is
  pinned within float-accumulation tolerance instead;
- the predicted ``quiet_horizon`` never overshoots the next event.
"""

import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

sys.path.insert(0, os.path.join(os.path.dirname(__file__), ".."))

from _hypothesis_compat import HAS_HYPOTHESIS, given, settings, st

from repro.configs.sim import tiny_cluster, tx_gaia
from repro.core import (
    build_statics,
    init_state,
    load_jobs,
    make_step,
    quiet_horizon,
    run_episode,
    run_fleet,
    summary,
)
from repro.core.placement import PLACEMENTS, make_policy
from repro.core.schedulers import SCHEDULERS, queued_mask
from repro.data import synth_workload
from repro.envs import SchedEnv
from repro.scenarios import demand_response

# SimState accumulator leaves that integrate power/price/carbon terms —
# the documented-tolerance set on non-shared power paths
_ACCUM = ("energy_kwh", "it_energy_kwh", "loss_energy_kwh",
          "cool_energy_kwh", "carbon_kg", "elec_cost_usd",
          "flops_integral", "sum_power_w")


def _run_both(cfg, statics, state, n_steps, scheduler, **kw):
    fs, tel = jax.jit(lambda s: run_episode(
        cfg, statics, s, n_steps, scheduler, summary_only=True, **kw))(state)
    fs2, tel2 = jax.jit(lambda s: run_episode(
        cfg, statics, s, n_steps, scheduler, macro=True, **kw))(state)
    return fs, tel, fs2, tel2


def _assert_equiv(fs, tel, fs2, tel2, *, exact_accum=True):
    for f in fs._fields:
        a, b = getattr(fs, f), getattr(fs2, f)
        if f == "key":
            a, b = jax.random.key_data(a), jax.random.key_data(b)
        if not exact_accum and f in _ACCUM:
            np.testing.assert_allclose(
                np.asarray(a), np.asarray(b), rtol=1e-5, atol=1e-6,
                err_msg=f"accumulator {f} beyond float tolerance")
            continue
        np.testing.assert_array_equal(
            np.asarray(a), np.asarray(b),
            err_msg=f"job/queue state field {f} diverged under macro")
    for f in tel._fields:
        if f == "macro_steps":     # differs BY DESIGN (the skip accounting)
            continue
        if getattr(tel, f) is None:   # streamed-admission counters: absent
            assert getattr(tel2, f) is None, f   # on the resident path
            continue
        np.testing.assert_allclose(
            np.asarray(getattr(tel, f)), np.asarray(getattr(tel2, f)),
            rtol=1e-6, atol=1e-9,
            err_msg=f"telemetry {f} beyond float tolerance")


def test_macro_actually_skips():
    cfg = tiny_cluster()
    jobs, bank = synth_workload(cfg, 16, 900.0, seed=0)
    statics = build_statics(cfg, bank)
    state = load_jobs(init_state(cfg, statics, jax.random.key(0)), jobs)
    _, _, fs2, tel2 = _run_both(cfg, statics, state, 900, "fcfs")
    assert float(tel2.n_steps) == 900
    # the engine must have fast-forwarded most of the episode, and the
    # skip accounting must surface through summary()
    assert float(tel2.macro_steps) < 0.25 * 900
    s = summary(fs2, tel2)
    assert s["ticks_simulated"] == 900
    assert s["macro_skip_ratio"] > 4.0


def test_macro_bitwise_fcfs_small():
    cfg = tiny_cluster()
    jobs, bank = synth_workload(cfg, 32, 900.0, seed=0)
    statics = build_statics(cfg, bank)
    state = load_jobs(init_state(cfg, statics, jax.random.key(0)), jobs)
    _assert_equiv(*_run_both(cfg, statics, state, 900, "fcfs"))


def test_macro_tx_gaia_replay_slice():
    """(a) TX-GAIA replay slice — the non-shared (chunked gemm) power
    path: job/queue state exact, accumulators within tolerance."""
    cfg = tx_gaia(max_jobs=64, max_nodes_per_job=4)
    jobs, bank = synth_workload(cfg, 30, 600.0, seed=5)
    statics = build_statics(cfg, bank)
    state = load_jobs(init_state(cfg, statics, jax.random.key(0)), jobs)
    fs, tel, fs2, tel2 = _run_both(cfg, statics, state, 600, "replay")
    _assert_equiv(fs, tel, fs2, tel2, exact_accum=False)
    assert float(fs2.n_completed) > 0          # the slice must do real work
    assert float(tel2.macro_steps) < float(tel2.n_steps)


def test_macro_dr_cap_crossing_breakpoints():
    """(b) a CapSchedule DR event inside the episode: fast-forwarded
    segments stop at both breakpoints and the throttle accounting stays
    bit-identical (shared power path)."""
    cfg = tiny_cluster()
    jobs, bank = synth_workload(cfg, 32, 900.0, seed=1)
    scn = demand_response(cfg, cap_w=4000.0, event_start_s=200.0,
                          event_len_s=300.0)
    statics = build_statics(cfg, bank, scenario=scn)
    state = load_jobs(init_state(cfg, statics, jax.random.key(0)), jobs)
    fs, tel, fs2, tel2 = _run_both(cfg, statics, state, 900, "fcfs")
    _assert_equiv(fs, tel, fs2, tel2)
    # the episode genuinely crossed the cap window (throttle engaged)
    assert float(tel.mean_throttle) < 1.0


def test_macro_with_failures():
    """(c) stochastic failures: fault clocks are event-sampled
    (exponential next-failure/next-repair times drawn at commit points),
    so crossings are exact breakpoints in the quiet horizon — the PRNG
    stream, kill counts and requeues are bit-identical AND the engine
    still fast-forwards between faults (the per-tick Bernoulli engine
    forced macro back to tick-by-tick whenever MTBF was finite)."""
    cfg = tiny_cluster(node_mtbf_hours=0.3)
    jobs, bank = synth_workload(cfg, 32, 900.0, seed=0)
    statics = build_statics(cfg, bank)
    state = load_jobs(init_state(cfg, statics, jax.random.key(0)), jobs)
    fs, tel, fs2, tel2 = _run_both(cfg, statics, state, 900, "fcfs")
    _assert_equiv(fs, tel, fs2, tel2)
    assert float(fs.n_killed) > 0              # failures actually fired
    # faults on no longer disables fast-forwarding
    assert float(tel2.macro_steps) < 0.5 * 900


def test_macro_policy_grid_equivalence():
    """(d) every selection x placement combo through the policy-as-data
    path (two compiled executables total: per-tick + macro)."""
    cfg = tiny_cluster()
    jobs, bank = synth_workload(cfg, 40, 600.0, seed=3)
    statics = build_statics(cfg, bank)
    state = load_jobs(init_state(cfg, statics, jax.random.key(0)), jobs)
    run_p = jax.jit(lambda s, pol: run_episode(
        cfg, statics, s, 400, pol, summary_only=True))
    run_m = jax.jit(lambda s, pol: run_episode(
        cfg, statics, s, 400, pol, macro=True))
    for sel in SCHEDULERS:
        for pl in PLACEMENTS:
            pol = make_policy(sel, pl)
            fs, tel = run_p(state, pol)
            fs2, tel2 = run_m(state, pol)
            try:
                _assert_equiv(fs, tel, fs2, tel2)
            except AssertionError as e:
                raise AssertionError(f"policy ({sel}, {pl}): {e}") from e


def test_macro_telemetry_windows_tick_aligned():
    """telemetry_every windows clamp the horizon, so windowed summaries
    match the per-tick ones window by window."""
    cfg = tiny_cluster()
    jobs, bank = synth_workload(cfg, 24, 900.0, seed=4)
    statics = build_statics(cfg, bank)
    state = load_jobs(init_state(cfg, statics, jax.random.key(0)), jobs)
    fs, wins = jax.jit(lambda s: run_episode(
        cfg, statics, s, 900, "fcfs", telemetry_every=90))(state)
    fs2, wins2 = jax.jit(lambda s: run_episode(
        cfg, statics, s, 900, "fcfs", telemetry_every=90, macro=True))(state)
    assert np.shape(wins2.n_steps) == (10,)
    np.testing.assert_array_equal(np.asarray(wins2.n_steps),
                                  np.full(10, 90.0))
    for f in wins._fields:
        if f == "macro_steps":
            continue
        if getattr(wins, f) is None:   # streamed-admission counters
            assert getattr(wins2, f) is None, f
            continue
        np.testing.assert_allclose(
            np.asarray(getattr(wins, f)), np.asarray(getattr(wins2, f)),
            rtol=1e-6, atol=1e-9, err_msg=f"window telemetry {f}")
    for f in fs._fields:
        a, b = getattr(fs, f), getattr(fs2, f)
        if f == "key":
            a, b = jax.random.key_data(a), jax.random.key_data(b)
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


def test_summary_accepts_windowed_telemetry():
    """summary(state, telemetry) must also digest the windowed
    (leading-window-axis) TelemetrySummary of telemetry_every runs —
    summing windows recovers the episode skip accounting."""
    cfg = tiny_cluster()
    jobs, bank = synth_workload(cfg, 16, 600.0, seed=0)
    statics = build_statics(cfg, bank)
    state = load_jobs(init_state(cfg, statics, jax.random.key(0)), jobs)
    fs, wins = jax.jit(lambda s: run_episode(
        cfg, statics, s, 600, "fcfs", telemetry_every=200, macro=True))(state)
    s = summary(fs, wins)
    assert s["ticks_simulated"] == 600
    assert s["macro_steps_taken"] == float(np.sum(np.asarray(wins.macro_steps)))
    assert s["macro_skip_ratio"] > 1.0


def test_macro_fleet_threads_through_run_fleet():
    from repro.scenarios import sample_scenarios

    cfg = tiny_cluster()
    jobs, bank = synth_workload(cfg, 24, 600.0, seed=0)
    statics = build_statics(cfg, bank)
    state = load_jobs(init_state(cfg, statics, jax.random.key(0)), jobs)
    scns = sample_scenarios(cfg, 4, seed=1)
    fs, _ = run_fleet(cfg, statics, state, 300, "fcfs", scenarios=scns,
                      summary_only=True)
    fs2, tel2 = run_fleet(cfg, statics, state, 300, "fcfs", scenarios=scns,
                          summary_only=True, macro=True)
    for f in fs._fields:
        a, b = getattr(fs, f), getattr(fs2, f)
        if f == "key":
            a, b = jax.random.key_data(a), jax.random.key_data(b)
        np.testing.assert_array_equal(
            np.asarray(a), np.asarray(b),
            err_msg=f"fleet field {f} diverged under macro")
    # every replica fast-forwards independently
    assert (np.asarray(tel2.macro_steps) < 300).all()


def test_macro_rejects_stacked_stepout_silently_summarizes():
    """macro=True cannot stack per-step StepOut; it returns the
    episode-wide summary instead (documented) and still errors loudly on
    the conflicting summary_only+telemetry_every combination."""
    from repro.core.sim import TelemetrySummary

    cfg = tiny_cluster()
    jobs, bank = synth_workload(cfg, 8, 300.0, seed=0)
    statics = build_statics(cfg, bank)
    state = load_jobs(init_state(cfg, statics, jax.random.key(0)), jobs)
    _, out = jax.jit(lambda s: run_episode(
        cfg, statics, s, 50, "fcfs", macro=True))(state)
    assert isinstance(out, TelemetrySummary)
    with pytest.raises(ValueError):
        run_episode(cfg, statics, state, 50, "fcfs", macro=True,
                    summary_only=True, telemetry_every=10)


def test_sched_env_macro_matches_scanned_idle_path():
    """The env's macro idle advance is bit-equivalent to the scanned
    per-tick idle sub-steps (rewards, infos, obs, final sim state)."""
    cfg = tiny_cluster(sched_max_candidates=4)
    wls = [synth_workload(cfg, 24, 900.0, seed=s) for s in range(2)]
    env_m = SchedEnv(cfg, wls, episode_steps=8, sim_steps_per_action=7,
                     macro=True)
    env_s = SchedEnv(cfg, wls, episode_steps=8, sim_steps_per_action=7,
                     macro=False)
    st_m, obs_m = env_m.reset(jax.random.key(3))
    st_s, obs_s = env_s.reset(jax.random.key(3))
    np.testing.assert_array_equal(np.asarray(obs_m), np.asarray(obs_s))
    step_m, step_s = jax.jit(env_m.step), jax.jit(env_s.step)
    for a in (0, 2, 4, 1, 0, 3):
        st_m, obs_m, r_m, d_m, info_m = step_m(st_m, jnp.int32(a))
        st_s, obs_s, r_s, d_s, info_s = step_s(st_s, jnp.int32(a))
        np.testing.assert_array_equal(np.asarray(r_m), np.asarray(r_s))
        np.testing.assert_array_equal(np.asarray(obs_m), np.asarray(obs_s))
        for k in info_m:
            np.testing.assert_array_equal(
                np.asarray(info_m[k]), np.asarray(info_s[k]),
                err_msg=f"info[{k}]")
    for f in st_m.sim._fields:
        a, b = getattr(st_m.sim, f), getattr(st_s.sim, f)
        if f == "key":
            a, b = jax.random.key_data(a), jax.random.key_data(b)
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b),
                                      err_msg=f"sim.{f}")


# --------------------------------------------------------------------------
def _quiet_probe_state(seed, warm_ticks):
    """Advance a fresh episode per-tick to a (likely mid-segment) state."""
    cfg = tiny_cluster()
    jobs, bank = synth_workload(cfg, 24, 900.0, seed=seed)
    statics = build_statics(cfg, bank)
    state = load_jobs(init_state(cfg, statics, jax.random.key(seed)), jobs)
    step = make_step(cfg, statics, "fcfs")
    if warm_ticks:
        def body(s, _):
            s, _out = step(s, jnp.int32(-1))
            return s, None
        state, _ = jax.lax.scan(body, state, None, length=warm_ticks)
    return cfg, statics, state, step


def _machine_signature(state):
    """Everything that must stay frozen across quiet ticks."""
    return jax.device_get((state.jstate, state.placement, state.free,
                           state.node_up, state.n_completed, state.n_killed,
                           jnp.sum(queued_mask(state))))


def _check_horizon_never_overshoots(seed, warm):
    """Property: advancing the predicted horizon per-tick changes NO
    machine state — arrivals, dispatches, completions, failures and
    repairs all lie strictly beyond it (and after k-1 ticks the state is
    still quiet: its 1-tick horizon check passes again by induction)."""
    cfg, statics, state, step = _quiet_probe_state(seed, warm)
    k = int(quiet_horizon(cfg, statics, state, "fcfs", max_ticks=256))
    if k == 0:
        return
    before = _machine_signature(state)

    def body(s, _):
        s, _out = step(s, jnp.int32(-1))
        return s, None
    advanced, _ = jax.lax.scan(body, state, None, length=k)
    after = _machine_signature(advanced)
    for x, y, name in zip(before, after,
                          ("jstate", "placement", "free", "node_up",
                           "n_completed", "n_killed", "queued")):
        np.testing.assert_array_equal(
            np.asarray(x), np.asarray(y),
            err_msg=f"{name} changed within quiet_horizon={k} "
                    f"(seed={seed}, warm={warm})")


if HAS_HYPOTHESIS:
    @settings(max_examples=20, deadline=None)
    @given(seed=st.integers(0, 7), warm=st.integers(0, 220))
    def test_quiet_horizon_never_overshoots(seed, warm):
        _check_horizon_never_overshoots(seed, warm)
else:
    # without hypothesis, still exercise the property on a fixed spread of
    # (workload seed, warm-up depth) pairs instead of skipping
    @pytest.mark.parametrize(
        "seed,warm",
        [(0, 0), (1, 50), (2, 120), (3, 220), (4, 33), (5, 77),
         (6, 150), (7, 201)])
    def test_quiet_horizon_never_overshoots(seed, warm):
        _check_horizon_never_overshoots(seed, warm)


def test_macro_full_resilience_stack():
    """(c') the whole resilience twin at once — node + rack fault clocks,
    a scheduled maintenance window downing a rack, a brownout forcing the
    degradation ladder, checkpoint/restart with write overhead and retry
    budgets: per-tick and macro stay bit-identical (state AND PRNG
    stream) and the engine still skips quiet stretches."""
    from repro.scenarios import resilience_drill

    cfg = tiny_cluster(node_mtbf_hours=0.5, node_repair_hours=0.2,
                       rack_mtbf_hours=1.5, rack_repair_hours=0.3,
                       ckpt_interval_s=240.0, ckpt_overhead_s=20.0,
                       max_job_retries=2, requeue_backoff_s=60.0,
                       outages_enabled=True, degrade_enabled=True)
    scn = resilience_drill(cfg, maint_rack=0, maint_start_s=500.0,
                           maint_len_s=400.0, brownout_start_s=1400.0,
                           brownout_len_s=300.0, brownout_level=2)
    jobs, bank = synth_workload(cfg, 32, 1500.0, seed=11)
    statics = build_statics(cfg, bank, scenario=scn)
    state = load_jobs(init_state(cfg, statics, jax.random.key(2)), jobs)
    fs, tel, fs2, tel2 = _run_both(cfg, statics, state, 2000, "fcfs")
    _assert_equiv(fs, tel, fs2, tel2)
    assert float(fs.n_killed) > 0
    assert float(fs.lost_node_s) > 0
    assert float(tel2.macro_steps) < 0.5 * 2000
    s = summary(fs2, tel2)
    assert s["goodput_frac"] < 1.0 and s["lost_node_seconds"] > 0


def test_macro_full_serving_stack():
    """(e) the whole serving twin at once — diurnal traffic with a burst
    window, admission control, load shedding, timeout/backoff retries
    with terminal drops, and an autoscale wake in flight from t=0:
    per-tick and macro stay bit-identical (every SimState field incl.
    the PRNG stream, and all telemetry) and the engine still skips the
    quiet trough stretches."""
    from repro.scenarios import diurnal_serving

    cfg = tiny_cluster(serving_enabled=True, serving_nodes=4,
                       serving_concurrency=4.0, serving_service_s=3.0,
                       serving_queue_cap=60.0, serving_timeout_s=20.0,
                       serving_slo_s=6.0, serving_wake_s=90.0,
                       serving_max_retries=2, serving_backoff_s=5.0)
    scn = diurnal_serving(cfg, peak_rps=8.0, base_frac=0.05,
                          period_s=1800.0, burst_start_s=600.0,
                          burst_len_s=200.0, burst_mult=4.0)
    jobs, bank = synth_workload(cfg, 24, 900.0, seed=7)
    statics = build_statics(cfg, bank, scenario=scn)
    state = load_jobs(init_state(cfg, statics, jax.random.key(1)), jobs)
    # start the pool half-asleep with target = full pool: apply_serving
    # opens a wake batch on tick 0, so the wake-completion breakpoint is
    # genuinely exercised
    state = state._replace(srv_active=jnp.float32(2.0))
    fs, tel, fs2, tel2 = _run_both(cfg, statics, state, 1800, "fcfs")
    _assert_equiv(fs, tel, fs2, tel2)
    # every rung of the overload ladder actually fired
    assert float(fs.srv_shed) > 0
    assert float(fs.srv_retried) > 0
    assert float(fs.srv_dropped) > 0
    assert float(fs.srv_completed) > 0
    assert float(fs.srv_active) == cfg.serving_nodes     # wake completed
    assert float(tel2.macro_steps) < 0.85 * 1800         # still skips


def test_quiet_horizon_visible_queue_blocks():
    """A dispatch-visible queued job pins the conservative horizon to 0
    unless the caller proves the queue unservable."""
    cfg = tiny_cluster()
    jobs, bank = synth_workload(cfg, 8, 100.0, seed=0)
    jobs["submit_t"][:] = 0.0
    statics = build_statics(cfg, bank)
    state = load_jobs(init_state(cfg, statics, jax.random.key(0)), jobs)
    state = state._replace(t=jnp.float32(1.0))
    assert int(quiet_horizon(cfg, statics, state, "fcfs")) == 0
    assert int(quiet_horizon(cfg, statics, state, "fcfs",
                             assume_undispatchable=True)) > 0
    # the no-dispatch mode never blocks on queue visibility
    assert int(quiet_horizon(cfg, statics, state, "none")) > 0


# --------------------------------------------------------------------------
def test_bench_compare_tool(tmp_path, capsys):
    """run.py --compare: per-row speedup table, non-zero exit only on
    >20% regressions."""
    import json

    from benchmarks.run import compare_artifacts, main

    a = {"rows": [{"name": "x", "us_per_call": 100.0, "derived": ""},
                  {"name": "y", "us_per_call": 50.0, "derived": ""},
                  {"name": "gone", "us_per_call": 10.0, "derived": ""}]}
    b = {"rows": [{"name": "x", "us_per_call": 90.0, "derived": ""},
                  {"name": "y", "us_per_call": 49.0, "derived": ""},
                  {"name": "new", "us_per_call": float("nan"),
                   "derived": "FAILED"}]}
    pa, pb = tmp_path / "a.json", tmp_path / "b.json"
    pa.write_text(json.dumps(a))
    pb.write_text(json.dumps(b))
    assert compare_artifacts(str(pa), str(pb)) == 0
    main(["--compare", str(pa), str(pb)])       # no SystemExit: no regression
    capsys.readouterr()

    b["rows"][0]["us_per_call"] = 121.0         # x regresses >20%
    pb.write_text(json.dumps(b))
    assert compare_artifacts(str(pa), str(pb)) == 1
    with pytest.raises(SystemExit):
        main(["--compare", str(pa), str(pb)])
    out = capsys.readouterr().out
    assert "REGRESSION" in out
