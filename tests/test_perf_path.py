"""PR2 hot-path rebuild: equivalence + constant-memory telemetry tests.

- sort-free cumsum placement must be BIT-equivalent to the legacy argsort
  ``first_fit`` over random states (property test);
- ``lax.top_k`` RL candidates must match the argsort prefix;
- the fused power-scatter Pallas kernel must match the two-pass
  scatter + node-power oracle;
- windowed / episode-wide telemetry accumulators must match reductions of
  the full per-step StepOut stack.
"""

import jax
import jax.numpy as jnp
import numpy as np
from _hypothesis_compat import given, settings, st

from repro.configs.sim import tiny_cluster
from repro.core import (
    build_statics,
    init_state,
    load_jobs,
    run_episode,
    run_fleet,
)
from repro.core import schedulers as sched
from repro.core.power import compute_power, placement_amounts, job_utilization
from repro.data import synth_workload
from repro.kernels import ref


def _setup(seed=0, n_jobs=24, horizon=900.0):
    cfg = tiny_cluster()
    jobs, bank = synth_workload(cfg, n_jobs, horizon, seed=seed)
    statics = build_statics(cfg, bank)
    state = load_jobs(init_state(cfg, statics, jax.random.key(seed)), jobs)
    return cfg, statics, state


def _random_states(state, n, seed):
    keys = jax.random.split(jax.random.key(seed), n)

    def perturb(s, key):
        k1, k2, k3 = jax.random.split(key, 3)
        jstate = jnp.where(
            jax.random.bernoulli(k3, 0.3, s.jstate.shape),
            0, s.jstate)
        return s._replace(
            free=s.free * jax.random.uniform(k1, s.free.shape),
            t=jax.random.uniform(k2, (), minval=0.0, maxval=900.0),
            jstate=jstate,
        )

    return jax.vmap(perturb, in_axes=(None, 0))(state, keys)


@settings(max_examples=15, deadline=None)
@given(seed=st.integers(0, 1000), job=st.integers(0, 23))
def test_property_cumsum_placement_equals_argsort(seed, job):
    cfg, _, state = _setup(seed=seed % 7)
    states = _random_states(state, 16, seed)
    K = cfg.max_nodes_per_job
    row_new, ok_new = jax.vmap(
        lambda s: sched.first_fit(s, jnp.int32(job), K))(states)
    row_old, ok_old = jax.vmap(
        lambda s: sched.first_fit_argsort(s, jnp.int32(job), K))(states)
    np.testing.assert_array_equal(np.asarray(row_new), np.asarray(row_old))
    np.testing.assert_array_equal(np.asarray(ok_new), np.asarray(ok_old))


def test_cumsum_placement_edge_cases():
    cfg, _, state = _setup()
    K = cfg.max_nodes_per_job
    # more nodes requested than exist -> infeasible, all -1
    s = state._replace(n_nodes=state.n_nodes.at[0].set(cfg.n_nodes + 1))
    row, ok = sched.first_fit(s, jnp.int32(0), K)
    assert not bool(ok) and (np.asarray(row) == -1).all()
    # zero-node request -> feasible, empty row (matches argsort path)
    s = state._replace(n_nodes=state.n_nodes.at[0].set(0))
    row, ok = sched.first_fit(s, jnp.int32(0), K)
    row2, ok2 = sched.first_fit_argsort(s, jnp.int32(0), K)
    assert bool(ok) == bool(ok2)
    np.testing.assert_array_equal(np.asarray(row), np.asarray(row2))


@settings(max_examples=10, deadline=None)
@given(seed=st.integers(0, 100))
def test_property_topk_candidates_match_argsort(seed):
    cfg, _, state = _setup(seed=seed % 5)
    state = _random_states(state, 1, seed)
    state = jax.tree.map(lambda a: a[0], state)
    k = cfg.sched_max_candidates
    got = np.asarray(sched.rl_candidates(cfg, state))
    m = np.asarray(sched.queued_mask(state))
    score = np.where(m, np.asarray(state.submit_t), sched.BIG)
    idx = np.argsort(score, kind="stable")[:k]
    want = np.where(m[idx], idx, -1)
    np.testing.assert_array_equal(got, want)


def test_fused_power_scatter_matches_two_pass():
    cfg, statics, state = _setup()
    s, _ = jax.jit(lambda s: run_episode(cfg, statics, s, 80, "fcfs"))(state)
    p_ref = compute_power(cfg, s, statics, use_kernel=False)
    p_fused = compute_power(cfg, s, statics, use_kernel=True)
    for name, a, b in zip(p_ref._fields, p_ref, p_fused):
        np.testing.assert_allclose(
            np.asarray(a), np.asarray(b), rtol=1e-5, err_msg=name)


def test_power_scatter_ref_matches_pallas_kernel():
    from repro.kernels.node_power import power_scatter_pallas

    rng = np.random.default_rng(0)
    N, JK = 100, 192
    place = rng.integers(-1, N, JK).astype(np.int32)
    cabs = (rng.uniform(0, 8, JK) * (place >= 0)).astype(np.float32)
    gabs = (rng.uniform(0, 2, JK) * (place >= 0)).astype(np.float32)
    capc = rng.uniform(8, 48, N).astype(np.float32)
    capg = rng.uniform(1, 4, N).astype(np.float32)
    idle = rng.uniform(80, 300, N).astype(np.float32)
    cd = rng.uniform(100, 400, N).astype(np.float32)
    gd = rng.uniform(0, 600, N).astype(np.float32)
    up = rng.integers(0, 2, N).astype(np.float32)
    mx = idle + cd + gd
    kw = dict(rect_peak=0.965, rect_load=0.55, rect_curv=0.12,
              conv_eff=0.975)
    got = power_scatter_pallas(place, cabs, gabs, capc, capg, idle, cd, gd,
                               up, mx, block_jk=128, interpret=True, **kw)
    want = ref.power_scatter_ref(place, cabs, gabs, capc, capg, idle, cd,
                                 gd, up, mx, **kw)
    for g, w in zip(got, want):
        np.testing.assert_allclose(np.asarray(g), np.asarray(w), rtol=1e-5)


def test_placement_amounts_zeroes_invalid_slots():
    cfg, statics, state = _setup()
    s, _ = jax.jit(lambda s: run_episode(cfg, statics, s, 50, "fcfs"))(state)
    cpu_u, gpu_u = job_utilization(cfg, s, statics)
    place, cabs, gabs = placement_amounts(s, cpu_u, gpu_u)
    invalid = np.asarray(place) < 0
    assert (np.asarray(cabs)[invalid] == 0).all()
    assert (np.asarray(gabs)[invalid] == 0).all()


# ---------------------------------------------------------------------------
def test_telemetry_summary_only_matches_full_stack():
    cfg, statics, state = _setup()
    fs, outs = jax.jit(
        lambda s: run_episode(cfg, statics, s, 200, "fcfs"))(state)
    fs2, tel = jax.jit(
        lambda s: run_episode(cfg, statics, s, 200, "fcfs",
                              summary_only=True))(state)
    # identical final state either way
    np.testing.assert_allclose(float(fs.energy_kwh), float(fs2.energy_kwh))
    np.testing.assert_allclose(float(fs.n_completed), float(fs2.n_completed))
    o = jax.device_get(outs)
    np.testing.assert_allclose(
        float(tel.energy_kwh), o.energy_kwh_step.sum(), rtol=1e-5)
    np.testing.assert_allclose(
        float(tel.carbon_kg), o.carbon_kg_step.sum(), rtol=1e-5)
    np.testing.assert_allclose(
        float(tel.completed), o.completed_now.sum(), rtol=1e-6)
    np.testing.assert_allclose(float(tel.reward), o.reward.sum(),
                               rtol=1e-4, atol=1e-4)
    np.testing.assert_allclose(
        float(tel.mean_facility_w), o.facility_w.mean(), rtol=1e-5)
    np.testing.assert_allclose(
        float(tel.mean_pue), o.pue.mean(), rtol=1e-5)
    np.testing.assert_allclose(
        float(tel.max_facility_w), o.facility_w.max(), rtol=1e-6)
    np.testing.assert_allclose(
        float(tel.max_queue_len), o.queue_len.max(), rtol=1e-6)
    assert float(tel.n_steps) == 200


def test_telemetry_windows_match_full_stack():
    cfg, statics, state = _setup()
    every = 25
    fs, outs = jax.jit(
        lambda s: run_episode(cfg, statics, s, 200, "fcfs"))(state)
    fs2, wins = jax.jit(
        lambda s: run_episode(cfg, statics, s, 200, "fcfs",
                              telemetry_every=every))(state)
    np.testing.assert_allclose(float(fs.t), float(fs2.t))
    o = jax.device_get(outs)
    n_win = 200 // every
    assert np.shape(wins.mean_facility_w) == (n_win,)
    np.testing.assert_allclose(
        np.asarray(wins.mean_facility_w),
        o.facility_w.reshape(n_win, every).mean(1), rtol=1e-5)
    np.testing.assert_allclose(
        np.asarray(wins.energy_kwh),
        o.energy_kwh_step.reshape(n_win, every).sum(1), rtol=1e-5)
    np.testing.assert_allclose(
        np.asarray(wins.max_queue_len),
        o.queue_len.reshape(n_win, every).max(1), rtol=1e-6)
    np.testing.assert_allclose(
        float(np.asarray(wins.completed).sum()), float(fs.n_completed))


def test_telemetry_every_must_divide_n_steps():
    cfg, statics, state = _setup()
    import pytest

    with pytest.raises(ValueError):
        run_episode(cfg, statics, state, 201, "fcfs", telemetry_every=25)
    # episode-wide summary conflicts with windowing — must be loud
    with pytest.raises(ValueError):
        run_episode(cfg, statics, state, 200, "fcfs", telemetry_every=25,
                    summary_only=True)


def test_fleet_summary_only_constant_size_and_chaining():
    from repro.scenarios import sample_scenarios

    cfg, statics, state = _setup()
    scns = sample_scenarios(cfg, 4, seed=1)
    fs, outs = run_fleet(cfg, statics, state, 60, "fcfs", scenarios=scns)
    fs2, tel = run_fleet(cfg, statics, state, 60, "fcfs", scenarios=scns,
                         summary_only=True)
    # O(R) telemetry, not O(R*T)
    assert np.shape(tel.energy_kwh) == (4,)
    assert np.shape(outs.energy_kwh_step) == (4, 60)
    np.testing.assert_allclose(
        np.asarray(tel.energy_kwh),
        np.asarray(outs.energy_kwh_step).sum(1), rtol=1e-5)
    np.testing.assert_allclose(
        np.asarray(fs.energy_kwh), np.asarray(fs2.energy_kwh), rtol=1e-6)
    # chained sweep: batched final states feed straight back in
    fs3, _ = run_fleet(cfg, statics, fs2, 60, "fcfs", scenarios=scns,
                       summary_only=True)
    assert (np.asarray(fs3.t) >= np.asarray(fs.t)).all()
