"""Streamed admission: a trace longer than the job table replays through
it (``core.state``: ``Statics.trace`` / ``SimState.stream``; the
``tick.admit`` stage of ``core.sim``), with the same answers as a table
that holds the whole trace, whenever live jobs fit the table."""

import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs.sim import tiny_cluster
from repro.core import (
    QUEUED,
    build_statics,
    init_state,
    load_jobs,
    run_episode,
    run_segment,
    telem_zero,
    trace_records,
)
from repro.core import schedulers as sched
from repro.core.placement import make_policy
from repro.core.sim import _telem_finalize
from repro.data import synth_workload, write_supercloud_csvs, load_supercloud
from repro.utils.errors import ConfigError

N, SLOTS, TICKS = 96, 16, 3000
SELECTS = ("fcfs", "sjf", "priority", "easy")
PLACES = ("first_fit", "best_fit")
INTEGRALS = ("energy_kwh", "it_energy_kwh", "loss_energy_kwh",
             "cool_energy_kwh", "carbon_kg", "elec_cost_usd",
             "flops_integral", "sum_power_w", "n_completed", "sum_wait",
             "sum_slowdown")
# A node's load sums its jobs' shares in slot order, and a streamed table
# holds its jobs in other slots than a table that holds the whole trace,
# so float32 power sums may round apart by a few ulps; every integral
# follows them. Job records are exact.
INTEGRAL_RTOL = 1e-5


def _workload(n=N, horizon=6000.0, seed=3, mean_dur_s=150.0):
    """``n`` jobs arriving over 0.9 of ``horizon``: the generator piles its
    last arrivals at 0.9 of the horizon, after the replayed ticks, so live
    jobs stay within the 16 slots."""
    cfg = tiny_cluster(max_jobs=SLOTS)
    return synth_workload(cfg, n, horizon, seed=seed, mean_dur_s=mean_dur_s)


def _setups(jobs, bank, slots=SLOTS):
    """(cfg, statics, state) streamed through ``slots`` slots, and resident
    in a table of 128 that holds the whole trace."""
    n = len(jobs["submit_t"])
    small = tiny_cluster(max_jobs=slots)
    st_s = build_statics(small, bank, jobs=jobs)
    s0 = load_jobs(init_state(small, st_s, jax.random.key(0)), jobs)
    big = tiny_cluster(max_jobs=128)
    pad = {k: np.concatenate([v, np.zeros((128 - n,) + v.shape[1:],
                                          v.dtype)])
           for k, v in bank.items()}
    st_b = build_statics(big, pad)
    s1 = load_jobs(init_state(big, st_b, jax.random.key(0)), jobs)
    return (small, st_s, s0), (big, st_b, s1)


@functools.lru_cache(maxsize=None)
def _runner(cfg, macro):
    """One compiled episode per table and mode; the policy is data."""
    return jax.jit(lambda statics, s, pol: run_episode(
        cfg, statics, s, TICKS, pol, summary_only=not macro, macro=macro))


@pytest.fixture(scope="module")
def setups():
    return _setups(*_workload())


def _records_equal(fs, fr, n):
    a, b = trace_records(fs), trace_records(fr)
    for k in a:
        np.testing.assert_array_equal(a[k][:n], b[k][:n], err_msg=k)


def _integrals_close(fs, fr):
    for k in INTEGRALS:
        np.testing.assert_allclose(float(getattr(fs, k)),
                                   float(getattr(fr, k)),
                                   rtol=INTEGRAL_RTOL, err_msg=k)


@pytest.mark.parametrize("macro", [False, True], ids=["per_tick", "macro"])
@pytest.mark.parametrize("place", PLACES)
@pytest.mark.parametrize("select", SELECTS)
def test_streamed_matches_resident(setups, select, place, macro):
    (small, st_s, s0), (big, st_b, s1) = setups
    pol = make_policy(select, place)
    fs, tel = _runner(small, macro)(st_s, s0, pol)
    fr, tel_r = _runner(big, macro)(st_b, s1, pol)
    _records_equal(fs, fr, N)
    _integrals_close(fs, fr)
    # the table refilled well past its 16 slots, never overflowing
    assert float(tel.admitted) > 2 * SLOTS
    assert float(tel.admit_overflow) == 0.0
    assert 0.0 < float(tel.live_slot_ticks) <= SLOTS * float(tel.macro_steps)
    # the resident path carries nothing of it
    assert fr.stream is None and st_b.trace is None
    assert tel_r.admitted is None and tel_r.live_slot_ticks is None


def test_macro_equals_per_tick_streamed(setups):
    """The eager fcfs + first_fit program (the day cell's) per tick and in
    macro steps: every state leaf bitwise, admission counts equal."""
    (small, st_s, s0), _ = setups

    def run(macro):
        return jax.jit(lambda st, s: run_episode(
            small, st, s, TICKS, "fcfs", summary_only=True,
            macro=macro))(st_s, s0)

    (fs, tel), (fm, tel_m) = run(False), run(True)
    _assert_leaves_equal(fs, fm)
    assert float(tel.admitted) == float(tel_m.admitted) > 0
    assert float(tel.admit_overflow) == float(tel_m.admit_overflow) == 0
    # the engine fast-forwarded, so it counted live slots on fewer ticks
    assert float(tel_m.macro_steps) < 0.5 * float(tel.macro_steps)


def _tied_workload():
    """Jobs submitted on even minutes and lasting whole minutes: many
    submit and duration ties. Were ties broken by slot, every selection
    would start some of them in another order than this table's, on
    other nodes, and the records would differ."""
    jobs, bank = _workload(mean_dur_s=100.0, seed=5)
    jobs = dict(jobs)
    jobs["submit_t"] = (np.floor(jobs["submit_t"] / 120.0) * 120.0
                        ).astype(np.float32)
    jobs["dur"] = np.maximum(np.round(jobs["dur"] / 60.0) * 60.0,
                             60.0).astype(np.float32)
    jobs["priority"] = jobs["submit_t"]
    return jobs, bank


@pytest.mark.parametrize("select", SELECTS)
def test_ties_follow_trace_order(select):
    """Equal submit times (and, for sjf, durations) start in trace order
    whichever slots the jobs landed in."""
    jobs, bank = _tied_workload()
    assert len(np.unique(jobs["submit_t"])) < 0.6 * N
    (small, st_s, s0), (big, st_b, s1) = _setups(jobs, bank)
    fs, tel = jax.jit(lambda st, s: run_episode(
        small, st, s, TICKS, select, summary_only=True))(st_s, s0)
    fr, _ = jax.jit(lambda st, s: run_episode(
        big, st, s, TICKS, select, summary_only=True))(st_b, s1)
    assert float(tel.admit_overflow) == 0.0
    _records_equal(fs, fr, N)
    _integrals_close(fs, fr)


def test_pick_breaks_ties_by_trace_id():
    """Two visible jobs with equal scores: the one earlier in the trace
    wins, though it sits in the later slot."""
    jobs, bank = _workload()
    cfg = tiny_cluster(max_jobs=SLOTS)
    statics = build_statics(cfg, bank, jobs=jobs)
    s = load_jobs(init_state(cfg, statics, jax.random.key(0)), jobs)
    tid = jnp.arange(SLOTS, dtype=jnp.int32)[::-1] + 40
    s = s._replace(t=jnp.float32(10.0),
                   submit_t=jnp.full((SLOTS,), 5.0, jnp.float32),
                   dur_est=jnp.full((SLOTS,), 60.0, jnp.float32),
                   priority=jnp.zeros((SLOTS,), jnp.float32),
                   stream=s.stream._replace(tid=tid))
    for select in SELECTS:
        job = int(sched.SCHEDULERS[select](cfg, s, statics))
        assert job == SLOTS - 1, select


def test_admit_overflow_counted():
    """Long jobs on a 4-slot table: due jobs find it full. Every such tick
    counts, the same per tick and in macro steps."""
    jobs, bank = _workload(n=24, horizon=1200.0, mean_dur_s=900.0)
    (small, st_s, s0), _ = _setups(jobs, bank, slots=4)
    out = [jax.jit(lambda st, s: run_episode(
        small, st, s, 900, "fcfs", summary_only=not m, macro=m))(st_s, s0)
        for m in (False, True)]
    (fs, tel), (fm, tel_m) = out
    assert float(tel.admit_overflow) > 0
    assert float(tel.admit_overflow) == float(tel_m.admit_overflow)
    assert float(tel.admitted) == float(tel_m.admitted)
    _records_equal(fs, fm, 24)


def _assert_leaves_equal(a, b):
    fa = jax.tree_util.tree_flatten_with_path(a)[0]
    fb = jax.tree_util.tree_leaves(b)
    assert len(fa) == len(fb)
    for (path, x), y in zip(fa, fb):
        if jax.dtypes.issubdtype(x.dtype, jax.dtypes.prng_key):
            x, y = jax.random.key_data(x), jax.random.key_data(y)
        np.testing.assert_array_equal(np.asarray(x), np.asarray(y),
                                      err_msg=jax.tree_util.keystr(path))


def test_snapshot_resume_across_refill_is_bitwise(tmp_path):
    """Kill after the first snapshot, resume: state (cursor and outcome
    record included) and telemetry equal the uninterrupted snapshotted
    run bit for bit, across refills of the table; the state equals the
    unsegmented run's too (segment edges only add event ticks to the
    skip accounting)."""
    import os
    import shutil

    jobs, bank = _workload()
    cfg = tiny_cluster(max_jobs=SLOTS)
    statics = build_statics(cfg, bank, jobs=jobs)
    s0 = load_jobs(init_state(cfg, statics, jax.random.key(0)), jobs)
    whole = run_episode(cfg, statics, s0, 1200, "fcfs", macro=True)
    d = str(tmp_path / "snap")
    ref = run_episode(cfg, statics, s0, 1200, "fcfs", macro=True,
                      snapshot_every_s=300.0, snapshot_dir=d)
    snaps = sorted(os.listdir(d))     # the newest three are kept
    assert len(snaps) == 3
    for s in snaps[1:]:       # killed after tick 600's snapshot
        shutil.rmtree(os.path.join(d, s))
    got = run_episode(cfg, statics, s0, 1200, "fcfs", macro=True,
                      snapshot_every_s=300.0, resume_from=d)
    first = jax.jit(lambda st, s: run_segment(
        cfg, st, s, telem_zero(cfg, st), 300, "fcfs", macro=True))(
            statics, s0)[0]
    assert int(first.stream.cursor) > SLOTS       # the table refilled
    assert int(ref[0].stream.cursor) > int(first.stream.cursor)
    _assert_leaves_equal(ref, got)
    _assert_leaves_equal(whole[0], got[0])


def test_segments_match_one_episode():
    """Hourly-style segments carrying (state, acc), as the day cell's
    driver runs them, equal one call."""
    jobs, bank = _workload()
    cfg = tiny_cluster(max_jobs=SLOTS)
    statics = build_statics(cfg, bank, jobs=jobs)
    s0 = load_jobs(init_state(cfg, statics, jax.random.key(0)), jobs)
    fs, tel = run_episode(cfg, statics, s0, 1500, "fcfs", macro=True)
    seg = jax.jit(lambda st, s, a: run_segment(cfg, st, s, a, 300, "fcfs",
                                               macro=True))
    s, a = s0, telem_zero(cfg, statics)
    for _ in range(5):
        s, a = seg(statics, s, a)
    a = _telem_finalize(a)
    _records_equal(fs, s, N)
    for k in INTEGRALS:
        assert float(getattr(fs, k)) == float(getattr(s, k)), k
    assert float(a.admitted) == float(tel.admitted)


def test_unadmitted_jobs_read_queued(setups):
    (small, st_s, s0), _ = setups
    rec = trace_records(s0)
    assert (rec["state"] == QUEUED).all()
    assert not rec["start"].any() and not rec["end"].any()
    assert int(s0.stream.cursor) == SLOTS
    np.testing.assert_array_equal(np.asarray(s0.stream.tid),
                                  np.arange(SLOTS))


def test_loaders_keep_every_job(tmp_path):
    """The CSV loader and the generator keep a trace longer than the
    table, with a bank row per job, and strict ingestion reports nothing
    dropped or skipped."""
    cfg = tiny_cluster(max_jobs=SLOTS)
    path = write_supercloud_csvs(str(tmp_path), cfg, n_jobs=40,
                                 horizon_s=600.0, seed=1)
    jobs, bank, rep = load_supercloud(path, cfg, validate="strict",
                                      return_report=True)
    assert len(jobs["submit_t"]) == 40
    assert bank["cpu"].shape[0] == bank["gpu"].shape[0] == 40
    assert bank["net_tx"].shape == (40,)
    assert not [w for w in rep["scheduler"].warnings
                if w["check"] == "truncated"]
    assert rep["scheduler"].n_ok == 40
    assert rep["cpu_telemetry"].n_skipped_unknown_id == 0
    assert rep["gpu_telemetry"].n_skipped_unknown_id == 0
    jobs, bank = synth_workload(cfg, 40, 600.0, seed=1)
    assert bank["cpu"].shape[0] == 40
    statics = build_statics(cfg, bank, jobs=jobs)
    assert statics.trace.submit_t.shape == (40,)
    assert statics.trace.due.shape == (41,)


def test_mismatched_inputs_are_refused(setups):
    from repro.core import run_fleet
    from repro.data import stack_workloads

    (small, st_s, s0), (big, st_b, s1) = setups
    jobs, bank = _workload()
    with pytest.raises(ConfigError, match="jobs="):
        build_statics(small, bank)
    # a streamed trace against a resident state, and the reverse
    with pytest.raises(ConfigError, match="disagree"):
        run_episode(small, st_s, s0._replace(stream=None), 10, "fcfs")
    with pytest.raises(ConfigError, match="disagree"):
        run_episode(big, st_b._replace(trace=st_s.trace), s1, 10, "fcfs")
    with pytest.raises(ConfigError, match="run_episode/run_segment"):
        run_fleet(small, st_s, s0, 10, "fcfs", summary_only=True)
    with pytest.raises(ConfigError, match="job table"):
        stack_workloads(small, [(jobs, bank)])
