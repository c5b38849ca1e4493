"""A fast-tick chunk reads each job's telemetry as one window of quanta.

``power.job_utilization_chunk`` gives the (C, J) utilization of a chunk of
C ticks from one (1, M) slice of each job's bank row and a select per
tick. It must read the very element the per-tick gather reads, so it is
held here to ``vmap(job_utilization)`` over the chunk's tick times, bit for
bit, in each bank layout (one workload, banked, streamed), around quantum
boundaries, at the row's end, at late float32 times, and where the window
would be as long as the chunk (the per-tick gather is kept there).
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs.sim import tiny_cluster
from repro.core import build_statics, init_state, load_jobs
from repro.core import power
from repro.core.sim import CHUNK_TICKS
from repro.core.state import DONE, QUEUED, RUNNING
from repro.data import synth_workload

J, Q, W, N_TRACE = 16, 24, 3, 40
LAYOUTS = ("resident", "banked", "streamed")
# each job's age at the chunk's first tick time (10 s quanta, 24 of them)
AGES = np.array([
    0.0, 10.0, 50.0,          # on a quantum boundary
    8.0, 18.0, 98.0,          # at 8 mod 10: the chunk meets 3 quanta
    220.0, 224.5,             # window start past Q - M: the slice clamps
    230.0, 233.0, 400.0,      # in the last quantum, where the index clips
    -3.0, -40.0,              # start_t after t: age 0 until it passes
    27.0, 35.0, 61.0,         # queued, finished, and one more running
], np.float32)
STATES = np.full(J, RUNNING, np.int32)
STATES[[13, 2]] = QUEUED
STATES[[14, 9]] = DONE


def _setup(layout, q):
    """(statics, state) with random telemetry in ``layout``'s bank of
    ``q`` quanta a row."""
    rng = np.random.default_rng(18)
    cfg = tiny_cluster(max_jobs=J)
    if layout == "streamed":
        jobs, bank = synth_workload(cfg, N_TRACE, 3000.0, seed=18)
        statics = build_statics(cfg, bank, jobs=jobs)
        state = load_jobs(init_state(cfg, statics, jax.random.key(0)), jobs)
        tid = rng.permutation(N_TRACE)[:J].astype(np.int32)
        tid[5] = -1                                   # an empty slot
        state = state._replace(stream=state.stream._replace(
            tid=jnp.asarray(tid)))
        shape = (N_TRACE, q)
    else:
        statics = build_statics(cfg)
        state = init_state(cfg, statics, jax.random.key(0))
        shape = (W, J, q) if layout == "banked" else (J, q)
        if layout == "banked":
            state = state._replace(workload=jnp.int32(W - 1))
    statics = statics._replace(
        cpu_trace=jnp.asarray(rng.random(shape), jnp.float32),
        gpu_trace=jnp.asarray(rng.random(shape), jnp.float32))
    return statics, state


# (dt, quanta a bank row, the window's length M): at dt = quanta M would
# reach the chunk's 16 ticks and the per-tick gather is kept; a bank row
# shorter than the chunk is read whole
SPACINGS = {"dt1": (1.0, Q, 3), "dt0.7": (0.7, Q, 3),
            "dt_eq_quanta": (10.0, Q, 18), "short_bank": (10.0, 12, 12)}


@pytest.mark.parametrize("spacing", sorted(SPACINGS))
@pytest.mark.parametrize("t0", [1234.0, 86_397.0, 604_793.0])
@pytest.mark.parametrize("layout", LAYOUTS)
def test_window_reads_what_each_tick_reads(layout, t0, spacing):
    dt, q, m = SPACINGS[spacing]
    cfg = tiny_cluster(max_jobs=J, dt=dt)
    statics, state = _setup(layout, q)
    C = CHUNK_TICKS
    assert power.chunk_window(cfg, C, q) == m
    ts = jnp.float32(t0) + cfg.dt * jnp.arange(1, C + 1, dtype=jnp.float32)
    start_t = ts[0] - jnp.asarray(AGES)
    state = state._replace(t=jnp.float32(t0), start_t=start_t,
                           jstate=jnp.asarray(STATES))

    got = jax.jit(lambda s: power.job_utilization_chunk(
        cfg, s, statics, ts))(state)
    want = jax.jit(lambda s: power._per_tick_utilization(
        cfg, s, statics, ts))(state)
    for g, w in zip(got, want):
        assert g.shape == (C, J)
        np.testing.assert_array_equal(np.asarray(g), np.asarray(w))
    # the chunk reads telemetry (not only zeros), and masks what is not
    # running
    cpu = np.asarray(got[0])
    assert (cpu[:, STATES == RUNNING] > 0).all()
    assert (cpu[:, STATES != RUNNING] == 0).all()


def test_window_covers_three_quanta_at_tx_gaia_spacing():
    """16 ticks of 1 s meet at most 3 quanta of 10 s: the window the
    TX-GAIA cells read; a bank shorter than that is read whole."""
    cfg = tiny_cluster(max_jobs=J)
    assert power.chunk_window(cfg, CHUNK_TICKS, 361) == 3
    assert power.chunk_window(cfg, CHUNK_TICKS, 2) == 2


def test_macro_episode_is_bitwise_the_per_tick_gathers(monkeypatch):
    """A macro episode above the dense budget (the count-matrix path,
    whose fast ticks read telemetry by chunk) gives the same state and
    telemetry, bit for bit, as one whose chunks gather tick by tick."""
    from repro.configs.sim import tx_gaia
    from repro.core import run_episode
    from repro.core import sim

    cfg = tx_gaia(max_jobs=64, max_nodes_per_job=4)
    assert not power.use_dense_scatter(64 * 4, cfg.n_nodes)
    jobs, bank = synth_workload(cfg, 48, 900.0, seed=18)
    statics = build_statics(cfg, bank)
    state = load_jobs(init_state(cfg, statics, jax.random.key(0)), jobs)

    def episode():
        return jax.jit(lambda s: run_episode(cfg, statics, s, 900, "fcfs",
                                             macro=True))(state)

    fs, tel = episode()
    monkeypatch.setattr(sim, "job_utilization_chunk",
                        power._per_tick_utilization)
    fs2, tel2 = episode()
    assert float(fs.n_completed) > 0
    assert float(tel.macro_steps) < 0.5 * float(tel.n_steps)
    for want, got in ((fs2, fs), (tel2, tel)):
        for f, a in want._asdict().items():
            if a is None or f == "key":
                continue
            np.testing.assert_array_equal(np.asarray(getattr(got, f)),
                                          np.asarray(a), err_msg=f)
