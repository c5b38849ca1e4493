"""EASY's release count through the per-job node-count matrix.

``schedulers.shadow_time`` counts, for each running job, the slots whose
node could host the queue's head (``power.flagged_counts``). The CPU
gathers the node flags through the J*K slots; accelerators contract the
(J, N) node-count matrix with the flags. Both forms are called directly
here, so both run on the CPU, and are held to each other and to a NumPy
reference bit for bit. A contended EASY episode with the accelerators'
form patched in must dispatch exactly as the gather does: the chip's sweep
never forms a queue, so this is what guards that path.
"""

import jax
import numpy as np
import pytest

from repro.core import power
from repro.core.state import DONE, RUNNING

SHAPES = {"small": (16, 4, 10), "tx_gaia": (512, 64, 928)}   # J, K, N
CASES = ["invalid", "repeats", "empty", "none_flagged", "batch4"]


def _inputs(shape, case, rng=None):
    """(J, K) int32 placement and (N,) bool flags; a leading axis of 4
    for ``batch4``."""
    J, K, N = SHAPES[shape]
    rng = np.random.default_rng(16) if rng is None else rng
    if case == "batch4":
        parts = [_inputs(shape, c, rng)
                 for c in ("invalid", "repeats", "empty", "none_flagged")]
        return tuple(np.stack(p) for p in zip(*parts))
    place = rng.integers(0, N, (J, K)).astype(np.int32)
    flags = rng.random(N) < 0.5
    if case == "empty":
        place[:] = -1
    elif case == "none_flagged":
        flags[:] = False
    elif case == "repeats":
        place[0] = place[0, 0]                # every slot on one node
        place[1, 1] = place[1, 0]             # one node twice
        flags[place[0, 0]] = True
    if case in ("invalid", "none_flagged"):
        place[rng.random((J, K)) < 0.4] = -1
        place[::5] = -1                       # whole jobs not placed
    return place, flags


def _np_counts(place, flags):
    safe = np.where(place >= 0, place, 0)
    hit = (place >= 0) & np.take_along_axis(
        flags, safe.reshape(flags.shape[:-1] + (-1,)), axis=-1
    ).reshape(place.shape)
    return hit.sum(axis=-1).astype(np.float32)


@pytest.mark.parametrize("case", CASES)
@pytest.mark.parametrize("shape", sorted(SHAPES))
def test_count_form_equals_the_gather(shape, case):
    place, flags = _inputs(shape, case)

    def run(fn):
        f = jax.vmap(fn) if place.ndim == 3 else fn
        return np.asarray(jax.jit(f)(place, flags))

    got = run(power._count_flagged_counts)
    old = run(power._gather_flagged_counts)
    assert got.dtype == old.dtype == np.float32
    np.testing.assert_array_equal(got, old)
    np.testing.assert_array_equal(got, _np_counts(place, flags))
    np.testing.assert_array_equal(run(power.flagged_counts), old)


def _contended_easy():
    """A tiny cluster swamped by bursts of jobs: the head blocks and EASY
    backfills behind its reservation."""
    from repro.configs.sim import tiny_cluster
    from repro.core import build_statics, init_state, load_jobs
    from repro.data import synth_workload

    cfg = tiny_cluster()
    jobs, bank = synth_workload(cfg, 60, 1800.0, seed=16, arrival="burst",
                                mean_dur_s=600.0)
    statics = build_statics(cfg, bank)
    state = load_jobs(init_state(cfg, statics, jax.random.key(16)), jobs)
    return cfg, statics, state


@pytest.mark.parametrize("macro", [False, True], ids=["per_tick", "macro"])
def test_count_form_drives_a_contended_easy_episode(macro, monkeypatch):
    from repro.core import run_episode

    cfg, statics, state = _contended_easy()

    def episode(counts):
        c = jax.jit(lambda s: run_episode(cfg, statics, s, 1800, "easy",
                                          macro=macro)).lower(state).compile()
        # the count build is compiled only where the count form runs
        assert ("tick.node_counts" in c.as_text()) is counts
        return c(state)[0]

    fs = episode(False)
    monkeypatch.setattr(power, "_gather_flagged_counts",
                        power._count_flagged_counts)
    fs2 = episode(True)
    for f in ("jstate", "start_t", "end_t", "placement", "free"):
        np.testing.assert_array_equal(np.asarray(getattr(fs2, f)),
                                      np.asarray(getattr(fs, f)), err_msg=f)
    # the queue backed up and EASY backfilled: some job started before a
    # job submitted earlier than it
    sub, start = np.asarray(state.submit_t), np.asarray(fs.start_t)
    began = np.isin(np.asarray(fs.jstate), (RUNNING, DONE))
    assert (began & (start > sub + 60.0)).sum() >= 10
    s, t = sub[began], start[began]
    assert ((s[:, None] < s[None, :]) & (t[:, None] > t[None, :])).any()
