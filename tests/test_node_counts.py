"""Job -> node reductions through the per-job node-count matrix.

Above the dense one-hot budget, ``power.scatter_add_nodes`` picks its form
by the platform it is lowered for: an XLA scatter-add over the J*K slots on
the CPU, and on accelerators the (J, N) count matrix ``job_node_counts``
contracted with the per-job amounts. The count path is called directly
here, so it runs on the CPU, and is held to the scatter path and to a NumPy
reference: counts and integer releases exactly, float loads to 1e-6.
"""

import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import power

SHAPES = {"small": (16, 4, 10), "tx_gaia": (512, 64, 928)}   # J, K, N
CASES = ["invalid", "repeats", "empty", "batch4"]


def _table(case, J, K, N, rng):
    """(J, K) int32 placement, or (4, J, K) for ``batch4``."""
    if case == "batch4":
        return np.stack([_table(c, J, K, N, rng)
                         for c in ("invalid", "repeats", "empty", "invalid")])
    if case == "empty":
        return np.full((J, K), -1, np.int32)
    place = rng.integers(0, N, (J, K)).astype(np.int32)
    if case == "invalid":
        place[rng.random((J, K)) < 0.4] = -1
        place[::5] = -1                       # whole jobs not placed
    else:
        place[0] = place[0, 0]                # every slot on one node
        place[1, 1] = place[1, 0]             # one node twice
        place[2, -1] = -1
    return place


def _np_counts(place, N):
    cnt = np.zeros(place.shape[:-1] + (N,), np.float64)
    for idx in np.ndindex(place.shape):
        if place[idx] >= 0:
            cnt[idx[:-1] + (place[idx],)] += 1
    return cnt


def _inputs(shape, case):
    J, K, N = SHAPES[shape]
    rng = np.random.default_rng(14)
    place = _table(case, J, K, N, rng)
    lead = place.shape[:-2]
    req = rng.integers(1, 48, lead + (3, J)).astype(np.float32)
    free = rng.integers(0, 400, lead + (3, N)).astype(np.float32)
    util = rng.random(lead + (2, J)).astype(np.float32)
    return place, req, free, req[..., :2, :] * util, N


def _batched(fn, place):
    return jax.jit(jax.vmap(fn) if place.ndim == 3 else fn)


@pytest.mark.parametrize("case", CASES)
@pytest.mark.parametrize("shape", sorted(SHAPES))
def test_counts_equal_the_scatter_built_matrix(shape, case):
    place, _, _, _, N = _inputs(shape, case)
    got = _batched(lambda p: power.job_node_counts(p, N), place)(place)
    old = _batched(lambda p: power._scatter_node_counts(p, N), place)(place)
    np.testing.assert_array_equal(np.asarray(got), np.asarray(old))
    np.testing.assert_array_equal(np.asarray(got), _np_counts(place, N))


@pytest.mark.parametrize("case", CASES)
@pytest.mark.parametrize("shape", sorted(SHAPES))
def test_release_is_exact_for_integer_requests(shape, case):
    place, req, free, _, N = _inputs(shape, case)
    got = _batched(power._count_path, place)(place, req, free)
    old = _batched(power._scatter_path, place)(place, req, free)
    np.testing.assert_array_equal(np.asarray(got), np.asarray(old))
    want = free + np.einsum("...rj,...jn->...rn", req.astype(np.float64),
                            _np_counts(place, N))
    np.testing.assert_array_equal(np.asarray(got), want)


@pytest.mark.parametrize("case", CASES)
@pytest.mark.parametrize("shape", sorted(SHAPES))
def test_loads_agree_for_float_amounts(shape, case):
    place, _, _, amounts, N = _inputs(shape, case)
    zeros = np.zeros(amounts.shape[:-1] + (N,), np.float32)
    got = _batched(power._count_path, place)(place, amounts, zeros)
    old = _batched(power._scatter_path, place)(place, amounts, zeros)
    want = np.einsum("...rj,...jn->...rn", amounts.astype(np.float64),
                     _np_counts(place, N))
    np.testing.assert_allclose(np.asarray(got), np.asarray(old), rtol=1e-6)
    np.testing.assert_allclose(np.asarray(got), want, rtol=1e-6)


@pytest.mark.parametrize("shape", sorted(SHAPES))
def test_scatter_add_nodes_matches_the_count_path(shape):
    """The public entry (the dense one-hot on the small shape, the scatter
    on the CPU at TX-GAIA shape) against the count path."""
    place, req, free, _, _ = _inputs(shape, "repeats")
    got = power.scatter_add_nodes(jnp.asarray(place), jnp.asarray(req),
                                  free.shape[-1], base=jnp.asarray(free))
    np.testing.assert_array_equal(
        np.asarray(got), np.asarray(power._count_path(place, req, free)))


def _cpu_hlo(fn, *args):
    return jax.jit(fn).lower(*args).compile().as_text()


@pytest.mark.parametrize("what,shape,scatter", [
    ("reduce", "small", False), ("reduce", "tx_gaia", True),
    ("counts", "tx_gaia", True)])
def test_cpu_lowering_keeps_its_form(what, shape, scatter):
    """On the CPU the platform choice lowers the scatter above the dense
    budget and the one-hot below it, and the macro engine's count matrix
    is the scatter; the count build is never compiled there, so its stage
    is absent."""
    place, req, free, _, N = _inputs(shape, "invalid")
    if what == "reduce":
        text = _cpu_hlo(lambda p, r, f: power.scatter_add_nodes(p, r, N, f),
                        place, req, free)
    else:
        text = _cpu_hlo(lambda p: power.node_counts(p, N), place)
    assert bool(re.search(r"\bscatter\(", text)) is scatter
    assert "tick.node_counts" not in text


def test_count_path_drives_an_episode_like_the_scatter(monkeypatch):
    """A macro episode above the dense budget with the accelerators' form
    swapped in for the CPU's: the same job records and free pool, and the
    telemetry to float tolerance."""
    from repro.configs.sim import tx_gaia
    from repro.core import build_statics, init_state, load_jobs, run_episode
    from repro.data import synth_workload

    cfg = tx_gaia(max_jobs=64, max_nodes_per_job=4)
    assert not power.use_dense_scatter(64 * 4, cfg.n_nodes)
    jobs, bank = synth_workload(cfg, 48, 600.0, seed=14)
    statics = build_statics(cfg, bank)
    state = load_jobs(init_state(cfg, statics, jax.random.key(0)), jobs)

    def episode(counts):
        c = jax.jit(lambda s: run_episode(cfg, statics, s, 600, "fcfs",
                                          macro=True)).lower(state).compile()
        assert ("tick.node_counts" in c.as_text()) is counts
        return c(state)

    fs, tel = episode(False)
    monkeypatch.setattr(power, "_scatter_path", power._count_path)
    monkeypatch.setattr(power, "_scatter_node_counts", power.job_node_counts)
    fs2, tel2 = episode(True)
    assert float(fs.n_completed) > 0
    for f in ("jstate", "start_t", "end_t", "placement", "free"):
        np.testing.assert_array_equal(np.asarray(getattr(fs2, f)),
                                      np.asarray(getattr(fs, f)), err_msg=f)
    for f, a in tel._asdict().items():
        if a is None:   # streamed-admission counters: absent on this path
            assert getattr(tel2, f) is None, f
            continue
        np.testing.assert_allclose(np.asarray(getattr(tel2, f)),
                                   np.asarray(a), rtol=1e-6, err_msg=f)
