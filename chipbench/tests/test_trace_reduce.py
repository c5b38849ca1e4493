"""The trace reduction on a small hand-built trace: busy union, idle
share, per-op seconds and gaps named by the harness's host spans."""

from types import SimpleNamespace as NS

import pytest

from chipbench.trace_reduce import reduce_space, self_times, union


def ev(name, start, dur):
    return NS(name=name, start_ns=float(start), duration_ns=float(dur))


def space():
    host = NS(name="/host:CPU", lines=[NS(name="python", events=[
        ev("window", 0, 1000),
        ev("window.episode", 0, 600),
        ev("host.summary", 600, 400)])])
    ops = [ev("while.1", 100, 300), ev("fusion.1", 100, 100),
           ev("fusion.2", 250, 100), ev("fusion.1", 700, 100),
           ev("before", -50, 80)]
    dev = NS(name="/device:TPU:0", lines=[
        NS(name="XLA Ops", events=ops),
        NS(name="XLA Modules", events=[ev("jit_step", 0, 1000)])])
    other = NS(name="/device:TPU:0 SparseCore", lines=[
        NS(name="XLA Ops", events=[ev("x", 0, 1000)])])
    return NS(planes=[host, dev, other])


def test_self_times_subtract_nested_ops():
    evs = [("loop", 0, 10), ("a", 1, 3), ("b", 3, 6), ("c", 4, 5),
           ("d", 12, 13)]
    assert sorted(self_times(evs)) == [("a", 2), ("b", 2), ("c", 1),
                                       ("d", 1), ("loop", 5)]


def test_union_merges_overlaps():
    assert union([(5, 7), (0, 2), (1, 3), (7, 8)]) == [[0, 3], [5, 8]]


def test_reduce_space():
    red = reduce_space(space(), n_chips=1)
    # busy: [0,30) clipped "before" + [100,400) + [700,800) = 430 ns
    assert red["busy_s"] == pytest.approx(430e-9)
    assert red["window_s"] == pytest.approx(1000e-9)
    assert red["idle_frac"] == [pytest.approx(0.57)]
    ops = dict(red["breakdown"]["device_ops"])
    # self times: the loop's 300 ns less its two 100 ns bodies
    assert ops == pytest.approx({"fusion.1": 200e-9, "while.1": 100e-9,
                                 "fusion.2": 100e-9, "before": 30e-9})
    gaps = red["breakdown"]["idle_gaps"]
    # gaps: [400,700) centred in the episode, [800,1000) in the summary
    # read, [30,100) in the episode; longest first
    assert gaps == [["window.episode", pytest.approx(300e-9)],
                    ["host.summary", pytest.approx(200e-9)],
                    ["window.episode", pytest.approx(70e-9)]]


def test_no_device_plane_is_an_error():
    s = space()
    s.planes = s.planes[:1]
    with pytest.raises(ValueError):
        reduce_space(s, n_chips=1)


def test_recorded_chip_trace():
    """A trace recorded on one TPU v5e by ``record_trace.py``: three
    calls of a 512 x 512 program, each followed by a 10 ms host read."""
    import os

    from chipbench.trace_reduce import reduce_trace

    red = reduce_trace(os.path.join(os.path.dirname(__file__), "data"))
    assert 5e-6 < red["busy_s"] < 2e-5           # three ~3 us fusions
    assert 0.03 < red["window_s"] < 0.05          # the `window` host span
    assert red["idle_frac"][0] > 0.99
    names = [n for n, _ in red["breakdown"]["device_ops"]]
    assert names[0].startswith("%fusion")
    longest = red["breakdown"]["idle_gaps"][:3]
    assert [n for n, _ in longest] == ["host.summary"] * 3
    assert all(s > 0.009 for _, s in longest)
