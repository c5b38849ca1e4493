"""The stage reduction: the protobuf decoder on a recorded chip trace, the
reduction on a hand-built trace, a recorded trace of a program with
stage names, and gaps named by the fleet runner's host spans."""

import os
from types import SimpleNamespace as NS

import pytest

from chipbench import stages, trace_reduce

HERE = os.path.dirname(__file__)
SMALL = os.path.join(HERE, "data", "small.xplane.pb")
# beside data/, not in it: trace_reduce's test reads the newest trace there
STAGES_DIR = os.path.join(HERE, "data_stages")


# -------------------------------------------------------- wire encoding
def _varint(x):
    out = bytearray()
    while True:
        b, x = x & 0x7F, x >> 7
        out.append(b | (0x80 if x else 0))
        if not x:
            return bytes(out)


def _field(n, v):
    """One field: an int as a varint, bytes or str length-delimited."""
    if isinstance(v, int):
        return _varint(n << 3) + _varint(v)
    if isinstance(v, str):
        v = v.encode()
    return _varint(n << 3 | 2) + _varint(len(v)) + v


def _msg(*fields):
    return b"".join(_field(n, v) for n, v in fields)


def _hlo_proto(instrs):
    """HloProto of one module of one computation, from (name, op_name,
    operand names) triples; ids count from 1."""
    ids = {name: k + 1 for k, (name, _, _) in enumerate(instrs)}
    comp = _msg(*[(2, _msg(
        (1, name), (7, _msg((1, "t"), (2, op))), (35, ids[name]),
        (36, b"".join(_varint(ids[o]) for o in operands))))
        for name, op, operands in instrs])
    return _msg((1, _msg((1, "m"), (3, comp))))


def _space(programs):
    """XSpace bytes: a device plane, then the metadata plane holding one
    ``Hlo Proto`` stat per program id."""
    stat_md = _msg((1, 7), (2, _msg((1, 7), (2, "Hlo Proto"))))
    other_md = _msg((1, 3), (2, _msg((1, 3), (2, "other"))))
    ev_md = [_msg((1, pid), (2, _msg(
        (1, pid), (2, f"jit_p({pid})"),
        (5, _msg((1, 3), (4, 1))),
        (5, _msg((1, 7), (6, _hlo_proto(instrs)))))))
        for pid, instrs in programs.items()]
    meta = _msg((2, "/host:metadata"), (5, other_md), (5, stat_md),
                *[(4, e) for e in ev_md])
    dev = _msg((1, 1), (2, "/device:TPU:0"))
    return _msg((1, dev), (1, meta))


# ------------------------------------------------------------ the trace
def ev(name, start, dur):
    return NS(name=name, start_ns=float(start), duration_ns=float(dur))


def op(instr, start, dur):
    return ev(f"%{instr} = f32[8]{{0}} fusion(f32[8]{{0}} %p)", start, dur)


PROGRAMS = {
    11: [("while.1", "jit(a)/while/body/macro.fast/while", []),
         ("fusion.1", "jit(a)/while/body/macro.fast/tick.tail/add", []),
         ("fusion.2", "jit(a)/while/body/macro.fast/mul", []),
         ("fusion.0", "jit(a)/while/body/macro.horizon/reduce_min", [])],
    # reuses instruction names of program 11 with other stages; sort.4
    # and copy.3 were made by the compiler and carry no op_name
    22: [("p.0", "", []),
         ("fusion.1", "jit(b)/macro.event/tick.complete/reduce_sum", []),
         ("fusion.2", "jit(b)/tick.power/dot_general", []),
         ("copy.3", "", ["p.0"]),
         ("sort.4", "", ["fusion.1", "copy.3"]),
         ("fusion.5", "jit(b)/while", ["sort.4"])],
}


def space():
    host = NS(name="/host:CPU", lines=[NS(name="python", events=[
        ev("window", 0, 1000), ev("window.segment", 0, 900),
        ev("host.fleet.call", 420, 170)])])
    ops = [op("fusion.0", -50, 80),                    # clipped to 30
           op("while.1", 100, 300), op("fusion.1", 100, 100),
           op("fusion.2", 250, 100),
           op("fusion.1", 600, 100), op("copy.3", 800, 20),
           op("sort.4", 820, 30),
           op("fusion.2", 950, 150),                   # clipped to 50
           op("fusion.9", 2000, 10)]                   # outside the window
    dev = NS(name="/device:TPU:0", lines=[
        NS(name="XLA Modules", events=[ev("jit_a(11)", -100, 600),
                                       ev("jit_b(22)", 600, 1500)]),
        NS(name="XLA Ops", events=ops)])
    return NS(planes=[host, dev])


# ---------------------------------------------------------------- tests
def test_decoder_reads_recorded_chip_trace():
    with open(SMALL, "rb") as f:
        names = stages.hlo_modules(f.read())
    assert list(names) == [3651694296383631776]
    (comp,) = [c for c in names[3651694296383631776]
               if any(n == "fusion" for n, _, _, _ in c)]
    ops = {name: op for name, op, _, _ in comp}
    assert ops["fusion"] == "jit(<lambda>)/dot_general"


def test_decoder_on_hand_built_space():
    modules = stages.hlo_modules(_space(PROGRAMS))
    assert sorted(modules) == [11, 22]
    for pid, instrs in PROGRAMS.items():
        (comp,) = modules[pid]
        ids = {name: k + 1 for k, (name, _, _) in enumerate(instrs)}
        assert comp == [(name, op, ids[name], [ids[o] for o in operands])
                        for name, op, operands in instrs]
    assert stages.hlo_modules(_msg((1, _msg((2, "/device:TPU:0"))))) == {}


def test_module_stages_infers_compiler_made_instructions():
    comp = [("p.0", "", 1, []),
            ("fusion.5", "jit(a)/macro.count_matrix/select_n", 2, [1]),
            ("convert.1", "jit(a)/macro.event/tick.power/convert", 3, [1]),
            ("sort.1", "", 4, [2, 3]),            # a tie: the first's
            ("copy.2", "", 5, [1]),               # reads none: its user's
            ("fusion.7", "", 6, [4, 5, 3]),       # 2 count_matrix, 1 power
            ("fusion.8", "", 7, [1]),             # nothing to go by
            ("add.9", "jit(a)/while/add", 8, [6])]
    assert stages.module_stages([comp]) == {
        "p.0": ("macro.count_matrix", True),      # from its users
        "fusion.5": ("macro.count_matrix", False),
        "convert.1": ("macro.event/tick.power", False),
        "sort.1": ("macro.count_matrix", True),
        "copy.2": ("macro.count_matrix", True),
        "fusion.7": ("macro.count_matrix", True),
        "fusion.8": (None, False),
        "add.9": (None, False)}


@pytest.mark.parametrize("op_name,key", [
    ("jit(f)/while/body/macro.fast/tick.tail/add", "macro.fast/tick.tail"),
    ("jit(_fleet)/vmap()/while/body/macro.event/tick.dispatch/while/body/"
     "closed_call/jit(searchsorted)/while", "macro.event/tick.dispatch"),
    ("jit(f)/while/body/tick.telemetry/add", "tick.telemetry"),
    ("jit(f)/macro.event/tick.power/broadcast_in_dim;"
     "jit(f)/macro.event/tick.power/reshape", "macro.event/tick.power"),
    ("jit(f)/while/add;jit(f)/macro.fast/mul", "macro.fast"),
    ("jit(f)/ticks.x/macro/add", None),
    ("", None),
])
def test_stage_of(op_name, key):
    assert stages.stage_of(op_name) == key


def test_reduce_space():
    red = stages.reduce_space(space(), stages.hlo_modules(_space(PROGRAMS)))
    assert red["window_s"] == pytest.approx(1000e-9)
    # busy: [0,30) + [100,400) + [600,700) + [800,850) + [950,1000)
    assert red["busy_s"] == pytest.approx(530e-9)
    assert red["stages"] == pytest.approx({
        "macro.fast": 200e-9,         # the loop's 300 ns less its body's
        "macro.fast/tick.tail": 100e-9,
        "macro.event/tick.complete": 150e-9,   # with sort.4 and copy.3
        "macro.horizon": 30e-9,
        "tick.power": 50e-9})
    assert red["inferred"] == pytest.approx(
        {"macro.event/tick.complete": 50e-9})
    assert sum(red["stages"].values()) == pytest.approx(red["busy_s"])
    ops = {(k, p, i, g): s for k, p, i, g, s in red["ops"]}
    assert ops[("macro.fast/tick.tail", "jit_a", "fusion.1", False)] == \
        pytest.approx(100e-9)
    assert ops[("macro.event/tick.complete", "jit_b", "fusion.1", False)] \
        == pytest.approx(100e-9)
    assert ops[("macro.event/tick.complete", "jit_b", "sort.4", True)] == \
        pytest.approx(30e-9)


def test_reduce_sums_over_chips():
    s = space()
    second = NS(name="/device:TPU:1", lines=s.planes[1].lines)
    s.planes.append(second)
    red = stages.reduce_space(s, stages.hlo_modules(_space(PROGRAMS)),
                              n_chips=2)
    assert red["busy_s"] == pytest.approx(1060e-9)
    assert red["stages"]["macro.fast"] == pytest.approx(400e-9)


def test_ops_outside_known_programs_are_unscoped():
    red = stages.reduce_space(space(), {})
    assert set(red["stages"]) == {"unscoped/jit_a", "unscoped/jit_b"}
    assert sum(red["stages"].values()) == pytest.approx(530e-9)


def test_gap_inside_fleet_call_is_named_by_it():
    """``trace_reduce`` names an idle gap by the innermost host span at its
    middle: ``host.fleet.call`` inside ``window.segment``."""
    gaps = trace_reduce.reduce_space(space(), n_chips=1)["breakdown"][
        "idle_gaps"]
    # [400, 600) is centred in the call; [700, 800) and [30, 100) only in
    # the segment; [850, 950) in the window
    assert gaps == [["host.fleet.call", pytest.approx(200e-9)],
                    ["window.segment", pytest.approx(100e-9)],
                    ["window", pytest.approx(100e-9)],
                    ["window.segment", pytest.approx(70e-9)]]


def test_recorded_stage_trace():
    """Recorded on one TPU v5e by ``record_stages.py``: three calls of a
    program with a ``macro.event`` op, a ``macro.fast`` loop whose body is
    ``tick.tail`` and an unscoped op, each after 5 ms of
    ``host.fleet.prepare``."""
    red = stages.reduce_trace(STAGES_DIR)
    st = red["stages"]
    assert {"macro.event", "macro.fast", "macro.fast/tick.tail"} <= set(st)
    assert [k for k in st if k.startswith("unscoped/")] == \
        ["unscoped/jit_program"]
    assert sum(st.values()) == pytest.approx(red["busy_s"], rel=5e-3)
    # the loop body's four matmuls outweigh the one before the loop
    assert st["macro.fast/tick.tail"] > 2 * st["macro.event"]
    gaps = trace_reduce.reduce_trace(STAGES_DIR)["breakdown"]["idle_gaps"][:3]
    assert [n for n, _ in gaps] == ["host.fleet.prepare"] * 3
    assert all(s > 0.004 for _, s in gaps)


def test_record_rehearsal(tiny, tmp_path):
    """The recording form on the host CPU (no TPU plane to reduce): one
    window of the tiny sweep, traced under the harness's ``window`` span,
    with the fleet runner's host spans around every segment's call."""
    from jax.profiler import ProfileData

    from repro.utils import invariants
    from tiny_cells import TINY_SWEEP

    out = str(tmp_path / "rec")
    counters = stages.record(TINY_SWEEP, 3100000007, 0.5, out,
                             require_tpu=False)
    assert counters["calls"] >= 1 and counters["replica_ticks"] > 0
    space = ProfileData.from_file(
        trace_reduce.newest_xplane(os.path.join(out, "trace")))
    names = [n for n, _, _ in trace_reduce._harness_spans(space)]
    assert names.count("window") == 1
    assert names.count("host.fleet.prepare") == counters["calls"]
    assert names.count("host.fleet.call") == counters["calls"]
    assert names.count("host.fleet.audit") == \
        (counters["calls"] if invariants.enabled() else 0)
    assert not os.path.exists(os.path.join(out, "inputs"))
