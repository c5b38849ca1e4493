"""Reduce a profiler trace (``.xplane.pb``) to device seconds per stage of
the twin.

The twin names its stages with ``jax.named_scope``: ``tick.*`` inside a
tick, ``macro.*`` inside the macro step (``repro.core.sim``). The names
reach the compiled program as each instruction's ``op_name``, which the
trace keeps in one ``Hlo Proto`` per program on its ``/host:metadata``
plane. Each op on the ``XLA Ops`` line of a ``/device:TPU:<n>`` plane is
looked up in the program of the ``XLA Modules`` event around it, by the
instruction name its event starts with (``%fusion.178 = ...``), and
counted under the key made of the stage names in its ``op_name``,
outermost first, joined by ``/`` (``macro.fast/tick.tail``). An op the
compiler made carries no ``op_name`` (the TPU compiler rewrites a scatter
into a sort and a segmented-sum fusion that way); it takes the stage of
the instructions around it in the program (``module_stages``), and the
table says how much time was attributed so (``inferred``). An op with no
stage counts under ``unscoped/<program name>``.

Times are self times (an op's length less that of the ops nested in it,
as a loop's body is in the loop), clipped to the harness's ``window``
span as ``trace_reduce`` clips them, and summed over chips; the stages
add up to the chips' busy time.

    python -m chipbench.stages <trace dir> [--chips n]   # the table as JSON
    python -m chipbench.stages --workload <cell> --seed <n> --seconds <s>

The second form records one window of a cell through the cell's own
driver, as ``chipbench/run.py --trace 1`` does, into a directory it keeps
(``--out``), and prints the table with each stage's device microseconds
per replica-tick. It needs the chips the cell asks for.

The protobuf wire format is decoded here, for the few fields the lookup
needs, so the reduction needs nothing beyond JAX.
"""

from __future__ import annotations

import argparse
import bisect
import json
import os
import re
import sys

from chipbench.trace_reduce import (
    DEVICE,
    OPS_LINE,
    _harness_spans,
    newest_xplane,
    self_times,
    union,
)

MODULES_LINE = "XLA Modules"
METADATA_PLANE = "/host:metadata"
HLO_STAT = "Hlo Proto"
STAGE = re.compile(r"^(tick|macro)\.\w+$")
PROGRAM_ID = re.compile(r"^(.*)\((\d+)\)$")
TOP = 20


# ----------------------------------------------------------- wire format
def _varint(buf, i: int) -> tuple:
    x = shift = 0
    while True:
        b = buf[i]
        i += 1
        x |= (b & 0x7F) << shift
        if b < 0x80:
            return x, i
        shift += 7


def _fields(buf):
    """(field number, value) of each field of one message: an int for a
    varint, a memoryview for a length-delimited or fixed-width field."""
    i, n = 0, len(buf)
    while i < n:
        key, i = _varint(buf, i)
        field, wire = key >> 3, key & 7
        if wire == 0:
            v, i = _varint(buf, i)
        elif wire == 2:
            size, i = _varint(buf, i)
            v, i = buf[i:i + size], i + size
        elif wire in (1, 5):
            size = 8 if wire == 1 else 4
            v, i = buf[i:i + size], i + size
        else:
            raise ValueError(f"unsupported protobuf wire type {wire}")
        yield field, v


def _text(v) -> str:
    return bytes(v).decode("utf-8", "replace")


def _ints(v) -> list:
    """A repeated integer field's value: one varint, or a packed run."""
    if isinstance(v, int):
        return [v]
    out, i = [], 0
    while i < len(v):
        x, i = _varint(v, i)
        out.append(x)
    return out


def _computations(hlo_proto) -> list:
    """The computations of one serialized ``HloProto`` (``hlo_module`` 1;
    module ``computations`` 3; computation ``instructions`` 2), each a
    list of instructions (``name`` 1, ``metadata`` 7 with ``op_name`` 2,
    ``id`` 35, ``operand_ids`` 36) as (name, op_name, id, operand ids)."""
    comps = []
    for f, module in _fields(hlo_proto):
        if f != 1:
            continue
        for f2, comp in _fields(module):
            if f2 != 3:
                continue
            instrs = []
            for f3, instr in _fields(comp):
                if f3 != 2:
                    continue
                name = op_name = ""
                iid, operands = None, []
                for f4, v in _fields(instr):
                    if f4 == 1:
                        name = _text(v)
                    elif f4 == 7:
                        op_name = next((_text(m) for f5, m in _fields(v)
                                        if f5 == 2), "")
                    elif f4 == 35:
                        iid = v
                    elif f4 == 36:
                        operands += _ints(v)
                instrs.append((name, op_name, iid, operands))
            comps.append(instrs)
    return comps


def hlo_modules(raw) -> dict:
    """Program id -> its computations (``_computations``), from the ``Hlo
    Proto`` stats of an ``XSpace``'s ``/host:metadata`` plane (``XSpace``
    ``planes`` 1; ``XPlane`` ``name`` 2, ``event_metadata`` 4 and
    ``stat_metadata`` 5, maps of key 1 to value 2; ``XEventMetadata``
    ``id`` 1, ``stats`` 5; ``XStat`` ``metadata_id`` 1, ``bytes_value`` 6;
    ``XStatMetadata`` ``id`` 1, ``name`` 2)."""
    raw = memoryview(raw)
    for f, plane in _fields(raw):
        if f != 1:
            continue
        fields = list(_fields(plane))
        if next((_text(v) for g, v in fields if g == 2), "") \
                != METADATA_PLANE:
            continue
        hlo_stat = None
        for g, entry in fields:
            if g == 5:
                sm = dict(_fields(dict(_fields(entry))[2]))
                if _text(sm.get(2, b"")) == HLO_STAT:
                    hlo_stat = sm[1]
        out = {}
        for g, entry in fields:
            if g != 4:
                continue
            em = list(_fields(dict(_fields(entry))[2]))
            pid = next(v for h, v in em if h == 1)
            for h, stat in em:
                st = dict(_fields(stat)) if h == 5 else {}
                if st.get(1) == hlo_stat and 6 in st:
                    out[pid] = _computations(st[6])
        return out
    return {}


# ------------------------------------------------------------ reduction
def stage_of(op_name: str) -> str | None:
    """The stage key of an ``op_name``: its ``tick.*`` and ``macro.*``
    components joined by ``/``, or None. An instruction the compiler
    merged from several carries their names joined by ``;``: the first
    with a stage gives the key."""
    for name in op_name.split(";"):
        stages = [p for p in name.split("/") if STAGE.match(p)]
        if stages:
            return "/".join(stages)
    return None


def _post_order(comp) -> list:
    """Instruction ids of one computation, each after its operands."""
    operands = {iid: ops for _, _, iid, ops in comp}
    seen, order = set(), []
    for _, _, root, _ in comp:
        stack = [(root, False)]
        while stack:
            k, done = stack.pop()
            if done:
                order.append(k)
            elif k not in seen and k in operands:
                seen.add(k)
                stack.append((k, True))
                stack.extend((o, False) for o in reversed(operands[k]))
    return order


def _vote(stage: dict, ids) -> str | None:
    """The stage most common among ``ids``; a tie goes to the first."""
    votes = {}
    for k in ids:
        if stage.get(k):
            votes[stage[k]] = votes.get(stage[k], 0) + 1
    return max(votes, key=votes.get) if votes else None


def module_stages(module) -> dict:
    """Instruction name -> (stage key or None, inferred) of one program.

    An instruction's stage is the one its ``op_name`` names. One with no
    ``op_name`` at all was made by the compiler: the TPU compiler rewrites
    a scatter into a sort and a segmented-sum fusion, and adds layout
    copies, without metadata. Such an instruction takes the stage most
    common among the instructions it reads (those inferred so before it
    included); where they name none, the stage most common among the
    instructions that read it. Either way it is marked inferred."""
    out = {}
    for comp in module:
        stage = {iid: stage_of(op) for _, op, iid, _ in comp}
        made = {iid: ops for _, op, iid, ops in comp if not op}
        users = {}
        for _, _, iid, ops in comp:
            for o in ops:
                users.setdefault(o, []).append(iid)
        order = [k for k in _post_order(comp) if k in made]
        for k in order:
            stage[k] = _vote(stage, made[k])
        for k in reversed(order):
            stage[k] = stage[k] or _vote(stage, users.get(k, ()))
        for name, _, iid, _ in comp:
            out[name] = (stage[iid], iid in made and stage[iid] is not None)
    return out


def _instruction(event_name: str) -> str:
    """``%fusion.178 = f32[3,928]... fusion(...)`` -> ``fusion.178``."""
    head = event_name.split(" =", 1)[0]
    return head[1:] if head.startswith("%") else head


def _chip_ops(plane, stages: dict) -> list:
    """((stage key, program name, instruction, inferred), start, end) of
    every op on one device plane; ``stages`` maps a program id to its
    ``module_stages``."""
    modules = []
    for line in plane.lines:
        if line.name == MODULES_LINE:
            for ev in line.events:
                m = PROGRAM_ID.match(ev.name)
                name, pid = (m.group(1), int(m.group(2))) if m \
                    else (ev.name, None)
                modules.append((ev.start_ns, ev.start_ns + ev.duration_ns,
                                name, pid))
    modules.sort()
    starts = [m[0] for m in modules]
    ops = []
    for line in plane.lines:
        if line.name != OPS_LINE:
            continue
        for ev in line.events:
            s, e = ev.start_ns, ev.start_ns + ev.duration_ns
            k = bisect.bisect_right(starts, s) - 1
            if k >= 0 and s < modules[k][1]:
                _, _, prog, pid = modules[k]
            else:
                prog, pid = "(no module)", None
            instr = _instruction(ev.name)
            key, inferred = stages.get(pid, {}).get(instr, (None, False))
            ops.append(((key or "unscoped/" + prog, prog, instr, inferred),
                        s, e))
    return ops


def reduce_space(space, modules: dict, n_chips: int = 1) -> dict:
    """Device seconds per stage key over the first ``n_chips`` chips:
    ``stages`` (key -> seconds, summed over chips, largest first),
    ``inferred`` (the part of each key's seconds spent in instructions
    without an ``op_name``, whose stage ``module_stages`` inferred),
    ``busy_s`` (the union of op intervals, summed over chips),
    ``window_s`` and ``ops`` (the largest ops: key, program, instruction,
    inferred, seconds). ``modules`` is ``hlo_modules`` of the same
    trace."""
    planes = {int(m.group(1)): p for p in space.planes
              if (m := DEVICE.match(p.name))}
    if not planes:
        raise ValueError("trace has no TPU device plane")
    chips = sorted(planes)[:n_chips]
    stages_of = {pid: module_stages(m) for pid, m in modules.items()}
    per_chip = {c: _chip_ops(planes[c], stages_of) for c in chips}
    win = [s for s in _harness_spans(space) if s[0] == "window"]
    if win:
        w0, w1 = win[0][1], win[0][2]
    else:
        w0 = min(s for c in chips for _, s, _ in per_chip[c])
        w1 = max(e for c in chips for _, _, e in per_chip[c])
    per_op, busy = {}, 0.0
    for c in chips:
        clipped = [(n, max(s, w0), min(e, w1)) for n, s, e in per_chip[c]
                   if e > w0 and s < w1]
        for op, t in self_times(clipped):
            per_op[op] = per_op.get(op, 0.0) + t * 1e-9
        busy += sum(e - s for s, e in union((s, e) for _, s, e in clipped)
                    ) * 1e-9
    stages, inferred = {}, {}
    for (key, _, _, guessed), t in per_op.items():
        stages[key] = stages.get(key, 0.0) + t
        if guessed:
            inferred[key] = inferred.get(key, 0.0) + t
    ops = sorted(per_op.items(), key=lambda x: -x[1])[:TOP]

    def largest_first(d):
        return dict(sorted(d.items(), key=lambda x: -x[1]))

    return {"busy_s": busy, "window_s": (w1 - w0) * 1e-9,
            "stages": largest_first(stages),
            "inferred": largest_first(inferred),
            "ops": [[*op, s] for op, s in ops]}


def reduce_trace(trace_dir: str, n_chips: int = 1) -> dict:
    """``reduce_space`` of the newest ``.xplane.pb`` under ``trace_dir``."""
    from jax.profiler import ProfileData

    path = newest_xplane(trace_dir)
    with open(path, "rb") as f:
        raw = f.read()
    return reduce_space(ProfileData.from_serialized_xspace(raw),
                        hlo_modules(raw), n_chips)


# ------------------------------------------------------------ recording
def record(name: str, seed: int, seconds: float, out: str,
           require_tpu: bool = True) -> dict:
    """One traced window of cell ``name`` through its own driver, set up
    and warmed as ``chipbench/run.py`` does, recorded under ``out``.
    Returns the driver's counters for the window."""
    import gc
    import importlib
    import shutil

    import jax

    from chipbench import run

    spec = run.load_cell(name)
    jax.config.update("jax_compilation_cache_dir",
                      os.path.join(run.CACHE, "jax"))
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    chips = spec["cell"]["chips"]
    if require_tpu:
        run.chips_or_exit(chips)
    driver = importlib.import_module(
        "chipbench.drivers." + spec["mix"]["driver"]).DRIVER
    shutil.rmtree(out, ignore_errors=True)
    os.makedirs(out)
    drv = driver(spec["config"]["sim"], spec["mix"], seed, chips,
                 os.path.join(out, "inputs"))
    drv.warm()
    gc.collect()
    gc.freeze()
    gc.disable()
    try:
        jax.profiler.start_trace(os.path.join(out, "trace"))
        with jax.profiler.TraceAnnotation("window"):
            drv.window(min(seconds, run.TRACE_S))
        jax.profiler.stop_trace()
    finally:
        gc.enable()
    counters = dict(drv.counters)
    drv.release()
    shutil.rmtree(os.path.join(out, "inputs"), ignore_errors=True)
    return counters


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("trace_dir", nargs="?",
                    help="a directory holding a recorded .xplane.pb")
    ap.add_argument("--chips", type=int, default=1,
                    help="chips to reduce over (first form)")
    ap.add_argument("--workload", help="record one window of this cell")
    ap.add_argument("--seed", type=int)
    ap.add_argument("--seconds", type=float, default=10.0)
    ap.add_argument("--out", help="where the recording is kept (default "
                    ".chipbench_cache/stages/<cell>.<seed>)")
    args = ap.parse_args(argv)
    if (args.trace_dir is None) == (args.workload is None):
        ap.error("give a trace directory or --workload, not both")
    if args.trace_dir is not None:
        print(json.dumps(reduce_trace(args.trace_dir, args.chips), indent=1))
        return 0
    if args.seed is None:
        ap.error("--workload needs --seed")
    from chipbench import run

    out = args.out or os.path.join(run.CACHE, "stages",
                                   f"{args.workload}.{args.seed}")
    counters = record(args.workload, args.seed, args.seconds, out)
    chips = run.load_cell(args.workload)["cell"]["chips"]
    table = reduce_trace(os.path.join(out, "trace"), chips)
    ticks = counters["replica_ticks"]
    table.update(workload=args.workload, seed=args.seed, trace_dir=out,
                 counters=counters,
                 us_per_replica_tick={k: s * 1e6 / ticks
                                      for k, s in table["stages"].items()})
    print(json.dumps(table, indent=1))
    return 0


if __name__ == "__main__":
    sys.exit(main())
