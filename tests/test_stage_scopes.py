"""Stage names in the compiled twin.

Every stage of the tick and of the macro step is traced under a
``jax.named_scope`` (``tick.*``, ``macro.*``), and a profiler trace of
the compiled program attributes device time to a stage by each
instruction's ``op_name`` (``chipbench/stages.py``). These tests compile
the episode and fleet programs on the CPU and check, in the optimised
HLO, that every stage the configuration enables is named, and that every
instruction the program's loops run belongs to a stage, save the loops'
own counters and conditions.
"""

import re

import jax
import jax.numpy as jnp
import pytest

from repro.configs.sim import tiny_cluster, tx_gaia
from repro.core import build_statics, init_state, load_jobs, run_episode
from repro.core.fleet import _fleet, policy_scenario_grid
from repro.core.placement import policy_grid
from repro.core.power import use_dense_scatter
from repro.data import synth_workload
from repro.scenarios import sample_scenarios

TICK = {"tick.complete", "tick.dispatch", "tick.power", "tick.load",
        "tick.tail", "tick.telemetry"}
TICK_ALL = TICK | {"tick.faults", "tick.serving", "tick.thermal"}
MACRO = {"macro.event", "macro.horizon", "macro.fast"}
MACRO_CHUNKED = MACRO | {"macro.count_matrix", "macro.power_chunk"}
STAGE = re.compile(r"^(tick|macro)\.\w+$")

HEADER = re.compile(r"^(?:ENTRY )?%?([\w.\-]+) \(.*\{$")
INSTR = re.compile(r"^\s*(?:ROOT )?%?([\w.\-]+) = ")
OP_NAME = re.compile(r'op_name="([^"]*)"')
CALLED = re.compile(r"\b(calls|to_apply|body|condition|true_computation|"
                    r"false_computation)=%?([\w.\-]+)")
BRANCHES = re.compile(r"branch_computations=\{([^}]*)\}")


def _shape_and_opcode(rest):
    """(shape, opcode) of an instruction's text after ``name = ``."""
    if rest.startswith("("):             # tuple shape
        depth = 0
        for k, ch in enumerate(rest):
            depth += (ch == "(") - (ch == ")")
            if depth == 0:
                break
        shape = rest[:k + 1]
    else:
        shape = rest.split(" ", 1)[0]
    m = re.match(r"\s*([\w\-]+)\(", rest[len(shape):])
    return shape, m.group(1) if m else ""


def parse_hlo(text):
    """Computation name -> list of its instructions, each a dict of
    ``shape``, ``opcode``, ``op_name`` (None without metadata) and the
    computations it calls, by attribute."""
    comps, cur = {}, None
    for line in text.splitlines():
        if cur is None:
            m = HEADER.match(line)
            if m:
                cur = comps.setdefault(m.group(1), [])
            continue
        if line.strip() == "}":
            cur = None
            continue
        m = INSTR.match(line)
        if not m:
            continue
        rest = line[m.end():]
        shape, opcode = _shape_and_opcode(rest)
        on = OP_NAME.search(rest)
        calls = CALLED.findall(rest)
        for b in BRANCHES.findall(rest):
            calls += [("branch", c.strip().lstrip("%"))
                      for c in b.split(",")]
        cur.append({"shape": shape, "opcode": opcode,
                    "op_name": on.group(1) if on else None, "calls": calls})
    return comps


def stages_named(comps):
    return {p for ins in comps.values() for i in ins if i["op_name"]
            for p in i["op_name"].split("/") if STAGE.match(p)}


def loop_instructions(comps):
    """Instructions of every ``while`` body and of what the bodies call,
    except conditions (a loop's own) and reducers (``to_apply``: their
    instructions carry the reduction's name alone)."""
    conds, bodies = set(), set()
    for ins in comps.values():
        for i in ins:
            for attr, c in i["calls"]:
                if attr == "condition":
                    conds.add(c)
                elif attr == "body":
                    bodies.add(c)
    seen, todo = set(), list(bodies)
    while todo:
        c = todo.pop()
        if c in seen or c in conds:
            continue
        seen.add(c)
        todo += [callee for attr, callee in
                 (x for i in comps[c] for x in i["calls"])
                 if attr != "to_apply"]
    return [i for c in seen for i in comps[c]]


def loop_machinery(i):
    """A loop's own counter, condition, lane mask or constants: scalar or
    per-lane integer and predicate arithmetic, the condition a vmapped
    loop re-evaluates in its body (``body_pred``), the select by which it
    holds finished lanes (named by the ``while`` itself), and the
    constants ``scan`` hands its body (``closed_call``)."""
    name = i["op_name"]
    return (re.match(r"(s32|pred)\[\d*\]", i["shape"]) is not None
            or "/body_pred/" in name
            or name.endswith(("/while", "/closed_call")))


def unscoped(comps):
    """Loop instructions from the program's name stack (an ``op_name``
    path) that carry no stage name and are no loop machinery."""
    return [i for i in loop_instructions(comps)
            if i["op_name"] and "/" in i["op_name"]
            and not any(STAGE.match(p) for p in i["op_name"].split("/"))
            and not loop_machinery(i)]


def _inputs(cfg, n_jobs=24):
    jobs, bank = synth_workload(cfg, n_jobs, 600.0, seed=0)
    statics = build_statics(cfg, bank)
    state = load_jobs(init_state(cfg, statics, jax.random.key(0)), jobs)
    return statics, state


def _episode_hlo(cfg, macro):
    statics, state = _inputs(cfg)
    f = jax.jit(lambda st, s: run_episode(cfg, st, s, 120, "fcfs",
                                          summary_only=not macro,
                                          macro=macro))
    return f.lower(statics, state).compile().as_text()


FULL_STACK = dict(thermal_enabled=True, node_mtbf_hours=2.0,
                  serving_enabled=True, serving_nodes=4)
SMALL = tiny_cluster(**FULL_STACK)
LARGE = tx_gaia(max_jobs=64, max_nodes_per_job=4)


def test_configs_straddle_the_dense_scatter_split():
    for cfg, dense in ((SMALL, True), (LARGE, False)):
        slots = cfg.max_jobs * cfg.max_nodes_per_job
        assert use_dense_scatter(slots, cfg.n_nodes) is dense


@pytest.mark.parametrize("cfg,macro,want", [
    (SMALL, False, TICK_ALL),
    (SMALL, True, TICK_ALL | MACRO),
    (LARGE, False, TICK),
    (LARGE, True, TICK | MACRO_CHUNKED),
], ids=["small-tick", "small-macro", "large-tick", "large-macro"])
def test_episode_stages(cfg, macro, want):
    comps = parse_hlo(_episode_hlo(cfg, macro))
    assert stages_named(comps) == want
    assert loop_instructions(comps)
    assert unscoped(comps) == []


def test_fleet_policy_grid_stages():
    """Scopes survive ``vmap`` over replicas and ``lax.switch`` over the
    policy grid."""
    cfg = tiny_cluster()
    statics, state = _inputs(cfg)
    _, pols = policy_grid(["fcfs", "sjf"], ["first_fit"])
    pols, scns = policy_scenario_grid(pols, sample_scenarios(cfg, 1, seed=1))
    keys = jax.random.split(state.key, 2)
    batched = jax.tree.map(lambda a: jnp.broadcast_to(a, (2,) + a.shape),
                           state)
    kw = (("macro", True), ("summary_only", True))
    text = _fleet.lower(cfg, statics, scns, pols, batched, keys, 120,
                        "fcfs", kw).compile().as_text()
    comps = parse_hlo(text)
    assert stages_named(comps) == TICK | MACRO
    assert unscoped(comps) == []
