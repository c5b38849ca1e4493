"""Compile the twin's kernels and TX-GAIA step for a described v5e chip.

Nothing runs: ``get_topology_desc`` describes a TPU v5e that is not
attached, and XLA's TPU compiler (with Mosaic for the Pallas kernels)
compiles for it here. That catches what interpret mode hides — block
shapes Mosaic cannot tile, more VMEM than a kernel may use, a program
larger than the chip's 16 GiB HBM — without a chip.

The topology is described inside a fixture, never at import: only one
process at a time may load the TPU library, and every test worker
imports this file.
"""

import math
import os
import re

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.configs.sim import tiny_cluster, tx_gaia
from repro.core import build_statics, init_state, make_macro_step, make_step
from repro.core import power, sim
from repro.core.placement import make_policy
from repro.core.sim import CHUNK_TICKS, telem_zero
from repro.kernels.node_power import node_power_pallas, power_scatter_pallas
from repro.kernels.rack_thermal import rack_thermal_pallas
from repro.utils.hlo import tpu_kernel_calls

V5E_HBM_BYTES = 16 * 2**30
SLOTS = 512 * 64                    # tx_gaia(): max_jobs x max_nodes_per_job
RECT = dict(rect_peak=0.965, rect_load=0.55, rect_curv=0.12, conv_eff=0.975)


@pytest.fixture(scope="module")
def one_chip():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache

    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    # a compile for a described chip is written to a persistent cache but
    # cannot be read back without one: keep the cache out of it
    was_on = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 - any failure means: cannot describe
        jax.config.update("jax_enable_compilation_cache", was_on)
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", was_on)


def _sds(tree, sharding):
    return jax.tree.map(
        lambda a: jax.ShapeDtypeStruct(jnp.shape(a), a.dtype,
                                       sharding=sharding), tree)


def _compile(fn, *args):
    compiled = jax.jit(fn).lower(*args).compile()
    m = compiled.memory_analysis()
    used = (m.argument_size_in_bytes + m.output_size_in_bytes
            + m.temp_size_in_bytes + m.generated_code_size_in_bytes)
    assert used < V5E_HBM_BYTES, f"{used} bytes do not fit a v5e"
    return compiled


INSTR = re.compile(r"^\s*(?:ROOT )?%?([\w.\-]+) = ")
ARRAY = re.compile(r"\w+\[([\d,]*)\]")


def _elems(shape):
    """Elements of the largest array in an HLO shape (tuples included)."""
    return max((math.prod(int(d) for d in dims.split(",") if d)
                for dims in ARRAY.findall(shape)), default=0)


def _instructions(text):
    """(name, shape, opcode, operand names) of each HLO instruction."""
    for line in text.splitlines():
        m = INSTR.match(line)
        if not m:
            continue
        rest, depth, end = line[m.end():], 0, 0
        if rest.startswith("("):          # a tuple shape nests parentheses
            for end, ch in enumerate(rest, 1):
                depth += (ch == "(") - (ch == ")")
                if depth == 0:
                    break
        else:
            end = rest.find(" ")
        shape = rest[:end]
        op = re.match(r"\s*([\w\-]+)\(([^)]*)\)", rest[end:])
        if op:
            yield (m.group(1), shape, op.group(1),
                   [a.strip().lstrip("%") for a in op.group(2).split(",")])


def slot_reductions(text, n_slots):
    """Scatters and sorts of the optimised HLO ``text`` that touch
    ``n_slots`` or more elements: the job-table -> node scatter-adds, and
    the sorts a TPU compiler rewrites them into."""
    ops = list(_instructions(text))
    shapes = {name: shape for name, shape, _, _ in ops}
    found = []
    for name, _, opcode, operands in ops:
        if opcode not in ("scatter", "sort"):
            continue
        sizes = [_elems(shapes[name])] + [_elems(shapes.get(a, ""))
                                          for a in operands]
        if max(sizes) >= n_slots:
            found.append(f"{opcode} {name}")
    return found


def large_gathers(text, n_elems):
    """Gathers of the optimised HLO ``text`` whose output has ``n_elems``
    or more elements: a small table read through every job slot, which a
    TPU runs element by element."""
    return [f"gather {name}" for name, shape, opcode, _ in _instructions(text)
            if opcode == "gather" and _elems(shape) >= n_elems]


def _policy_step_text(sharding=None):
    """Optimised HLO of the TX-GAIA policy-mode step (a traced
    ``Policy``, EASY's backfill among the switch's branches), compiled for
    ``sharding``'s device or, without one, for the CPU."""
    cfg = tx_gaia()
    statics = build_statics(cfg)
    state = init_state(cfg, statics, jax.random.key(0))
    args = (statics, state, make_policy("easy", "first_fit"))

    def step(st, s, p):
        return make_step(cfg, st, p)(s, jnp.int32(-1))

    if sharding is None:
        return jax.jit(step).lower(*args).compile().as_text()
    return _compile(step, *(_sds(a, sharding) for a in args)).as_text()


DISPATCH_COUNTS = re.compile(r'tick\.dispatch/[^"]*tick\.node_counts')


def test_power_scatter_compiles_at_tx_gaia_width(one_chip):
    jk, n = 512 * 64, 672           # tx_gaia(): max_jobs x max_nodes_per_job
    f = lambda *a: power_scatter_pallas(*a, **RECT, interpret=False)
    args = ([jax.ShapeDtypeStruct((jk,), jnp.int32, sharding=one_chip)]
            + [jax.ShapeDtypeStruct((jk,), jnp.float32, sharding=one_chip)] * 2
            + [jax.ShapeDtypeStruct((n,), jnp.float32, sharding=one_chip)] * 7)
    c = _compile(f, *args)
    assert tpu_kernel_calls(c.as_text()) == {"power_scatter": 1}


def test_rack_thermal_compiles_at_tx_gaia_width(one_chip):
    n, r = 672, 21
    f = lambda *a: rack_thermal_pallas(*a, alpha=0.01, interpret=False)
    S = lambda shape, dt=jnp.float32: jax.ShapeDtypeStruct(
        shape, dt, sharding=one_chip)
    c = _compile(f, S((n,)), S((n,), jnp.int32), S((r,)), S(()), S((r,)))
    assert tpu_kernel_calls(c.as_text()) == {"rack_thermal": 1}


def test_node_power_compiles_for_a_64_env_batch(one_chip):
    e, n = 64, 672
    f = lambda *a: node_power_pallas(*a, **RECT, interpret=False)
    S = lambda shape: jax.ShapeDtypeStruct(shape, jnp.float32,
                                           sharding=one_chip)
    c = _compile(f, S((e, n)), S((e, n)), S((n,)), S((n,)), S((n,)),
                 S((e, n)), S((n,)))
    assert tpu_kernel_calls(c.as_text()) == {"node_power": 1}


@pytest.mark.parametrize("kernels", [False, True], ids=["xla", "kernels"])
def test_tx_gaia_step_compiles(one_chip, kernels, monkeypatch):
    """The per-tick step at ``tx_gaia()`` defaults with thermals on; with
    ``kernels`` the power and thermal reductions go through the compiled
    Pallas kernels (interpret mode follows the default backend, which is
    the CPU here, so the test steers it off)."""
    from repro.kernels import ops

    monkeypatch.setattr(ops, "interpret_mode", lambda: False)
    cfg = tx_gaia(thermal_enabled=True)
    statics = build_statics(cfg)
    state = init_state(cfg, statics, jax.random.key(0))

    def step(st, s):
        return make_step(cfg, st, "fcfs", use_power_kernel=kernels,
                         use_thermal_kernel=kernels)(s, jnp.int32(-1))

    c = _compile(step, _sds(statics, one_chip), _sds(state, one_chip))
    want = {"power_scatter": 1, "rack_thermal": 1} if kernels else {}
    assert tpu_kernel_calls(c.as_text()) == want
    # release and loads take the node-count matrix on the chip
    assert slot_reductions(c.as_text(), SLOTS) == []
    assert "tick.node_counts" in c.as_text()


def test_tx_gaia_step_keeps_the_scatter_on_cpu():
    """The same step lowered for the CPU keeps the slot scatter-adds
    (cheap there), which ``slot_reductions`` finds."""
    cfg = tx_gaia()
    statics = build_statics(cfg)
    state = init_state(cfg, statics, jax.random.key(0))
    text = jax.jit(lambda st, s: make_step(cfg, st, "fcfs")(
        s, jnp.int32(-1))).lower(statics, state).compile().as_text()
    assert len(slot_reductions(text, SLOTS)) >= 2
    assert "tick.node_counts" not in text


def test_tx_gaia_policy_step_counts_releases_through_the_matrix(one_chip):
    """On the chip, EASY's count of head-capable releases contracts the
    node-count matrix: no gather through the J*K slots, and the build is
    named under the dispatch."""
    text = _policy_step_text(one_chip)
    assert large_gathers(text, SLOTS) == []
    assert slot_reductions(text, SLOTS) == []
    assert DISPATCH_COUNTS.search(text)


def test_tx_gaia_policy_step_keeps_the_gather_on_cpu():
    """The same step lowered for the CPU keeps the slot gather (cheap
    there), which ``large_gathers`` finds."""
    text = _policy_step_text()
    assert len(large_gathers(text, SLOTS)) >= 1
    assert "tick.node_counts" not in text


def test_tx_gaia_macro_step_compiles(one_chip):
    cfg = tx_gaia()
    statics = build_statics(cfg)
    state = init_state(cfg, statics, jax.random.key(0))
    acc = telem_zero(cfg, statics)

    def macro(st, s, a):
        return make_macro_step(cfg, st, "fcfs")(s, a, 3600)

    c = _compile(macro, _sds(statics, one_chip), _sds(state, one_chip),
                 _sds(acc, one_chip))
    # release, loads and the count matrix: no slot scatter or sort
    assert slot_reductions(c.as_text(), SLOTS) == []
    assert "tick.node_counts" in c.as_text()


def _macro_program(cfg, sharding):
    """(lowered, compiled) TX-GAIA-style macro step of ``cfg`` for
    ``sharding``'s device."""
    statics = build_statics(cfg)
    state = init_state(cfg, statics, jax.random.key(0))
    acc = telem_zero(cfg, statics)

    def macro(st, s, a):
        return make_macro_step(cfg, st, "fcfs")(s, a, 3600)

    lowered = jax.jit(macro).lower(*(_sds(a, sharding)
                                     for a in (statics, state, acc)))
    return lowered, lowered.compile()


STACK_TABLES = ("FileNames", "FunctionNames", "FileLocations", "StackFrames")


def _program(text):
    """Optimised HLO less metadata and the stack-frame tables: what the
    chip runs, whatever source lines it came from."""
    out, table = [], False
    for line in text.splitlines():
        if line in STACK_TABLES or table:
            table = line != ""
            continue
        out.append(re.sub(r", metadata=\{[^}]*\}", "", line))
    return "\n".join(out)


def _stage_gathers(text, stage):
    """Elements of each gather of the optimised HLO ``text`` named under
    ``stage``."""
    return [_elems(shape) for name, shape, opcode, _ in _instructions(text)
            if opcode == "gather" and re.search(
                rf"{name} = .*op_name=\"[^\"]*{re.escape(stage)}/", text)]


def test_tx_gaia_fast_ticks_read_telemetry_by_window(one_chip):
    """The TX-GAIA macro step's fast-tick chunk reads each job's telemetry
    as one (1, M) window of its bank row (M = 3 for 16 ticks of 1 s and
    10 s quanta), not CHUNK_TICKS x max_jobs element gathers. The TPU
    compiler splits each window gather into M gathers of max_jobs
    elements, so the chunk reads at most 2 x M x max_jobs elements."""
    cfg = tx_gaia()
    J = cfg.max_jobs
    M = power.chunk_window(cfg, CHUNK_TICKS, build_statics(cfg).cpu_trace
                           .shape[-1])
    assert M == 3
    lowered, compiled = _macro_program(cfg, one_chip)
    windows = re.findall(rf"= f32\[{J},1,{M}\][^ ]* gather\(.*"
                         rf"slice_sizes=\{{1,{M}\}}",
                         lowered.as_text(dialect="hlo"))
    assert len(windows) == 2                   # cpu and gpu utilization
    text = compiled.as_text()
    assert large_gathers(text, CHUNK_TICKS * J) == []
    chunk = _stage_gathers(text, "macro.power_chunk")
    assert chunk and sum(chunk) <= 2 * M * J


def test_tx_gaia_fast_ticks_per_tick_gathers_are_found(one_chip,
                                                       monkeypatch):
    """With the tick-by-tick gather put back, the same program holds
    gathers of CHUNK_TICKS x max_jobs elements, which ``large_gathers``
    finds."""
    monkeypatch.setattr(sim, "job_utilization_chunk",
                        power._per_tick_utilization)
    cfg = tx_gaia()
    _, compiled = _macro_program(cfg, one_chip)
    assert len(large_gathers(compiled.as_text(),
                             CHUNK_TICKS * cfg.max_jobs)) == 2


def test_shared_path_program_is_the_same_either_way(one_chip, monkeypatch):
    """A test-size macro step takes the shared per-tick power path, which
    reads no chunk: its optimised program is the same whichever form
    reads a chunk's telemetry."""
    cfg = tiny_cluster()
    assert power.use_dense_scatter(cfg.max_jobs * cfg.max_nodes_per_job,
                                   cfg.n_nodes)
    window = _macro_program(cfg, one_chip)[1].as_text()
    assert "macro.power_chunk" not in window
    monkeypatch.setattr(sim, "job_utilization_chunk",
                        power._per_tick_utilization)
    per_tick = _macro_program(cfg, one_chip)[1].as_text()
    assert _program(window) == _program(per_tick)
