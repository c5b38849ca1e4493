"""The harness end to end on the host CPU at tiny size: every driver's
answers agree with the plain reference, and a broken timed path, planted
under the harness, comes out as not correct."""

import json
import os
import subprocess
import sys

import jax
import jax.numpy as jnp
import pytest

from tiny_cells import ROOT, TINY_REPLAY, TINY_SHARD, TINY_SWEEP


def _run(run, cell, hook=None, seed=2**31 + 7):
    out, answers = run.run_cell(cell, seed, 1.0, False, require_tpu=False,
                                driver_hook=hook)
    return out, answers


@pytest.mark.parametrize("cell", [TINY_REPLAY, TINY_SWEEP])
def test_driver_matches_reference(tiny, cell, capsys):
    out, answers = _run(tiny, cell)
    window = json.loads(capsys.readouterr().out.splitlines()[-1])["window"]
    # set-up warmed every program the window drives, restarts included
    assert window["compiles"] == 0, window
    assert out["correct"], out["checks"]
    assert out["checks"]["job_mismatch"]["value"] == 0.0
    assert out["checks"]["accum_rel_err"]["value"] < 1e-5
    assert set(out["metrics"]) == {"sim_s_per_s", "setup_s"}
    assert list(out)[-1] == "checks"
    if cell == TINY_SWEEP:   # one replica from each eighth of the fleet
        reps = {int(a["what"].split()[1]) for a in answers}
        assert sorted(r // 8 for r in reps) == list(range(8))


def test_sharded_sweep_on_four_host_devices(tmp_path):
    """The four-chip sweep's mesh path, on four forced host devices: its
    answers agree with the reference, and a fleet whose last chip's block
    is left unchanged comes out as not correct."""
    script = f"""
import json, sys
sys.path[:0] = [{ROOT!r}, {os.path.join(ROOT, 'src')!r},
                {os.path.join(ROOT, 'chipbench', 'tests')!r}]
import tiny_cells
from chipbench import run
root = tiny_cells.write_tree({str(tmp_path)!r})
run.ROOT, run.BENCH, run.CACHE = root, root + "/chipbench", root + "/.cache"
from test_rehearsal import _break_sweep
out, _ = run.run_cell({TINY_SHARD!r}, 11, 1.0, False, require_tpu=False)
bad, _ = run.run_cell({TINY_SHARD!r}, 12, 1.0, False, require_tpu=False,
                      driver_hook=_break_sweep("chip"))
print(json.dumps([out, bad]))
"""
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=4")
    res = subprocess.run([sys.executable, "-c", script], env=env,
                         capture_output=True, text=True, timeout=600)
    assert res.returncode == 0, res.stderr[-3000:]
    out, bad = json.loads(res.stdout.strip().splitlines()[-1])
    assert out["correct"], out["checks"]
    assert out["device"]["count"] == 4
    # the last chip's block of replicas never comes back from the mesh
    assert bad["correct"] is False and bad["failed"] > 0


def _copy(tree):
    return jax.tree.map(jnp.copy, tree)


def _fleet_of(state, like):
    """``state`` with the replica axis of ``like`` (broadcast if single)."""
    if jnp.ndim(state.t) == jnp.ndim(like.t):
        return state
    r = like.t.shape[0]
    return jax.tree.map(lambda a: jnp.broadcast_to(a, (r,) + jnp.shape(a)),
                        state)


def _break_replay(fault):
    def hook(drv):
        fn = drv.fn

        def broken(statics, s):
            fs, tel = fn(statics, s)
            if fault == "unchanged":
                fs = s
            elif fault == "altered":
                fs = fs._replace(energy_kwh=fs.energy_kwh * 1.001)
            return fs, tel
        drv.fn = broken
    return hook


def _break_sweep(fault):
    def hook(drv):
        seg = drv._segment

        def broken(st):
            keep = _copy(st)
            new, tel = seg(st)
            old = _fleet_of(keep, new)
            if fault == "unchanged":
                new = old
            elif fault in ("half", "chip"):
                r = new.t.shape[0]
                h = r // 2 if fault == "half" else r - r // 4
                new = jax.tree.map(
                    lambda n, o: n.at[h:].set(o[h:]) if n.ndim else n,
                    new, old)
            elif fault == "altered":
                new = new._replace(energy_kwh=new.energy_kwh * 1.001)
            return new, tel
        drv._segment = broken
    return hook


@pytest.mark.parametrize("cell,hook", [
    (TINY_REPLAY, _break_replay("unchanged")),
    (TINY_REPLAY, _break_replay("altered")),
    (TINY_SWEEP, _break_sweep("unchanged")),
    (TINY_SWEEP, _break_sweep("half")),
    (TINY_SWEEP, _break_sweep("altered")),
], ids=["replay-unchanged", "replay-altered", "sweep-unchanged",
        "sweep-half-batch", "sweep-altered"])
def test_broken_timed_path_is_not_correct(tiny, cell, hook):
    out, _ = _run(tiny, cell, hook)
    assert out["correct"] is False
    assert out["failed"] > 0


def test_no_tpu_no_result():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    res = subprocess.run(
        [sys.executable, os.path.join(ROOT, "chipbench", "run.py"),
         "--workload", "replay.txgaia.hour", "--seed", "1", "--seconds", "1",
         "--trace", "0"], env=env, capture_output=True, text=True,
        timeout=300)
    assert res.returncode != 0
    assert res.stdout == ""


def test_benchmark_files_alone_give_no_result(tmp_path):
    """Without the program beside it the benchmark fails, printing nothing."""
    import shutil

    shutil.copy(os.path.join(ROOT, "BENCHMARK.json"), tmp_path)
    shutil.copytree(os.path.join(ROOT, "chipbench"), tmp_path / "chipbench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    res = subprocess.run(
        [sys.executable, "chipbench/run.py", "--workload",
         "replay.txgaia.hour", "--seed", "1", "--seconds", "1", "--trace",
         "0"], cwd=tmp_path, env=dict(os.environ, JAX_PLATFORMS="cpu"),
        capture_output=True, text=True, timeout=300)
    assert res.returncode != 0
    assert res.stdout == ""
