"""chip_smoke.py's phases at tiny size on the CPU backend.

The script itself refuses to run without a TPU; these tests drive the
same phase functions on ``tiny_cluster()`` so a broken path, argument or
comparison shows up here before a chip run. The four-chip phases run in a
subprocess on four forced host devices (the device count locks at jax's
first init).
"""

import json
import os
import subprocess
import sys
import textwrap

import jax
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)

import chip_smoke  # noqa: E402
from repro.configs.sim import tiny_cluster  # noqa: E402


@pytest.fixture(scope="module")
def clock():
    with chip_smoke.CompileClock() as c:
        yield c


def _check_line(line, phase):
    assert line["phase"] == phase
    assert line["compile_s"] > 0.0 and line["steady_s"] > 0.0
    json.dumps(line)                     # the line must be printable JSON
    return line


def test_replay_phase(tmp_path, clock):
    line = _check_line(chip_smoke.phase_replay(
        tiny_cluster(), n_ticks=300, seed=0, workdir=str(tmp_path),
        ref_device=jax.devices("cpu")[0], clock=clock), "replay")
    assert set(line["checks"]) == {"macro_vs_pertick", "chip_vs_cpu"}
    assert line["summary"]["pertick"]["completed"] > 0
    assert "cpu_pertick" in line["runs"]


def test_kernel_phase(tmp_path, clock):
    line = _check_line(chip_smoke.phase_kernels(
        tiny_cluster(), n_ticks=200, seed=1, workdir=str(tmp_path),
        ref_device=jax.devices("cpu")[0], clock=clock), "kernels")
    assert line["checks"]["kernels_vs_xla"]["state"]["ok"]
    assert line["checks"]["xla_vs_cpu"]["state"]["ok"]
    # the CPU backend interprets the kernels: no Mosaic call to look for
    assert "tpu_custom_call" not in line["checks"]


def test_full_stack_phase(clock):
    # the 16-node test cluster's own fault and serving knobs
    line = _check_line(chip_smoke.phase_full_stack(
        tiny_cluster(), n_ticks=1800, seed=1, clock=clock,
        node_mtbf_hours=0.5, rack_mtbf_hours=1.5, serving_nodes=4,
        serving_queue_cap=60.0), "full_stack")
    fired = line["summary"]["fired"]
    assert fired["n_killed"] > 0 and fired["srv_shed"] > 0


def test_fleet_phase(tmp_path, clock):
    line = _check_line(chip_smoke.phase_fleet(
        tiny_cluster(), n_ticks=300, seed=0, workdir=str(tmp_path),
        n_scenarios=2, selects=("fcfs", "sjf"), places=("first_fit",),
        clock=clock), "fleet")
    assert line["summary"]["replicas"] == 4
    assert line["checks"]["replica0_vs_single"]["state"]["ok"]


def test_ppo_phase(clock):
    line = _check_line(chip_smoke.phase_ppo(
        tiny_cluster(sched_max_candidates=4), n_envs=4, rollout_len=4,
        n_iterations=2, n_jobs=16, horizon_s=600.0, seed=0, clock=clock),
        "ppo")
    assert line["summary"]["env_transitions"] == 32


def test_compare_flags_bf16_sized_error():
    """The float check must catch an error of the size a bf16-rounded
    contraction leaves (~1e-4) and accept float32 noise (~1e-7)."""
    import numpy as np

    from repro.core.sim import TelemetrySummary

    base = TelemetrySummary(*[np.float32(100.0)] * len(
        TelemetrySummary._fields))
    near = base._replace(mean_facility_w=np.float32(100.00001))
    far = base._replace(mean_facility_w=np.float32(100.01))
    assert chip_smoke.compare(base, near, "near")["ok"]
    with pytest.raises(AssertionError, match="mean_facility_w"):
        chip_smoke.compare(base, far, "far")


def test_four_chip_phases_on_host_devices():
    code = textwrap.dedent("""
        import jax
        import chip_smoke
        from repro.configs.sim import tiny_cluster

        with chip_smoke.CompileClock() as clock:
            a = chip_smoke.phase_sharded_fleet(
                tiny_cluster(), n_devices=4, replicas=8, n_ticks=400,
                seed=0, clock=clock, node_mtbf_hours=0.5,
                rack_mtbf_hours=1.5)
            b = chip_smoke.phase_distributed_ppo(
                tiny_cluster(sched_max_candidates=4), n_devices=4,
                n_envs=8, rollout_len=4, n_iterations=2, n_jobs=16,
                horizon_s=600.0, seed=0, clock=clock)
        assert a["checks"]["sharded_vs_vmapped_blocks"]["devices"] == 4
        assert a["checks"]["sharded_vs_vmapped"]["state"]["ok"]
        assert b["summary"]["devices"] == 4
        print("FOUR_CHIP_PATH OK")
    """)
    env = dict(os.environ)
    env["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
    env["PYTHONPATH"] = os.pathsep.join([os.path.join(ROOT, "src"), ROOT])
    r = subprocess.run([sys.executable, "-c", code], capture_output=True,
                       text=True, timeout=600, env=env, cwd=ROOT)
    assert r.returncode == 0, f"STDOUT:\n{r.stdout}\nSTDERR:\n{r.stderr}"
    assert "FOUR_CHIP_PATH OK" in r.stdout


def test_script_refuses_without_a_tpu():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    r = subprocess.run([sys.executable, os.path.join(ROOT, "chip_smoke.py")],
                       capture_output=True, text=True, timeout=300, env=env)
    assert r.returncode != 0
    assert '"ok"' not in r.stdout
