"""The control: the plain reference computed in bfloat16, put in the
program's place, must come out as not correct under the limits the chip
cells hold (here at tiny size, on three seeds)."""

import json
import os

import ml_dtypes
import pytest

from chipbench import compare, reference, tracegen
from tiny_cells import ROOT, tiny_files


@pytest.mark.parametrize("cell,mix,policies,ticks", [
    ("replay.txgaia.hour", "tiny_replay", [("fcfs", "first_fit")], 600),
    ("sweep.txgaia.grid64", "tiny_sweep",
     [("fcfs", "first_fit"), ("easy", "best_fit")], 60),
])
@pytest.mark.parametrize("seed", [1, 2**31 + 3, 977])
def test_bf16_control_fails_the_cell_limits(tmp_path, cell, mix, policies,
                                            ticks, seed):
    files = tiny_files()
    sim = files["chipbench/configs/tiny.json"]["sim"]
    mix = files[f"chipbench/traffic/{mix}.json"]
    with open(os.path.join(ROOT, "chipbench", "limits", cell + ".json")) as f:
        limits = json.load(f)
    path = tracegen.write_csvs(str(tmp_path), sim,
                               tracegen.make_jobs(sim, mix, seed))
    data = reference.read_dataset(path, sim)
    for select, place in policies:
        args = (sim, data, mix["scenarios"][0], select, place, {ticks})
        ref = reference.run(*args)[ticks]
        ctl = reference.run(*args, wdtype=ml_dtypes.bfloat16)[ticks]
        reading = compare.compare(ctl, ref)
        assert any(reading[k] > limits[k] for k in compare.NUMBERS), reading


def test_calibrate_control_reading_fails_limits(tiny):
    """``calibrate.control_reading`` on the answers a tiny sweep run
    compared: the bf16 control fails a limit the program kept."""
    from chipbench import calibrate
    from tiny_cells import TINY_SWEEP

    seed = 2**31 + 11
    out, answers = tiny.run_cell(TINY_SWEEP, seed, 1.0, False,
                                 require_tpu=False)
    spec = tiny.load_cell(TINY_SWEEP)
    ctl = calibrate.control_reading(spec, seed, answers)
    assert out["correct"]
    assert any(ctl[k] > spec["limits"][k] for k in compare.NUMBERS), ctl
