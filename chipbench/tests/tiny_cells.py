"""Tiny cells for rehearsing the harness on the host CPU: a 16-node
cluster with a 64-job table, the same drivers, reference and comparison
as the chip cells."""

import dataclasses
import json
import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
for p in (os.path.join(ROOT, "src"), ROOT):
    if p not in sys.path:
        sys.path.insert(0, p)

TINY_REPLAY = "replay.tiny"
TINY_SWEEP = "sweep.tiny"
TINY_SHARD = "sweep.tiny.shard4"


def tiny_sim() -> dict:
    from repro.configs.sim import tiny_cluster

    return dataclasses.asdict(tiny_cluster())


def _mix(name):
    with open(os.path.join(ROOT, "chipbench", "traffic", name)) as f:
        return json.load(f)


def tiny_files() -> dict:
    """File name -> content of a benchmark tree with the tiny cells."""
    replay = _mix("replay_hour.json")
    replay.update(n_jobs=48, arrival_span_s=540.0, mean_dur_s=200.0,
                  max_dur_s=600.0, ticks=600)
    sweep = _mix("grid64_15m.json")
    sweep.update(n_jobs=48, arrival_span_s=240.0, mean_dur_s=200.0,
                 max_dur_s=300.0, segment_ticks=20, cycle_ticks=60)
    bench = json.load(open(os.path.join(ROOT, "BENCHMARK.json")))
    bench["configs"] = [{"name": "tiny", "source": "tests",
                         "file": "chipbench/configs/tiny.json",
                         "reduced": [], "why": "CPU rehearsal"}]
    bench["workloads"] = [
        {"name": TINY_REPLAY, "config": "tiny", "traffic": "tiny_replay",
         "chips": 1, "why": "CPU rehearsal"},
        {"name": TINY_SWEEP, "config": "tiny", "traffic": "tiny_sweep",
         "chips": 1, "why": "CPU rehearsal"},
        {"name": TINY_SHARD, "config": "tiny", "traffic": "tiny_sweep",
         "chips": 4, "why": "CPU rehearsal on four host devices"}]
    for m in bench["per_layer"]:
        if "workloads" in m:
            m["workloads"] = [TINY_SWEEP, TINY_SHARD]
    limits = {"job_mismatch": 0.0, "accum_rel_err": 1e-4}
    files = {"BENCHMARK.json": bench,
             "chipbench/configs/tiny.json": {"name": "tiny", "sim": tiny_sim()},
             "chipbench/traffic/tiny_replay.json": replay,
             "chipbench/traffic/tiny_sweep.json": sweep}
    for cell in (TINY_REPLAY, TINY_SWEEP, TINY_SHARD):
        files[f"chipbench/limits/{cell}.json"] = limits
    return files


def write_tree(root: str) -> str:
    for rel, content in tiny_files().items():
        path = os.path.join(root, rel)
        os.makedirs(os.path.dirname(path), exist_ok=True)
        with open(path, "w") as f:
            json.dump(content, f)
    os.symlink(os.path.join(ROOT, "chipbench", "metrics"),
               os.path.join(root, "chipbench", "metrics"))
    return root
