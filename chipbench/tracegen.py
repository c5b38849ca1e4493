"""SuperCloud-schema trace generator of the benchmark.

A copy of the twin's own generator (``repro.data.synth_trace`` and
``repro.data.trace_io.write_supercloud_csvs``), kept here so that the
yardstick does not drift with the program. Two changes make it a
benchmark input:

- Every seed gets the same schedule. Arrival times and job shapes
  (duration, partition, nodes, per-node request, recorded queue delay)
  are drawn once from the traffic file's ``population_seed``, so the
  events the twin simulates, and the work of a run, do not change with
  the seed. ``--seed`` draws each job's telemetry: its utilisation
  levels, their wander and their noise, which move the power chain and
  every energy integral.
- Times are whole seconds and memory whole GB, so that the dataset's
  columns parse back to exactly the values written.

The CSVs are those of Samsi et al., HPEC'21: ``scheduler-log.csv``,
``cpu-telemetry.csv`` (10 s quanta) and ``gpu-telemetry.csv``.
"""

from __future__ import annotations

import csv
import os

import numpy as np

SCHED_COLS = ["job_id", "time_submit", "time_start", "time_end", "nodes_alloc",
              "cpus_req", "gpus_req", "mem_req_gb", "partition", "state"]
CPU_COLS = ["timestamp", "node", "job_id", "cpu_util"]
GPU_COLS = ["timestamp", "node", "gpu_index", "job_id", "util_pct", "power_w"]


def _types(sim: dict):
    """(GPU node type, CPU node type) of the configuration."""
    gpu = next(t for t in sim["node_types"] if t["gpus"] > 0)
    cpu = next(t for t in sim["node_types"] if t["gpus"] == 0)
    return gpu, cpu


def make_jobs(sim: dict, mix: dict, seed: int) -> dict:
    """The trace of one run as plain arrays (one entry per job, in
    ``job_id`` order) plus per-job utilisation profiles on 10 s quanta."""
    n = int(mix["n_jobs"])
    if n > sim["max_jobs"]:
        raise ValueError(f"{n} jobs exceed the job table ({sim['max_jobs']})")
    gpu_t, cpu_t = _types(sim)
    pop = np.random.default_rng(int(mix["population_seed"]))
    span = float(mix["arrival_span_s"])
    gaps = pop.exponential(span / n, n)
    arrive = np.cumsum(gaps) - gaps[0]
    submit = np.floor(arrive * (span / max(arrive[-1], 1e-9)))
    dur = np.clip(np.round(pop.lognormal(np.log(mix["mean_dur_s"]),
                                         mix["dur_sigma"], n)),
                  mix["min_dur_s"], mix["max_dur_s"])
    is_gpu = pop.random(n) < mix["gpu_fraction"]
    n_nodes = np.where(is_gpu, np.minimum(
        2 ** pop.integers(0, int(mix["gpu_node_exp_max"]) + 1, n),
        sim["max_nodes_per_job"]), 1)
    gpus = np.where(is_gpu, pop.integers(1, gpu_t["gpus"] + 1, n), 0)
    cores = np.where(is_gpu,
                     pop.integers(4, max(gpu_t["cpu_cores"] // 2, 5), n),
                     pop.integers(1, max(cpu_t["cpu_cores"] // 2, 2), n))
    mem = np.where(is_gpu, np.round(pop.uniform(16, gpu_t["mem_gb"] / 2, n)),
                   np.round(pop.uniform(2, cpu_t["mem_gb"] / 4, n)))
    queue_delay = np.round(np.abs(pop.normal(20, 10, n)))

    rng = np.random.default_rng(seed)
    base_cpu = rng.uniform(0.25, 0.95, n)
    base_gpu = np.where(is_gpu, rng.uniform(0.35, 0.98, n), 0.0)
    period = rng.uniform(120, 900, n)
    q_total = int(np.ceil(dur.max() / sim["trace_quanta"])) + 1
    tgrid = np.arange(q_total)[None, :] * sim["trace_quanta"]
    wob = 0.08 * np.sin(2 * np.pi * tgrid / period[:, None])
    noise = rng.normal(0, 0.03, (n, q_total))
    ramp = np.clip(tgrid / 60.0, 0, 1)
    cpu_u = np.clip((base_cpu[:, None] + wob + noise) * ramp, 0, 1)
    gpu_u = np.clip((base_gpu[:, None] + wob + noise) * ramp, 0, 1)
    return {
        "submit": submit, "start": submit + queue_delay, "dur": dur,
        "n_nodes": n_nodes.astype(np.int64), "cores": cores, "gpus": gpus,
        "mem": mem, "is_gpu": is_gpu, "cpu_util": cpu_u, "gpu_util": gpu_u,
    }


def write_csvs(path: str, sim: dict, jobs: dict) -> str:
    """Write ``jobs`` as the dataset's three CSVs under ``path``."""
    os.makedirs(path, exist_ok=True)
    n = len(jobs["submit"])
    tq = sim["trace_quanta"]
    gpu_t, cpu_t = _types(sim)
    with open(os.path.join(path, "scheduler-log.csv"), "w", newline="") as f:
        w = csv.writer(f)
        w.writerow(SCHED_COLS)
        for j in range(n):
            start = jobs["start"][j]
            w.writerow([j + 1, f"{jobs['submit'][j]:.1f}", f"{start:.1f}",
                        f"{start + jobs['dur'][j]:.1f}", int(jobs["n_nodes"][j]),
                        int(jobs["cores"][j]), int(jobs["gpus"][j]),
                        f"{jobs['mem'][j]:.1f}",
                        gpu_t["name"] if jobs["is_gpu"][j] else cpu_t["name"],
                        "COMPLETED"])
    n_nodes_total = sum(t["count"] for t in sim["node_types"])
    with open(os.path.join(path, "cpu-telemetry.csv"), "w", newline="") as f:
        w = csv.writer(f)
        w.writerow(CPU_COLS)
        for j in range(n):
            for q in range(jobs["cpu_util"].shape[1]):
                if q * tq > jobs["dur"][j]:
                    break
                w.writerow([f"{q * tq:.1f}", f"n{j % n_nodes_total:04d}", j + 1,
                            f"{jobs['cpu_util'][j, q]:.4f}"])
    with open(os.path.join(path, "gpu-telemetry.csv"), "w", newline="") as f:
        w = csv.writer(f)
        w.writerow(GPU_COLS)
        for j in range(n):
            if not jobs["is_gpu"][j]:
                continue
            for q in range(jobs["gpu_util"].shape[1]):
                if q * tq > jobs["dur"][j]:
                    break
                u = jobs["gpu_util"][j, q]
                w.writerow([f"{q * tq:.1f}", f"n{j % n_nodes_total:04d}", 0,
                            j + 1, f"{100 * u:.2f}", f"{55 + 245 * u:.1f}"])
    return path
