"""Shared part of the window drivers: the general code behind every
traffic mix. Each driver is a module of this package, named by the
traffic file's ``driver`` key and exporting ``DRIVER``.

A driver builds the cell's inputs from ``--seed`` (set-up), warms up the
shapes the window uses, drives the program's own entry points for the
window, and hands back the answers the window produced together with what
the reference needs to recompute them.
"""

from __future__ import annotations

import os

import jax
import numpy as np

from chipbench import tracegen
from chipbench.reference import ACCUMULATORS
from repro.configs.sim import NodeType, SimConfig
from repro.core import build_statics, init_state, load_jobs
from repro.data import load_supercloud
from repro.scenarios.events import no_cap
from repro.scenarios.scenario import Scenario
from repro.scenarios.signals import sinusoid

annotate = jax.profiler.TraceAnnotation


def sim_config(sim: dict) -> SimConfig:
    kw = dict(sim)
    kw["node_types"] = tuple(NodeType(**t) for t in sim["node_types"])
    return SimConfig(**kw)


def scenario_of(p: dict) -> Scenario:
    """A Scenario built through the program's own API from the traffic
    file's fixed signal parameters (no demand-response events)."""
    sig = {k: sinusoid(p[k]["mean"], p[k]["amp"], p[k]["period_s"],
                       p[k]["phase"], noise_amp=p[k].get("noise_amp", 0.0),
                       noise_seed=p[k].get("noise_seed", 0.0))
           for k in ("carbon", "price", "wetbulb")}
    return Scenario(power_cap=no_cap(0.0), **sig)


def answer_of(state, n_jobs: int, i: int | None = None) -> dict:
    """Host copy of one replica's answer: time, the trace's job records
    and the integrals (``i`` picks a replica of a batched state)."""
    names = ("t", "jstate", "start_t", "end_t") + ACCUMULATORS
    s = jax.device_get({k: getattr(state, k) if i is None
                        else getattr(state, k)[i] for k in names})
    out = {"t": float(s["t"]), "state": np.asarray(s["jstate"])[:n_jobs],
           "start": np.asarray(s["start_t"], np.float64)[:n_jobs],
           "end": np.asarray(s["end_t"], np.float64)[:n_jobs]}
    out.update({k: float(s[k]) for k in ACCUMULATORS})
    return out


class Driver:
    """Inputs from the seed; the trace goes through the dataset's CSVs and
    the program's strict, validated ingestion."""

    def __init__(self, sim: dict, mix: dict, seed: int, chips: int,
                 workdir: str):
        self.sim, self.mix, self.seed, self.chips = sim, mix, seed, chips
        self.cfg = sim_config(sim)
        self.data_dir = tracegen.write_csvs(
            os.path.join(workdir, "trace"), sim,
            tracegen.make_jobs(sim, mix, seed))
        jobs, bank, report = load_supercloud(
            self.data_dir, self.cfg, validate="strict", return_report=True)
        bad = sum(r.n_quarantined for r in report.values())
        if bad:
            raise RuntimeError(f"ingestion quarantined {bad} rows")
        self.n_jobs = len(jobs["submit_t"])
        self.scenarios = [dict(s) for s in mix["scenarios"]]
        self.statics = build_statics(self.cfg, bank,
                                     scenario=scenario_of(self.scenarios[0]))
        self.state0 = load_jobs(
            init_state(self.cfg, self.statics, jax.random.key(seed)), jobs)
        self.counters = {"calls": 0, "replica_ticks": 0.0, "macro_steps": 0.0,
                         "lane_mean": 0.0, "lane_max": 0.0}

    def end_to_end(self, window_s: float) -> dict:
        """End-to-end metrics of the window: simulated seconds of every
        replica completed, over the window's wall time."""
        return {"sim_s_per_s":
                self.counters["replica_ticks"] * self.cfg.dt / window_s}

    def _count(self, n_steps, macro_steps, lanes: int | None = None):
        c = self.counters
        ns, ms = np.asarray(n_steps, np.float64), np.asarray(macro_steps,
                                                            np.float64)
        c["calls"] += 1
        c["replica_ticks"] += float(ns.sum())
        c["macro_steps"] += float(ms.sum())
        if lanes:
            blocks = ms.reshape(-1, lanes)
            c["lane_mean"] += float(blocks.mean(axis=1).sum())
            c["lane_max"] += float(blocks.max(axis=1).sum())


