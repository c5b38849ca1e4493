"""``replay_day``: a trace longer than the job table, streamed through it.
``run_segment(..., macro=True)`` advances one segment (a simulated hour)
per call, carrying the state, with one host read of the segment's
telemetry, as a user tailing an hourly report would; the state restarts
from the initial one at the end of each day.

The trace is generated with its own length as the generator's table
bound (``tracegen.make_jobs`` refuses a trace longer than the table it
is given), and the program must load every job of it: a loader that
returns fewer raises in set-up. A window in which a due trace job found
no slot (``admit_overflow``) no longer replays the trace and raises
instead of answering."""

from __future__ import annotations

import os
import time

import jax
import numpy as np

from chipbench import tracegen
from chipbench.drivers.common import Driver, annotate, scenario_of, sim_config
from chipbench.reference import ACCUMULATORS
from repro.core import (build_statics, init_state, load_jobs, run_segment,
                        telem_zero, trace_records)
from repro.data import load_supercloud

ADMIT = ("admitted", "admit_overflow", "live_slot_ticks")


def answer_of(state) -> dict:
    """Host copy of one replica's answer: time, every trace job's record
    in trace order (``trace_records``) and the integrals."""
    rec = trace_records(state)
    s = jax.device_get({k: getattr(state, k) for k in ("t",) + ACCUMULATORS})
    out = {"t": float(s["t"]), "state": rec["state"],
           "start": np.asarray(rec["start"], np.float64),
           "end": np.asarray(rec["end"], np.float64)}
    out.update({k: float(s[k]) for k in ACCUMULATORS})
    return out


class ReplayDay(Driver):
    def __init__(self, sim: dict, mix: dict, seed: int, chips: int,
                 workdir: str):
        self.sim, self.mix, self.seed, self.chips = sim, mix, seed, chips
        self.cfg = cfg = sim_config(sim)
        n = int(mix["n_jobs"])
        self.data_dir = tracegen.write_csvs(
            os.path.join(workdir, "trace"), sim,
            tracegen.make_jobs(dict(sim, max_jobs=n), mix, seed))
        jobs, bank, report = load_supercloud(
            self.data_dir, cfg, validate="strict", return_report=True)
        bad = sum(r.n_quarantined for r in report.values())
        if bad:
            raise RuntimeError(f"ingestion quarantined {bad} rows")
        if len(jobs["submit_t"]) != n:
            raise RuntimeError(
                f"the loader returned {len(jobs['submit_t'])} of the "
                f"trace's {n} jobs: the program cannot replay a trace "
                f"longer than its {cfg.max_jobs}-slot job table")
        self.n_jobs = n
        self.scenarios = [dict(s) for s in mix["scenarios"]]
        self.statics = build_statics(cfg, bank, jobs=jobs,
                                     scenario=scenario_of(self.scenarios[0]))
        self.state0 = load_jobs(
            init_state(cfg, self.statics, jax.random.key(seed)), jobs)
        self.acc0 = telem_zero(cfg, self.statics)
        self.seg = int(mix["segment_ticks"])
        self.day = int(mix["ticks"]) // self.seg
        self.fn = jax.jit(lambda statics, s, a: run_segment(
            cfg, statics, s, a, self.seg, mix["select"],
            placement=mix["place"], macro=True))
        self.counters = {"calls": 0, "replica_ticks": 0.0, "macro_steps": 0.0,
                         "lane_mean": 0.0, "lane_max": 0.0,
                         "slots": cfg.max_jobs, **dict.fromkeys(ADMIT, 0.0)}
        self.st, self.last, self.done = None, None, None
        self.k = 0

    def warm(self):
        jax.device_get(self.fn(self.statics, self.state0, self.acc0)[1])

    def window(self, seconds: float) -> float:
        t0 = time.perf_counter()
        st = self.state0 if self.st is None else self.st
        while True:
            with annotate("window.segment"):
                st, tel = self.fn(self.statics, st, self.acc0)
            with annotate("host.summary"):
                tel = jax.device_get(tel)
            self._count(tel.n_steps, tel.macro_steps)
            for k in ADMIT:
                self.counters[k] += float(getattr(tel, k))
            self.k += 1
            self.last = st
            if self.k == self.day:
                self.done, self.k, st = st, 0, self.state0
            if time.perf_counter() - t0 >= seconds:
                self.st = st
                return time.perf_counter() - t0

    def answers(self) -> list:
        """The end of the last whole day and the window's end."""
        if self.counters["admit_overflow"]:
            raise RuntimeError(
                f"{self.counters['admit_overflow']:.0f} ticks found a due "
                f"trace job outside the full {self.cfg.max_jobs}-slot table: "
                "the replay no longer follows the trace")
        ends = []
        if self.k:
            ends.append((self.last, self.k * self.seg))
        if self.done is not None:
            ends.append((self.done, self.day * self.seg))
        mix = self.mix
        return [{"what": f"tick {ticks} of a day", "select": mix["select"],
                 "place": mix["place"], "scenario": self.scenarios[0],
                 "ticks": ticks, "got": answer_of(state)}
                for state, ticks in ends]

    def release(self):
        self.st = self.last = self.done = None


DRIVER = ReplayDay
