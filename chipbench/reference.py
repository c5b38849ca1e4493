"""Plain reference of the batch twin: one replica, one tick at a time, in
NumPy.

It reads the dataset's CSVs itself and follows the twin's published step
order (RAPS' fixed-dt loop): completions free their nodes, up to two
dispatch attempts select a job and place it, the power chain turns the
running jobs' telemetry into facility power, and progress and the energy,
carbon and cost integrals advance. Nothing of the program is imported.

``wdtype`` is the working precision of every per-job and per-node float
(submit and recorded start times, work, free pool, loads, power); the
clock and the integrals are kept in float64. The configuration states
float32; ``ml_dtypes.bfloat16`` is the control.

Only what the benchmark's configurations turn on is implemented; anything
else raises, so a configuration the reference cannot follow never passes
as checked.
"""

from __future__ import annotations

import csv
import json
import math
import os

import numpy as np

QUEUED, RUNNING, DONE = 1, 2, 3
BIG = 1e18
HARMONICS = (2.718, 5.196, 9.424, 17.03)
ACCUMULATORS = ("energy_kwh", "it_energy_kwh", "loss_energy_kwh",
                "cool_energy_kwh", "carbon_kg", "elec_cost_usd",
                "flops_integral", "sum_power_w", "n_completed", "sum_wait",
                "sum_slowdown")


def read_dataset(path: str, sim: dict) -> dict:
    """The scheduler log and telemetry, band-averaged onto the trace
    quanta; jobs in ``job_id`` order."""
    with open(os.path.join(path, "scheduler-log.csv")) as f:
        rows = sorted(csv.DictReader(f), key=lambda r: int(r["job_id"]))
    col = lambda k: np.array([float(r[k]) for r in rows], np.float32)
    submit, start, end = col("time_submit"), col("time_start"), col("time_end")
    dur = np.maximum(end - start, np.float32(1.0))
    tq = sim["trace_quanta"]
    n = len(rows)
    nq = max(int(np.ceil(dur.max() / tq)) + 1, 8)
    ids = {int(r["job_id"]): i for i, r in enumerate(rows)}
    util = {}
    for name, ucol, scale in (("cpu", "cpu_util", 1.0),
                              ("gpu", "util_pct", 0.01)):
        tot = np.zeros((n, nq), np.float32)
        cnt = np.zeros((n, nq), np.float32)
        with open(os.path.join(path, f"{name}-telemetry.csv")) as f:
            for r in csv.DictReader(f):
                j = ids[int(r["job_id"])]
                q = min(int(float(r["timestamp"]) / tq), nq - 1)
                tot[j, q] += float(r[ucol]) * scale
                cnt[j, q] += 1.0
        util[name] = np.where(cnt > 0, tot / np.maximum(cnt, 1), 0.0)
    req = np.stack([col("cpus_req"), col("gpus_req"), col("mem_req_gb")])
    for j in range(n):   # the dataset's rule: no telemetry -> 70% busy
        qmax = min(int(dur[j] / tq) + 1, nq)
        if util["cpu"][j, :qmax].max() == 0:
            util["cpu"][j, :qmax] = 0.7
        if req[1, j] > 0 and util["gpu"][j, :qmax].max() == 0:
            util["gpu"][j, :qmax] = 0.7
    return {"submit": submit, "priority": start, "dur": dur,
            "n_nodes": np.array([int(r["nodes_alloc"]) for r in rows]),
            "req": req, "cpu": util["cpu"], "gpu": util["gpu"]}


def signal(p: dict, t: float) -> float:
    """mean + amp sin(2 pi t / period + phase) + deterministic harmonic
    wander of amplitude ``noise_amp``."""
    period = max(p["period_s"], 1e-6)
    v = p["mean"] + p["amp"] * math.sin(2 * math.pi * t / period + p["phase"])
    if p.get("noise_amp", 0.0):
        s = sum(math.sin(2 * math.pi * h / period * t
                         + p["noise_seed"] * (1 + i) * 2.39996)
                for i, h in enumerate(HARMONICS))
        v += p["noise_amp"] * s / math.sqrt(len(HARMONICS))
    return v


def _unsupported(sim: dict, scenario: dict) -> list:
    off = [k for k in ("thermal_enabled", "serving_enabled", "outages_enabled",
                       "degrade_enabled") if sim[k]]
    off += [k for k in ("node_mtbf_hours", "rack_mtbf_hours", "power_cap_w",
                        "ckpt_overhead_s") if sim[k] > 0]
    if scenario.get("power_cap"):
        off.append("power_cap events")
    return off


class Twin:
    """One replica of the twin under a selection x placement policy."""

    def __init__(self, sim: dict, data: dict, scenario: dict, select: str,
                 place: str, wdtype=np.float32):
        bad = _unsupported(sim, scenario)
        if bad:
            raise NotImplementedError(f"reference has no model of {bad}")
        if select not in ("fcfs", "sjf", "priority", "easy") or \
                place not in ("first_fit", "best_fit"):
            raise NotImplementedError(f"policy {select}+{place}")
        self.sim, self.scn, self.select, self.place = sim, scenario, select, place
        w = self.w = wdtype
        cap, idle, cdyn, gdyn, nmax, gfl = [], [], [], [], [], []
        for t in sim["node_types"]:
            for _ in range(t["count"]):
                cap.append([t["cpu_cores"], t["gpus"], t["mem_gb"]])
                idle.append(t["idle_w"] + t["gpus"] * t["gpu_idle_w"])
                cdyn.append(t["cpu_dyn_w"])
                gdyn.append(t["gpus"] * t["gpu_dyn_w"])
                nmax.append(idle[-1] + cdyn[-1] + gdyn[-1])
                gfl.append(t["peak_gflops"])
        self.cap = np.array(cap, np.float64).T.astype(w)
        self.idle, self.cdyn, self.gdyn, self.nmax, self.gflops = (
            np.array(a, np.float64).astype(w)
            for a in (idle, cdyn, gdyn, nmax, gfl))
        self.free = self.cap.copy()
        n = len(data["submit"])
        self.submit = data["submit"].astype(w).astype(np.float64)
        self.prio = data["priority"].astype(w).astype(np.float64)
        self.dur = data["dur"].astype(w)
        self.work = data["dur"].astype(w)
        self.n_nodes = data["n_nodes"].astype(np.int64)
        self.req = data["req"].astype(w)
        self.cpu_tr, self.gpu_tr = data["cpu"].astype(w), data["gpu"].astype(w)
        self.state = np.full(n, QUEUED)
        self.start = np.zeros(n)
        self.end = np.zeros(n)
        self.nodes = [np.zeros(0, np.int64)] * n
        self.t = 0.0
        self.acc = dict.fromkeys(ACCUMULATORS, 0.0)

    # ----------------------------------------------------------- dispatch
    def _feasible(self, j):
        return np.all(self.free >= self.req[:, j:j + 1], axis=0)

    def _pick(self, score, mask):
        if not mask.any():
            return -1
        return int(np.argmin(np.where(mask, score, BIG)))

    def _fits_now(self, j):
        return int(self._feasible(j).sum()) >= self.n_nodes[j]

    def _shadow(self, head):
        """EASY reservation: when enough head-capable nodes come free if
        running jobs end at their requested walltimes."""
        free_ok = self._feasible(head)
        need = max(self.n_nodes[head] - int(free_ok.sum()), 0)
        if need == 0:
            return self.t
        head_ok = np.all(self.cap >= self.req[:, head:head + 1], axis=0)
        run = np.flatnonzero(self.state == RUNNING)
        est = self.start[run] + self.dur[run].astype(np.float64)
        rel = np.array([head_ok[self.nodes[j]].sum() for j in run], float)
        order = np.argsort(est, kind="stable")
        reached = np.cumsum(rel[order]) >= need
        return est[order][np.argmax(reached)] if reached.any() else BIG

    def _select(self):
        vis = (self.state == QUEUED) & (self.submit <= self.t)
        if self.select == "sjf":
            return self._pick(self.dur.astype(np.float64), vis)
        if self.select == "priority":
            return self._pick(-self.prio, vis)
        head = self._pick(self.submit, vis)
        if self.select == "fcfs" or head < 0 or self._fits_now(head):
            return head
        t_sh = self._shadow(head)
        cand = vis & (self.t + self.dur.astype(np.float64) <= t_sh)
        cand[head] = False
        js = np.flatnonzero(cand)
        if len(js):
            fit = np.all(self.free[:, None, :] >= self.req[:, js, None], axis=0)
            cand[js] = fit.sum(axis=1) >= self.n_nodes[js]
        return self._pick(self.submit, cand)

    def _place(self, j):
        ok = self._feasible(j)
        k = self.n_nodes[j]
        if ok.sum() < k:
            return None
        if self.place == "first_fit":
            return np.flatnonzero(ok)[:k]
        frac = self.free / np.maximum(self.cap, self.w(1e-6))
        score = (frac[0] + frac[1] + frac[2]) / self.w(3.0)
        key = np.where(ok, score.astype(np.float64), np.inf)
        return np.argsort(key, kind="stable")[:k]

    # --------------------------------------------------------------- tick
    def tick(self):
        sim, w, a = self.sim, self.w, self.acc
        dt = sim["dt"]
        self.t += dt
        t = self.t
        for j in np.flatnonzero((self.state == RUNNING) & (self.work <= 0)):
            self.free[:, self.nodes[j]] += self.req[:, j:j + 1]
            wait = max(self.start[j] - self.submit[j], 0.0)
            run = max(t - self.start[j], dt)
            a["n_completed"] += 1
            a["sum_wait"] += wait
            a["sum_slowdown"] += max((wait + run) / run, 1.0)
            self.state[j], self.end[j] = DONE, t
            self.nodes[j] = np.zeros(0, np.int64)
        for _ in range(2):
            j = self._select()
            if j < 0:
                continue
            row = self._place(j)
            if row is None:
                continue
            self.free[:, row] -= self.req[:, j:j + 1]
            self.state[j], self.start[j], self.nodes[j] = RUNNING, t, row

        run = np.flatnonzero(self.state == RUNNING)
        age = np.maximum(t - self.start[run], 0.0)
        qi = np.clip((age / sim["trace_quanta"]).astype(np.int64), 0,
                     self.cpu_tr.shape[1] - 1)
        cpu_u, gpu_u = self.cpu_tr[run, qi], self.gpu_tr[run, qi]
        counts = [len(self.nodes[j]) for j in run]
        where = (np.concatenate([self.nodes[j] for j in run])
                 if len(run) else np.zeros(0, np.int64))
        n_nodes = self.cap.shape[1]
        loads = []
        for r, u in ((0, cpu_u), (1, gpu_u)):
            amt = np.repeat((self.req[r, run] * u).astype(w), counts)
            node = np.zeros(n_nodes, w)
            np.add.at(node, where, amt)
            loads.append(np.clip(node / np.maximum(self.cap[r], w(1e-6)),
                                 w(0), w(1)))
        cf, gf = loads
        it = self.idle + cf * self.cdyn + gf * self.gdyn
        lf = np.clip(it / np.maximum(self.nmax, w(1)), w(0), w(1.2))
        eta = np.clip(w(sim["rect_eff_peak"]) - w(sim["rect_eff_curv"])
                      * np.square(lf - w(sim["rect_eff_load"])), w(0.5), w(1))
        inp = it / (eta * w(sim["conv_eff"]))
        it_w, in_w = float(np.sum(it, dtype=w)), float(np.sum(inp, dtype=w))
        gflops = float(np.sum(self.gflops * np.maximum(cf, gf), dtype=w))
        wb = signal(self.scn["wetbulb"], t)
        cop = max(sim["cop_base"] + sim["cop_wetbulb_coef"]
                  * (wb - sim["wetbulb_ref_c"]), sim["cop_min"])
        cool = in_w / cop
        fac = in_w + cool

        self.work[run] = self.work[run] - w(dt)
        dt_h = dt / 3600.0
        e = fac * dt_h / 1000.0
        a["energy_kwh"] += e
        a["it_energy_kwh"] += it_w * dt_h / 1000.0
        a["loss_energy_kwh"] += (in_w - it_w) * dt_h / 1000.0
        a["cool_energy_kwh"] += cool * dt_h / 1000.0
        a["carbon_kg"] += e * signal(self.scn["carbon"], t) / 1000.0
        a["elec_cost_usd"] += e * signal(self.scn["price"], t)
        a["flops_integral"] += gflops * dt
        a["sum_power_w"] += fac

    def record(self) -> dict:
        """The answer at the current tick: time, each job's discrete
        record, and the integrals."""
        return {"t": self.t, "state": self.state.copy(),
                "start": self.start.copy(), "end": self.end.copy(),
                **self.acc}


def run(sim, data, scenario, select, place, ticks, wdtype=np.float32) -> dict:
    """Records of one replica at each tick count in ``ticks``."""
    twin = Twin(sim, data, scenario, select, place, wdtype)
    out = {}
    for k in range(1, max(ticks) + 1):
        twin.tick()
        if k in ticks:
            out[k] = twin.record()
    return out


def records(sim, data, answers, wdtype=np.float32) -> list:
    """The reference's record for each answer (an answer names its policy,
    scenario and tick count), one run per replica of the answers."""
    def replica(a):
        return a["select"], a["place"], json.dumps(a["scenario"], sort_keys=True)

    ticks = {}
    for a in answers:
        ticks.setdefault(replica(a), set()).add(a["ticks"])
    done = {}
    for a in answers:
        if replica(a) not in done:
            done[replica(a)] = run(sim, data, a["scenario"], a["select"],
                                   a["place"], ticks[replica(a)], wdtype)
    return [done[replica(a)][a["ticks"]] for a in answers]
