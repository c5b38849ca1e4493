"""The comparison that decides ``correct``: answers of the timed path
against the plain reference (``reference.py``).

Two numbers, each held to its own limit (``limits/<workload>.json``):

- ``job_mismatch``: the share of the trace's jobs whose discrete record
  (state, start tick, end tick) differs from the reference's, worst answer
  of the run. A program at another tick than the reference reads 1.
- ``accum_rel_err``: the largest relative gap of an integral (energy, IT
  energy, conversion loss, cooling, carbon, cost, delivered GFLOP, summed
  power, completions, waits, slowdowns) from the reference's, worst
  answer of the run.
"""

from __future__ import annotations

import numpy as np

from chipbench.reference import ACCUMULATORS

NUMBERS = ("job_mismatch", "accum_rel_err")


def compare(got: dict, ref: dict) -> dict:
    """Both numbers, and the integral that set the second, for one answer."""
    if got["t"] != ref["t"]:
        mismatch = 1.0
    else:
        differ = ((got["state"] != ref["state"]) | (got["start"] != ref["start"])
                  | (got["end"] != ref["end"]))
        mismatch = float(np.mean(differ))
    worst, field = 0.0, None
    for k in ACCUMULATORS:
        r = float(ref[k])
        err = abs(float(got[k]) - r) / max(abs(r), 1e-6)
        if not np.isfinite(err):
            err = float("inf")
        if err >= worst:
            worst, field = err, k
    return {"job_mismatch": mismatch, "accum_rel_err": worst,
            "worst_integral": field}


def judge(results: list, limits: dict) -> dict:
    """The run's worst reading of each number beside its limit, and
    whether every answer kept within all limits."""
    checks = {}
    for k in NUMBERS:
        vals = [r[k] for r in results]
        checks[k] = {"value": max(vals) if vals else float("inf"),
                     "limit": limits[k]}
    failed = sum(any(r[k] > limits[k] for k in NUMBERS) for r in results)
    ok = bool(results) and failed == 0
    return {"correct": ok, "failed": failed, "checks": checks}
