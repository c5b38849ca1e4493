"""Readings that the limits of ``limits/<cell>.json`` are set from.

    python3 chipbench/calibrate.py --workload <cell> --seeds 1-12 \
        --seconds 5 --control-seeds 3

For each seed, one whole run of the cell (set-up, a window of
``--seconds``, the comparison with the reference) gives the program's
reading of each compared number. For the first ``--control-seeds`` seeds
the control, the reference computed in bfloat16, is put in the program's
place for the same answers and read the same way. Prints one JSON line
per seed and then a summary: the largest reading of the program and the
smallest of the control, per number. Runs every seed in one process, so
compiled programs are shared.
"""

import argparse
import json
import os
import sys
import tempfile
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from chipbench import run  # noqa: E402


def seeds_of(text: str) -> list:
    out = []
    for part in text.split(","):
        lo, _, hi = part.partition("-")
        out += list(range(int(lo), int(hi or lo) + 1))
    return out


def control_reading(spec: dict, seed: int, answers: list) -> dict:
    """The control's worst reading over the answers the run compared."""
    import ml_dtypes

    from chipbench import compare, reference, tracegen

    sim = spec["config"]["sim"]
    with tempfile.TemporaryDirectory() as tmp:
        path = tracegen.write_csvs(tmp, sim,
                                   tracegen.make_jobs(sim, spec["mix"], seed))
        data = reference.read_dataset(path, sim)
    ref = reference.records(sim, data, answers)
    ctl = reference.records(sim, data, answers, wdtype=ml_dtypes.bfloat16)
    results = [compare.compare(c, r) for c, r in zip(ctl, ref)]
    return {k: max(r[k] for r in results) for k in compare.NUMBERS}


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--control-seeds", type=int, default=3)
    args = ap.parse_args(argv)
    from chipbench.compare import NUMBERS

    spec = run.load_cell(args.workload)
    prog, ctl = [], []
    for i, seed in enumerate(seeds_of(args.seeds)):
        out, answers = run.run_cell(args.workload, seed, args.seconds, False,
                                    t_start=time.time())
        line = {"seed": seed, "correct": out["correct"],
                "program": {k: max(a["reading"][k] for a in answers)
                            for k in NUMBERS},
                "worst": max(answers, key=lambda a: a["reading"][
                    "accum_rel_err"])["reading"]["worst_integral"],
                "metrics": out["metrics"]}
        prog.append(line["program"])
        if i < args.control_seeds:
            line["control"] = control_reading(spec, seed, answers)
            ctl.append(line["control"])
        print(json.dumps(line), flush=True)
    print(json.dumps({"summary": {
        "seeds": len(prog),
        "program_max": {k: max(p[k] for p in prog) for k in NUMBERS},
        "control_min": {k: min(c[k] for c in ctl) for k in NUMBERS}
        if ctl else None}}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
