# One function per paper table. Print ``name,us_per_call,derived`` CSV and
# write a BENCH_<n>.json perf-trajectory artifact.
"""Benchmark harness — one bench per paper table/figure:

  replay_tx_gaia_1h        Fig 2 top-left  (throughput/energy during replay)
  sched_*                  RAPS scheduler table (+ Fan et al. 45% reference)
  ppo_scheduler            Fig 2 top-right (PPO reward curve)
  power_prediction_replay  Fig 2 bottom    (power prediction from replay)
  congestion_bw_*          network-congestion model [14]
  vmapped_sim_*            beyond-paper: vectorized-twin RL throughput
  rollout_* / ppo_iteration  lightweight-state RL rollout engine (BENCH_4)
  replay_tx_gaia_1h_faults[_macro] / faults_smoke_*  resilience twin:
                           event-sampled fault clocks under macro (BENCH_7)
  serving_diurnal_day_* / serving_smoke_* / serving_ppo_slo  serving twin:
                           SLO-aware overload ladder under macro (BENCH_9)
  fleet_*replicas          beyond-paper: scenario-sweep fleet throughput
  fleet_sharded_* / fleet_vmapped_*  device-sharded fleet (run_fleet mesh=)
                           vs single-device vmap, incl. the lockstep-
                           adversarial macro workload (BENCH_8)
  replay_snapshot_*        durable twin: segmented snapshot/resume driver
                           overhead vs vanilla replay (BENCH_10)
  dispatch_* / power_scatter_*  sort-free placement + fused power kernel
  pallas_*                 kernel microbenches vs oracles
  train/decode_reduced_*   LM substrate throughput (reduced configs)
  roofline_flops_crosscheck  analytic perfmodel vs compiled dry-run

Every run appends to the perf trajectory: results land in
``benchmarks/BENCH_<n>.json`` (n = 1 + highest existing), so successive
PRs can diff hot-path numbers against the recorded baseline. See
``docs/performance.md`` for how to read the artifact.

Usage:
  python benchmarks/run.py            # full suite
  python benchmarks/run.py --smoke    # tiny configs, seconds (CI gate)
  python benchmarks/run.py --out P    # write the artifact to path P
  python benchmarks/run.py --compare BENCH_a.json BENCH_b.json
                                      # per-row speedup table a -> b;
                                      # exits non-zero on >20% regressions
"""

import argparse
import glob
import json
import os
import re
import sys
import threading
import time
import traceback

_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, os.path.join(_ROOT, "src"))
sys.path.insert(0, _ROOT)   # so `benchmarks.*` imports work as a script

BENCH_DIR = os.path.dirname(os.path.abspath(__file__))


def _trajectory_numbers() -> list:
    return sorted(
        int(m.group(1))
        for p in glob.glob(os.path.join(BENCH_DIR, "BENCH_*.json"))
        if (m := re.fullmatch(r"BENCH_(\d+)\.json", os.path.basename(p)))
    )


def _warn_trajectory_gaps() -> list:
    """LOUDLY report holes in the numbered BENCH_<n> trajectory (e.g. a PR
    that referenced an artifact which never landed in-tree). The rule
    (docs/performance.md): numbering is always 1 + highest existing — gaps
    are never silently backfilled, because BENCH_<n> is read as "the
    artifact PR n produced" and a late write would masquerade as history.
    """
    nums = _trajectory_numbers()
    missing = sorted(set(range(1, max(nums, default=0) + 1)) - set(nums))
    if missing:
        print(
            f"# WARNING: perf trajectory has gaps — missing "
            f"{', '.join(f'BENCH_{n}.json' for n in missing)}; "
            "numbering continues from the highest existing artifact and "
            "gaps stay empty (see docs/performance.md)", file=sys.stderr)
    return missing


def _next_artifact_path() -> str:
    return os.path.join(
        BENCH_DIR, f"BENCH_{max(_trajectory_numbers(), default=0) + 1}.json")


def _named(fn, name, **kw):
    def run():
        return fn(**kw)

    run.__name__ = name
    return run


def _benches(smoke: bool):
    from benchmarks.bench_dispatch import bench_dispatch, bench_policy_grid
    from benchmarks.bench_rl import bench_rl

    if smoke:
        from benchmarks.bench_fleet import bench_fleet_sharded
        from benchmarks.bench_serving import bench_serving_smoke
        from benchmarks.bench_sim import (
            bench_faults_smoke,
            bench_macro_smoke,
            bench_snapshot_overhead,
            bench_thermal_smoke,
            bench_vectorized_envs,
        )

        return [
            _named(bench_dispatch, "bench_dispatch", smoke=True),
            bench_vectorized_envs,
            bench_macro_smoke,
            bench_thermal_smoke,
            bench_faults_smoke,
            bench_serving_smoke,
            bench_snapshot_overhead,
            _named(bench_policy_grid, "bench_policy_grid", smoke=True),
            _named(bench_rl, "bench_rl", smoke=True),
            _named(bench_fleet_sharded, "bench_fleet_sharded", smoke=True),
        ]

    from benchmarks.bench_fleet import bench_fleet, bench_fleet_sharded
    from benchmarks.bench_kernels import bench_kernels
    from benchmarks.bench_serving import bench_serving, bench_serving_smoke
    from benchmarks.bench_lm import (
        bench_decode_reduced,
        bench_roofline_crosscheck,
        bench_train_reduced,
    )
    from benchmarks.bench_sim import (
        bench_congestion_model,
        bench_faults,
        bench_faults_smoke,
        bench_macro_smoke,
        bench_power_prediction,
        bench_replay_throughput,
        bench_rl_training,
        bench_scheduler_comparison,
        bench_snapshot_overhead,
        bench_thermal,
        bench_thermal_smoke,
        bench_vectorized_envs,
    )

    return [
        bench_replay_throughput,
        bench_thermal,
        bench_faults,
        bench_macro_smoke,
        bench_thermal_smoke,
        bench_faults_smoke,
        bench_serving,
        bench_serving_smoke,
        bench_snapshot_overhead,
        bench_scheduler_comparison,
        bench_power_prediction,
        bench_congestion_model,
        bench_rl_training,
        bench_vectorized_envs,
        bench_rl,
        bench_dispatch,
        bench_policy_grid,
        bench_fleet,
        bench_fleet_sharded,
        bench_kernels,
        bench_train_reduced,
        bench_decode_reduced,
        bench_roofline_crosscheck,
    ]


REGRESSION_THRESHOLD = 1.20   # >20% slower counts as a regression


def compare_artifacts(path_a: str, path_b: str,
                      threshold: float = REGRESSION_THRESHOLD) -> int:
    """Print a per-row speedup table between two BENCH artifacts and
    return the number of rows regressing beyond ``threshold`` (b slower
    than a). Rows are matched by name; unmatched, failed (nan) and
    zero-time rows are listed but never counted as regressions — the
    trajectory must stay diffable even when a bench set changes shape."""
    num = lambda p: (m := re.fullmatch(r"BENCH_(\d+)\.json",
                                       os.path.basename(p))) and int(m.group(1))
    na_n, nb_n = num(path_a), num(path_b)
    if na_n and nb_n and abs(nb_n - na_n) > 1:
        skipped = [f"BENCH_{i}.json"
                   for i in range(min(na_n, nb_n) + 1, max(na_n, nb_n))
                   if not os.path.exists(os.path.join(BENCH_DIR,
                                                      f"BENCH_{i}.json"))]
        if skipped:
            print(f"# NOTE: comparing across a trajectory gap — "
                  f"{', '.join(skipped)} never landed; deltas span more "
                  "than one PR (see docs/performance.md)", file=sys.stderr)
    a = json.load(open(path_a))
    b = json.load(open(path_b))
    rows_a = {r["name"]: r for r in a["rows"]}
    rows_b = {r["name"]: r for r in b["rows"]}
    na, nb = os.path.basename(path_a), os.path.basename(path_b)
    width = max([len(n) for n in rows_a] + [len(n) for n in rows_b] + [4])
    print(f"{'name':<{width}}  {na:>14}  {nb:>14}  {'speedup':>8}  verdict")
    regressions = []
    for name in list(rows_a) + [n for n in rows_b if n not in rows_a]:
        ra, rb = rows_a.get(name), rows_b.get(name)
        if ra is None or rb is None:
            tag = "only in " + (nb if ra is None else na)
            us = (rb or ra)["us_per_call"]
            print(f"{name:<{width}}  {'-' if ra is None else us:>14}  "
                  f"{'-' if rb is None else us:>14}  {'-':>8}  {tag}")
            continue
        ua, ub = ra["us_per_call"], rb["us_per_call"]
        bad = lambda u: (not isinstance(u, (int, float)) or u != u or u <= 0)
        if bad(ua) or bad(ub):
            if bad(ua) != bad(ub):
                # failed on exactly one side: likely a REAL breakage (or
                # fix) introduced between the two artifacts — warn loudly,
                # but never count it as a perf regression
                side = na if bad(ua) else nb
                print(f"# WARNING: {name!r} failed/timed out only in "
                      f"{side} — investigate before trusting this diff",
                      file=sys.stderr)
                tag = f"skipped (failed only in {side})"
            else:
                tag = "skipped (failed/zero-time row)"
            print(f"{name:<{width}}  {ua!s:>14}  {ub!s:>14}  {'-':>8}  {tag}")
            continue
        speedup = ua / ub
        verdict = "ok"
        if ub > ua * threshold:
            verdict = f"REGRESSION (>{(threshold - 1) * 100:.0f}%)"
            regressions.append(name)
        elif speedup >= threshold:
            verdict = "improved"
        print(f"{name:<{width}}  {ua:>14.1f}  {ub:>14.1f}  "
              f"{speedup:>7.2f}x  {verdict}")
    if regressions:
        print(f"# {len(regressions)} regression(s): {regressions}",
              file=sys.stderr)
    return len(regressions)


def _run_bench_guarded(bench, timeout_s: float):
    """Run one bench on a daemon worker thread. Returns
    (result_rows | None, exception | None, timed_out). On timeout the
    worker keeps running detached (XLA compiles are not interruptible
    from Python), so the caller must end the run: a next bench would
    share the device with it."""
    out = {"rows": None, "exc": None}

    def work():
        try:
            out["rows"] = list(bench())
        except BaseException as e:  # noqa: BLE001 - reported per-row
            out["exc"] = e

    th = threading.Thread(target=work, daemon=True)
    th.start()
    th.join(timeout_s if timeout_s and timeout_s > 0 else None)
    if th.is_alive():
        return None, None, True
    return out["rows"], out["exc"], False


RETRY_BACKOFF_S = 2.0


def main(argv=None) -> None:
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--smoke", action="store_true",
                    help="tiny configs only (CI benchmark smoke gate)")
    ap.add_argument("--out", default=None,
                    help="artifact path (default: benchmarks/BENCH_<n>.json)")
    ap.add_argument("--only", default=None,
                    help="comma-separated substring filter on bench function "
                         "names (e.g. --only policy_grid,dispatch)")
    ap.add_argument("--timeout", type=float, default=0.0,
                    help="per-bench wall-clock budget in seconds (0 = none); "
                         "a bench over budget is recorded with "
                         "timed_out=true and ends the run with a non-zero "
                         "exit")
    ap.add_argument("--compare", nargs=2, metavar=("A.json", "B.json"),
                    default=None,
                    help="diff two BENCH artifacts row-by-row instead of "
                         "running benches; exit non-zero on >20%% regressions")
    args = ap.parse_args(argv)

    if args.compare:
        n_reg = compare_artifacts(*args.compare)
        if n_reg:
            raise SystemExit(1)
        return

    benches = _benches(args.smoke)
    if args.only:
        pats = [p.strip() for p in args.only.split(",") if p.strip()]
        benches = [
            b for b in benches
            if any(p in getattr(b, "__name__", repr(b)) for p in pats)
        ]
        if not benches:
            raise SystemExit(f"--only {args.only!r} matched no benches")

    print("name,us_per_call,derived")
    rows, failed = [], []
    timed_out = False
    for bench in benches:
        bench_name = getattr(bench, "__name__", repr(bench))
        # transient failures (thread-pool races, flaky first compile) get
        # ONE retry with a short backoff; a second strike is recorded. A
        # timeout gets none: its thread still holds the device
        retries = 0
        while True:
            result, exc, timed_out = _run_bench_guarded(bench, args.timeout)
            if result is not None or timed_out or retries >= 1:
                break
            retries += 1
            print(f"# {bench_name} failed ({exc!r}); retrying once in "
                  f"{RETRY_BACKOFF_S:.0f}s", file=sys.stderr, flush=True)
            time.sleep(RETRY_BACKOFF_S)
        if result is not None:
            for name, us, derived in result:
                print(f"{name},{us:.1f},{derived}", flush=True)
                rows.append(
                    {"name": name, "us_per_call": round(us, 1),
                     "derived": derived, "retries": retries,
                     "timed_out": False})
        else:
            if exc is not None:
                traceback.print_exception(type(exc), exc, exc.__traceback__)
            failed.append(bench_name)
            detail = (f"TIMEOUT>{args.timeout:.0f}s" if timed_out
                      else f"FAILED:{exc!r}")
            print(f"{bench_name},nan,{detail}", flush=True)
            rows.append(
                {"name": bench_name, "us_per_call": None, "derived": detail,
                 "retries": retries, "timed_out": bool(timed_out)})
            if timed_out:
                break

    # smoke numbers (tiny configs) and --only subsets must not claim a
    # numbered BENCH_<n> trajectory slot by default: numbered artifacts are
    # diffed row-by-row across PRs, so partial row sets break the
    # comparison (pass --out explicitly to place one deliberately).
    # --only wins over --smoke so a filtered smoke run can never overwrite
    # the full-row BENCH_smoke.json either.
    if args.out:
        out = args.out
    elif args.only:
        out = os.path.join(BENCH_DIR, "BENCH_partial.json")
    elif args.smoke:
        out = os.path.join(BENCH_DIR, "BENCH_smoke.json")
    else:
        _warn_trajectory_gaps()
        out = _next_artifact_path()
    with open(out, "w") as f:
        json.dump({
            "created": time.strftime("%Y-%m-%dT%H:%M:%S"),
            "mode": "smoke" if args.smoke else "full",
            "only": args.only,
            "failed": failed,
            "rows": rows,
        }, f, indent=1)
    print(f"# perf artifact -> {out}", file=sys.stderr)
    if timed_out:
        raise SystemExit(f"{failed[-1]} timed out after {args.timeout:.0f}s; "
                         "run stopped (its thread still holds the device)")
    if failed:
        raise SystemExit(f"benches failed: {failed}")


if __name__ == "__main__":
    from repro.utils.compile_cache import enable_compile_cache

    enable_compile_cache()
    main()
