"""Run one benchmark cell once on the chip this process is started on.

    python3 chipbench/run.py --workload <cell> --seed <n> --seconds <s> \
        --trace <0|1>

Loads the cell named in ``BENCHMARK.json`` (its configuration file under
``chipbench/configs/``, its traffic file under ``chipbench/traffic/``),
builds the inputs from ``--seed``, compiles and warms up every shape the
window uses (set-up), drives the window for ``--seconds``, checks the
window's answers against the plain reference, and prints one JSON object
as the last line of standard output. With ``--trace 0`` its metrics are
the cell's end-to-end metrics; with ``--trace 1`` the window runs under
the profiler and the metrics are the cell's per-layer metrics, each read
by its own reader under ``chipbench/metrics/``.

Exits non-zero, printing no result, when JAX finds no TPU or fewer chips
than the cell asks for.
"""

import time

T_START = time.time()

import argparse  # noqa: E402
import gc  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
import tempfile  # noqa: E402

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
BENCH = os.path.join(ROOT, "chipbench")
CACHE = os.path.join(ROOT, ".chipbench_cache")
# the profiler records every device op (~2e5 a second in the replay), so
# a traced run records only the first calls of its window, up to this
TRACE_S = 10.0
for p in (os.path.join(ROOT, "src"), ROOT):
    if p not in sys.path:
        sys.path.insert(0, p)


def load_cell(name: str) -> dict:
    """The cell's entry, configuration, traffic mix, limits and metrics,
    all found by the names in ``BENCHMARK.json``."""
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        bench = json.load(f)
    cells = {w["name"]: w for w in bench["workloads"]}
    if name not in cells:
        raise SystemExit(f"unknown workload {name!r}; one of {sorted(cells)}")
    cell = cells[name]
    conf = next(c for c in bench["configs"] if c["name"] == cell["config"])
    with open(os.path.join(ROOT, conf["file"])) as f:
        config = json.load(f)
    with open(os.path.join(BENCH, "traffic", cell["traffic"] + ".json")) as f:
        mix = json.load(f)
    with open(os.path.join(BENCH, "limits", name + ".json")) as f:
        limits = json.load(f)

    def reports(m):
        return "workloads" not in m or name in m["workloads"]

    e2e = [m for m in bench["end_to_end"] if reports(m)]
    e2e_names = {m["name"] for m in e2e}
    layer = [m for m in bench["per_layer"]
             if name in m.get("workloads", ())
             or ("workloads" not in m and m["moves"] in e2e_names)]
    return {"cell": cell, "config": config, "mix": mix, "limits": limits,
            "end_to_end": e2e, "per_layer": layer}


def read_metric(name: str, ctx: dict):
    """The value that ``chipbench/metrics/<name>.py`` reads, or None."""
    import importlib.util

    path = os.path.join(BENCH, "metrics", name + ".py")
    spec = importlib.util.spec_from_file_location(
        "chipbench_metric_" + name.replace(".", "_"), path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read(ctx)


def chips_or_exit(need: int):
    """The first ``need`` TPU devices, of a kind in ``peaks.json``; exits
    2 without a result otherwise."""
    import jax

    devs = jax.devices()
    if devs[0].platform != "tpu" or len(devs) < need:
        print(f"chipbench: need {need} TPU chip(s), JAX found "
              f"{len(devs)} {devs[0].platform} device(s)", file=sys.stderr)
        sys.exit(2)
    with open(os.path.join(BENCH, "peaks.json")) as f:
        kinds = json.load(f)["devices"]
    if devs[0].device_kind not in kinds:
        print(f"chipbench: no peaks for device kind {devs[0].device_kind!r} "
              "in chipbench/peaks.json", file=sys.stderr)
        sys.exit(2)
    return devs[:need]


def run_cell(name: str, seed: int, seconds: float, trace: bool,
             require_tpu: bool = True, t_start: float | None = None,
             driver_hook=None) -> tuple:
    """One run of one cell: (the result object, the answers compared,
    each with the run's reading). ``driver_hook`` lets a test break the
    timed path underneath the harness."""
    spec = load_cell(name)
    import jax

    jax.config.update("jax_compilation_cache_dir", os.path.join(CACHE, "jax"))
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    cell = spec["cell"]
    devs = chips_or_exit(cell["chips"]) if require_tpu else \
        jax.devices()[:cell["chips"]]

    from chipbench import compare, reference
    from chipbench.clock import CompileClock
    import importlib
    from chipbench.trace_reduce import reduce_trace

    t_start = T_START if t_start is None else t_start
    with CompileClock() as clock, tempfile.TemporaryDirectory() as tmp:
        driver = importlib.import_module(
            "chipbench.drivers." + spec["mix"]["driver"]).DRIVER
        drv = driver(
            spec["config"]["sim"], spec["mix"], seed, cell["chips"], tmp)
        if driver_hook is not None:
            driver_hook(drv)
        drv.warm()
        # host steadiness: what set-up left alive is frozen out of the
        # interpreter's cyclic collector, which stays off in the window, so
        # no full collection over JAX's objects stalls a call in it
        gc.collect()
        gc.freeze()
        gc.disable()
        t_setup = time.time()
        trace_dir = os.path.join(CACHE, "trace")
        traced = None
        if trace:
            shutil.rmtree(trace_dir, ignore_errors=True)
            jax.profiler.start_trace(trace_dir)
            with jax.profiler.TraceAnnotation("window"):
                window_s = drv.window(min(seconds, TRACE_S))
            jax.profiler.stop_trace()
            traced = dict(drv.counters)
            if seconds > window_s:
                window_s += drv.window(seconds - window_s)
        else:
            window_s = drv.window(seconds)
        gc.enable()
        t_end = time.time()
        peak = max((d.memory_stats() or {}).get("peak_bytes_in_use", 0)
                   for d in devs)
        compiles = clock.compiles(t_setup, t_end)
        print(json.dumps({"window": {"seconds": window_s, "compiles": compiles,
                                     **drv.counters}}), flush=True)
        answers = drv.answers()
        drv.release()

        sim = spec["config"]["sim"]
        refs = reference.records(
            sim, reference.read_dataset(drv.data_dir, sim), answers)
        results = []
        for a, ref in zip(answers, refs):
            r = compare.compare(a.pop("got"), ref)
            r["what"] = a["what"]
            a["reading"] = r
            results.append(r)
        verdict = compare.judge(results, spec["limits"])

        c = drv.counters
        ctx = {"compile_s": clock.compile_s(t_start, t_setup),
               "counters": c, "traced": traced, "window_s": window_s,
               "chips": cell["chips"]}
        device = {"platform": devs[0].platform, "kind": devs[0].device_kind,
                  "count": len(devs), "memory_peak_bytes": int(peak)}
        out = {"correct": verdict["correct"], "attempted": len(results),
               "failed": verdict["failed"]}
        if trace:
            red = reduce_trace(trace_dir, n_chips=cell["chips"])
            shutil.rmtree(trace_dir, ignore_errors=True)
            ctx["trace"] = red
            device.update(busy_s=red["busy_s"], window_s=red["window_s"])
            out["breakdown"] = red["breakdown"]
            wanted = spec["per_layer"]
            vals = {m["name"]: read_metric(m["name"], ctx) for m in wanted}
        else:
            wanted = spec["end_to_end"]
            vals = {"setup_s": t_setup - t_start, **drv.end_to_end(window_s)}
        out["metrics"] = {m["name"]: {"value": vals[m["name"]], "unit": m["unit"]}
                          for m in wanted if vals.get(m["name"]) is not None}
        out["device"] = device
        out["checks"] = verdict["checks"]
    worst = max(results, key=lambda r: (r["job_mismatch"], r["accum_rel_err"]),
                default=None)
    if worst is not None:
        print(f"worst answer: {worst['what']}, integral "
              f"{worst['worst_integral']}", file=sys.stderr)
    for k, v in verdict["checks"].items():
        print(f"check {k}: {v['value']!r} (limit {v['limit']!r})",
              file=sys.stderr)
    return out, answers


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    out, _ = run_cell(args.workload, args.seed, args.seconds,
                      bool(args.trace))
    print(json.dumps(out), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
