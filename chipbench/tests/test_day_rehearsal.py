"""The day cell's harness end to end on the host CPU at tiny size: a trace
of seven times the 64-slot job table streams through it, one segment per
call, and the answers agree with the plain reference; a planted fault in
admission, or a loader that keeps only what fits the table, gives no
correct result."""

import json
import os

import pytest

from tiny_cells import tiny_files, write_tree

TINY_DAY = "replay.tiny.day"


def day_files() -> dict:
    """The tiny benchmark tree with a tiny day cell beside its cells."""
    files = tiny_files()
    with open(os.path.join(os.path.dirname(__file__), "..", "traffic",
                           "replay_day.json")) as f:
        mix = json.load(f)
    mix.update(n_jobs=448, arrival_span_s=6480.0, mean_dur_s=40.0,
               max_dur_s=1800.0, ticks=7200, segment_ticks=600)
    bench = files["BENCHMARK.json"]
    bench["workloads"].append({"name": TINY_DAY, "config": "tiny",
                               "traffic": "tiny_day", "chips": 1,
                               "why": "CPU rehearsal"})
    for m in bench["per_layer"]:
        if m["name"] == "table_occupancy":
            m["workloads"] = [TINY_DAY]
    files["chipbench/traffic/tiny_day.json"] = mix
    files[f"chipbench/limits/{TINY_DAY}.json"] = {"job_mismatch": 0.0,
                                                  "accum_rel_err": 1e-4}
    return files


@pytest.fixture
def day(tmp_path, monkeypatch):
    """chipbench.run pointed at a tree of tiny cells, the day cell too."""
    import tiny_cells
    from chipbench import run

    monkeypatch.setattr(tiny_cells, "tiny_files", day_files)
    root = write_tree(str(tmp_path))
    monkeypatch.setattr(run, "ROOT", root)
    monkeypatch.setattr(run, "BENCH", os.path.join(root, "chipbench"))
    monkeypatch.setattr(run, "CACHE", os.path.join(root, ".cache"))
    return run


def _run(run, hook=None, seed=2**31 + 5):
    return run.run_cell(TINY_DAY, seed, 1.0, False, require_tpu=False,
                        driver_hook=hook)


def test_day_driver_matches_reference(day, capsys):
    out, answers = _run(day)
    window = json.loads(capsys.readouterr().out.splitlines()[-1])["window"]
    assert window["compiles"] == 0, window
    assert out["correct"], out["checks"]
    assert out["checks"]["job_mismatch"]["value"] == 0.0
    # float32 integrals over a day of ticks: above the hour cells' 1e-5
    assert out["checks"]["accum_rel_err"]["value"] < 1e-4
    assert set(out["metrics"]) == {"sim_s_per_s", "setup_s"}
    # the trace streamed: the answers held all 448 jobs (the comparison
    # reads every job of the CSVs), the table refilled many times over,
    # and no due job ever found it full
    assert window["admitted"] > 448 - 64
    assert window["admit_overflow"] == 0.0
    # the window ran past a whole day, which is one of the answers
    assert 7200 in {a["ticks"] for a in answers}
    occ = day.read_metric("table_occupancy", {"counters": window})
    assert 0.0 < occ < 1.0


def _skip_refill(admit):
    """Admission that advances the cursor past one trace job on every
    tick it refills a slot: that job never enters the table."""
    def broken(state, statics):
        state, admitted, overflow = admit(state, statics)
        st = state.stream
        return (state._replace(stream=st._replace(
            cursor=st.cursor + (admitted > 0))), admitted, overflow)
    return broken


def _reverse_refill(admit):
    """Admission that takes the pending trace jobs from the trace's end."""
    def broken(state, statics):
        import jax.numpy as jnp

        n = statics.trace.submit_t.shape[0]
        mirror = lambda a: a[..., ::-1]
        flipped = statics._replace(trace=statics.trace._replace(**{
            k: mirror(getattr(statics.trace, k))
            for k in ("submit_t", "dur", "n_nodes", "req", "part",
                      "priority", "ckpt_interval")}))
        state, admitted, overflow = admit(state, flipped)
        st = state.stream
        j = state.jstate.shape[0]
        tid = jnp.where(st.tid >= j, n - 1 - st.tid + j, st.tid)
        return (state._replace(stream=st._replace(tid=tid)), admitted,
                overflow)
    return broken


@pytest.mark.parametrize("fault,caught_by", [
    (_skip_refill, "comparison"), (_reverse_refill, "overflow guard")],
    ids=["refill-skipped", "refill-out-of-order"])
def test_planted_admission_fault_is_not_correct(day, monkeypatch, fault,
                                                caught_by):
    from repro.core import sim

    monkeypatch.setattr(sim, "_admit", fault(sim._admit))
    if caught_by == "overflow guard":
        # the trace's first jobs fall due outside a table that is full
        with pytest.raises(RuntimeError, match="outside the full"):
            _run(day)
        return
    out, _ = _run(day)
    assert out["correct"] is False
    assert out["failed"] > 0


def test_ties_by_slot_are_not_correct(day, monkeypatch):
    """Selection that breaks ties by slot index, as the resident table
    may: once slots are reused, jobs submitted in the same second start
    in another order than the trace's, on other nodes."""
    from repro.core import schedulers

    monkeypatch.setattr(schedulers, "_pick", lambda state, score, mask:
                        schedulers._masked_argmin(score, mask))
    out, _ = _run(day)
    assert out["correct"] is False
    assert out["checks"]["job_mismatch"]["value"] > 0


def test_truncating_loader_fails_in_setup(day, monkeypatch):
    """A program whose loader keeps only the jobs that fit the table (as
    before streamed admission) fails before any window."""
    from chipbench.drivers import replay_day

    load = replay_day.load_supercloud

    def truncating(path, cfg, **kw):
        jobs, bank, rep = load(path, cfg, **kw)
        j = cfg.max_jobs
        jobs = {k: v[..., :j] if k == "req" else v[:j]
                for k, v in jobs.items()}
        return jobs, {k: v[:j] for k, v in bank.items()}, rep

    monkeypatch.setattr(replay_day, "load_supercloud", truncating)
    reached = []
    with pytest.raises(RuntimeError, match="64 of the trace's 448 jobs"):
        _run(day, hook=reached.append)
    assert not reached
