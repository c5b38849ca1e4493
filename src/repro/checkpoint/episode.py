"""Mid-episode snapshot/resume for ``run_episode`` / ``run_fleet``.

Long trace replays and fleet sweeps die to preemption hours in; this
module makes them durable without touching the traced step. The episode
is cut into host-level *segments* of ``round(snapshot_every_s/cfg.dt)``
ticks, each executed by ``sim.run_segment`` — the exact
``summary_only``/``macro`` program bodies threading a RAW (un-finalized)
``TelemetrySummary`` accumulator — and after every segment a
crash-atomic checkpoint (``checkpoint.ckpt``: tmp-then-rename) captures

    {"state": SimState (PRNG key via key_data), "acc": raw accumulator}

plus a run *fingerprint* in the manifest (digests of cfg, scheduler/
policies, statics, the caller's workload table, the initial PRNG stream,
``n_steps`` and forwarded kwargs). Resume recomputes the fingerprint
from the caller's arguments and refuses — with a typed
:class:`~repro.utils.errors.CheckpointError` naming the diverging
component — to splice a snapshot into a different run.

Bit-identity guarantee (pinned by ``tests/test_snapshot.py`` and the
chaos harness): kill at ANY snapshot boundary, resume, and the final
``SimState`` (every leaf, PRNG stream included), ``TelemetrySummary``
and ``summary()`` dict are bit-identical to the same run left
uninterrupted — segment edges clamp the macro fast-forward exactly like
``telemetry_every`` window edges (PR 5's contract), per-tick scans split
associatively at tick boundaries, finalization (the mean_*/n division)
happens once at the end, and fleet PRNG keys are split/folded ONCE per
run then carried through snapshots. The device mesh is deliberately NOT
fingerprinted: sharded fleets are bit-identical to vmapped ones, so a
sweep may resume on a different device count (elastic restart).
"""

from __future__ import annotations

import hashlib
from functools import partial
from typing import Any, Dict, Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro.checkpoint import ckpt
from repro.utils.errors import CheckpointError, ConfigError

FINGERPRINT_SCHEMA = 1


def _digest(s: str) -> str:
    return hashlib.sha256(s.encode()).hexdigest()[:16]


def _tree_digest(tree: Any) -> str:
    """Order-stable digest over a pytree's leaf names, dtypes and bytes."""
    from repro.utils.tree import tree_map_with_path_names

    h = hashlib.sha256()

    def visit(name, leaf):
        x = leaf
        if ckpt._is_key_array(x):
            x = jax.random.key_data(x)
        arr = np.asarray(jax.device_get(x))
        h.update(name.encode())
        h.update(str(arr.dtype).encode())
        h.update(str(arr.shape).encode())
        h.update(arr.tobytes())
        return leaf

    tree_map_with_path_names(visit, tree)
    return h.hexdigest()[:16]


def _sched_token(scheduler) -> str:
    if isinstance(scheduler, str):
        return f"name:{scheduler}"
    # placement.Policy (possibly batched): ids are concrete at the host level
    sel = np.asarray(jax.device_get(scheduler.select)).tolist()
    plc = np.asarray(jax.device_get(scheduler.place)).tolist()
    return f"policy:{sel}/{plc}"


# SimState fields that define the WORKLOAD a run was started with — the
# job table installed by load_jobs plus the banked-trace selector.
_WORKLOAD_FIELDS = ("submit_t", "dur_est", "n_nodes", "req", "part",
                    "priority", "ckpt_interval", "workload")


def run_fingerprint(
    kind: str,
    cfg,
    scheduler,
    statics,
    state,
    n_steps: int,
    kw: Dict[str, Any],
    *,
    extra: Optional[Dict[str, Any]] = None,
) -> Dict[str, Any]:
    """Component-wise fingerprint of a (fleet) episode's launch arguments.

    Computed from the CALLER's arguments both at run start and at resume
    — never from the evolving snapshot — so every component is a pure
    function of "what run did you ask for". Kept component-wise (not one
    rolled-up hash) so a mismatch can name the part that diverged.
    """
    fp = {
        "schema": FINGERPRINT_SCHEMA,
        "kind": kind,
        "cfg": _digest(repr(cfg)),
        "scheduler": _digest(_sched_token(scheduler)),
        "statics": _tree_digest(statics),
        "workload": _tree_digest(
            {f: getattr(state, f) for f in _WORKLOAD_FIELDS}),
        "prng": _tree_digest({"key": state.key}),
        "n_steps": int(n_steps),
        "kw": _digest(repr(tuple(sorted((k, repr(v)) for k, v in kw.items())))),
    }
    fp.update(extra or {})
    return fp


def check_fingerprint(saved: Dict[str, Any], want: Dict[str, Any],
                      directory: str) -> None:
    """Raise a loud, component-naming ``CheckpointError`` on mismatch."""
    bad = sorted(
        k for k in set(saved) | set(want) if saved.get(k) != want.get(k))
    if bad:
        detail = "; ".join(
            f"{k}: checkpoint={saved.get(k)!r} vs current={want.get(k)!r}"
            for k in bad)
        raise CheckpointError(
            f"snapshot in {directory} belongs to a different run — "
            f"mismatched fingerprint component(s) {bad} ({detail}). "
            "Pass the same cfg/scheduler/statics/workload/seed/n_steps "
            "the snapshot was written with, or point resume_from at the "
            "right directory.", field=",".join(bad))


def _restore_latest(directory: str, like: Dict[str, Any],
                    want_fp: Dict[str, Any]):
    """(tree, ticks) from the newest snapshot, or (None, 0) if none yet.

    An empty/missing directory is NOT an error: a run killed before its
    first snapshot legitimately resumes from t=0.
    """
    step = ckpt.latest_step(directory)
    if step is None:
        return None, 0
    meta = ckpt.read_meta(directory, step)
    extra = meta.get("extra", {})
    check_fingerprint(extra.get("fingerprint", {}), want_fp, directory)
    tree = ckpt.restore(directory, step, like)
    return tree, int(extra["ticks"])


# Single-episode segment under jit — scheduler strings ride the static
# cache; Policy schedulers are traced data (policy is not None wins).
@partial(jax.jit,
         static_argnames=("cfg", "n_ticks", "sched_name", "kw_items",
                          "macro"))
def _episode_segment(cfg, statics, state, acc, policy, n_ticks, sched_name,
                     kw_items, macro):
    from repro.core.sim import run_segment

    who = sched_name if policy is None else policy
    return run_segment(cfg, statics, state, acc, n_ticks, who, macro=macro,
                       **dict(kw_items))


def _snapshot_plan(cfg, n_steps: int, snapshot_every_s, telemetry_every: int,
                   summary_only: bool, macro: bool) -> int:
    """Validate the mode and return the segment length in ticks."""
    if telemetry_every > 1 or not (summary_only or macro):
        raise ConfigError(
            "snapshot/resume needs an episode-wide summary so the "
            "telemetry accumulator can ride in the checkpoint: pass "
            "summary_only=True (or macro=True) and telemetry_every<=1; "
            f"got summary_only={summary_only}, macro={macro}, "
            f"telemetry_every={telemetry_every}")
    if snapshot_every_s is None or not np.isfinite(snapshot_every_s):
        return int(n_steps)
    if snapshot_every_s <= 0:
        raise ConfigError(
            f"snapshot_every_s must be positive (or None/inf to snapshot "
            f"only at episode end), got {snapshot_every_s}")
    return max(1, int(round(float(snapshot_every_s) / float(cfg.dt))))


def run_episode_snapshotted(
    cfg,
    statics,
    state,
    n_steps: int,
    scheduler,
    *,
    telemetry_every: int,
    summary_only: bool,
    macro: bool,
    snapshot_every_s,
    snapshot_dir: Optional[str],
    resume_from: Optional[str],
    snapshot_keep: int,
    kw: Dict[str, Any],
):
    """Host-level segmented drive of one episode (see module docstring)."""
    from repro.core import sim
    from repro.utils import invariants

    if isinstance(state.t, jax.core.Tracer):
        raise ConfigError(
            "snapshotting is host-level orchestration (it writes files "
            "between segments); call run_episode eagerly, not under "
            "jit/vmap — wrap only the snapshot-free path in jit")
    seg_ticks = _snapshot_plan(cfg, n_steps, snapshot_every_s,
                               telemetry_every, summary_only, macro)
    if snapshot_dir is None:
        snapshot_dir = resume_from
    fp = run_fingerprint("episode", cfg, scheduler, statics, state,
                         n_steps, kw)
    acc = sim.telem_zero(cfg, statics)
    ticks = 0
    if resume_from is not None:
        tree, ticks = _restore_latest(
            resume_from, {"state": state, "acc": acc}, fp)
        if tree is not None:
            state, acc = tree["state"], tree["acc"]

    sched_name = scheduler if isinstance(scheduler, str) else None
    policy = None if isinstance(scheduler, str) else scheduler
    kw_items = tuple(sorted(kw.items()))
    # with the checkify harness on, drive segments eagerly so the
    # per-committed-step invariant suite runs exactly as in run_episode
    eager_check = invariants.enabled()
    while ticks < n_steps:
        n = int(min(seg_ticks, n_steps - ticks))
        if eager_check:
            state, acc = sim.run_segment(
                cfg, statics, state, acc, n, scheduler, macro=macro, **kw)
        else:
            state, acc = _episode_segment(
                cfg, statics, state, acc, policy, n, sched_name, kw_items,
                macro)
        ticks += n
        if snapshot_dir is not None:
            ckpt.save(snapshot_dir, ticks, {"state": state, "acc": acc},
                      extra_meta={"ticks": ticks, "fingerprint": fp},
                      keep=snapshot_keep)
    return state, sim.telem_finalize(acc)


def run_fleet_snapshotted(
    cfg,
    statics,
    scenarios,
    policies,
    state,
    keys,
    n_steps: int,
    scheduler: str,
    kw: Dict[str, Any],
    *,
    mesh,
    mesh_axis: str,
    snapshot_every_s,
    snapshot_dir: Optional[str],
    resume_from: Optional[str],
    snapshot_keep: int,
):
    """Segmented fleet sweep: one snapshot covers the whole replica batch.

    ``state`` arrives replica-batched with ``keys`` already derived by
    ``run_fleet``'s normal split/fold_in schedule; they are installed
    into ``state.key`` HERE, once, so segments (and resumed runs) never
    re-derive them — the per-replica streams are bit-identical to the
    single-call fleet.
    """
    from repro.core import fleet, sim

    seg_ticks = _snapshot_plan(
        cfg, n_steps, snapshot_every_s, kw.get("telemetry_every", 1),
        kw.get("summary_only", False), kw.get("macro", False))
    if snapshot_dir is None:
        snapshot_dir = resume_from
    state = state._replace(key=keys)
    R = int(jnp.shape(state.t)[0])
    fp = run_fingerprint(
        "fleet", cfg, scheduler, statics, state, n_steps, kw,
        extra={
            "replicas": R,
            "scenarios": _tree_digest(scenarios),
            "policies": "none" if policies is None
            else _tree_digest(policies),
        })
    acc = jax.tree.map(lambda a: jnp.broadcast_to(a, (R,) + jnp.shape(a)),
                       sim.telem_zero(cfg, statics))
    ticks = 0
    if resume_from is not None:
        tree, ticks = _restore_latest(
            resume_from, {"state": state, "acc": acc}, fp)
        if tree is not None:
            state, acc = tree["state"], tree["acc"]

    while ticks < n_steps:
        n = int(min(seg_ticks, n_steps - ticks))
        state, acc = fleet.fleet_segment(
            cfg, statics, scenarios, policies, state, acc, n, scheduler, kw,
            mesh=mesh, mesh_axis=mesh_axis)
        ticks += n
        if snapshot_dir is not None:
            ckpt.save(snapshot_dir, ticks, {"state": state, "acc": acc},
                      extra_meta={"ticks": ticks, "fingerprint": fp},
                      keep=snapshot_keep)
    return state, jax.vmap(sim.telem_finalize)(acc)


__all__ = [
    "run_fingerprint",
    "check_fingerprint",
    "run_episode_snapshotted",
    "run_fleet_snapshotted",
]
