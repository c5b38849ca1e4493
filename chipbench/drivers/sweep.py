"""``sweep``: ``run_fleet(..., policies=, scenarios=, macro=True,
summary_only=True)`` over a policy x scenario grid in fixed segments,
chaining the replica-batched states and restarting from the initial
states at the end of each cycle; on a fleet mesh when the cell asks for
more than one chip.

Every call, the first of a cycle included, takes a replica-batched
state, so set-up and the window drive one compiled program."""

from __future__ import annotations

import time

import jax
import jax.numpy as jnp
import numpy as np

from chipbench.drivers.common import Driver, annotate, answer_of, scenario_of
from repro.core.fleet import policy_scenario_grid, run_fleet
from repro.core.placement import policy_grid


class Sweep(Driver):
    def __init__(self, *a, **k):
        super().__init__(*a, **k)
        mix = self.mix
        names, pols = policy_grid(mix["selects"], mix["places"])
        self.policy_names = [n.split("+") for n in names]
        scns = [scenario_of(s) for s in self.scenarios]
        self.pols, self.scns = policy_scenario_grid(pols, scns)
        self.R = len(names) * len(scns)
        self.seg = int(mix["segment_ticks"])
        self.cycle = int(mix["cycle_ticks"]) // self.seg
        self.mesh = None
        if self.chips > 1:
            from repro.launch.mesh import make_fleet_mesh
            self.mesh = make_fleet_mesh(self.chips)
        self.lanes = self.R // self.chips
        self.fleet0 = self._batched(self.state0)
        self.st, self.last, self.done = None, None, None
        self.k = 0

    def _batched(self, state):
        """``state`` on every replica, each with the key that ``run_fleet``
        splits off for it from an unbatched state."""
        keys = jax.random.split(state.key, self.R)
        fleet = jax.tree.map(
            lambda a: jnp.broadcast_to(a, (self.R,) + jnp.shape(a)),
            state)._replace(key=keys)
        if self.mesh is not None:
            from jax.sharding import NamedSharding, PartitionSpec
            fleet = jax.device_put(fleet, NamedSharding(
                self.mesh, PartitionSpec(self.mesh.axis_names[0])))
        return fleet

    def _fresh(self):
        """A copy of the initial fleet: every call donates its state."""
        return jax.tree.map(jnp.copy, self.fleet0)

    def _segment(self, st):
        return run_fleet(self.cfg, self.statics, st, self.seg,
                         policies=self.pols, scenarios=self.scns, macro=True,
                         summary_only=True, mesh=self.mesh)

    def warm(self):
        jax.device_get(self._segment(self._fresh())[1].n_steps)

    def window(self, seconds: float) -> float:
        t0 = time.perf_counter()
        st = self._fresh() if self.st is None else self.st
        while True:
            with annotate("window.segment"):
                st, tel = self._segment(st)
            with annotate("host.summary"):
                ms, ns = jax.device_get((tel.macro_steps, tel.n_steps))
            self._count(ns, ms, self.lanes)
            self.k += 1
            self.last = st
            if self.k == self.cycle:
                self.done, self.k, st = st, 0, self._fresh()
            if time.perf_counter() - t0 >= seconds:
                self.st = st
                return time.perf_counter() - t0

    def replicas(self) -> list:
        """One replica from each of eight equal blocks of the fleet, drawn
        from the seed, so every chip's block and both halves are seen."""
        rng = np.random.default_rng(self.seed)
        per = self.R // 8
        return [b * per + int(rng.integers(per)) for b in range(8)]

    def answers(self) -> list:
        ends = []
        if self.k:
            ends.append((self.last, self.k * self.seg))
        if self.done is not None:
            ends.append((self.done, self.cycle * self.seg))
        out = []
        n_scn = len(self.scenarios)
        for state, ticks in ends:
            for r in self.replicas():
                sel, place = self.policy_names[r // n_scn]
                out.append({"what": f"replica {r} at tick {ticks}",
                            "select": sel, "place": place,
                            "scenario": self.scenarios[r % n_scn],
                            "ticks": ticks,
                            "got": answer_of(state, self.n_jobs, r)})
        return out

    def release(self):
        self.st = self.last = self.done = self.fleet0 = None


DRIVER = Sweep
