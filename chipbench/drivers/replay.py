"""``replay``: ``run_episode(..., macro=True)`` over a SuperCloud-schema
trace, one whole episode per call, back to back."""

from __future__ import annotations

import time

import jax
import numpy as np

from chipbench.drivers.common import Driver, annotate, answer_of
from repro.core import run_episode


class Replay(Driver):
    def __init__(self, *a, **k):
        super().__init__(*a, **k)
        cfg, mix = self.cfg, self.mix
        self.fn = jax.jit(lambda statics, s: run_episode(
            cfg, statics, s, int(mix["ticks"]), mix["select"],
            placement=mix["place"], macro=True))
        self.outs = []

    def warm(self):
        jax.device_get(self.fn(self.statics, self.state0)[1])

    def window(self, seconds: float) -> float:
        t0 = time.perf_counter()
        while True:
            with annotate("window.episode"):
                fs, tel = self.fn(self.statics, self.state0)
            with annotate("host.summary"):
                tel = jax.device_get(tel)
            self._count(tel.n_steps, tel.macro_steps)
            self.outs.append(fs)
            if time.perf_counter() - t0 >= seconds:
                return time.perf_counter() - t0

    def answers(self) -> list:
        """Episodes drawn from the seed (the last always among them)."""
        rng = np.random.default_rng(self.seed)
        n = len(self.outs)
        pick = sorted({n - 1, *rng.choice(n, min(2, n), replace=False)})
        ticks = int(self.mix["ticks"])
        return [{"what": f"episode {i}", "select": self.mix["select"],
                 "place": self.mix["place"], "scenario": self.scenarios[0],
                 "ticks": ticks, "got": answer_of(self.outs[i], self.n_jobs)}
                for i in pick]

    def release(self):
        self.outs.clear()


DRIVER = Replay
