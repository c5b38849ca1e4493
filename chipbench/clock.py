"""Compile clock, copied from the twin's ``chip_smoke.CompileClock``: the
union of JAX's trace, lower and backend-compile spans (they nest for inner
jits) over an interval, and a count of backend compiles."""

from __future__ import annotations

import jax


class CompileClock:
    _EVENTS = ("/jax/core/compile/jaxpr_trace_duration",
               "/jax/core/compile/jaxpr_to_mlir_module_duration",
               "/jax/core/compile/backend_compile_duration")
    _BACKEND = "/jax/core/compile/backend_compile_duration"

    def __init__(self):
        self.spans = []

    def _listen(self, event, start, end, **_):
        if event in self._EVENTS:
            self.spans.append((start, end, event))

    def compile_s(self, t0: float, t1: float) -> float:
        total, covered = 0.0, t0
        for s, e, _ in sorted(self.spans):
            s, e = max(s, covered), min(e, t1)
            if e > s:
                total += e - s
                covered = e
        return total

    def compiles(self, t0: float, t1: float) -> int:
        """Backend compiles that started inside [t0, t1]."""
        return sum(1 for s, _, ev in self.spans
                   if ev == self._BACKEND and t0 <= s <= t1)

    def __enter__(self):
        jax.monitoring.register_event_time_span_listener(self._listen)
        return self

    def __exit__(self, *exc):
        jax.monitoring.unregister_event_time_span_listener(self._listen)
