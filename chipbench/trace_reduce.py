"""Reduce a profiler trace (``.xplane.pb``) to the benchmark's device
numbers.

- busy: the union of the intervals in which an operation ran on a chip
  (the ``XLA Ops`` line of each ``/device:TPU:<n>`` plane), clipped to
  the harness's ``window`` span on the host;
- idle share: 1 - busy / window, per chip;
- top device ops: self seconds per op name (less the ops nested in it,
  as a while loop's body is), averaged over the chips;
- idle gaps: the longest stretches in which chip 0 ran nothing, each
  named by the innermost harness span (``window.*``, ``host.*``) that the
  host was in at the gap's middle.

    python -m chipbench.trace_reduce <trace dir>   # prints the reduction
"""

from __future__ import annotations

import glob
import json
import os
import re
import sys

DEVICE = re.compile(r"^/device:TPU:(\d+)$")
OPS_LINE = "XLA Ops"
TOP = 10


def newest_xplane(trace_dir: str) -> str:
    found = glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                      recursive=True)
    if not found:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    return max(found, key=os.path.getmtime)


def union(intervals) -> list:
    """Sorted, merged [start, end) intervals."""
    out = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return out


def self_times(events):
    """(name, self time) per event: its length less that of the events
    nested inside it (a while loop's op holds its body's ops)."""
    out, stack = [], []          # stack of [name, start, end, child time]
    for n, s, e in sorted(events, key=lambda x: (x[1], -x[2])):
        while stack and s >= stack[-1][2]:
            top = stack.pop()
            out.append((top[0], top[2] - top[1] - top[3]))
        if stack:
            stack[-1][3] += e - s
        stack.append([n, s, e, 0.0])
    out += [(n, e - s - kids) for n, s, e, kids in stack]
    return out


def _harness_spans(space):
    spans = []
    for plane in space.planes:
        if not plane.name.startswith("/host:"):
            continue
        for line in plane.lines:
            for ev in line.events:
                n = ev.name
                if n == "window" or n.startswith(("window.", "host.")):
                    spans.append((n, ev.start_ns, ev.start_ns + ev.duration_ns))
    return spans


def reduce_space(space, n_chips: int) -> dict:
    spans = _harness_spans(space)
    devices = {}
    for plane in space.planes:
        m = DEVICE.match(plane.name)
        if not m:
            continue
        ops = [(ev.name, ev.start_ns, ev.start_ns + ev.duration_ns)
               for line in plane.lines if line.name == OPS_LINE
               for ev in line.events]
        devices[int(m.group(1))] = ops
    if not devices:
        raise ValueError("trace has no TPU device plane")
    chips = sorted(devices)[:n_chips]
    win = [s for s in spans if s[0] == "window"]
    if win:
        w0, w1 = win[0][1], win[0][2]
    else:
        w0 = min(s for c in chips for _, s, _ in devices[c])
        w1 = max(e for c in chips for _, _, e in devices[c])
    window_s = (w1 - w0) * 1e-9
    busy, per_op = [], {}
    for c in chips:
        clipped = [(n, max(s, w0), min(e, w1)) for n, s, e in devices[c]
                   if e > w0 and s < w1]
        for n, t in self_times(clipped):
            per_op[n] = per_op.get(n, 0.0) + t * 1e-9 / len(chips)
        merged = union((s, e) for _, s, e in clipped)
        busy.append(sum(e - s for s, e in merged) * 1e-9)
        if c == chips[0]:
            gaps, prev = [], w0
            for s, e in merged + [[w1, w1]]:
                if s > prev:
                    gaps.append((prev, s))
                prev = max(prev, e)
    named = []
    for g0, g1 in gaps:
        mid = (g0 + g1) / 2
        inside = [sp for sp in spans if sp[1] <= mid < sp[2]]
        name = min(inside, key=lambda sp: sp[2] - sp[1])[0] if inside \
            else "outside harness spans"
        named.append([name, (g1 - g0) * 1e-9])
    named.sort(key=lambda x: -x[1])
    ops = sorted(per_op.items(), key=lambda x: -x[1])[:TOP]
    return {"busy_s": sum(busy) / len(busy), "busy_s_chips": busy,
            "window_s": window_s,
            "idle_frac": [1.0 - b / window_s for b in busy],
            "breakdown": {"device_ops": [[n, s] for n, s in ops],
                          "idle_gaps": named[:TOP]}}


def reduce_trace(trace_dir: str, n_chips: int = 1) -> dict:
    from jax.profiler import ProfileData

    return reduce_space(ProfileData.from_file(newest_xplane(trace_dir)),
                        n_chips)


if __name__ == "__main__":
    print(json.dumps(reduce_trace(sys.argv[1], int(sys.argv[2])
                                  if len(sys.argv) > 2 else 1), indent=1))
