"""From a profiler trace to the numbers the per-layer readers need.

A JAX profiler trace (``.xplane.pb``, read with ``jax.profiler.ProfileData``)
holds one plane per device (``/device:TPU:<i>``) with the lines ``XLA
Modules`` (each program run) and ``XLA Ops`` (each operation; a loop's
operation spans the operations of its body), and a host plane whose
``python`` line holds the benchmark's ``bench.step`` annotations and the
Python calls under them.

* The window is the span of the ``bench.step`` annotations: whole steps of
  the system under test, without the profiler's own start and stop.
* Busy time is the union of the program runs on a device inside the
  window, averaged over the devices.
* Operation time is an operation's own time: its span less the spans of the
  operations nested in it.  Pallas kernels are ``custom-call`` operations
  with ``custom_call_target="tpu_custom_call"``; a reader tells its kernel
  apart by a pattern on the operation's text.
* Each idle gap inside the window is put down to the innermost host Python
  call that spans its middle.
"""

from __future__ import annotations

import bisect
import dataclasses
import glob
import json
import re
from collections import defaultdict

STEP = "bench.step"


def load_events(path: str) -> list:
    """Every event of a trace file as ``(plane, line, name, start_ns, dur_ns)``."""
    from jax.profiler import ProfileData

    pd = ProfileData.from_file(path)
    out = []
    for plane in pd.planes:
        for line in plane.lines:
            for ev in line.events:
                out.append((plane.name, line.name, ev.name, float(ev.start_ns), float(ev.duration_ns)))
    return out


def _union(intervals):
    total, end = 0.0, float("-inf")
    for s, e in sorted(intervals):
        if e <= end:
            continue
        total += e - max(s, end)
        end = e
    return total


def _merge(intervals):
    out = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return out


def _self_times(events):
    """Own time of each event of one line, nested events subtracted."""
    evs = sorted(events, key=lambda e: (e[0], -e[1]))
    own = [d for _, d, _ in evs]
    stack = []
    for i, (s, d, _) in enumerate(evs):
        while stack and evs[stack[-1]][0] + evs[stack[-1]][1] <= s:
            stack.pop()
        if stack:
            own[stack[-1]] -= d
        stack.append(i)
    return [(evs[i][2], max(own[i], 0.0)) for i in range(len(evs))]


def label(op: str) -> str:
    """A short name for an operation's text: its kind, result and name."""
    m = re.match(r"%?(\S+) = (.+?) ([a-z][a-z\-]*)\(", op)
    if not m:
        return op[:120]
    name, shape, kind = m.groups()
    if kind == "custom-call":
        kind = "pallas" if "tpu_custom_call" in op else kind
    return f"{kind} {shape[:60]} {name}"


@dataclasses.dataclass
class Reduced:
    window_s: float
    busy_s: float
    ops: dict      # op text -> own seconds (averaged over devices)
    modules: dict  # program name -> seconds
    gaps: dict     # host call -> idle seconds

    def op_seconds(self, pattern: str) -> float:
        rx = re.compile(pattern, re.S)
        return sum(v for k, v in self.ops.items() if rx.search(k))

    def top_ops(self, n: int) -> list:
        agg: dict = defaultdict(float)
        for k, v in self.ops.items():
            agg[label(k)] += v
        return [[k, v] for k, v in sorted(agg.items(), key=lambda kv: -kv[1])[:n]]

    def top_gaps(self, n: int) -> list:
        return [[k, v] for k, v in sorted(self.gaps.items(), key=lambda kv: -kv[1])[:n]]


def reduce_events(events, n_chips: int = 1) -> Reduced:
    steps = [(s, s + d) for p, l, n, s, d in events if n == STEP and p.startswith("/host")]
    if not steps:
        raise ValueError("the trace holds no bench.step annotation")
    w0, w1 = min(s for s, _ in steps), max(e for _, e in steps)
    devices = sorted({p for p, *_ in events if p.startswith("/device:")})[:n_chips]
    if not devices:
        raise ValueError("the trace holds no device plane")
    busy, idle_spans = 0.0, []
    ops: dict = defaultdict(float)
    modules: dict = defaultdict(float)
    for dev in devices:
        runs = [(max(s, w0), min(s + d, w1), n) for p, l, n, s, d in events
                if p == dev and l == "XLA Modules" and s + d > w0 and s < w1]
        busy += _union([(s, e) for s, e, _ in runs])
        for s, e, n in runs:
            modules[n.split("(")[0]] += (e - s) / 1e9 / len(devices)
        if dev == devices[0]:
            merged = _merge([(s, e) for s, e, _ in runs])
            edges = [w0] + [x for iv in merged for x in iv] + [w1]
            idle_spans = [(edges[i], edges[i + 1]) for i in range(0, len(edges), 2)
                          if edges[i + 1] > edges[i]]
        line = [(s, d, n) for p, l, n, s, d in events
                if p == dev and l == "XLA Ops" and s >= w0 and s + d <= w1]
        for n, own in _self_times(line):
            ops[n] += own / 1e9 / len(devices)
    host = sorted([(s, s + d, n) for p, l, n, s, d in events
                   if p.startswith("/host") and l.startswith("python") and n != STEP
                   and not n.startswith("$profiler.py")], key=lambda e: e[0])
    starts = [h[0] for h in host]
    gaps: dict = defaultdict(float)
    for s, e in idle_spans:
        mid = (s + e) / 2
        best = None
        for h in host[: bisect.bisect_right(starts, mid)]:
            if h[1] >= mid and (best is None or h[1] - h[0] <= best[1] - best[0]):
                best = h
        gaps[best[2] if best else "(no host call)"] += (e - s) / 1e9
    return Reduced(window_s=(w1 - w0) / 1e9, busy_s=busy / 1e9 / len(devices), ops=dict(ops),
                   modules=dict(modules), gaps=dict(gaps))


def reduce_dir(trace_dir, n_chips: int = 1) -> Reduced:
    files = sorted(glob.glob(f"{trace_dir}/**/*.xplane.pb", recursive=True))
    if not files:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    return reduce_events(load_events(files[-1]), n_chips)


def read_events(path: str) -> list:
    with open(path) as f:
        return [tuple(e) for e in json.load(f)]
