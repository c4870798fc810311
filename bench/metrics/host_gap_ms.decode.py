"""Host loop between megasteps: milliseconds the device stood idle in the
traced window, per ``decode_megastep`` span the traced steps ran (program
spans).  The idle time is the window less the device's busy time, as
``trace_reduce`` reckons both; a device that is never idle reads 0.

Its split goes to ``counters.json`` as ``host_ms_by_span``: per program
span, the host's own milliseconds (its time less that of the program spans
inside it) per megastep, on ``perf_counter``, over the same steps.  Time in
``megastep_sync`` is the host waiting on the device; the rest is host work
the device may wait on.  Step time in no program span is ``(no span)``."""

import bisect
from collections import defaultdict


def _in_steps(spans, steps) -> list:
    """Per traced step, the spans that start and end inside it, in start order."""
    spans = sorted(((t0, t0 + d, n) for n, t0, d, _ in spans), key=lambda x: (x[0], -x[1]))
    starts = [s[0] for s in spans]
    return [[sp for sp in spans[bisect.bisect_left(starts, st["t0"]):
                                bisect.bisect_right(starts, st["t1"])] if sp[1] <= st["t1"]]
            for st in steps]


def _own_ms_by_span(per_step, steps) -> dict:
    """Own seconds per span name over the steps (spans nest by containment),
    and the steps' time in no span."""
    own: dict = defaultdict(float)
    for st, inside in zip(steps, per_step):
        stack: list = []
        covered = 0.0
        for t0, t1, n in inside:
            while stack and stack[-1][1] <= t0:
                stack.pop()
            if stack:
                own[stack[-1][2]] -= t1 - t0
            else:
                covered += t1 - t0
            own[n] += t1 - t0
            stack.append((t0, t1, n))
        own["(no span)"] += (st["t1"] - st["t0"]) - covered
    return own


def read(rec):
    if rec.trace is None:
        return None
    t0, t1 = rec.trace_window
    steps = [s for s in rec.counters.get("steps", []) if s["t0"] >= t0 and s["t1"] <= t1]
    per_step = _in_steps(rec.spans, steps)
    megasteps = sum(1 for inside in per_step for sp in inside if sp[2] == "decode_megastep")
    if not megasteps:
        return None
    own = _own_ms_by_span(per_step, steps)
    rec.counters["host_ms_by_span"] = {k: 1e3 * v / megasteps
                                       for k, v in sorted(own.items(), key=lambda kv: -kv[1])}
    idle = max(rec.trace.window_s - rec.trace.busy_s, 0.0)
    return 1e3 * idle / megasteps
