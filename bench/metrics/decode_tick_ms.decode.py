"""Decode megastep: milliseconds per decode tick, the window's
``decode_megastep`` span time over the ticks those spans ran (program
spans, ``steps`` argument).  Host replay of the window's tokens is outside
the span; the device's work and the dispatch are inside."""


def read(rec):
    t0, t1 = rec.window
    spans = [(d, a["steps"]) for n, s, d, a in rec.spans if n == "decode_megastep" and t0 <= s <= t1]
    ticks = sum(k for _, k in spans)
    if not ticks:
        return None
    return 1e3 * sum(d for d, _ in spans) / ticks
