"""Scheduler: mean share of the decode slots that hold a live request, over
the window's ``decode_megastep`` spans (program spans, ``live`` argument)."""


def read(rec):
    t0, t1 = rec.window
    live = [a["live"] for n, s, d, a in rec.spans if n == "decode_megastep" and t0 <= s <= t1]
    if not live:
        return None
    return 100.0 * sum(live) / (len(live) * rec.counters["slots"])
