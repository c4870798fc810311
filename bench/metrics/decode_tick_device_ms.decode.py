"""Decode megastep on the device: milliseconds of device time per decode
tick.  The runs of the megastep program (``XLA Modules`` named for
``_megastep_fn``) in the traced window, over the decode ticks of the traced
steps that launched them (the benchmark's step records, ``ticks``).

The same layer as ``decode_tick_ms.decode`` read on the device's clock: the
span time less the host's share of it (argument uploads, dispatch, the
``device_get``); the two agree while the device is seldom idle."""


def read(rec):
    if rec.trace is None:
        return None
    t0, t1 = rec.trace_window
    ticks = sum(s.get("ticks", 0) for s in rec.counters.get("steps", [])
                if s["t0"] >= t0 and s["t1"] <= t1)
    device_s = sum(v for k, v in rec.trace.modules.items() if k.endswith("_megastep_fn"))
    if not ticks or device_s <= 0:
        return None
    return 1e3 * device_s / ticks
