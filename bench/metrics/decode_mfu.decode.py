"""Whole decode step: the least time the traced window's work could take on
the chip (every weight read once per tick, every live token's cache read
once, the int8 operations of the live rows, ``work.py``; the larger of
operations over the int8 peak and bytes over HBM bandwidth) over the traced
window's length.  Bytes bound it at these batch sizes."""

from bench import work


def read(rec):
    if rec.trace is None or "model" not in rec.work or rec.trace.window_s <= 0:
        return None
    ops, nbytes = rec.work["model"]
    least, _ = work.least_seconds(ops, nbytes, rec.peaks["int8_ops"], rec.peaks["hbm_bytes_s"])
    return 100.0 * least / rec.trace.window_s
